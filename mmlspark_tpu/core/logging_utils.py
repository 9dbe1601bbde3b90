"""Structured telemetry on every fit/transform.

Analog of the reference's ``SynapseMLLogging`` (core/.../logging/
SynapseMLLogging.scala:49-172): wrap each stage's constructor/fit/transform
in a JSON log record carrying uid, class, method, wall-clock seconds and
error info, with secret scrubbing (logging/common/Scrubber.scala:1).
Instead of posting to MS-Fabric "certified events"
(CertifiedEventClient.scala:16-21) records go to a process-local sink the
host application can drain or redirect.
"""

from __future__ import annotations

import json
import logging
import re
import threading
import traceback
import uuid
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from mmlspark_tpu.core.timer import span

logger = logging.getLogger("mmlspark_tpu")

_SECRET_PATTERNS = [
    re.compile(r"(sig|key|token|password|secret|authorization)=[^&\s\"]+", re.I),
    re.compile(r"Bearer\s+[A-Za-z0-9._\-]+"),
    re.compile(r"sk-[A-Za-z0-9\-_]{10,}"),
]


def scrub(text: str) -> str:
    """Remove credential-looking substrings (Scrubber.scala analog)."""
    for pat in _SECRET_PATTERNS:
        text = pat.sub(lambda m: m.group(0).split("=")[0] + "=[REDACTED]"
                       if "=" in m.group(0) else "[REDACTED]", text)
    return text


class TelemetrySink:
    """In-process event buffer; swap `emit` to forward elsewhere."""

    def __init__(self, capacity: int = 10_000):
        self.capacity = capacity
        self.events: List[Dict[str, Any]] = []

    def emit(self, event: Dict[str, Any]) -> None:
        if len(self.events) >= self.capacity:
            del self.events[: self.capacity // 2]
        self.events.append(event)
        logger.debug("telemetry %s", json.dumps(event, default=str))

    def drain(self) -> List[Dict[str, Any]]:
        out, self.events = self.events, []
        return out


SINK = TelemetrySink()

_WARNED_ONCE: set = set()
_WARNED_LOCK = threading.Lock()


def warn_once(key: str, message: str, *args: Any) -> bool:
    """Log a degradation warning exactly once per process (keyed), and
    record it as a telemetry event so A/B labels stay honest even when
    the log stream is discarded. Returns True when this call emitted.

    Used by every graceful-degradation path (retry exhaustion,
    checkpoint skip, serving backpressure, kernel fallbacks) — a long
    run that silently degrades would otherwise report false health.
    """
    with _WARNED_LOCK:
        if key in _WARNED_ONCE:
            return False
        _WARNED_ONCE.add(key)
    logger.warning(message, *args)
    SINK.emit({"event": "degradation", "key": key,
               "message": scrub(message % args if args else message)})
    return True


def reset_warn_once() -> None:
    """Test hook: forget emitted once-per-process warnings."""
    with _WARNED_LOCK:
        _WARNED_ONCE.clear()


def new_uid(prefix: str) -> str:
    return f"{prefix}_{uuid.uuid4().hex[:12]}"


@contextmanager
def log_stage_method(uid: str, class_name: str, method: str,
                     extra: Optional[Dict[str, Any]] = None):
    """One record a ``fit()``/``transform()``, and the root of its
    spans: the body runs under ``span("<Class>.<method>", uid=uid)``
    (core/timer.py), and the record emitted at the end carries the
    root's ``start_s``/``end_s`` (``time.perf_counter()``) and
    ``spans``, everything that closed beneath it, in order of start, and
    ``counts``, what the stage counted on the root itself."""
    record: Dict[str, Any] = {
        "uid": uid,
        "className": class_name,
        "method": method,
        **(extra or {}),
    }
    root = span(f"{class_name}.{method}", uid=uid)
    try:
        with root:
            yield record
    except Exception as e:  # noqa: BLE001 — telemetry must not swallow
        record["error"] = scrub(f"{type(e).__name__}: {e}")
        record["traceback"] = scrub(traceback.format_exc(limit=5))
        raise
    finally:
        record["seconds"] = root.end_s - root.start_s
        record["start_s"], record["end_s"] = root.start_s, root.end_s
        record["spans"] = [s.as_record() for s in root.spans]
        if root.counts:
            record["counts"] = dict(root.counts)
        SINK.emit(record)
