"""Wall-clock instrumentation: one span recorder.

Analog of the reference's ``StopWatch`` (core/utils/StopWatch.scala:1) and
the LightGBM ``TaskInstrumentationMeasures``/``InstrumentationMeasures``
(lightgbm/.../LightGBMPerformance.scala:11-66), which mark
init/network/dataPrep/datasetCreation/validation/iterations phases per
task and aggregate per batch. Here every timed interval of the program
is a :class:`span`: a name, a start and an end on ``time.perf_counter()``,
the span that was open when it started, and the identifier of the
``fit()``/``transform()`` it belongs to. For its duration a span holds a
``jax.profiler.TraceAnnotation`` of the same name, so under a live
profiler session the interval also stands in the trace's host plane, on
the clock the device ops are on. With no session the annotation is idle:
"tracing off" is "no profiler session", and nothing else is switched.
"""

from __future__ import annotations

import contextvars
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

# the innermost open span of this thread (or asyncio task): a thread
# starts with none, so two threads never adopt each other's spans
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "mmlspark_tpu_span", default=None)

_TraceAnnotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported at the first span so
    that importing the package does not import JAX."""
    global _TraceAnnotation
    from jax.profiler import TraceAnnotation
    _TraceAnnotation = TraceAnnotation
    return TraceAnnotation


class span:
    """``with span("scorer.put", bytes=n):`` times its body.

    On exit, raised or not, the span holds ``start_s`` and ``end_s``
    (``time.perf_counter()``), ``parent`` (the span open on this thread
    when it started, or ``None``), ``uid`` and ``counts``: the work done
    at this boundary (rows, bytes), which the body may add to.

    A span given a ``uid`` is a root (``log_stage_method`` opens one a
    ``fit()``/``transform()``, under the stage's uid); so is a span
    opened with nothing above it. A root's ``spans`` lists, in order of
    start, every span opened beneath it up to and including any nested
    root; the others take its ``uid``. Nothing else keeps a span, so
    an inner function called in a loop with no root above it leaves
    nothing behind.
    """

    __slots__ = ("name", "uid", "counts", "start_s", "end_s", "parent",
                 "spans", "_home", "_measures", "_annotation", "_token")

    def __init__(self, name: str, uid: Optional[str] = None,
                 measures: Optional["InstrumentationMeasures"] = None,
                 **counts: Any):
        self.name, self.uid, self.counts = name, uid, counts
        self._measures = measures
        self.start_s = self.end_s = None
        self.parent = self.spans = self._home = None

    def __enter__(self) -> "span":
        parent = self.parent = _CURRENT.get()
        if parent is None:
            self.spans = []
        else:
            self._home = (parent.spans if parent.spans is not None
                          else parent._home)
            self._home.append(self)
            if self.uid is None:
                self.uid = parent.uid
            else:
                self.spans = []
            if self._measures is None:
                self._measures = parent._measures
        self._token = _CURRENT.set(self)
        self._annotation = (_TraceAnnotation or _trace_annotation())(
            self.name)
        self._annotation.__enter__()
        self.start_s = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end_s = time.perf_counter()
        self._annotation.__exit__(exc_type, exc, tb)
        _CURRENT.reset(self._token)
        if self._measures is not None:
            self._measures._add(self.name, self.end_s - self.start_s)
        return False

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s

    def as_record(self) -> Dict[str, Any]:
        """The span as a plain dict, for a telemetry record; ``parent``
        is the parent's name."""
        record = {"name": self.name, "start_s": self.start_s,
                  "end_s": self.end_s,
                  "parent": self.parent.name if self.parent else None,
                  "counts": dict(self.counts)}
        if self.spans is not None and self.parent is not None:
            record["uid"] = self.uid     # a nested stage's own root
        return record


def current_span() -> Optional[span]:
    """The innermost span open on this thread; directly inside a stage's
    ``_fit``/``_transform`` that is the root ``log_stage_method`` opened,
    whose ``counts`` go into the stage's telemetry record."""
    return _CURRENT.get()


class StopWatch:
    def __init__(self):
        self._start: Optional[float] = None
        self.elapsed = 0.0

    def start(self) -> "StopWatch":
        self._start = time.perf_counter()
        return self

    def stop(self) -> float:
        if self._start is not None:
            self.elapsed += time.perf_counter() - self._start
            self._start = None
        return self.elapsed

    @contextmanager
    def measure(self):
        self.start()
        try:
            yield self
        finally:
            self.stop()


class InstrumentationMeasures:
    """Seconds and counts summed by span name, queryable after a fit.

    ``phase(name)`` opens a :class:`span`; it, and every span opened
    beneath it by code that knows nothing of this object, adds its
    duration under its own name when it closes. A nested span is named
    in full by whoever opens it (``binning.transform``) and adds nothing
    to its parent's figure.
    """

    def __init__(self):
        self._phases: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._order: List[str] = []

    def phase(self, name: str, **counts: Any) -> span:
        return span(name, measures=self, **counts)

    def _add(self, name: str, seconds: float) -> None:
        if name not in self._phases:
            self._order.append(name)
        self._phases[name] = self._phases.get(name, 0.0) + seconds
        self._counts[name] = self._counts.get(name, 0) + 1

    def seconds(self, name: str) -> float:
        return self._phases.get(name, 0.0)

    def count(self, name: str) -> int:
        return self._counts.get(name, 0)

    def as_dict(self) -> Dict[str, float]:
        return {n: self._phases[n] for n in self._order}

    def __repr__(self) -> str:
        body = ", ".join(f"{n}={v:.4f}s" for n, v in self.as_dict().items())
        return f"InstrumentationMeasures({body})"
