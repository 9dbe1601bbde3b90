"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to the few
numbers the per-layer metrics read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A
device plane is one whose name starts with ``/device:``; on a TPU its
line ``XLA Ops`` carries one event per operation that ran on the chip
and ``XLA Modules`` one per executed program. Where a plane has no
``XLA Ops`` line (the CPU backend has none at all) the reduction finds
nothing and says so by returning ``None``; it never substitutes a host
line for a device line.

Everything is in seconds on the trace's own clock; host annotations
and device ops share it to about a millisecond (in the recorded test
trace the first device op starts 1 ms before the annotation that
dispatched it). ``busy`` is the
length of the union of the op intervals: two ops that overlap on the
chip are not counted twice, and a nested op (a fusion inside a while
body) adds nothing to its parent.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under a ``start_trace`` directory."""
    paths = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def union_length(intervals: Iterable[Interval]) -> float:
    """Length covered by at least one of the intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``[lo, hi]`` no interval covers, in time order."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if e <= lo or s >= hi:
            continue
        if s > edge:
            out.append((edge, min(s, hi)))
        edge = max(edge, e)
    if edge < hi:
        out.append((edge, hi))
    return out


@dataclass
class DeviceTrace:
    """The events of one device plane: ``(name, start_s, end_s)``."""
    plane: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)

    def matching(self, pattern: str, line: str = "ops"
                 ) -> List[Tuple[str, float, float]]:
        rx = re.compile(pattern)
        return [ev for ev in getattr(self, line) if rx.search(ev[0])]

    def busy_s(self, lo: Optional[float] = None,
               hi: Optional[float] = None) -> float:
        ivs = [(max(s, lo) if lo is not None else s,
                min(e, hi) if hi is not None else e)
               for _, s, e in self.ops]
        return union_length((s, e) for s, e in ivs if e > s)

    def span(self) -> Optional[Interval]:
        if not self.ops:
            return None
        return (min(s for _, s, _ in self.ops),
                max(e for _, _, e in self.ops))

    def op_seconds(self) -> Dict[str, float]:
        """Summed duration per op name (nested ops count for themselves
        as well as inside their parent: a ranking, not a partition)."""
        out: Dict[str, float] = {}
        for name, s, e in self.ops:
            out[name] = out.get(name, 0.0) + (e - s)
        return out

    def union_seconds(self, pattern: str) -> float:
        """Time in which at least one op matching ``pattern`` ran."""
        return union_length((s, e) for _, s, e in self.matching(pattern))


@dataclass
class Trace:
    devices: List[DeviceTrace]
    # host TraceAnnotations by name: (name, start_s, end_s)
    annotations: List[Tuple[str, float, float]]

    def device(self, index: int = 0) -> Optional[DeviceTrace]:
        return self.devices[index] if index < len(self.devices) else None

    def annotation_at(self, t: float, names: Sequence[str]) -> Optional[str]:
        """The innermost (shortest) named annotation that covers ``t``."""
        best = None
        for name, s, e in self.annotations:
            if name in names and s <= t <= e:
                if best is None or (e - s) < best[1]:
                    best = (name, e - s)
        return best[0] if best else None


def _device_index(plane_name: str) -> int:
    m = re.search(r"(\d+)\s*$", plane_name)
    return int(m.group(1)) if m else 0


def load(path: str, annotation_names: Sequence[str] = ()) -> Optional[Trace]:
    """Read one ``.xplane.pb``. ``None`` when no device plane carries an
    ``XLA Ops`` line with at least one event (nothing ran on a device,
    or the backend writes no device plane)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    annotations: List[Tuple[str, float, float]] = []
    wanted = set(annotation_names)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = DeviceTrace(plane=plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    target = dev.ops
                elif line.name == MODULES_LINE:
                    target = dev.modules
                else:
                    continue
                for ev in line.events:
                    s = ev.start_ns / 1e9
                    target.append((ev.name, s, s + ev.duration_ns / 1e9))
            if dev.ops:
                devices.append(dev)
        elif wanted and plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        s = ev.start_ns / 1e9
                        annotations.append(
                            (ev.name, s, s + ev.duration_ns / 1e9))
    if not devices:
        return None
    devices.sort(key=lambda d: _device_index(d.plane))
    return Trace(devices=devices, annotations=annotations)


def busy_and_window(trace: Trace) -> Tuple[float, float]:
    """``(busy_s, window_s)`` as the result line's ``device`` wants
    them: busy seconds averaged over the devices traced, and the length
    from the first op's start to the last op's end on any of them."""
    spans = [d.span() for d in trace.devices]
    lo = min(s for s, _ in spans)
    hi = max(e for _, e in spans)
    busy = sum(d.busy_s() for d in trace.devices) / len(trace.devices)
    return busy, hi - lo


def short_name(op_name: str) -> str:
    """``%fusion.17 = f32[...] fusion(...)`` -> ``fusion.17 f32[...]``:
    the TPU names an op by its whole HLO line; a breakdown wants the
    instruction's name and its result's shape."""
    head, sep, rest = op_name.partition(" = ")
    if not sep:
        return op_name[:96]
    return (head.lstrip("%") + " " + rest.split(" ", 1)[0])[:96]


def breakdown(trace: Trace, annotation_names: Sequence[str],
              top: int = 10) -> Dict[str, list]:
    """The ten device ops that took most time on device 0 and its ten
    longest idle gaps, each gap named by the host annotation that
    covers its middle (``unannotated`` where none does)."""
    dev = trace.devices[0]
    totals: Dict[str, float] = {}
    for name, seconds in dev.op_seconds().items():
        key = short_name(name)
        totals[key] = totals.get(key, 0.0) + seconds
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    lo, hi = dev.span()
    idle = sorted((g for g in gaps(((s, e) for _, s, e in dev.ops), lo, hi)
                   if g[1] - g[0] >= 1e-6),           # not the 1 ns seams
                  key=lambda g: g[0] - g[1])[:top]
    named = [[trace.annotation_at((s + e) / 2, annotation_names)
              or "unannotated", e - s] for s, e in idle]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": named}
