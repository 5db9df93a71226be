"""From a profiler trace to device busy time, idle gaps and top operations.

Times are nanoseconds on the trace's own clock, on which the profiler puts
the device's operations and the host's ``TraceAnnotation`` spans alike.

- Busy time is the union of the intervals in which an operation ran on a
  device, inside the traced window; the idle share is one minus busy over
  the window.
- An idle gap is a stretch of the window in which no operation ran; it is
  named by the host span that overlaps it most (``unspanned`` where none
  does).
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]             # (start, end)
Named = Tuple[float, float, str]           # (start, end, name)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
DEVICE_OPS_LINE = "XLA Ops"


def merge(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to [lo, hi], sorted, disjoint."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in clipped:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def name_gaps(idle: Sequence[Interval], spans: Sequence[Named]
              ) -> List[Tuple[str, float]]:
    """Each gap as (name of the span overlapping it most, length),
    longest first."""
    spans = sorted(spans)
    out = []
    for gs, ge in idle:
        best, best_overlap = "unspanned", 0.0
        for ss, se, name in spans:
            if ss >= ge:
                break
            overlap = min(ge, se) - max(gs, ss)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        out.append((best, ge - gs))
    return sorted(out, key=lambda x: -x[1])


def op_name(text: str) -> str:
    """``%fusion.553 = (f32[...]) fusion(...), kind=...`` -> ``fusion.553``"""
    return text.split(" = ", 1)[0].lstrip("%")


def top_ops(events: Iterable[Named], lo: float, hi: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The operations that took most time inside [lo, hi], summed by name.
    A loop's operation covers the operations of its body, which are
    listed too."""
    total: Dict[str, float] = defaultdict(float)
    for s, e, name in events:
        if e > lo and s < hi:
            total[op_name(name)] += min(e, hi) - max(s, lo)
    return sorted(total.items(), key=lambda x: -x[1])[:n]


def read_xplane(trace_dir: str, span_names: Iterable[str]
                ) -> Tuple[Dict[str, List[Named]], List[Named]]:
    """(device plane -> its operations, host spans named in ``span_names``)
    from the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{paths}")
    data = ProfileData.from_file(paths[0])
    wanted = set(span_names)
    devices: Dict[str, List[Named]] = {}
    spans: List[Named] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines if line.name == DEVICE_OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for line in plane.lines for e in line.events
                         if e.name in wanted)
    return devices, spans
