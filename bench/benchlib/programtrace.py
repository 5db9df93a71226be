"""The program's own names in a profiler trace: device time by model scope,
and device idle by the program's host spans.

Times are nanoseconds on the trace's clock, as in ``tracereduce``.

- The model code puts ``jax.named_scope`` names on its operations
  (``SCOPES``, and any other name, such as ``flash`` inside
  ``attention``); each device operation carries the op name of its HLO
  instruction, a path such as ``jit(train_step)/transpose(jvp(attention))/
  dot_general``.  A scope counts an operation when it is a component of
  that path, looked at through transform wrappers, so nested scopes count
  for each of their enclosing ones.
- Each busy nanosecond of a device goes to the innermost operation running
  then: a loop's ``while`` covers the operations of its body and keeps
  only the time none of them covers.  So the scopes and the unscoped rest
  add up to busy time.
- The loop's host spans (``sysom.loop.*``, ``repro.core.spans``) carry the
  step they belong to; device idle is split by the spans in which it fell,
  each idle nanosecond counted once per group of spans however they nest.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from benchlib.tracereduce import merge

Interval = Tuple[float, float]
Span = Tuple[float, float, str, Optional[int]]   # (start, end, name, step)

SCOPES = ("embed", "attention", "mlp", "head_loss", "optimizer")
UNSCOPED = "unscoped"
# the program's host spans, grouped as their idle is read
IDLE_GROUPS = {
    "dispatch": ("sysom.loop.next_batch", "sysom.loop.dispatch"),
    "step_wait": ("sysom.loop.step_wait",),
    "loss_fetch": ("sysom.loop.loss_fetch",),
    "observe": ("sysom.loop.observe",),
}
PROGRAM_SPAN_PREFIX = "sysom."

_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                      r'metadata=\{op_name="([^"]*)"', re.M)
# a Pallas kernel's metadata, a JSON object of strings: printed over
# several lines, closing with a brace at the start of a line, unless it is
# empty and printed as ``{}``
_KERNEL_METADATA = re.compile(r"kernel_metadata=\{\n.*?\n\}", re.S)


def components(op_path: str) -> List[str]:
    """The names along ``op_path``, each looked at through transform
    wrappers (``transpose(jvp(attention))`` is ``attention``).  Merged
    metadata (``a;b``) is read by its first path."""
    out = []
    for comp in op_path.split(";", 1)[0].split("/"):
        m = _WRAPPER.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPER.match(comp)
        out.append(comp)
    return out


def scope_of(op_path: str) -> str:
    """The first scope among ``SCOPES`` that is a component of
    ``op_path``, or ``UNSCOPED``."""
    return next((c for c in components(op_path) if c in SCOPES), UNSCOPED)


def op_paths(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``metadata={op_name=...}``, from the text of
    a compiled HLO module."""
    return dict(_OP_NAME.findall(
        _KERNEL_METADATA.sub("kernel_metadata={}", hlo_text)))


def in_scope(by_op: Mapping[str, float], paths: Mapping[str, str],
             name: str) -> float:
    """Busy time of the instructions whose op path has ``name`` as a
    component, from busy time by instruction name."""
    return sum(t for op, t in by_op.items()
               if name in components(paths.get(op, "")))


def innermost(events: Iterable[Tuple[float, float, str]], lo: float,
              hi: float) -> Dict[str, float]:
    """Busy time inside [lo, hi] by key, each nanosecond given to the
    latest-started event still running: the innermost of nested events."""
    total: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[float, str]] = []        # (end, key), innermost last
    t = lo

    def advance(to: float) -> None:
        nonlocal t
        while stack and t < to:
            end, key = stack[-1]
            if end <= t:
                stack.pop()
                continue
            step_end = min(end, to)
            total[key] += step_end - t
            t = step_end
        t = max(t, to)

    clipped = [(max(s, lo), min(e, hi), k) for s, e, k in events
               if e > lo and s < hi]
    # of events starting together the longest is the outermost
    for s, e, key in sorted(clipped, key=lambda ev: (ev[0], -ev[1])):
        advance(s)
        stack.append((e, key))
    advance(hi)
    return dict(total)


def device_by_scope(by_op: Mapping[str, float], paths: Mapping[str, str]
                    ) -> Dict[str, float]:
    """Busy time by scope (``SCOPES`` and ``UNSCOPED``) from busy time by
    instruction name (``innermost`` of a device's operations)."""
    out: Dict[str, float] = defaultdict(float)
    for name, t in by_op.items():
        out[scope_of(paths.get(name, ""))] += t
    return dict(out)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_by_span(idle: Sequence[Interval], spans: Iterable[Span],
                 lo: float, hi: float,
                 groups: Mapping[str, Sequence[str]] = IDLE_GROUPS
                 ) -> Dict[str, float]:
    """Idle time inside [lo, hi] that fell in each group's spans (the union
    of the group's spans, so nested or repeated spans count it once)."""
    spans = list(spans)
    idle = merge(idle, lo, hi)
    return {g: overlap(idle, merge(((sp[0], sp[1]) for sp in spans
                                    if sp[2] in names), lo, hi))
            for g, names in groups.items()}


def step_wait_lag(ops: Iterable[Interval], spans: Iterable[Span]
                  ) -> List[Tuple[int, float]]:
    """(step, how long the step's last device operation ran past the end of
    its ``sysom.loop.step_wait``) for each step whose wait and next
    dispatch the trace holds; a step's operations are those that start
    between its own dispatch and the next."""
    spans = list(spans)
    dispatch = {sp[3]: sp[0] for sp in spans
                if sp[2] == "sysom.loop.dispatch"}
    wait = {sp[3]: sp[1] for sp in spans if sp[2] == "sysom.loop.step_wait"}
    ops = sorted(ops)
    starts = [s for s, _ in ops]
    out = []
    for step, end in sorted(wait.items()):
        if step not in dispatch or step + 1 not in dispatch:
            continue
        mine = ops[bisect.bisect_left(starts, dispatch[step]):
                   bisect.bisect_left(starts, dispatch[step + 1])]
        if mine:
            out.append((step, max(e for _, e in mine) - end))
    return out


def read_spans(trace_dir: str) -> List[Span]:
    """The program's host spans, (start, end, name, its ``step`` argument
    or None), from the one ``.xplane.pb`` under ``trace_dir``; its device
    operations are ``tracereduce.read_xplane``'s."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{paths}")
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name, _step(e.stats))
             for plane in ProfileData.from_file(paths[0]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PROGRAM_SPAN_PREFIX)]
    return sorted(spans, key=lambda sp: sp[:2])


def _step(stats) -> Optional[int]:
    for key, value in stats:
        if key == "step":
            return int(value)
    return None
