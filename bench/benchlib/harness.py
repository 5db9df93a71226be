"""Drive one cell: the program's own training loop, timed from outside.

The window runs ``repro.train.loop.train_loop`` wired as the training
launcher wires it for ``--full``: a ``CentralService`` (traffic with
observability), a ``DataPipeline`` over a ``SyntheticCorpus`` seeded from
``--seed``, a ``LoopConfig`` with the launcher's rules, no checkpoint
directory.  The harness adds nothing to the loop; it sees it through the
objects it hands in and through thin wrappers:

- its pipeline (a ``DataPipeline`` subclass) keeps the window: the first
  ``warmup_steps`` batch requests are set-up, the next opens the window,
  and the first request at or after ``--seconds`` closes it by raising
  ``StopWindow`` out of ``next(pipeline)``; the window's clock is the host
  clock between successive batch requests, so it covers input, dispatch,
  the loss transfer, the agent and the service;
- the loop's ``jax.jit`` of the train step is wrapped, so the harness sees
  the state that goes into the first four steps and the loss that each
  step returns; the compiled program is the loop's own; a traced run also
  keeps the jitted step and its arguments' shapes, to name the trace's
  device operations by the step's HLO once the window has closed;
- ``NodeAgent.submit``/``flush``, ``SamplingProfiler._snapshot`` and the
  service's ``process`` are wrapped to time them and, in a traced run, to
  put a ``TraceAnnotation`` span on the profiler's clock.

Correctness: once the window has closed and the program's state is freed,
the plain reference (``bench/reference``) retrains the first steps from
the seed's weights on the same batches; ``reftrain.gaps`` compares them
with what the program's first steps produced, each against the limit in
the configuration's file.  Each label of those batches has to be the next
token, as far as the tokens show it.  Runs with an agent must also deliver
every step's profile to the service with no upload failure, and run a
service cycle every tenth step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import shutil
import statistics
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchlib import flops, peaks, programtrace, reftrain, tracereduce
from benchlib.compileclock import CompileClock
from benchlib.spec import Cell, metric_reader

SPANS = ("next_batch", "step_dispatch", "loss_sync", "agent_submit",
         "agent_flush", "sampler_snapshot", "service_process")
CAPTURE_STEPS = 3          # the reference follows this many steps
FLUSH_EVERY = 10           # the loop flushes and runs the service so often


class StopWindow(Exception):
    """Raised out of ``next(pipeline)`` when the window has closed."""


def check_devices(chips: int):
    """The devices the cell runs on; exits when they are not TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"[bench] no TPU for this cell: JAX sees {len(devs)} "
              f"{devs[0].platform} device(s) ({devs[0].device_kind}); the "
              f"cell needs {chips} TPU chip(s)", file=sys.stderr)
        raise SystemExit(1)
    return devs


def percentile(values: List[float], pct: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * pct / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class Probe:
    """What the wrappers see of one run."""
    warmup: int
    seconds: float
    tracing: bool
    trace_from: int            # window steps before the trace starts
    trace_steps: int
    trace_dir: str
    b1: float
    requests: int = 0
    t_open: Optional[float] = None
    t_close: Optional[float] = None
    request_times: List[float] = dataclasses.field(default_factory=list)
    batches: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)
    calls: int = 0
    params0: Any = None
    grad_norms: Optional[Dict[str, float]] = None
    change_norms: Optional[Dict[str, float]] = None
    agent: Any = None
    submits: int = 0
    cycles: int = 0
    # (request index, span name, seconds) of every wrapped call
    timings: List[tuple] = dataclasses.field(default_factory=list)
    trace_requests: Optional[tuple] = None     # (first, last) traced
    # the sampler thread's CPU seconds at the window's open and close
    sampler_cpu: List[Optional[float]] = dataclasses.field(
        default_factory=list)
    service: Any = None
    # a traced run's jitted step and its arguments' shapes
    step_fn: Any = None
    step_args: Any = None

    # -- spans ------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.timings.append((self.requests - 1, name,
                             time.perf_counter() - t0))

    # -- the window, kept at each batch request ---------------------------
    def on_request(self) -> None:
        now = time.monotonic()
        i = self.requests
        self.requests += 1
        if i < self.warmup:
            return
        if i == self.warmup:
            self.t_open = now
            self.sampler_cpu.append(self._sampler_cpu())
        self.request_times.append(now)
        k = i - self.warmup
        if self.tracing and k == self.trace_from:
            self._start_trace(i)
        closing = now - self.t_open >= self.seconds
        if self.tracing and self.trace_requests and \
                self.trace_requests[1] is None and \
                (k == self.trace_from + self.trace_steps or closing):
            self._stop_trace(i)
        if closing:
            self.t_close = now
            self.sampler_cpu.append(self._sampler_cpu())
            raise StopWindow()

    def _sampler_cpu(self) -> Optional[float]:
        if self.agent is None:
            return None
        return self.agent.sampler.cpu_seconds

    def _start_trace(self, i: int) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self.trace_requests = (i, None)

    def _stop_trace(self, i: int) -> None:
        import jax
        self.trace_requests = (self.trace_requests[0], i)
        jax.profiler.stop_trace()

    # -- the train step, as the loop calls it -----------------------------
    def observe_step(self, jitted):
        import jax

        def step(state, batch):
            i = self.calls
            self.calls += 1
            if i == 0:
                self.params0 = jax.device_get(state["params"])
                if self.tracing:
                    self.step_fn = jitted
                    self.step_args = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=x.sharding),
                        (state, batch))
            elif i == 1:
                paths = reftrain.leaf_paths(state["opt"]["m"])
                self.grad_norms = {
                    p: float(n) / (1.0 - self.b1) for p, n in
                    zip(paths, reftrain.leaf_norms(state["opt"]["m"]))}
            elif i == CAPTURE_STEPS:
                paths = reftrain.leaf_paths(state["params"])
                old = jax.device_put(self.params0)
                self.change_norms = dict(zip(paths, map(float, (
                    reftrain.change_norms(state["params"], old)))))
                del old
                self.params0 = None
            with self.span("step_dispatch"):
                new_state, metrics = jitted(state, batch)
            metrics = dict(metrics, loss=_Loss(metrics["loss"], self))
            return new_state, metrics

        return step


class _Loss:
    """The step's loss, as the loop reads it: ``float`` is the sync."""

    def __init__(self, value, probe: Probe):
        self.value, self.probe = value, probe

    def __float__(self) -> float:
        with self.probe.span("loss_sync"):
            v = float(self.value)
        self.probe.losses.append(v)
        return v


class _JaxView:
    """The ``jax`` the loop module sees: itself, but ``jit`` of the train
    step comes back wrapped by the probe."""

    def __init__(self, jax_mod, probe: Probe):
        self._jax, self._probe = jax_mod, probe

    def __getattr__(self, name):
        return getattr(self._jax, name)

    def jit(self, fun, *args, **kwargs):
        jitted = self._jax.jit(fun, *args, **kwargs)
        if getattr(fun, "__name__", "") == "train_step":
            return self._probe.observe_step(jitted)
        return jitted


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def program_config(cell: Cell):
    """The program's configuration, checked key by key against the
    configuration's file."""
    from repro import configs
    cfg = configs.get(cell.config["registry"])
    wrong = {k: (v, getattr(cfg, k, "<missing>"))
             for k, v in cell.config["model"].items()
             if getattr(cfg, k, "<missing>") != v}
    if wrong:
        raise SystemExit(f"[bench] the program's {cell.config['registry']!r} "
                         f"differs from {cell.config_name}'s file (file, "
                         f"program): {wrong}")
    return cfg


def loop_config(traffic, seed: int):
    """``LoopConfig`` by the launcher's rules for ``--steps T``."""
    from repro.train.loop import LoopConfig
    t = traffic["total_steps"]
    return LoopConfig(
        total_steps=t, warmup_steps=max(t // 20, 5),
        peak_lr=traffic["peak_lr"], schedule="cosine", log_every=10,
        checkpoint_every=max(t // 4, 10), checkpoint_dir=None,
        observability=traffic["observability"],
        sampling_rate=traffic["sampling_rate"], seed=seed)


def drive(cell: Cell, seed: int, seconds: float, tracing: bool) -> Probe:
    """Run the program's loop through set-up and one window."""
    import jax
    from repro.core.agent import NodeAgent
    from repro.core.samplers import SamplingProfiler
    from repro.core.service import CentralService
    from repro.data import DataPipeline, SyntheticCorpus
    from repro.models import build_model
    from repro.train import loop as loop_mod

    traffic, config = cell.traffic, cell.config
    cfg = program_config(cell)
    probe = Probe(warmup=traffic["setup_steps"], seconds=seconds,
                  tracing=tracing, trace_from=traffic["trace_from_step"],
                  trace_steps=traffic["trace_steps"],
                  trace_dir=str(cell.root / ".bench_trace" / cell.name),
                  b1=config["train"]["b1"])

    class Pipeline(DataPipeline):
        def __next__(self):
            probe.on_request()
            with probe.span("next_batch"):
                batch = super().__next__()
            if len(probe.batches) < CAPTURE_STEPS:
                probe.batches.append(reftrain.to_host(batch))
            return batch

    class Service(CentralService):
        def process(self):
            with probe.span("service_process"):
                out = super().process()
            probe.cycles += 1
            return out

    def submit(agent, profile, _orig=NodeAgent.submit):
        probe.agent = agent
        probe.submits += 1
        with probe.span("agent_submit"):
            _orig(agent, profile)

    def flush(agent, _orig=NodeAgent.flush):
        with probe.span("agent_flush"):
            return _orig(agent)

    def snapshot(sampler, _orig=SamplingProfiler._snapshot):
        with jax.profiler.TraceAnnotation("sampler_snapshot"):
            _orig(sampler)

    corpus = SyntheticCorpus(cfg.vocab_size, seq_len=config["seq_len"],
                             seed=seed)
    pipeline = Pipeline(corpus, global_batch=config["batch"])
    service = Service() if traffic["observability"] else None
    with contextlib.ExitStack() as patches:
        patches.enter_context(_patched(loop_mod, "jax", _JaxView(jax, probe)))
        patches.enter_context(_patched(NodeAgent, "submit", submit))
        patches.enter_context(_patched(NodeAgent, "flush", flush))
        if tracing:
            patches.enter_context(
                _patched(SamplingProfiler, "_snapshot", snapshot))
        try:
            loop_mod.train_loop(build_model(cfg), pipeline,
                                loop_config(traffic, seed), service=service)
        except StopWindow:
            pass
        else:
            raise SystemExit(
                f"[bench] the loop ran out of its {traffic['total_steps']} "
                f"steps before the {seconds} s window closed")
    if probe.agent is not None and probe.agent.cfg.hz != traffic["hz"]:
        raise SystemExit(f"[bench] the agent sampled at {probe.agent.cfg.hz}"
                         f" Hz, the traffic states {traffic['hz']} Hz")
    probe.service = service
    return probe


# ---------------------------------------------------------------------------
# reading a run
# ---------------------------------------------------------------------------


def end_to_end(cell: Cell, probe: Probe, t_start: float) -> Dict[str, float]:
    periods = [b - a for a, b in zip(probe.request_times,
                                     probe.request_times[1:])]
    window = probe.t_close - probe.t_open
    tokens = cell.config["batch"] * cell.config["seq_len"]
    return {"tokens_per_s": len(periods) * tokens / window,
            "step_ms_p95": percentile(periods, 95) * 1e3,
            "setup_s": probe.t_open - t_start}


@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric's reader is given."""
    cell: Cell
    steps: int                    # steps in the traced stretch
    window_s: float               # its length on the host clock
    busy_s: Optional[float]       # device busy inside it, mean over chips
    step_flops: float
    peak_flops: float
    timings: Dict[str, List[float]]   # span -> seconds, traced steps only
    # the sampler's CPU seconds over the whole window, and its length: the
    # thread's CPU clock advances in scheduler ticks, too coarse for the
    # traced stretch alone
    sampler_cpu_s: Optional[float]
    sampler_window_s: float
    agent: bool
    device_kind: str = ""
    # busy seconds by HLO instruction, each busy nanosecond given to the
    # innermost operation (``programtrace.innermost``), mean over chips
    device_s_by_op: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    op_path: Dict[str, str] = dataclasses.field(   # instruction -> op name
        default_factory=dict)
    # device idle seconds in the program's loop spans, by
    # ``programtrace.IDLE_GROUPS`` group, for the groups whose spans the
    # traced stretch holds; mean over chips
    idle_s_by_span: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def in_scope(self, name: str) -> float:
        """Busy seconds of the operations whose op name has ``name`` as a
        component (``programtrace.components``), nested scopes included."""
        return programtrace.in_scope(self.device_s_by_op, self.op_path, name)


def compiled_text(step, args) -> str:
    """The HLO text of this program's own compile of ``step``.  The
    persistent compile cache keys a module without its debug information,
    where the scopes live, so a cached executable may carry the op names
    of another build of the same program: compile past the cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return step.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _mean_by_key(dicts: List[Dict[str, float]]) -> Dict[str, float]:
    """Each key's mean over ``dicts`` (0 where one lacks it); {} for none."""
    keys = sorted({k for d in dicts for k in d})
    return {k: sum(d.get(k, 0.0) for d in dicts) / len(dicts) for k in keys}


def read_trace(cell: Cell, probe: Probe, kind: str, chips: int):
    """(TraceContext, breakdown) of the traced stretch of the window."""
    first, last = probe.trace_requests
    devices, spans = tracereduce.read_xplane(probe.trace_dir, SPANS)
    loop_spans = programtrace.read_spans(probe.trace_dir)
    shutil.rmtree(probe.trace_dir, ignore_errors=True)
    starts = sorted(s for s, _, n in spans if n == "next_batch")
    lo, hi = starts[0], starts[-1]
    steps = len(starts) - 1
    used = sorted(devices)[:chips]
    busy = [tracereduce.busy(((s, e) for s, e, _ in devices[d]), lo, hi)
            for d in used]
    ops = [ev for d in used for ev in devices[d]]
    idle = tracereduce.gaps(((s, e) for s, e, _ in devices[used[0]]), lo,
                            hi) if used else []
    breakdown = {
        "device_ops": [[n, t / 1e9] for n, t in
                       tracereduce.top_ops(ops, lo, hi)],
        "idle_gaps": [[n, t / 1e9] for n, t in
                      tracereduce.name_gaps(idle, spans)[:10]],
    }
    by_op = _mean_by_key([programtrace.innermost(
        ((s, e, tracereduce.op_name(n)) for s, e, n in devices[d]), lo, hi)
        for d in used])
    paths = (programtrace.op_paths(compiled_text(probe.step_fn,
                                                 probe.step_args))
             if by_op else {})
    idle_by_span = _mean_by_key([programtrace.idle_by_span(
        tracereduce.gaps(((s, e) for s, e, _ in devices[d]), lo, hi),
        loop_spans, lo, hi) for d in used])
    # a group none of whose spans the stretch holds has nothing to read
    for group, names in programtrace.IDLE_GROUPS.items():
        if not any(sp[2] in names and sp[1] > lo and sp[0] < hi
                   for sp in loop_spans):
            idle_by_span.pop(group, None)
    timings: Dict[str, List[float]] = {n: [] for n in SPANS}
    for req, name, secs in probe.timings:
        if first <= req < last:
            timings[name].append(secs)
    cpu = None
    if len(probe.sampler_cpu) == 2 and None not in probe.sampler_cpu:
        cpu = probe.sampler_cpu[1] - probe.sampler_cpu[0]
    ctx = TraceContext(
        cell=cell, steps=steps, window_s=(hi - lo) / 1e9,
        busy_s=statistics.mean(busy) / 1e9 if used else None,
        step_flops=flops.train_step_flops(
            cell.config["model"], cell.config["batch"],
            cell.config["seq_len"], flops.family(cell.config, cell.root)),
        peak_flops=peaks.peak(kind), timings=timings, sampler_cpu_s=cpu,
        sampler_window_s=probe.t_close - probe.t_open,
        agent=probe.agent is not None, device_kind=kind,
        device_s_by_op={k: v / 1e9 for k, v in by_op.items()},
        op_path=paths,
        idle_s_by_span={k: v / 1e9 for k, v in idle_by_span.items()})
    return ctx, breakdown


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def program_readings(probe: Probe) -> reftrain.Readings:
    return reftrain.Readings(probe.losses[:CAPTURE_STEPS],
                             probe.grad_norms or {}, probe.change_norms or {})


def reference_readings(cell: Cell, seed: int, batches, q=reftrain.identity,
                       keep_rows: int = 0) -> reftrain.Readings:
    import jax
    config = cell.config
    ref = reftrain.load_reference(config["reference"], cell.root)
    opt = dict(config["train"], peak_lr=cell.traffic["peak_lr"],
               warmup_steps=max(cell.traffic["total_steps"] // 20, 5))
    return reftrain.reference_steps(
        ref, config["model"], opt, batches, jax.random.PRNGKey(seed), q=q,
        keep_rows=keep_rows)


def checks(cell: Cell, probe: Probe, ref: reftrain.Readings
           ) -> Dict[str, Dict[str, float]]:
    """Each number compared, beside its limit; a run is correct when none
    is over."""
    limits = cell.config["limits"]
    got = program_readings(probe)
    gaps = reftrain.gaps(got, ref)
    out = {k: {"value": gaps[k], "limit": v} for k, v in limits.items()}
    # the reference trains on the pipeline's labels: each has to be the
    # next token, as far as the tokens show it (the last one they cannot)
    out["labels_not_next_token"] = {"value": sum(
        int(np.count_nonzero(b["labels"][:, :-1] != b["tokens"][:, 1:]))
        for b in probe.batches), "limit": 0}
    out["nonfinite_losses"] = {
        "value": sum(not math.isfinite(x) for x in probe.losses), "limit": 0}
    steps = probe.calls
    if cell.traffic["observability"]:
        counters = probe.agent.counters() if probe.agent else {}
        out["profiles_missing"] = {
            "value": steps - probe.service.ingested, "limit": 0}
        out["upload_failures"] = {
            "value": counters.get("upload_failures", 1), "limit": 0}
        out["cycles_missing"] = {
            "value": steps // FLUSH_EVERY - probe.cycles, "limit": 0}
    else:
        out["agent_calls"] = {"value": probe.submits, "limit": 0}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, tracing: bool,
        t_start: float) -> Dict[str, Any]:
    devs = check_devices(cell.chips)
    if not cell.config.get("limits"):
        raise SystemExit(f"[bench] {cell.config_name}'s file states no "
                         f"limits, so no run of it can be judged correct")
    # found before any run, so that a configuration without its FLOP
    # count fails at once
    flops.family(cell.config, cell.root)
    import jax
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # every program, however quick to compile, comes from the cache on
    # the second run, so set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with CompileClock() as clock:
        probe = drive(cell, seed, seconds, tracing)
    e2e = end_to_end(cell, probe, t_start)
    print(f"[bench] set-up {e2e['setup_s']:.3f} s: {clock.report()}; "
          f"{clock.between(probe.t_open, probe.t_close)} compiles inside "
          f"the window", file=sys.stderr)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs[:cell.chips])}
    result: Dict[str, Any] = {}
    if tracing:
        ctx, breakdown = read_trace(cell, probe, devs[0].device_kind,
                                    cell.chips)
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"], cell.root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = breakdown
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    gc.collect()
    ref = reference_readings(cell, seed, probe.batches)
    compared = checks(cell, probe, ref)
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    for name, c in compared.items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    steps = len(probe.request_times) - 1
    return dict({"correct": correct, "attempted": steps,
                 "failed": sum(not math.isfinite(x) for x in probe.losses),
                 "metrics": metrics, "device": device}, **result,
                checks=compared)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    from benchlib.spec import find_cell

    t_start = time.monotonic() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = find_cell(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    print(json.dumps(result))
    return 0
