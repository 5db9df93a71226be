"""One traced run of a cell, read by the program's own names.

    python3 bench/trace_report.py --workload <name> --seed <n> --seconds <s>

from the root of a checkout, on a machine with the TPU chips the cell asks
for.  It drives the cell as a ``bench/run.py --trace 1`` run does and
reads the trace by the names the program puts there
(``benchlib.programtrace``): device time per traced step by model scope,
with the op names taken from the train step's HLO, compiled anew after
the window (``harness.read_trace``); device idle per traced step by the
loop's ``sysom.loop.*`` spans; and how far each step's last device
operation ran past its ``sysom.loop.step_wait``.  Beside them stand the
harness's own per-layer metrics and breakdown, and the host cost of the
spans the loop opens in a step while no trace is taken.  The last line of standard output
is one JSON object.  The correctness check of ``bench/run.py`` is left out.
"""
import time

T_START = time.monotonic()

import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from benchlib import harness, programtrace, tracereduce  # noqa: E402
from benchlib.spec import find_cell, metric_reader  # noqa: E402

HOST_COST_STEPS = 20000


def host_cost_us_per_step(steps: int = HOST_COST_STEPS):
    """Host microseconds a step spends in the loop's step annotation, its
    five spans and the wait on a ready step counter, with no trace
    active; the loop's agent spans come every tenth step.  None for a
    program that opens no spans."""
    import jax
    import jax.numpy as jnp
    try:
        from repro.core.spans import span
    except ImportError:
        return None

    ready = jax.block_until_ready(jnp.int32(0))
    names = ("sysom.loop.next_batch", "sysom.loop.dispatch",
             "sysom.loop.loss_fetch", "sysom.loop.observe")

    def annotated():
        for i in range(steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                for name in names:
                    with span(name, step=i):
                        pass
                with span("sysom.loop.step_wait", step=i):
                    jax.block_until_ready(ready)
                if i % 10 == 9:
                    with span("sysom.agent.flush", step=i):
                        pass
                    with span("sysom.service.process", epoch=i):
                        pass

    def bare():
        for i in range(steps):
            pass

    def best(fn):
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return min(times)

    return 1e6 * (best(annotated) - best(bare)) / steps


def report(cell, seed: int, seconds: float, t_start: float) -> dict:
    """Drive one traced run of ``cell`` and read it (see the module)."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    devs = harness.check_devices(cell.chips)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    probe = harness.drive(cell, seed, seconds, True)
    e2e = harness.end_to_end(cell, probe, t_start)

    devices, marks = tracereduce.read_xplane(probe.trace_dir, ("next_batch",))
    spans = programtrace.read_spans(probe.trace_dir)
    ctx, breakdown = harness.read_trace(cell, probe, devs[0].device_kind,
                                        cell.chips)
    starts = sorted(s for s, _, _ in marks)
    lo, hi, steps = starts[0], starts[-1], len(starts) - 1

    lengths = {}
    for s, e, name, _ in spans:
        lengths.setdefault(name, []).append(e - s)
    out = {"device": devs[0].device_kind, "steps": steps,
           "step_period_ms": 1e3 * ctx.window_s / steps,
           "step_period_ms_median": statistics.median(
               b - a for a, b in zip(starts, starts[1:])) / 1e6,
           "end_to_end": e2e,
           "per_layer": {m["name"]: metric_reader(m["name"], cell.root)(ctx)
                         for m in cell.per_layer},
           "breakdown": breakdown,
           "host_cost_us_per_step": host_cost_us_per_step(),
           "span_counts": {n: len(v) for n, v in lengths.items()},
           "span_ms_mean": {n: statistics.mean(v) / 1e6
                            for n, v in lengths.items()}}
    if not devices:
        return out
    ops = [(s, e) for s, e, _ in devices[sorted(devices)[0]]]
    per_step = {k: v * 1e3 / steps for k, v in ctx.device_s_by_op.items()}
    scopes = programtrace.device_by_scope(per_step, ctx.op_path)
    unscoped = sorted(((t, n) for n, t in per_step.items() if
                       programtrace.scope_of(ctx.op_path.get(n, "")) ==
                       programtrace.UNSCOPED), reverse=True)[:8]
    idle = tracereduce.gaps(ops, lo, hi)
    # per step, between successive batch requests: a stall moves the mean
    # and not the median
    step_idle = [programtrace.overlap(idle, [(a, b)])
                 for a, b in zip(starts, starts[1:])]
    # > 0: the step's last device operation ended after the wait
    lags = [lag / 1e3 for _, lag in programtrace.step_wait_lag(ops, spans)]
    out.update(
        busy_ms=sum(per_step.values()),
        device_ms_by_scope=dict(sorted(scopes.items())),
        unscoped_top_ms={n: [t, ctx.op_path.get(n, "")[-120:]]
                         for t, n in unscoped},
        idle_ms=sum(e - s for s, e in idle) / 1e6 / steps,
        idle_ms_median=statistics.median(step_idle) / 1e6,
        idle_ms_by_span={k: v * 1e3 / steps
                         for k, v in ctx.idle_s_by_span.items()},
        step_wait_lag_us={"steps": len(lags),
                          "late": sum(lag > 0 for lag in lags),
                          "max": max(lags, default=None),
                          "median": statistics.median(lags) if lags
                          else None})
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    print(json.dumps(report(find_cell(args.workload), args.seed,
                            args.seconds, T_START)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
