"""Benchmark harness: one module per paper table/figure + the roofline
table.  Prints ``name,us_per_call,derived`` CSV lines per the contract.

  bench_overhead     — Table 2 (throughput vs sampling rate; asserts the
                       0.10-rate sampler cpu_fraction stays under its
                       pre-batch measurement)
  bench_unwind       — Fig 3  (frame accuracy) + §3.3 cost analysis +
                       the batch-vs-scalar collection gate (≥5x, byte-
                       identical stacks/markers, fp_fraction pin)
  bench_symbols      — Fig 4 / §5.3 (misattribution)
  bench_straggler    — Fig 5  (slow-rank detection sweep)
  bench_aggregation  — §4    (10–50x volume reduction)
  bench_attribution  — blame-timeline vectorization gate (>=5x vs the
                       naive per-event walk) + sub-second 1k-rank
                       cascade localization cycles
  bench_cases        — §5.4  (five end-to-end case studies) + Fig 2
  bench_scenarios    — full scenario-registry matrix (every registered
                       scenario x streaming/columnar/sharded/pod)
  bench_service      — streaming service cycle growth + 1k-rank sharded
                       fleet
  bench_query        — query-plane gates: 32-reader ingest-regression
                       guard (< 1.2x cycle slowdown) + sustained-ingest
                       query throughput/p99 floors
  bench_trace        — columnar wire codec + encoded-vs-dataclass ingest
                       (incl. wire v3 session-vs-stateless volume)
  bench_fleet        — 32k-rank pod-tier smoke cell: sub-second facade
                       cycles, cascade root localized, wire v3
                       bytes-per-rank-iteration >=3x under v2, peak RSS
                       per rank
  bench_shm          — shared-memory collection plane: SPSC ring upload
                       >=3x pipe-RPC throughput at 32k-rank session
                       frames + facade parallel digest decode+merge
                       >=2x serial at 32 pods (cores-gated)
  bench_chaos        — pinned seeded fault storm (flapping faults,
                       agent dropouts, mitigation blips): all roots
                       localized, flip rate under threshold, zero
                       victims cordoned, replay rejects the decoy
  bench_pod_ft       — multi-process pod tier under pod loss: 25% of
                       pod workers SIGKILLed mid-storm — degraded
                       window visible (coverage + annotations), all
                       roots still localized, zero victims cordoned,
                       respawn + session resync restores coverage 1.0
  bench_roofline     — EXPERIMENTS §Roofline table from the dry-run

Besides the CSV lines on stdout, every run writes ``BENCH_service.json``
(name -> {us_per_call, derived}) so CI and future PRs can diff the perf
trajectory machine-readably.

Each bench module runs in a child process of its own, and this parent
never imports JAX: a bench that brings up an accelerator holds it only
for its own run, and no pod worker is ever forked from a process that
holds one.
"""
from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

MODULES = [
    "benchmarks.bench_cases",
    "benchmarks.bench_scenarios",
    "benchmarks.bench_straggler",
    "benchmarks.bench_unwind",
    "benchmarks.bench_symbols",
    "benchmarks.bench_aggregation",
    "benchmarks.bench_attribution",
    "benchmarks.bench_overhead",
    "benchmarks.bench_service",
    "benchmarks.bench_query",
    "benchmarks.bench_trace",
    "benchmarks.bench_fleet",
    "benchmarks.bench_shm",
    "benchmarks.bench_chaos",
    "benchmarks.bench_pod_ft",
    "benchmarks.bench_roofline",
]

JSON_PATH = os.environ.get("BENCH_JSON", "BENCH_service.json")


def lines_to_json(lines) -> dict:
    """Parse ``name,us_per_call,derived`` CSV lines (comments skipped)."""
    out = {}
    for line in lines:
        line = str(line)
        if line.startswith("#") or "," not in line:
            continue
        name, _, rest = line.partition(",")
        us, _, derived = rest.partition(",")
        try:
            us_val = float(us)
        except ValueError:
            us_val = None
        out[name.strip()] = {"us_per_call": us_val, "derived": derived}
    return out


def run_child(modname: str, out_path: str) -> None:
    """Child side: run one bench module, write its lines (and the error
    that stopped it, if any) to ``out_path`` as JSON."""
    lines: list = []
    error = None
    try:
        importlib.import_module(modname).run(lines)
    except Exception as e:  # noqa: BLE001
        error = repr(e)
    with open(out_path, "w") as f:
        json.dump({"lines": [str(l) for l in lines], "error": error}, f)


def run_module(modname: str) -> tuple:
    """Run one bench module in a child process; return its lines and
    the error that stopped it (None when it finished)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT)] + [p for p in [env.get("PYTHONPATH")] if p])
    with tempfile.TemporaryDirectory() as tmp:
        out_path = os.path.join(tmp, "lines.json")
        rc = subprocess.call([sys.executable, "-m", "benchmarks.run",
                              "--child", modname, out_path], env=env)
        if not os.path.exists(out_path):
            return [], f"bench child exited {rc} without a result"
        with open(out_path) as f:
            res = json.load(f)
    return res["lines"], res["error"]


def main() -> None:
    if sys.argv[1:2] == ["--child"]:
        run_child(*sys.argv[2:4])
        return
    only = sys.argv[1:] or None
    known = {m.split(".")[-1] for m in MODULES}
    if only and not set(only) <= known:
        print(f"unknown benchmark(s): {sorted(set(only) - known)}; "
              f"choose from {sorted(known)}", file=sys.stderr)
        sys.exit(2)
    lines: list = []
    failures = []
    for modname in MODULES:
        short = modname.split(".")[-1]
        if only and short not in only:
            continue
        t0 = time.monotonic()
        before = len(lines)
        try:
            child_lines, error = run_module(modname)
            lines.extend(child_lines)
            if error is not None:
                raise RuntimeError(error)
            # a bench that "passes" while emitting no measurements is a
            # silently-dead gate: the artifact diff would show nothing
            # regressed because nothing was measured
            if not lines_to_json(lines[before:]):
                raise RuntimeError(
                    f"{short}.run() produced no BENCH entries")
            lines.append(f"{short}_wall,{(time.monotonic()-t0)*1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            failures.append((short, repr(e)))
            lines.append(f"{short}_wall,0,FAILED:{e!r}"[:200])
        print(f"[bench] {short} done in {time.monotonic()-t0:.1f}s",
              file=sys.stderr)
    print("\n".join(str(l) for l in lines))
    # merge into any existing file so subset runs (e.g. CI's bench-smoke)
    # refresh their entries without clobbering the rest of the trajectory
    merged = {}
    if os.path.exists(JSON_PATH):
        try:
            with open(JSON_PATH) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
    merged.update(lines_to_json(lines))
    # the failure count is part of the trajectory file itself, so a
    # partial JSON from a red run can never be mistaken for a green one
    # by anything consuming the uploaded artifact
    merged["bench_run_failures"] = {
        "us_per_call": None,
        "derived": ";".join(f"{m}:{e}" for m, e in failures) or "none",
        "count": len(failures),
    }
    try:
        with open(JSON_PATH, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
        print(f"[bench] wrote {JSON_PATH}", file=sys.stderr)
    finally:
        # a failing bench module must fail the run (and CI) even if the
        # JSON write itself also blew up
        if failures:
            print(f"{len(failures)} benchmark(s) failed: {failures}",
                  file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
