"""Read the numbers that decide ``correct``, to set their limits.

    python3 bench/readings.py --workload <name> --seeds 1 2 3 [--out FILE]

For each seed, in one process on the chip: the program's first steps as a
run drives them (set-up only, no measured window), the plain reference in
float32, the control (the reference in fp8, put in the program's place)
and a planted fault (the reference with half of each batch left out, the
mean taken over the rest), each compared with the float32 reference as a
run compares the program.  A step that returns its state unchanged reads
1 on ``update_norm_gap`` by definition and needs no run.  One JSON line
per seed goes to standard output and to ``--out``.
"""
import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from benchlib import harness, reftrain  # noqa: E402
from benchlib.spec import find_cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = find_cell(args.workload)
    harness.check_devices(cell.chips)
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    half = cell.config["batch"] // 2
    for seed in args.seeds:
        t0 = time.monotonic()
        probe = harness.drive(cell, seed, 0.0, False)
        got = harness.program_readings(probe)
        batches = probe.batches
        del probe
        gc.collect()
        t1 = time.monotonic()
        ref = harness.reference_readings(cell, seed, batches)
        t2 = time.monotonic()
        ctl = harness.reference_readings(cell, seed, batches, q=reftrain.fp8)
        t3 = time.monotonic()
        halfb = harness.reference_readings(cell, seed, batches,
                                           keep_rows=half)
        line = {"workload": cell.name, "seed": seed,
                "program": reftrain.gaps(got, ref),
                "control_fp8": reftrain.gaps(ctl, ref),
                "half_batch": reftrain.gaps(halfb, ref),
                "excluded": reftrain.excluded_leaves(ref),
                "worst_leaves": {
                    who: {k: max(g, key=g.get) for k, g in (
                        ("grad", reftrain.leaf_gaps(r.grad_norms,
                                                    ref.grad_norms)),
                        ("update", reftrain.leaf_gaps(
                            r.change_norms, ref.change_norms,
                            reftrain.excluded_leaves(ref))))}
                    for who, r in (("program", got), ("control_fp8", ctl))},
                "losses": {"program": got.losses, "reference": ref.losses},
                "seconds": {"program": t1 - t0, "reference": t2 - t1,
                            "control": t3 - t2}}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
