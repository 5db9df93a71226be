"""Baseline vs optimized-variant roofline comparison for every train cell."""
import json
from pathlib import Path

from repro import configs
from repro.launch.mesh import (CHIPS_PER_POD, PRODUCTION_DEVICE_KIND,
                               device_peaks)
from repro.models.config import SHAPES

PEAKS = device_peaks(PRODUCTION_DEVICE_KIND)
R = Path(__file__).resolve().parents[1] / "results" / "dryrun"

OPT_VARIANT = {a: "chunked_attn" for a in configs.ASSIGNED_ARCHS}
OPT_VARIANT["mixtral-8x22b"] = "opt_moe_sp"
OPT_VARIANT["qwen3-moe-30b-a3b"] = "opt_moe_sp"
OPT_VARIANT["mamba2-370m"] = "baseline"   # attention-free: variant is a no-op


def terms(rec):
    return (rec["flops_per_device"] / PEAKS.flops_bf16,
            rec["bytes_per_device"] / PEAKS.hbm_bw,
            rec["collective_bytes_total"] / PEAKS.ici_bw)


def main():
    sh = SHAPES["train_4k"]
    print(f"{'arch':20s} {'variant':14s} {'base_bound':>11s} {'opt_bound':>10s} "
          f"{'gain':>7s} {'roofl%':>7s}")
    for arch in configs.ASSIGNED_ARCHS:
        v = OPT_VARIANT[arch]
        bp = R / f"{arch}_train_4k_pod1_baseline_cost.json"
        op = R / f"{arch}_train_4k_pod1_{v}_cost.json"
        if not (bp.exists() and op.exists()):
            print(f"{arch:20s} (missing records)")
            continue
        b = json.loads(bp.read_text())
        o = json.loads(op.read_text())
        if not (b.get("ok") and o.get("ok")):
            continue
        bb, ob = max(terms(b)), max(terms(o))
        cfg = configs.get(arch)
        mf = 6.0 * cfg.param_count(active_only=cfg.is_moe) * \
            sh.global_batch * sh.seq_len
        frac = mf / (CHIPS_PER_POD * PEAKS.flops_bf16) / ob * 100
        print(f"{arch:20s} {v:14s} {bb:11.2f} {ob:10.2f} "
              f"{bb/ob:6.1f}x {frac:7.2f}")


if __name__ == "__main__":
    main()
