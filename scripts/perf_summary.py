"""Generate the EXPERIMENTS §Perf before/after table from cost records."""
import json
from pathlib import Path

from repro.launch.mesh import (CHIPS_PER_POD, PRODUCTION_DEVICE_KIND,
                               device_peaks)
from repro import configs

PEAKS = device_peaks(PRODUCTION_DEVICE_KIND)
R = Path(__file__).resolve().parents[1] / "results" / "dryrun"

CELLS = {
    "A qwen2-0.5b/train_4k (warm-up: worst dense train)": (
        "qwen2-0.5b_train_4k",
        ["baseline", "chunked_attn", "chunked_attn_nofsdp", "opt_dense"]),
    "B minicpm-2b/prefill_32k (worst roofline fraction)": (
        "minicpm-2b_prefill_32k",
        ["baseline", "chunked_attn", "chunked_attn_sp", "opt_serve"]),
    "C mamba2-370m/prefill_32k (most collective-bound)": (
        "mamba2-370m_prefill_32k",
        ["baseline", "no_ssm_tp", "no_ssm_tp_nofsdp", "no_fsdp"]),
    "D mixtral-8x22b/train_4k (most representative)": (
        "mixtral-8x22b_train_4k",
        ["baseline", "opt_fsdp", "opt_moe", "opt_sp", "opt_moe_sp"]),
}


def model_flops(tag: str) -> float:
    arch, shape = tag.rsplit("_", 2)[0], "_".join(tag.rsplit("_", 2)[1:])
    cfg = configs.get(arch)
    from repro.models.config import SHAPES
    sh = SHAPES[shape]
    n = cfg.param_count(active_only=cfg.is_moe)
    tokens = sh.global_batch * (sh.seq_len if sh.kind != "decode" else 1)
    return (6.0 if sh.kind == "train" else 2.0) * n * tokens


def main():
    for title, (tag, variants) in CELLS.items():
        mf = model_flops(tag)
        ideal = mf / (CHIPS_PER_POD * PEAKS.flops_bf16)
        print(f"\n### {title}   MODEL_FLOPS={mf:.3e}, ideal={ideal:.4f}s")
        print(f"{'variant':26s} {'compute_s':>10s} {'memory_s':>10s} "
              f"{'coll_s':>9s} {'bound_s':>10s} {'roofline%':>9s} {'useful':>7s}")
        base_bound = None
        for v in variants:
            p = R / f"{tag}_pod1_{v}_cost.json"
            if not p.exists():
                print(f"{v:26s} (missing)")
                continue
            r = json.loads(p.read_text())
            comp = r["flops_per_device"] / PEAKS.flops_bf16
            mem = r["bytes_per_device"] / PEAKS.hbm_bw
            coll = r["collective_bytes_total"] / PEAKS.ici_bw
            bound = max(comp, mem, coll)
            if base_bound is None:
                base_bound = bound
            useful = mf / (r["flops_per_device"] * CHIPS_PER_POD)
            print(f"{v:26s} {comp:10.3f} {mem:10.3f} {coll:9.3f} "
                  f"{bound:10.3f} {100*ideal/bound:9.3f} {useful:7.3f}")
        if base_bound:
            print(f"{'=> improvement':26s} {'':10s} {'':10s} {'':9s} "
                  f"{base_bound/bound:9.1f}x")


if __name__ == "__main__":
    main()
