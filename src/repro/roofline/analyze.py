"""Three-term roofline analysis from dry-run artifacts (EXPERIMENTS §Roofline).

    compute    = HLO_FLOPs   / (chips * peak_FLOP/s)
    memory     = HLO_bytes   / (chips * HBM_bw)
    collective = coll_bytes  / (chips * link_bw)

The peaks come from ``launch.mesh.DEVICE_PEAKS`` for the record's
``device_kind`` (the chip the dry run's mesh models).

cost_analysis() FLOPs/bytes from the compiled per-device program are
multiplied back to global by ``devices`` (XLA reports the per-device
partition); collective bytes come from the HLO parse (roofline.hlo).
MODEL_FLOPS uses 6*N*D for training (2*N*D inference), N = active params.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

from repro.launch.mesh import CHIPS_PER_POD, device_peaks


@dataclasses.dataclass
class RooflineRow:
    arch: str
    shape: str
    variant: str
    kind: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops: float
    useful_ratio: float            # MODEL_FLOPS / HLO_FLOPS (global)
    roofline_fraction: float       # best-case fraction of peak on dominant
    note: str = ""

    @property
    def bound_time(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def analyze_record(rec: dict, chips: int = CHIPS_PER_POD) -> Optional[RooflineRow]:
    if not rec.get("ok"):
        return None
    cost = rec.get("cost_analysis", {})
    flops_per_dev = cost.get("flops", 0.0)
    bytes_per_dev = cost.get("bytes accessed", 0.0)
    devices = rec.get("devices", chips)
    peaks = device_peaks(rec.get("device_kind"))

    hlo_flops_global = flops_per_dev * devices
    hlo_bytes_global = bytes_per_dev * devices
    coll_bytes_global = rec.get("collective_bytes_total", 0) * devices

    compute_s = hlo_flops_global / (chips * peaks.flops_bf16)
    memory_s = hlo_bytes_global / (chips * peaks.hbm_bw)
    collective_s = coll_bytes_global / (chips * peaks.ici_bw)

    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    n = (rec.get("params_active") or rec.get("params_total") or 0)
    tokens = rec["global_batch"] * (rec["seq_len"] if rec["kind"] != "decode"
                                    else 1)
    mult = 6.0 if rec["kind"] == "train" else 2.0
    model_flops = mult * n * tokens
    useful = model_flops / hlo_flops_global if hlo_flops_global else 0.0
    # roofline fraction: useful work per second at the bound, vs peak
    bound = max(terms.values())
    roofline_fraction = (model_flops / (chips * peaks.flops_bf16) / bound
                         if bound > 0 else 0.0)

    return RooflineRow(
        arch=rec["arch"], shape=rec["shape"], variant=rec.get("variant", "?"),
        kind=rec["kind"], compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant,
        model_flops=model_flops, hlo_flops=hlo_flops_global,
        useful_ratio=useful, roofline_fraction=roofline_fraction)


def load_rows(results_dir, *, multi_pod: bool = False,
              variant: str = "baseline") -> List[RooflineRow]:
    """Prefers the unrolled cost-extrapolated records (*_cost.json): the
    scanned full-depth compile under-reports per-layer cost because XLA
    cost analysis counts a while-loop body once (DESIGN.md §Roofline)."""
    results_dir = Path(results_dir)
    rows = []
    for p in sorted(results_dir.glob("*.json")):
        if p.name.endswith("_cost.json"):
            continue
        rec = json.loads(p.read_text())
        if rec.get("multi_pod", False) != multi_pod:
            continue
        if rec.get("variant", "baseline") != variant:
            continue
        cost_p = results_dir / p.name.replace(".json", "_cost.json")
        if cost_p.exists():
            crec = json.loads(cost_p.read_text())
            if crec.get("ok"):
                rec = dict(rec)
                rec["cost_analysis"] = {
                    "flops": crec["flops_per_device"],
                    "bytes accessed": crec["bytes_per_device"]}
                rec["collective_bytes_total"] = crec["collective_bytes_total"]
                rec["collective_bytes_by_op"] = crec["collective_bytes_by_op"]
        row = analyze_record(rec)
        if row:
            rows.append(row)
    return rows


def format_table(rows: List[RooflineRow]) -> str:
    hdr = (f"{'arch':22s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'collect_s':>10s} {'dominant':>10s} {'useful':>7s} {'roofl%':>7s}")
    lines = [hdr, "-" * len(hdr)]
    for r in sorted(rows, key=lambda r: (r.arch, r.shape)):
        lines.append(
            f"{r.arch:22s} {r.shape:12s} {r.compute_s:10.3e} "
            f"{r.memory_s:10.3e} {r.collective_s:10.3e} {r.dominant:>10s} "
            f"{r.useful_ratio:7.3f} {100*r.roofline_fraction:7.2f}")
    return "\n".join(lines)


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default=str(
        Path(__file__).resolve().parents[3] / "results" / "dryrun"))
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()
    rows = load_rows(args.results, variant=args.variant)
    print(format_table(rows))


if __name__ == "__main__":
    main()
