"""Scenario-registry matrix — coverage beyond the five §5.4 cases.

Drives every scenario registered in ``repro.core.scenarios`` through all
four service paths (dataclass ingest, wire-encoded columnar, sharded
front-end, hierarchical pod tier over wire v3 sessions) via
``simcluster.run_scenario_matrix`` and reports, per scenario, the wall
time over the four paths and whether every path
produced the expected diagnosis.  The run *asserts* full coverage: one
MISS anywhere fails the benchmark (and CI's bench gate).
"""
from __future__ import annotations

import time
from typing import Dict, List

from repro.core.scenarios import default_registry
from repro.core.simcluster import SERVICE_PATHS, run_scenario_matrix


def run(out_lines: List[str]) -> Dict[str, float]:
    reg = default_registry()
    out_lines.append(
        "# scenario matrix: scenario,us_over_all_paths,verdict "
        f"(paths: {'/'.join(SERVICE_PATHS)})")
    total = ok = 0
    t_all = time.monotonic()
    for scen in reg:
        t0 = time.monotonic()
        results = run_scenario_matrix(registry=reg, scenarios=[scen])
        dt = time.monotonic() - t0
        per_path = results[scen.name]
        misses = [f"{p}:{r.first_cause}@{r.first_rank}"
                  for p, r in per_path.items() if not r.ok]
        total += len(per_path)
        ok += sum(r.ok for r in per_path.values())
        verdict = "OK" if not misses else "MISS:" + ";".join(misses)
        out_lines.append(
            f"scenario_{scen.name},{dt*1e6:.0f},"
            f"{verdict}:{scen.expected_cause}")
    wall = time.monotonic() - t_all
    out_lines.append(
        f"scenario_matrix_total,{wall*1e6:.0f},"
        f"{ok}/{total}_cells_ok_{len(reg)}_scenarios")
    assert len(reg) >= 10, f"registry shrank to {len(reg)} scenarios"
    assert ok == total, f"scenario matrix misses: {total - ok}/{total}"
    return {"scenarios": float(len(reg)), "cells_ok": float(ok),
            "wall_s": wall}


if __name__ == "__main__":
    lines: List[str] = []
    print(run(lines))
    print("\n".join(lines))
