"""Service-scale benchmark: the streaming service's ingest and cycle cost.

Four measurements back the bounded-state claims of the streaming service:

  1. ingest throughput (profiles/sec), single group;
  2. steady-state process() cycle latency while a uniform regression is
     *active* (the temporal path re-checks the group flame graph every
     cycle), at growing totals of ingested profiles.  Acceptance: the
     cycle latency grows sub-linearly in total ingested profiles — the
     state is the live window, not the history — and the retained
     iteration-time entries stay within the ring buffer;
  3. encoded columnar batches vs. per-dataclass ingest of one fleet;
  4. a 1,024-rank fleet (32 groups x 32 ranks) with concurrent
     heterogeneous faults driven into an 8-shard ShardedService, reporting
     sustained fleet ingest rate, cycle time, and that both injected root
     causes are diagnosed.

Emits ``name,us_per_call,derived`` CSV lines like every other module.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

from repro.core import simcluster as sc
from repro.core.service import CentralService
from repro.core.sharded import ShardedService

CHECKPOINTS = (40, 160, 640)            # iterations (8 ranks => x8 profiles)


def _ingest_throughput(iters: int = 120) -> float:
    svc = CentralService(window=50)
    cl = sc.SimCluster(n_ranks=8, seed=1, samples_per_iter=100)
    profiles = [p for _ in range(iters) for p in cl.step()]
    t0 = time.monotonic()
    for p in profiles:
        svc.ingest(p)
    dt = time.monotonic() - t0
    return len(profiles) / dt


def _steady_cycle_latency() -> Tuple[List[float], CentralService]:
    """Mean process() wall time (us) over the last 10 one-iteration cycles
    before each checkpoint, with a logging regression active throughout."""
    svc = CentralService(window=50)
    cl = sc.SimCluster(n_ranks=8, seed=2, samples_per_iter=100)
    cl.run(svc, 20, process_every=10)        # healthy baseline bootstrap
    cl.add_fault(sc.logging_overhead(start=20))
    out, done = [], 20
    for n in CHECKPOINTS:
        lat: List[float] = []
        for _ in range(n - done):
            for p in cl.step():
                svc.ingest(p)
            t0 = time.monotonic()
            svc.process()
            lat.append(time.monotonic() - t0)
        done = n
        tail = lat[-10:]
        out.append(sum(tail) / len(tail) * 1e6)
    return out, svc


def _fleet(n_groups: int = 32, ranks_per_group: int = 32, iters: int = 25,
           n_shards: int = 8) -> Dict[str, float]:
    fleet = sc.MultiGroupSimCluster(n_groups=n_groups,
                                    ranks_per_group=ranks_per_group,
                                    seed=3, samples_per_iter=40)
    svc = ShardedService(n_shards=n_shards, window=50)
    # concurrent heterogeneous faults in different groups
    fleet.add_fault(1, sc.nic_softirq(4, start=0))
    fleet.add_fault(5, sc.thermal_throttle(0, start=0))
    n = 0
    ingest_dt = process_dt = 0.0
    cycles = 0
    for i in range(iters):
        profiles = fleet.step()
        t0 = time.monotonic()
        for p in profiles:
            svc.ingest(p)
        ingest_dt += time.monotonic() - t0
        n += len(profiles)
        if (i + 1) % 5 == 0:
            t0 = time.monotonic()
            svc.process()
            process_dt += time.monotonic() - t0
            cycles += 1
    causes = {e.root_cause for e in svc.events}
    return {"ranks": fleet.n_ranks, "profiles": n,
            "ingest_rate": n / ingest_dt,
            "process_us": process_dt / max(cycles, 1) * 1e6,
            "events": len(svc.events),
            "diagnosed_nic": float("nic_softirq_contention" in causes),
            "diagnosed_gpu": float("gpu_uniform_slowdown" in causes)}


def run(out_lines: List[str]) -> Dict[str, float]:
    out_lines.append("# streaming service cycle + 1k-rank fleet")
    res: Dict[str, float] = {}

    tp = _ingest_throughput()
    out_lines.append(f"service_ingest_streaming,{1e6/tp:.1f},"
                     f"{tp:.0f}_profiles_per_s")
    res["ingest_streaming_per_s"] = tp

    lat, svc = _steady_cycle_latency()
    for n, us in zip(CHECKPOINTS, lat):
        out_lines.append(f"service_process_streaming_{n}iters,{us:.0f},us")
    # 16x more ingested profiles from first to last checkpoint: the
    # cycle must grow sub-linearly (bounded state)
    growth = lat[-1] / max(lat[0], 1e-9)
    data_growth = CHECKPOINTS[-1] / CHECKPOINTS[0]
    entries = svc.stats()["iter_time_entries"]
    out_lines.append(f"service_process_growth_streaming,0,{growth:.2f}x")
    out_lines.append(f"service_state_iter_entries,0,{entries:.0f}")
    res["process_growth_streaming"] = growth
    assert growth < data_growth / 2, (
        f"streaming process() grew {growth:.1f}x over a "
        f"{data_growth:.0f}x history increase — bounded state is broken")
    assert entries <= svc.window, \
        "streaming iteration-time history must be ring-buffered"

    # encoded columnar batches vs. per-dataclass ingest on one identical
    # fleet workload (same harness as bench_trace; see that module)
    from benchmarks.bench_trace import INGEST_SPEEDUP_FLOOR, \
        compare_fleet_ingest
    cmp_ = compare_fleet_ingest(iters=3)
    out_lines.append(f"service_ingest_encoded_columnar,"
                     f"{1e6/cmp_['col_rate']:.1f},"
                     f"{cmp_['col_rate']:.0f}_profiles_per_s")
    out_lines.append(f"service_ingest_columnar_speedup,0,"
                     f"{cmp_['speedup']:.2f}x_vs_dataclass")
    res["ingest_columnar_speedup"] = cmp_["speedup"]
    assert cmp_["speedup"] >= INGEST_SPEEDUP_FLOOR, (
        f"encoded columnar fleet ingest fell under "
        f"{INGEST_SPEEDUP_FLOOR}x: {cmp_}")

    fleet = _fleet()
    out_lines.append(f"service_fleet_ranks,0,{fleet['ranks']:.0f}")
    out_lines.append(f"service_fleet_ingest,{1e6/fleet['ingest_rate']:.1f},"
                     f"{fleet['ingest_rate']:.0f}_profiles_per_s")
    out_lines.append(f"service_fleet_process,{fleet['process_us']:.0f},"
                     f"{fleet['events']:.0f}_events")
    out_lines.append(f"service_fleet_diagnosed,0,"
                     f"nic={fleet['diagnosed_nic']:.0f}_"
                     f"gpu={fleet['diagnosed_gpu']:.0f}")
    res.update({f"fleet_{k}": v for k, v in fleet.items()})
    assert fleet["ranks"] >= 1000, "fleet benchmark must cover 1000+ ranks"
    assert fleet["diagnosed_nic"] and fleet["diagnosed_gpu"], (
        "fleet-scale sharded service missed an injected fault: "
        f"{fleet}")
    return res


if __name__ == "__main__":
    lines: List[str] = []
    print(run(lines))
    print("\n".join(lines))
