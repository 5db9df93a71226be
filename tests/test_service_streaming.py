"""Streaming-service invariants: bounded state, incremental flame graphs,
ring-buffered windows, and dataclass uploads converted to columns at the
service's door."""
import pytest

from repro.core import simcluster as sc
from repro.core.baseline import BaselineStore
from repro.core.events import ProfileBatch
from repro.core.flamegraph import FlameGraph
from repro.core.service import CentralService
from repro.core.sharded import ShardedService, shard_of
from repro.core.trace import encode_batch, to_columnar


# -- FlameGraph streaming primitives ----------------------------------------

def test_add_graph_matches_merge():
    a, b = FlameGraph(), FlameGraph()
    a.add(("main", "f"), 3)
    b.add(("main", "g"), 2)
    b.add(("main", "f"), 1)
    merged = a.merge(b)
    a.add_graph(b)
    assert a.counts == merged.counts
    assert a.total == merged.total


def test_decay_preserves_fractions_and_prunes():
    fg = FlameGraph()
    fg.add(("main", "hot"), 80)
    fg.add(("main", "cold"), 20)
    before = fg.function_fractions()
    fg.decay(0.5)
    after = fg.function_fractions()
    for fn, fr in before.items():
        assert after[fn] == pytest.approx(fr)
    # tiny stacks are dropped once decayed under the prune floor
    fg2 = FlameGraph()
    fg2.add(("x",), 1)
    for _ in range(20):
        fg2.decay(0.5)
    assert fg2.counts == {}
    assert fg2.total == 0


def test_copy_is_independent():
    fg = FlameGraph()
    fg.add(("a",), 5)
    snap = fg.copy()
    fg.add(("a",), 5)
    fg.decay(0.1)
    assert snap.counts[("a",)] == 5
    assert snap.total == 5


# -- bounded service state ---------------------------------------------------

def test_streaming_state_is_bounded():
    svc = CentralService(window=50)
    cl = sc.SimCluster(n_ranks=8, seed=0, samples_per_iter=100)
    cl.run(svc, 300, process_every=10)
    st = svc.stats()
    assert st["ingested"] == 300 * 8
    assert st["iter_time_entries"] <= 50           # ring buffer, not history
    assert st["ranks"] == 8
    # decayed per-rank graphs hold the *live* stack set, not one entry per
    # ever-observed sample: total weight ~ samples_per_iter * fg_window
    for fg in svc._rank_fg.values():
        assert fg.total < 100 * svc.fg_window * 2
        assert len(fg.counts) < 64


@pytest.mark.parametrize("fault", [
    sc.thermal_throttle(0, start=30),
    sc.nic_softirq(4, start=30),
    sc.logging_overhead(start=30),
])
def test_dataclass_ingest_matches_encoded_columnar(fault):
    """The door: one simulated stream, fed to one service as dataclasses
    and to another as wire-encoded columnar batches, diagnoses the same
    events with the same verdict confidences."""
    by_obj, by_wire = CentralService(window=50), CentralService(window=50)
    cl = sc.SimCluster(n_ranks=8, seed=7)
    for it in range(90):
        if it == 30:
            cl.add_fault(fault)
        profiles = cl.step()
        for p in profiles:
            by_obj.ingest(p)
        by_wire.ingest_encoded(encode_batch(to_columnar(
            ProfileBatch("job-0", profiles))))
        if (it + 1) % 10 == 0:
            by_obj.process()
            by_wire.process()

    def tuples(svc):
        return [(e.group_id, e.root_cause, e.category, e.straggler_rank)
                for e in svc.events]

    assert by_obj.events and tuples(by_obj) == tuples(by_wire)
    assert [e.verdict.confidence for e in by_obj.events] == pytest.approx(
        [e.verdict.confidence for e in by_wire.events])


def test_event_counts_incremental():
    svc = CentralService(window=50)
    cl = sc.SimCluster(n_ranks=8, seed=7)
    cl.run(svc, 30)
    cl.add_fault(sc.nic_softirq(4, start=30))
    cl.run(svc, 60)
    counts = svc.event_counts()
    assert counts.get("os_interference", 0) == sum(
        1 for e in svc.events if e.category == "os_interference")
    svc.ingest_log_line("job-0", "worker: CUDA out of memory at step 12")
    assert svc.event_counts()["software"] >= 1


def test_idle_groups_are_evicted():
    import time as _time
    svc = CentralService(window=50, group_ttl_s=100.0)
    cl = sc.SimCluster(n_ranks=4, seed=0, samples_per_iter=50)
    cl.run(svc, 20, process_every=10)
    g = cl.group_id
    assert g in svc._group_ranks
    svc._last_ingest[g] = _time.monotonic() - 101.0   # simulate idleness
    svc.process()
    assert svc.groups_evicted == 1
    assert g not in svc._group_ranks
    assert g not in svc.waterlines
    assert g not in svc._group_iter_time
    assert not any(gg == g for (gg, _r) in svc._rank_fg)
    assert not any(gg == g for (gg, _r) in svc._latest)
    assert g not in svc.detector._groups
    assert g not in svc.detector.aligner._groups
    # a re-appearing group starts clean and is analysed normally again
    cl.run(svc, 20, process_every=10)
    assert g in svc._group_ranks


# -- baseline store bounds ---------------------------------------------------

def test_baseline_store_lru_bound():
    store = BaselineStore(max_entries=3)
    fg = FlameGraph()
    fg.add(("m",), 1)
    for i in range(5):
        store.save("job", f"g{i}", fg, iter_time=0.1)
    assert len(store) == 3
    assert store.evicted == 2
    assert store.get("job", "g0") is None
    assert store.get("job", "g4") is not None
    assert store.iter_time("job", "g0") is None


def test_baseline_iter_time_reads_keep_entry_warm():
    """_check_temporal only touches a healthy group's baseline via
    iter_time(); that read must refresh LRU position or churn from other
    jobs evicts an actively-monitored baseline."""
    store = BaselineStore(max_entries=2)
    fg = FlameGraph()
    fg.add(("m",), 1)
    store.save("job", "live", fg, iter_time=0.1)
    store.save("job", "other0", fg, iter_time=0.1)
    assert store.iter_time("job", "live") == 0.1      # warm the live entry
    store.save("job", "other1", fg, iter_time=0.1)    # evicts other0
    assert store.get("job", "live") is not None
    assert store.get("job", "other0") is None


def test_baseline_store_snapshots_live_graphs():
    store = BaselineStore()
    fg = FlameGraph()
    fg.add(("m",), 10)
    store.save("job", "g", fg)
    fg.decay(0.01)                      # mutate the live graph afterwards
    saved = store.get("job", "g")
    assert saved.counts[("m",)] == 10


# -- sharded routing ---------------------------------------------------------

def test_shard_routing_is_stable_and_total():
    groups = [f"{h:016x}" for h in range(97)]
    for g in groups:
        idx = shard_of(g, 8)
        assert 0 <= idx < 8
        assert idx == shard_of(g, 8)    # deterministic


def test_sharded_service_routes_groups_to_distinct_shards():
    fleet = sc.MultiGroupSimCluster(n_groups=8, ranks_per_group=4, seed=1,
                                    samples_per_iter=40)
    svc = ShardedService(n_shards=4, window=50)
    fleet.run(svc, 12, process_every=6)
    assert svc.ingested == 8 * 4 * 12
    populated = [s for s in svc.shards if s.ingested]
    assert len(populated) >= 2          # groups actually spread out
    # each group's state lives on exactly its routed shard
    for g in fleet.group_ids():
        owner = svc.shard_for(g)
        for s in svc.shards:
            assert (g in s._group_ranks) == (s is owner)


def test_sharded_symbol_repo_is_shared():
    svc = ShardedService(n_shards=3)
    assert all(s.symbol_repo is svc.symbol_repo for s in svc.shards)


def test_sharded_log_lines_round_robin():
    svc = ShardedService(n_shards=2)
    for i in range(4):
        ev = svc.ingest_log_line("job-0", "NCCL timeout on rank 3")
        assert ev is not None and ev.root_cause == "nccl_timeout"
    assert svc.event_counts() == {"software": 4}
    assert all(len(s.events) == 2 for s in svc.shards)
