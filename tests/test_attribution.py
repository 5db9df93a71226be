"""Cross-layer causal attribution: blame timelines, per-collective
blame edges, cascade localization across overlapping communication
groups, and verdict provenance."""
import dataclasses

import numpy as np
import pytest

from repro.core import simcluster as sc
from repro.core.attribution import (CASCADE_EXPORT_CAUSE, BlameTimeline,
                                    CascadeExport, iteration_timelines,
                                    iteration_timelines_naive,
                                    localize_cascades)
from repro.core.events import CollectiveEvent
from repro.core.diffdiag import Verdict
from repro.core.service import CentralService, DiagnosticEvent
from repro.core.sharded import ShardedService
from repro.core.straggler import (BlameEdge, GroupBlame, StragglerAlert,
                                  StragglerDetector)
from repro.core.trace import ColumnarProfile, TraceTables
from repro.ft.mitigation import MitigationPlanner


# ---------------------------------------------------------------------------
# blame timelines
# ---------------------------------------------------------------------------


def _profile(tables, rank, *, group="g0", iter_time=1.0, colls=(),
             kernels=(), stacks=(), iteration=0):
    """colls: (op, entry, exit); kernels: (start, dur); stacks:
    (frames tuple, weight)."""
    intern = tables.strings.intern
    return ColumnarProfile(
        rank=rank, iteration=iteration, group_id=group,
        iter_time=iter_time, tables=tables,
        stack_ts=np.zeros(len(stacks)),
        stack_weight=np.array([w for _f, w in stacks], dtype=np.int64),
        stack_kind=np.full(len(stacks), intern("cpu"), dtype=np.int64),
        stack_id=np.array([tables.intern_stack(f) for f, _w in stacks],
                          dtype=np.int64),
        kern_name=np.array([intern(f"k{i}") for i in range(len(kernels))],
                           dtype=np.int64),
        kern_start=np.array([s for s, _d in kernels], dtype=np.float64),
        kern_dur=np.array([d for _s, d in kernels], dtype=np.float64),
        kern_stream=np.zeros(len(kernels), dtype=np.int64),
        coll_op=np.array([intern(op) for op, _e, _x in colls],
                         dtype=np.int64),
        coll_group=np.full(len(colls), intern(group), dtype=np.int64),
        coll_entry=np.array([e for _o, e, _x in colls], dtype=np.float64),
        coll_exit=np.array([x for _o, _e, x in colls], dtype=np.float64),
        coll_nbytes=np.zeros(len(colls), dtype=np.int64),
        coll_dev_dur=np.zeros(len(colls)),
        coll_instance=np.full(len(colls), -1, dtype=np.int64),
        coll_seq=np.full(len(colls), -1, dtype=np.int64))


def test_wait_blamed_on_latest_enterer():
    """Barrier semantics: ranks 0/1 enter early and wait; rank 2 enters
    last and is the culprit of every edge — its own wait is zero."""
    t = TraceTables()
    profs = [
        _profile(t, 0, colls=[("AllReduce", 0.10, 0.45)]),
        _profile(t, 1, colls=[("AllReduce", 0.20, 0.45)]),
        _profile(t, 2, colls=[("AllReduce", 0.40, 0.45)]),
    ]
    tls, edges = iteration_timelines(profs)
    by_rank = {x.rank: x for x in tls}
    assert by_rank[0].blocked_wait == pytest.approx(0.30)
    assert by_rank[1].blocked_wait == pytest.approx(0.20)
    assert by_rank[2].blocked_wait == 0.0
    # transfer = in-collective time after the instance started
    assert by_rank[0].transfer == pytest.approx(0.05)
    assert by_rank[2].transfer == pytest.approx(0.05)
    assert {(e.culprit_rank, e.victim_rank) for e in edges} == \
        {(2, 0), (2, 1)}
    assert all(e.group_id == "g0" and e.op == "AllReduce" for e in edges)


def test_components_sum_to_iter_time_and_exposed_compute():
    t = TraceTables()
    p = _profile(
        t, 0, iter_time=1.0,
        colls=[("AllReduce", 0.5, 0.7)],
        kernels=[(0.0, 0.3), (0.45, 0.15)],   # second overlaps [0.5,0.6]
        stacks=[(("main", "train"), 3), (("ncclAllReduce",), 1)])
    q = _profile(t, 1, iter_time=1.0, colls=[("AllReduce", 0.6, 0.7)])
    tls, _ = iteration_timelines([p, q])
    tl = next(x for x in tls if x.rank == 0)
    # kernel time 0.45 minus 0.10 overlapping the collective
    assert tl.compute == pytest.approx(0.35)
    assert tl.blocked_wait == pytest.approx(0.1)      # waited on rank 1
    assert tl.transfer == pytest.approx(0.1)
    # remainder 0.45 split by stack evidence: 3/4 host, 1/4 residual
    assert tl.host == pytest.approx(0.45 * 0.75)
    assert tl.residual == pytest.approx(0.45 * 0.25)
    assert tl.total == pytest.approx(tl.iter_time)
    # profile-level interval view agrees
    assert p.exposed_kernel_time() == pytest.approx(0.35)


def test_over_budget_components_scale_down():
    """Measured parts exceeding iter_time scale down proportionally, so
    the sum invariant holds even for inconsistent inputs."""
    t = TraceTables()
    p = _profile(t, 0, iter_time=0.1, kernels=[(0.0, 0.3)],
                 colls=[("AllReduce", 0.4, 0.5)])
    q = _profile(t, 1, iter_time=0.1, colls=[("AllReduce", 0.45, 0.5)])
    tls, _ = iteration_timelines([p, q])
    tl = next(x for x in tls if x.rank == 0)
    assert tl.total == pytest.approx(tl.iter_time)
    assert tl.residual == 0.0 and tl.host == 0.0


def test_vectorized_matches_naive_on_sim_iteration():
    t = TraceTables()
    cl = sc.SimCluster(n_ranks=12, seed=5, columnar=True, tables=t,
                       stack_variants=3)
    cl.add_fault(sc.nic_softirq(4))
    profs = cl.step()
    cl2 = sc.SimCluster(n_ranks=12, seed=5, columnar=False,
                        stack_variants=3)
    cl2.add_fault(sc.nic_softirq(4))
    tls, edges = iteration_timelines(profs)
    tls_n, edges_n = iteration_timelines_naive(cl2.step())
    for a, b in zip(tls, tls_n):
        assert (a.rank, a.group_id) == (b.rank, b.group_id)
        assert a.components() == pytest.approx(b.components(), abs=1e-9)
        assert a.total == pytest.approx(a.iter_time)
    assert [(e.culprit_rank, e.victim_rank) for e in edges] == \
        [(e.culprit_rank, e.victim_rank) for e in edges_n]
    assert all(e.culprit_rank == 4 for e in edges)


def test_skew_callable_realigns_entries():
    t = TraceTables()
    profs = [
        _profile(t, 0, colls=[("AllReduce", 0.10, 0.45)]),
        _profile(t, 1, colls=[("AllReduce", 0.40, 0.45)]),
    ]
    # rank 1's clock runs 0.35 ahead: aligned, rank 1 entered EARLIER
    skew = lambda rank, gid: 0.35 if rank == 1 else 0.0
    _tls, edges = iteration_timelines(profs, skew=skew)
    assert {(e.culprit_rank, e.victim_rank) for e in edges} == {(0, 1)}


# ---------------------------------------------------------------------------
# detector: blame edges + summaries, alerts as a view
# ---------------------------------------------------------------------------


def _instance(group, entries, exit_=1.0, op="AllReduce"):
    return [CollectiveEvent(rank=r, group_id=group, op=op, entry=e,
                            exit=exit_) for r, e in entries.items()]


def test_detector_emits_blame_edges_and_summary():
    det = StragglerDetector(window=20, min_instances=4)
    for i in range(6):
        entries = {r: i + r * 1e-5 for r in range(7)}
        entries[7] = i + 0.004                   # the straggler
        det.observe_instance(_instance("gA", entries, exit_=i + 0.01))
    edges = det.drain_edges()
    assert edges and all(isinstance(e, BlameEdge) for e in edges)
    assert all(e.culprit_rank == 7 for e in edges)
    assert {e.victim_rank for e in edges} == set(range(7))
    assert max(e.wait for e in edges) == pytest.approx(0.004, abs=1e-6)
    s = det.blame_summary("gA")
    assert isinstance(s, GroupBlame)
    assert s.culprit_rank == 7 and s.ranks == tuple(range(8))
    assert s.wait[0] == pytest.approx(0.004, abs=1e-6)
    assert s.wait[7] == pytest.approx(0.0, abs=1e-6)
    assert s.instances == 6
    # alerts are a view over the same windowed blame state
    alerts = det.check()
    assert alerts and alerts[0].rank == s.culprit_rank
    assert alerts[0].lateness == pytest.approx(s.culprit_lateness)
    det.forget_group("gA")
    assert det.blame_summary("gA") is None and not det.drain_edges()


# ---------------------------------------------------------------------------
# cascade localization
# ---------------------------------------------------------------------------


def _summary(group, culprit, lateness, *, ranks, wait=None, last_start=0.0):
    lat = {r: (lateness if r == culprit else 0.0) for r in ranks}
    return GroupBlame(
        group_id=group, ranks=tuple(sorted(ranks)), culprit_rank=culprit,
        culprit_lateness=lateness, lateness=lat, wait=wait or {},
        peer_wait=0.0, last_start=last_start, instances=50)


def _alert(group, rank, lateness):
    return StragglerAlert(group, rank, lateness, 0.0, 1e-5, 5.0, 50)


def test_localize_identity_without_cascade():
    alerts = [_alert("gA", 3, 2e-3)]
    summaries = {"gA": _summary("gA", 3, 2e-3, ranks=range(8),
                                wait={r: 2e-3 for r in range(8) if r != 3})}
    locs, exports = localize_cascades(alerts, summaries)
    assert not exports
    assert len(locs) == 1
    loc = locs[0]
    assert (loc.root_group, loc.root_rank) == ("gA", 3)
    assert loc.chain == ("gA",) and loc.alert is alerts[0]
    assert loc.victim_ranks == tuple(r for r in range(8) if r != 3)


def test_localize_follows_victim_bridge_to_root():
    """gB's culprit (7) is a victim in earlier gA; the root is gA's own
    culprit 1.  gB becomes an export pointing at gA."""
    summaries = {
        "gA": _summary("gA", 1, 1.5e-3, ranks=range(8),
                       wait={7: 1.5e-3}, last_start=0.070),
        "gB": _summary("gB", 7, 1.3e-3, ranks=[7, 8, 9, 10],
                       wait={}, last_start=0.082),
    }
    alerts = [_alert("gA", 1, 1.5e-3), _alert("gB", 7, 1.3e-3)]
    locs, exports = localize_cascades(alerts, summaries)
    assert len(locs) == 1
    loc = locs[0]
    assert (loc.root_group, loc.root_rank) == ("gA", 1)
    assert set(loc.affected_groups) == {"gA", "gB"}
    assert 7 in loc.victim_ranks
    assert len(exports) == 1
    exp = exports[0]
    assert isinstance(exp, CascadeExport)
    assert (exp.group_id, exp.via_rank, exp.root_group, exp.root_rank) \
        == ("gB", 7, "gA", 1)


def test_localize_same_culprit_dedupes_to_earliest_group():
    """A rank in two groups, slow in both (NIC flap): one root in the
    earlier group, the later group exports."""
    summaries = {
        "gA": _summary("gA", 4, 0.6e-3, ranks=range(8), last_start=0.070),
        "gB": _summary("gB", 4, 0.6e-3, ranks=[4, 8, 9, 10],
                       last_start=0.082),
    }
    alerts = [_alert("gB", 4, 0.6e-3), _alert("gA", 4, 0.6e-3)]
    locs, exports = localize_cascades(alerts, summaries)
    assert len(locs) == 1
    assert (locs[0].root_group, locs[0].root_rank) == ("gA", 4)
    # the root group's own alert is preferred over the triggering one
    assert locs[0].alert.group_id == "gA"
    assert [e.group_id for e in exports] == ["gB"]


def test_localize_dedupes_exports_and_synthesizes_root_alert():
    """Two flagged ranks in one victim group yield ONE export per
    (victim group, root); a root group that never alerted itself gets a
    summary-derived synthetic alert so the root event's evidence names
    the root, not the triggering victim."""
    summaries = {
        "gA": _summary("gA", 1, 1.5e-3, ranks=range(8),
                       wait={7: 1.5e-3, 6: 1.5e-3}, last_start=0.070),
        "gB": _summary("gB", 7, 1.3e-3, ranks=[6, 7, 8, 9],
                       wait={}, last_start=0.082),
    }
    # gB flags both bridges; gA raised no alert of its own
    summaries["gB"].lateness[6] = 1.2e-3
    alerts = [_alert("gB", 7, 1.3e-3), _alert("gB", 6, 1.2e-3)]
    locs, exports = localize_cascades(alerts, summaries)
    assert len(exports) == 1 and exports[0].group_id == "gB"
    assert len(locs) == 1
    loc = locs[0]
    assert (loc.root_group, loc.root_rank) == ("gA", 1)
    # synthetic alert is root-consistent
    assert (loc.alert.group_id, loc.alert.rank) == ("gA", 1)
    assert loc.alert.lateness == pytest.approx(1.5e-3)


def test_localize_guards_against_coincidental_rank_reuse():
    """Independent groups reusing local rank ids 0..7 must not fabricate
    cascade edges: the candidate neither precedes the victim by the
    margin nor explains its lateness with an upstream wait."""
    summaries = {
        "gA": _summary("gA", 4, 1.5e-3, ranks=range(8),
                       wait={r: 1.4e-3 for r in range(8) if r != 4},
                       last_start=0.0715),
        # same rank ids, its own unrelated culprit, near-identical phase
        "gB": _summary("gB", 2, 1.4e-3, ranks=range(8),
                       wait={r: 1.3e-3 for r in range(8) if r != 2},
                       last_start=0.0712),
    }
    alerts = [_alert("gA", 4, 1.5e-3), _alert("gB", 2, 1.4e-3)]
    locs, exports = localize_cascades(alerts, summaries)
    assert not exports
    assert {(l.root_group, l.root_rank) for l in locs} == \
        {("gA", 4), ("gB", 2)}


# ---------------------------------------------------------------------------
# service-level: cascade scenarios end-to-end + provenance
# ---------------------------------------------------------------------------


def _drive_cascade(svc, scen, baseline=30, fault=60):
    cl = scen.make_cluster(seed=7, columnar=False, native_unwind=False)
    for phase, iters in (("baseline", baseline), ("fault", fault)):
        if phase == "fault":
            cl.add_fleet_fault(scen.make_fault())
        for _ in range(iters):
            for p in cl.step():
                svc.ingest(p)
            if cl.iteration % 10 == 0:
                svc.process()
        svc.process()
    return cl


def test_cascade_root_event_carries_provenance():
    from repro.core.scenarios import default_registry
    reg = default_registry()
    scen = reg.get("cascade_swap_root_node")
    svc = CentralService(window=50, registry=reg)
    cl = _drive_cascade(svc, scen)
    gids = cl.group_ids()
    roots = [e for e in svc.events
             if e.root_cause == "memory_pressure_swap"]
    assert roots
    ev = roots[0]
    assert ev.group_id == gids[0] and ev.straggler_rank == 1
    v = ev.verdict
    assert v.culprit_rank == 1 and v.culprit_group == gids[0]
    assert 7 in v.victim_ranks        # the bridge rank waited on the root
    cascade = ev.evidence["cascade"]
    assert set(cascade["affected_groups"]) == set(gids)
    assert cascade["root_node"] == 0
    # the root rank's blame timeline rides the evidence
    assert ev.evidence["blame_timeline"]["iter_time"] > 0
    exports = [e for e in svc.events
               if e.root_cause == CASCADE_EXPORT_CAUSE]
    assert exports and all(e.group_id == gids[1] for e in exports)
    x = exports[0]
    assert x.verdict.layer == "cascade"
    assert x.verdict.evidence["exported_to"] == gids[0]
    assert x.verdict.culprit_group == gids[0]
    assert x.straggler_rank == 7 and x.category == "network"
    # a single-group incident: localization is the identity, and the
    # root event names its own rank as culprit with no cascade evidence
    svc = CentralService(window=50, registry=reg)
    cl = sc.SimCluster(n_ranks=8, seed=7)
    cl.run(svc, 30)
    cl.add_fault(sc.nic_softirq(4, start=30))
    cl.run(svc, 60)
    ev = svc.events[0]
    assert ev.root_cause == "nic_softirq_contention"
    assert ev.verdict.culprit_rank == 4
    assert ev.verdict.culprit_group == cl.group_id
    assert "cascade" not in ev.evidence


def test_sharded_cascade_matches_central():
    """Blame chains cross shard boundaries: the sharded facade must
    produce exactly the central service's cascade diagnoses."""
    from repro.core.scenarios import default_registry
    reg = default_registry()
    scen = reg.get("cascade_victim_group_export")

    def tuples(svc):
        _drive_cascade(svc, scen)
        return [(e.group_id, e.root_cause, e.category, e.straggler_rank)
                for e in svc.events]

    central = tuples(CentralService(window=50, registry=reg))
    sharded = tuples(ShardedService(n_shards=4, window=50, registry=reg))
    assert central and sharded == central
    assert any(c == CASCADE_EXPORT_CAUSE for _g, c, _cat, _r in central)


# ---------------------------------------------------------------------------
# mitigation consumes the provenance
# ---------------------------------------------------------------------------


def _event(category, rank, verdict):
    return DiagnosticEvent(
        job_id="j", group_id="gB", category=category, root_cause=verdict.root_cause,
        verdict=verdict, straggler_rank=rank, detected_at=0.0,
        diagnosis_latency_s=0.0)


def test_mitigation_never_cordons_cascade_victims():
    planner = MitigationPlanner()
    victim = Verdict(layer="cascade", root_cause=CASCADE_EXPORT_CAUSE,
                     confidence=0.8, evidence={}, culprit_rank=1,
                     culprit_group="gA", victim_ranks=(7,))
    acts = planner.on_diagnosis(_event("network", 7, victim))
    assert [a.kind for a in acts] == ["observe"]
    assert acts[0].target_nodes == [] and "gA" in acts[0].reason


def test_mitigation_cordons_localized_culprit_node():
    planner = MitigationPlanner(chips_per_node=8)
    root = Verdict(layer="os", root_cause="ecc_row_remap_stall",
                   confidence=0.7, evidence={}, culprit_rank=17,
                   culprit_group="gB", victim_ranks=(0, 1))
    acts = planner.on_diagnosis(_event("gpu_hardware", 17, root))
    assert [a.kind for a in acts] == ["cordon"]
    assert acts[0].target_nodes == [17 // 8]
