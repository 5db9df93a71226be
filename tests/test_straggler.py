"""Slow-rank detection + clock alignment + instance separation (§3.1–3.2)."""
import random

import pytest

from repro.core.collective import separate_instances
from repro.core.events import CollectiveEvent
from repro.core.straggler import ClockAligner, StragglerDetector


def _make_instance(i, late_rank=None, lateness=0.0, skews=None, n=8,
                   group="g1", base=0.0):
    skews = skews or {}
    evs = []
    t0 = base + i * 0.1
    entries = {r: t0 + (lateness if r == late_rank else 0.0)
               + random.Random(i * 100 + r).gauss(0, 5e-6) for r in range(n)}
    start = max(entries.values())
    exit_t = start + 9e-3
    for r in range(n):
        evs.append(CollectiveEvent(
            rank=r, group_id=group, op="AllReduce",
            entry=entries[r] + skews.get(r, 0.0),
            exit=exit_t + skews.get(r, 0.0), nbytes=1 << 20))
    return evs


def test_flags_late_rank_04ms():
    """The paper's Case 1 magnitude: 0.4 ms late entry in an 8-rank group."""
    det = StragglerDetector(window=50)
    for i in range(30):
        det.observe_instance(_make_instance(i, late_rank=0, lateness=0.4e-3))
    alerts = det.check()
    assert alerts and alerts[0].rank == 0
    assert 0.3e-3 < alerts[0].lateness < 0.5e-3


def test_no_false_positive_on_healthy_group():
    det = StragglerDetector(window=50)
    for i in range(30):
        det.observe_instance(_make_instance(i))
    assert det.check() == []


def test_clock_skew_does_not_fool_detector():
    """Rank 3 has a +5 ms clock offset but is NOT slow; barrier-exit
    alignment must absorb it."""
    skews = {3: 5e-3}
    det = StragglerDetector(window=50)
    for i in range(30):
        det.observe_instance(_make_instance(i, skews=skews))
    assert det.check() == []
    # and the aligner measured the skew (residuals are per (group, rank))
    assert abs(det.aligner.skew(3, "g1") - 5e-3) < 1e-3


def test_skewed_clock_straggler_still_found():
    skews = {3: 5e-3, 5: -2e-3}
    det = StragglerDetector(window=50)
    for i in range(30):
        det.observe_instance(_make_instance(i, late_rank=5, lateness=0.6e-3,
                                            skews=skews))
    alerts = det.check()
    assert alerts and alerts[0].rank == 5


def test_robust_mode_survives_two_stragglers():
    """Beyond-paper: 2/8 ranks slow — mean/std (paper) loses power,
    median/MAD keeps it (DESIGN.md §7-limitation improvement)."""
    paper = StragglerDetector(window=50, robust=False)
    robust = StragglerDetector(window=50, robust=True)
    for i in range(30):
        inst = _make_instance(i, late_rank=None)
        # make ranks 2 AND 3 late by hand
        inst = [CollectiveEvent(e.rank, e.group_id, e.op,
                                e.entry + (6e-2 if e.rank in (2, 3) else 0),
                                e.exit, e.nbytes) for e in inst]
        paper.observe_instance(inst)
        robust.observe_instance(inst)
    assert {a.rank for a in robust.check()} == {2, 3}
    assert len(paper.check()) == 0   # documented paper limitation (§7)


def test_instance_separation_by_temporal_overlap():
    random.seed(0)
    events = []
    for i in range(20):
        events.extend(_make_instance(i))
    random.shuffle(events)
    instances = separate_instances(events)
    assert len(instances) == 20
    for inst in instances:
        ranks = [e.rank for e in inst]
        assert sorted(ranks) == list(range(8))      # one event per rank
        lo = max(e.entry for e in inst)
        hi = min(e.exit for e in inst)
        assert lo <= hi                              # genuinely overlapping


def test_instance_separation_concurrent_ops():
    """Two overlapping AllReduces on different groups stay separate."""
    a = _make_instance(0, group="g1")
    b = _make_instance(0, group="g2")
    instances = separate_instances(a + b)
    assert len(instances) == 2
    groups = {inst[0].group_id for inst in instances}
    assert groups == {"g1", "g2"}


def test_ring_windows_match_bruteforce_after_wrap():
    """The vectorized per-group ring windows must agree with a
    brute-force recomputation once columns wrap — including instances
    covering only a subset of the group's ranks (per-rank cursors stay
    independent) and the every-``refresh_every`` skew median."""
    import numpy as np

    window, refresh = 6, 8
    det = StragglerDetector(window=window, min_instances=2)
    rng = random.Random(11)
    members = [4, 0, 9, 2]
    seen_late = {r: [] for r in members}     # per-rank lateness history
    seen_resid = {r: [] for r in members}    # per-rank exit residuals
    cached = {}                              # simulated skew cache
    since = {r: 0 for r in members}
    for step in range(40):
        ranks = list(members)
        if step % 5 == 3:                    # partial-membership instance
            ranks = ranks[:3]
        entries = np.array([step * 1.0 + rng.gauss(0, 1e-3)
                            for _ in ranks])
        exits = entries + 5e-3 + np.array([rng.gauss(0, 1e-4)
                                           for _ in ranks])
        det.observe_instance_arrays("g", "AllReduce", ranks,
                                    entries.copy(), exits.copy())
        # brute-force twin: residual windows + the lazy refresh cadence
        resid = exits - exits.mean()
        for r, rv in zip(ranks, resid.tolist()):
            seen_resid[r].append(rv)
            since[r] += 1
            if r not in cached or since[r] >= refresh:
                win = sorted(seen_resid[r][-window:])
                cached[r] = win[len(win) // 2]     # k-th smallest
                since[r] = 0
        aligned = entries - np.array([cached[r] for r in ranks])
        lateness = aligned - aligned.mean()
        for r, lv in zip(ranks, lateness.tolist()):
            seen_late[r].append(lv)

    gb = det.blame_summary("g")
    assert gb is not None
    for r in members:
        # windows advanced independently per rank (subset instances skip
        # the absent ranks), so each mean uses that rank's own last
        # ``window`` observations
        tail = seen_late[r][-window:]
        assert gb.lateness[r] == pytest.approx(sum(tail) / len(tail),
                                               abs=1e-15)
        assert det.aligner.skew(r, "g") == cached[r]
    det.forget_group("g")
    assert det.blame_summary("g") is None
    assert det.aligner.skew(members[0], "g") == 0.0


def test_service_collectives_match_object_separation():
    """The service separates instances straight off the collective
    columns; its detector must end in the state of the object route —
    ``separate_instances`` over the same events, one
    ``observe_instance`` per instance — edge for edge, window for
    window."""
    from repro.core import simcluster as sc
    from repro.core.service import CentralService
    cl = sc.SimCluster(n_ranks=8, seed=3)
    cl.add_fault(sc.nic_softirq(4))
    svc = CentralService(window=50)
    ref = StragglerDetector(window=50)
    events = []
    for _ in range(20):
        for p in cl.step():
            svc.ingest(p)
            events.extend(p.collectives)
    for inst in separate_instances(events):
        ref.observe_instance(inst)
    svc.collect_cycle()
    assert svc.last_edges and svc.last_edges == ref.drain_edges()
    ours, theirs = svc.last_summaries, ref.blame_summaries()
    assert {g: s.as_dict() for g, s in ours.items()} == \
        {g: s.as_dict() for g, s in theirs.items()}
