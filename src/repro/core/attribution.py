"""Cross-layer causal attribution: per-iteration blame timelines and
cross-group cascade localization.

The paper's central claim is that subtle OS-level issues trigger
*cascading* GPU delays and network slowdowns across communication
groups.  Pairwise diffing the slowest rank cannot see that: at the
barrier of a blocking collective every rank waits for the latest
enterer, so in a downstream group the apparent straggler is often a
pure *victim* — a rank that itself blocked in an upstream group's
collective (ARGUS's culprit/victim split; EROICA's cross-group delay
propagation).  This module adds the causal layer between detection and
differential diagnosis:

  1. **Blame timelines** — :func:`iteration_timelines` decomposes each
     rank's iteration, straight from ``ColumnarProfile`` columns (no
     dataclass materialization), into exposed compute, exposed host
     time, collective *blocked-wait* vs *transfer* time, and an
     unattributed OS/residual component.  Waits use the aligned-clock
     barrier semantics: a rank's wait inside a collective is blame
     assigned to the instance's latest-entering rank, never to the
     waiter.  :func:`iteration_timelines_naive` is the per-event Python
     reference walk (differential-tested; ``benchmarks/
     bench_attribution.py`` asserts the vectorized pass is >=5x).
  2. **Cascade localization** — :func:`localize_cascades` walks the
     windowed blame summaries (``StragglerDetector.blame_summary``)
     across overlapping communication groups: a group's culprit that
     *itself* blocked in an earlier group's collective re-exports the
     blame upstream, hop by hop, until the root (node, rank) whose
     lateness is self-caused.  Only the root is then handed to the
     layered ``diagnose()``; every other flagged group yields a
     ``cascade_blame_exported`` verdict pointing at the root.

Invariants:

  * Per-rank timeline components sum to ``iter_time`` exactly (parts
    exceeding it are scaled down proportionally; hypothesis-tested).
  * Blame totals are invariant under rank relabeling and profile
    ingestion order (hypothesis-tested).
  * Where no cascade exists, localization is the identity: every alert
    resolves to its own (group, rank), which is pairwise-diffed as the
    root (tested).

A note on cross-group identity: ranks are matched across groups by
rank id, so fleets must use globally unique rank ids for bridge ranks
(the cascade simulator does).  Fleets that reuse local 0..n-1 ids per
group are defended by the redirect guards — an upstream hop requires
the candidate group's collective to *precede* the victim's by
``precede_margin`` and the bridge's upstream wait to be at least
``wait_ratio`` of its downstream lateness, which coincidental id reuse
between independent groups does not satisfy.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.events import IterationProfile
from repro.core.straggler import BlameEdge, GroupBlame, StragglerAlert
from repro.core.trace import (ColumnarProfile, TraceTables, interval_overlap,
                              merged_intervals)

__all__ = [
    "CASCADE_EXPORT_CAUSE", "COLLECTIVE_STACK_MARKERS", "BlameTimeline",
    "TimelineBuilder", "iteration_timelines", "iteration_timelines_naive",
    "Localization", "CascadeExport", "localize_cascades",
]

#: Root cause carried by a victim-side verdict: the group's apparent
#: straggler merely imported wait from another group (see RUNBOOK.md).
CASCADE_EXPORT_CAUSE = "cascade_blame_exported"

#: Frame-name substrings marking stacks sampled *inside* a collective —
#: their weight is already accounted as wait/transfer, so they are
#: excluded when apportioning the non-kernel remainder to host time.
COLLECTIVE_STACK_MARKERS: Tuple[str, ...] = ("nccl", "Collective")


@dataclasses.dataclass(frozen=True)
class BlameTimeline:
    """One rank-iteration decomposed into attributable components.

    ``compute``       exposed GPU kernel time (outside collectives)
    ``host``          exposed host/CPU time (stack-sample apportioned)
    ``blocked_wait``  time blocked at collective barriers — blame this
                      rank *exported* onto the latest-entering ranks
    ``transfer``      in-collective time after the instance started
    ``residual``      unattributed remainder (OS interference, stalls,
                      events too brief for any sampled evidence)

    Components sum to ``iter_time`` exactly.
    """
    group_id: str
    rank: int
    iteration: int
    iter_time: float
    compute: float
    host: float
    blocked_wait: float
    transfer: float
    residual: float

    def components(self) -> Tuple[float, float, float, float, float]:
        return (self.compute, self.host, self.blocked_wait, self.transfer,
                self.residual)

    @property
    def total(self) -> float:
        return sum(self.components())

    def as_dict(self) -> Dict[str, float]:
        return {
            "iter_time": self.iter_time, "compute": self.compute,
            "host": self.host, "blocked_wait": self.blocked_wait,
            "transfer": self.transfer, "residual": self.residual,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, float], group_id: str = "",
                  rank: int = -1, iteration: int = -1) -> "BlameTimeline":
        """Rebuild from the ``as_dict`` wire form; identity fields come
        from the dict when present, else the keyword defaults (query
        responses carry them alongside the component row)."""
        return cls(
            group_id=str(d.get("group_id", group_id)),
            rank=int(d.get("rank", rank)),
            iteration=int(d.get("iteration", iteration)),
            iter_time=d["iter_time"], compute=d["compute"],
            host=d["host"], blocked_wait=d["blocked_wait"],
            transfer=d["transfer"], residual=d["residual"])


class TimelineBuilder:
    """Cached per-table derived state for timeline construction: a dense
    stack-id -> "samples inside a collective" mask, grown incrementally
    as the shared tables grow (the same amortization trick as
    ``TraceTables.stack_fns``)."""

    __slots__ = ("tables", "markers", "_fn_mask", "_sid_mask")

    def __init__(self, tables: TraceTables,
                 markers: Sequence[str] = COLLECTIVE_STACK_MARKERS):
        self.tables = tables
        self.markers = tuple(markers)
        self._fn_mask = np.zeros(0, dtype=bool)
        self._sid_mask = np.zeros(0, dtype=bool)

    def collective_sid_mask(self) -> np.ndarray:
        strings = self.tables.strings.strings
        nf = len(strings)
        if nf > self._fn_mask.shape[0]:
            old = self._fn_mask.shape[0]
            add = np.fromiter(
                (any(m in s for m in self.markers) for s in strings[old:nf]),
                dtype=bool, count=nf - old)
            self._fn_mask = np.concatenate([self._fn_mask, add])
        stacks = self.tables.stacks
        ns = len(stacks)
        if ns > self._sid_mask.shape[0]:
            old = self._sid_mask.shape[0]
            fn_mask = self._fn_mask
            add = np.fromiter(
                (bool(fn_mask[list(stacks[s])].any()) if stacks[s] else False
                 for s in range(old, ns)),
                dtype=bool, count=ns - old)
            self._sid_mask = np.concatenate([self._sid_mask, add])
        return self._sid_mask


def _gather(profiles: Sequence[ColumnarProfile],
            names: Sequence[str]) -> List[np.ndarray]:
    """Concatenate several columns across profiles in one pass."""
    cols: List[List[np.ndarray]] = [[] for _ in names]
    for p in profiles:
        for out, name in zip(cols, names):
            out.append(getattr(p, name))
    return [np.concatenate(c) for c in cols]


def iteration_timelines(
        profiles: Sequence[ColumnarProfile], *,
        skew: Optional[Callable[[int, str], float]] = None,
        builder: Optional[TimelineBuilder] = None,
        min_edge_wait: float = 50e-6,
) -> Tuple[List[BlameTimeline], List[BlameEdge]]:
    """Vectorized blame timelines for one synchronized iteration.

    ``profiles`` are the ``ColumnarProfile``s of the participating ranks
    (one or more groups; all sharing one table set).  Collective events
    are matched into instances by (group, op, per-profile occurrence);
    the instance start is the latest aligned entry, each rank's wait is
    blamed on that latest enterer (one :class:`BlameEdge` per waiting
    rank).  ``skew(rank, group_id)`` supplies per-rank clock skew (e.g.
    ``ClockAligner.skew``); None means aligned clocks.

    Everything runs as numpy column passes over the batch — per-event
    Python work is limited to materializing the (few) blame edges.
    """
    P = [p for p in profiles]
    if not P:
        return [], []
    tables = P[0].tables
    for p in P:
        if p.tables is not tables:
            raise ValueError("all profiles must share one TraceTables "
                             "(remap foreign profiles first)")
    if builder is None:
        builder = TimelineBuilder(tables)
    n = len(P)

    # -- collectives: instance matching + wait/transfer ----------------------
    c_lens = np.array([p.coll_entry.shape[0] for p in P], dtype=np.int64)
    n_coll = int(c_lens.sum())
    wait_p = np.zeros(n)
    transfer_p = np.zeros(n)
    edges: List[BlameEdge] = []
    if n_coll:
        c_pid = np.repeat(np.arange(n), c_lens)
        entry, exit_, group, op = _gather(
            P, ("coll_entry", "coll_exit", "coll_group", "coll_op"))
        ranks = np.repeat(np.array([p.rank for p in P], dtype=np.int64),
                          c_lens)
        if skew is None:
            aligned = entry
        else:
            get = tables.strings.get
            skews = np.fromiter(
                (skew(int(r), get(int(g)))
                 for r, g in zip(ranks.tolist(), group.tolist())),
                dtype=np.float64, count=n_coll)
            aligned = entry - skews
        # occurrence index of each event within its (profile, group, op)
        # channel, preserving column order — the i-th AllReduce of a
        # profile joins the i-th instance of that (group, op) channel
        S = np.int64(len(tables.strings) + 1)
        pkey = (c_pid.astype(np.int64) * S + group) * S + op
        order = np.argsort(pkey, kind="stable")
        sk = pkey[order]
        new_run = np.empty(n_coll, dtype=bool)
        new_run[0] = True
        np.not_equal(sk[1:], sk[:-1], out=new_run[1:])
        run_start = np.flatnonzero(new_run)
        run_len = np.empty(run_start.shape[0], dtype=np.int64)
        run_len[:-1] = np.diff(run_start)
        run_len[-1] = n_coll - run_start[-1]
        occ = np.empty(n_coll, dtype=np.int64)
        occ[order] = np.arange(n_coll) - np.repeat(run_start, run_len)
        ikey = (group * S + op) * np.int64(occ.max() + 1) + occ
        _uk, inv = np.unique(ikey, return_inverse=True)
        # instance start = latest aligned entry (barrier semantics)
        start = np.full(_uk.shape[0], -np.inf)
        np.maximum.at(start, inv, aligned)
        start_ev = start[inv]
        wait = np.maximum(start_ev - aligned, 0.0)
        transfer = np.maximum((exit_ - entry) - wait, 0.0)
        # culprit per instance: latest aligned entry, ties broken by rank
        # (matches the naive walk's (aligned, rank) lexicographic max)
        last = np.lexsort((ranks, aligned, inv))
        tail = np.flatnonzero(np.r_[inv[last][1:] != inv[last][:-1], True])
        culprit_by_inst = np.empty(_uk.shape[0], dtype=np.int64)
        culprit_by_inst[inv[last[tail]]] = ranks[last[tail]]
        culprit_ev = culprit_by_inst[inv]
        wait_p = np.bincount(c_pid, weights=wait, minlength=n)
        transfer_p = np.bincount(c_pid, weights=transfer, minlength=n)
        get = tables.strings.get
        em = np.flatnonzero((wait >= min_edge_wait) & (ranks != culprit_ev))
        edges = [BlameEdge(get(g), get(o), s, c, r, w)
                 for g, o, s, c, r, w in zip(
                     group[em].tolist(), op[em].tolist(),
                     start_ev[em].tolist(), culprit_ev[em].tolist(),
                     ranks[em].tolist(), wait[em].tolist())]

    # -- kernels: exposed compute (overlap with collectives removed) --------
    k_lens = np.array([p.kern_dur.shape[0] for p in P], dtype=np.int64)
    compute_p = np.zeros(n)
    if int(k_lens.sum()):
        k_pid = np.repeat(np.arange(n), k_lens)
        ks, kd = _gather(P, ("kern_start", "kern_dur"))
        compute_p = np.bincount(k_pid, weights=kd, minlength=n)
        if n_coll:
            # band every profile's times into a disjoint window so one
            # global merged-interval pass never mixes profiles
            ke = ks + kd
            lo = min(float(entry.min()), float(ks.min()))
            hi = max(float(exit_.max()), float(ke.max()))
            span = (hi - lo) + 1.0
            c_pid_f = np.repeat(np.arange(n, dtype=np.float64), c_lens)
            k_pid_f = np.repeat(np.arange(n, dtype=np.float64), k_lens)
            ms, me = merged_intervals((entry - lo) + c_pid_f * span,
                                      (exit_ - lo) + c_pid_f * span)
            overlap = interval_overlap((ks - lo) + k_pid_f * span,
                                       (ke - lo) + k_pid_f * span, ms, me)
            compute_p -= np.bincount(k_pid, weights=overlap, minlength=n)

    # -- stacks: apportion the remainder between host and residual ----------
    s_lens = np.array([p.stack_id.shape[0] for p in P], dtype=np.int64)
    host_frac = np.zeros(n)
    if int(s_lens.sum()):
        s_pid = np.repeat(np.arange(n), s_lens)
        sw, sid = _gather(P, ("stack_weight", "stack_id"))
        sw = sw.astype(np.float64)
        marked = builder.collective_sid_mask()[sid]
        tot_w = np.bincount(s_pid, weights=sw, minlength=n)
        coll_w = np.bincount(s_pid, weights=sw * marked, minlength=n)
        np.divide(tot_w - coll_w, tot_w, out=host_frac, where=tot_w > 0)

    # -- assembly: components sum to iter_time exactly ----------------------
    iter_t = np.array([p.iter_time for p in P], dtype=np.float64)
    parts = compute_p + wait_p + transfer_p
    over = (parts > iter_t) & (parts > 0)
    scale = np.where(over, iter_t / np.where(parts > 0, parts, 1.0), 1.0)
    compute_p, wait_p, transfer_p = (compute_p * scale, wait_p * scale,
                                     transfer_p * scale)
    remainder = np.maximum(iter_t - compute_p - wait_p - transfer_p, 0.0)
    host = remainder * host_frac
    residual = remainder - host
    timelines = [
        BlameTimeline(p.group_id, p.rank, p.iteration, p.iter_time,
                      c, h, w, t, r)
        for p, c, h, w, t, r in zip(
            P, compute_p.tolist(), host.tolist(), wait_p.tolist(),
            transfer_p.tolist(), residual.tolist())]
    return timelines, edges


def iteration_timelines_naive(
        profiles: Sequence[IterationProfile], *,
        skew: Optional[Callable[[int, str], float]] = None,
        min_edge_wait: float = 50e-6,
        markers: Sequence[str] = COLLECTIVE_STACK_MARKERS,
) -> Tuple[List[BlameTimeline], List[BlameEdge]]:
    """Reference decomposition: the per-event Python walk over the
    boundary-schema dataclasses.  Semantically identical to
    :func:`iteration_timelines` (differential-tested); exists as the
    legacy-ingest fallback and the benchmark baseline."""
    n = len(profiles)
    events: List[Tuple[Tuple[str, str, int], float, object, int]] = []
    occ_count: Dict[Tuple[int, str, str], int] = {}
    for i, p in enumerate(profiles):
        for c in p.collectives:
            ch = (i, c.group_id, c.op)
            occ = occ_count.get(ch, 0)
            occ_count[ch] = occ + 1
            al = c.entry - (skew(c.rank, c.group_id) if skew else 0.0)
            events.append(((c.group_id, c.op, occ), al, c, i))
    inst: Dict[Tuple[str, str, int], Tuple[float, int]] = {}
    for key, al, c, _i in events:
        cur = inst.get(key)
        if cur is None or (al, c.rank) > cur:
            inst[key] = (al, c.rank)
    wait_p, transfer_p = [0.0] * n, [0.0] * n
    edges: List[BlameEdge] = []
    for key, al, c, i in events:
        start, culprit = inst[key]
        w = max(0.0, start - al)
        wait_p[i] += w
        transfer_p[i] += max(0.0, (c.exit - c.entry) - w)
        if c.rank != culprit and w >= min_edge_wait:
            edges.append(BlameEdge(c.group_id, c.op, start, culprit,
                                   c.rank, w))
    timelines: List[BlameTimeline] = []
    for i, p in enumerate(profiles):
        compute = sum(k.duration for k in p.kernel_events)
        merged: List[List[float]] = []
        for c in sorted(p.collectives, key=lambda c: c.entry):
            if merged and c.entry <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], c.exit)
            else:
                merged.append([c.entry, c.exit])
        for k in p.kernel_events:
            k_end = k.start + k.duration
            for lo, hi in merged:
                compute -= max(0.0, min(k_end, hi) - max(k.start, lo))
        compute = max(0.0, compute)
        tot_w = coll_w = 0.0
        for s in p.cpu_samples:
            tot_w += s.weight
            if any(m in f for f in s.frames for m in markers):
                coll_w += s.weight
        host_frac = (tot_w - coll_w) / tot_w if tot_w > 0 else 0.0
        w, t = wait_p[i], transfer_p[i]
        parts = compute + w + t
        if parts > p.iter_time and parts > 0:
            scale = p.iter_time / parts
            compute, w, t = compute * scale, w * scale, t * scale
        remainder = max(0.0, p.iter_time - compute - w - t)
        host = remainder * host_frac
        timelines.append(BlameTimeline(
            p.group_id, p.rank, p.iteration, p.iter_time, compute, host,
            w, t, remainder - host))
    return timelines, edges


# ---------------------------------------------------------------------------
# cascade localization across overlapping communication groups
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Localization:
    """One localized root: where the blame chain terminated.  ``alert``
    is the root group's own alert when it raised one (always, in the
    no-cascade case), else the triggering downstream alert."""
    alert: StragglerAlert
    root_group: str
    root_rank: int
    chain: Tuple[str, ...]            # triggering group ... root group
    affected_groups: Tuple[str, ...]  # alerting groups resolved to this root
    victim_ranks: Tuple[int, ...]

    def node(self, chips_per_node: int = 8) -> int:
        return self.root_rank // chips_per_node


@dataclasses.dataclass(frozen=True)
class CascadeExport:
    """A flagged group whose blame localized elsewhere: its apparent
    straggler (``via_rank``) is a victim; the root is in another group."""
    group_id: str
    via_rank: int
    root_group: str
    root_rank: int
    wait: float                       # lateness observed in the victim group


def localize_cascades(
        alerts: Sequence[StragglerAlert],
        summaries: Dict[str, GroupBlame], *,
        wait_ratio: float = 0.8,
        support_ratio: float = 0.4,
        same_culprit_ratio: float = 0.6,
        precede_margin: float = 1e-3,
        min_wait: float = 50e-6,
        max_hops: int = 16,
) -> Tuple[List[Localization], List[CascadeExport]]:
    """Follow blame edges across overlapping groups to each alert's root.

    From an alert (group g, culprit c), one hop moves the blame to an
    earlier group g' when either:

      * g' also names c as its culprit (``same_culprit_ratio`` of the
        downstream lateness, same physical rank slow in both groups), or
      * c is a *victim* in g': its windowed mean blocked-wait there is
        at least ``wait_ratio`` of its downstream lateness (blame never
        amplifies across a hop) and g' has a culprit of its own with at
        least ``support_ratio`` of that lateness.

    Both hops additionally require the candidate group's collectives to
    *precede* the alerting group's by ``precede_margin``
    (``GroupBlame.last_start`` ordering) — blame only flows backwards
    in time — which is what keeps coincidental rank-id reuse between
    independent groups from fabricating edges.  Hops repeat (bounded by
    ``max_hops``) until the blame is self-caused; alerts resolving to
    one root deduplicate into a single :class:`Localization` (whose
    ``alert`` is the root group's own when it raised one, else a
    summary-derived synthetic), and every alerting group other than the
    root group becomes one :class:`CascadeExport` (deduplicated per
    (victim group, root)).
    """
    order: List[Tuple[str, int]] = []
    by_root: Dict[Tuple[str, int], Dict[str, object]] = {}
    exports: List[CascadeExport] = []
    exported: set = set()            # (victim group, root) pairs emitted
    for alert in alerts:
        g, c, late = alert.group_id, alert.rank, alert.lateness
        chain = [g]
        for _hop in range(max_hops):
            s_g = summaries.get(g)
            if s_g is None:
                break
            nxt, best = None, 0.0
            for g2, s2 in summaries.items():
                if g2 == g or g2 in chain or c not in s2.lateness:
                    continue
                if s2.last_start > s_g.last_start - precede_margin:
                    continue          # candidate must precede the victim
                if s2.culprit_rank == c:
                    if (s2.culprit_lateness >= same_culprit_ratio * late
                            and s2.culprit_lateness > best):
                        nxt, best = g2, s2.culprit_lateness
                    continue
                w = s2.wait.get(c, 0.0)
                if (w >= max(wait_ratio * late, min_wait)
                        and s2.culprit_lateness >= support_ratio * late
                        and s2.culprit_lateness > best):
                    nxt, best = g2, s2.culprit_lateness
            if nxt is None:
                break
            g = nxt
            c = summaries[g].culprit_rank
            late = summaries[g].culprit_lateness
            chain.append(g)
        key = (g, c)
        entry = by_root.get(key)
        if entry is None:
            entry = by_root[key] = {
                "alert": alert, "chain": tuple(chain),
                "affected": [alert.group_id],
                "own": alert.group_id == g and alert.rank == c}
        else:
            if alert.group_id not in entry["affected"]:
                entry["affected"].append(alert.group_id)
            if len(chain) > len(entry["chain"]):
                entry["chain"] = tuple(chain)
        if alert.group_id == g and alert.rank == c and not entry["own"]:
            entry["alert"], entry["own"] = alert, True   # prefer root's own
        if key not in order:
            order.append(key)
        if alert.group_id != g:
            exp_key = (alert.group_id, g, c)
            if exp_key not in exported:    # one export per (victim, root)
                exported.add(exp_key)
                exports.append(CascadeExport(alert.group_id, alert.rank,
                                             g, c, alert.lateness))
    locs: List[Localization] = []
    for key in order:
        g, c = key
        e = by_root[key]
        s = summaries.get(g)
        if not e["own"] and s is not None:
            # the root group never raised its own alert: synthesize one
            # from its blame summary so the emitted event's evidence is
            # self-consistent (and the network fallback judges the
            # ROOT's lateness, not the triggering victim group's)
            e["alert"] = StragglerAlert(
                group_id=g, rank=c, lateness=s.culprit_lateness,
                mean=0.0, std=0.0, zscore=0.0, window=s.instances)
        victims = set()
        if s is not None:
            floor = max(min_wait, 0.25 * max(s.culprit_lateness, 0.0))
            victims = {r for r, w in s.wait.items()
                       if r != c and w >= floor}
        victims |= {x.via_rank for x in exports
                    if x.root_group == g and x.root_rank == c}
        locs.append(Localization(
            alert=e["alert"], root_group=g, root_rank=c,
            chain=e["chain"], affected_groups=tuple(e["affected"]),
            victim_ranks=tuple(sorted(victims))))
    return locs, exports
