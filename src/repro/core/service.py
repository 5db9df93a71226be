"""Central analysis service (§3–§5): ingestion, symbol repo, slow-rank
detection, layered differential diagnosis, temporal baselines, SOP rules.

Pipeline per ingested batch:
  1. collective events -> instance separation -> StragglerDetector
     (per-collective blame edges + windowed blame summaries)
  2. CPU samples -> per-rank flame graphs -> CPUWaterline
  3. alert? -> cascade localization (repro.core.attribution): follow
     blame across overlapping communication groups to the root (node,
     rank), then layered diagnosis (GPU diff -> CPU diff -> OS diff)
     at the root only; victim groups get cascade_blame_exported events.
     No alert but iter-time regression? -> temporal baseline.
  4. every diagnosis becomes a DiagnosticEvent with a category matching the
     paper's Fig 2 taxonomy (gpu_hardware | os_interference | network |
     software) and a wall-clock diagnosis latency.

Streaming architecture: all analysis state is *bounded and maintained
incrementally at ingest time* — ring-buffered iteration-time windows and
exponentially-decayed per-(group, rank) flame graphs — so one
``process()`` cycle costs O(groups + alerts), not O(total ingested
samples).  That is what lets a single service instance sit under a
fleet-scale ingest stream the way the paper's regional deployments do
(§5: 80k+ GPUs, minutes-not-days).

One representation inside: an ``IterationProfile`` dataclass is converted
to a ``ColumnarProfile`` over the service's tables when it enters
``ingest``; everything after the door is columnar.

Invariants:

  * Ingest-representation equivalence: a profile produces the same
    diagnoses whether ingested as an ``IterationProfile`` dataclass, a
    native ``ColumnarProfile``, or a wire-encoded batch via
    ``ingest_encoded`` — asserted for every registered scenario across
    the streaming/columnar/sharded/pod paths
    (``simcluster.run_scenario_matrix``).
  * Registry immutability after service start: the service pins a frozen
    ``ScenarioRegistry.snapshot()`` at construction (``self.rules``);
    scenarios or rules registered later in the process never change what
    a running service diagnoses.
  * Bounded state: per-group state is evicted after ``group_ttl_s`` idle,
    baselines are LRU-bounded, and streaming accumulators are decayed —
    memory tracks the *live* fleet, not ingest history.
"""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict, deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core.attribution import (CASCADE_EXPORT_CAUSE, CascadeExport,
                                    Localization, TimelineBuilder,
                                    iteration_timelines, localize_cascades)
from repro.core.baseline import BaselineStore, compare_to_baseline
from repro.core.collective.instances import separate_instance_indices
from repro.core.diffdiag import Verdict, VerdictDamper, diagnose
from repro.core.events import IterationProfile
from repro.core.query import (BlameRoot, DiagnosisQueryAPI, EventLog,
                              FleetSnapshot, GroupView, RankHistory,
                              blame_roots_from)
from repro.core.scenarios import (LEGACY_CATEGORIES, ScenarioRegistry,
                                  default_registry)
from repro.core.spans import span
from repro.core.straggler import StragglerAlert, StragglerDetector
from repro.core.symbols.repo import SymbolRepository
from repro.core.trace import (ColumnFlameGraph, ColumnarProfile, RemapCache,
                              TraceTables, decode_batch, profile_to_columnar,
                              remap_profile)
from repro.core.waterline import CPUWaterline

__all__ = ["CATEGORY_BY_CAUSE", "LOG_SOP_RULES", "DiagnosticEvent",
           "CentralService"]

# Fig 2 taxonomy — backwards-compatible alias; the live mapping (which
# grows with registered scenarios/rules) is the registry's category map.
CATEGORY_BY_CAUSE = dict(LEGACY_CATEGORIES)

# log-based SOP rules (the paper's 1,454 "software" events, median 1 min)
LOG_SOP_RULES: List[Tuple[str, str]] = [
    ("CUDA out of memory", "oom"),
    ("NCCL timeout", "nccl_timeout"),
    ("ECC error", "gpu_ecc"),
    ("checkpoint write failed", "ckpt_storage"),
    ("Loss is NaN", "loss_nan"),
]

# temporal path: a group's recent iteration time this far above its
# baseline triggers a flame-graph comparison against the baseline
ITER_REGRESSION = 0.05
# verdict flap damping (diffdiag.VerdictDamper): a cause flip on a
# standing verdict needs FLAP_CONFIRM consecutive cycles; contested or
# absent standings decay by FLAP_DECAY per cycle and retire after
# FLAP_RETIRE absent cycles
FLAP_CONFIRM = 2
FLAP_DECAY = 0.7
FLAP_RETIRE = 4


@dataclasses.dataclass
class DiagnosticEvent:
    job_id: str
    group_id: str
    category: str
    root_cause: str
    verdict: Optional[Verdict]
    straggler_rank: Optional[int]
    detected_at: float
    diagnosis_latency_s: float
    evidence: Dict[str, object] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, object]:
        """Stable wire form — the one result envelope query responses
        use from either service.  Field names match the dataclass;
        ``verdict`` nests its own ``to_dict``.  ``detected_at`` stamps
        are strictly increasing in emission order within a service
        (see ``_sequence``), so serialized event streams sort back
        into exactly the emission order."""
        return {
            "job_id": self.job_id, "group_id": self.group_id,
            "category": self.category, "root_cause": self.root_cause,
            "verdict": (self.verdict.to_dict()
                        if self.verdict is not None else None),
            "straggler_rank": self.straggler_rank,
            "detected_at": self.detected_at,
            "diagnosis_latency_s": self.diagnosis_latency_s,
            "evidence": self.evidence,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, object]) -> "DiagnosticEvent":
        d = dict(d)
        v = d.get("verdict")
        d["verdict"] = Verdict.from_dict(v) if v is not None else None
        return cls(**d)  # type: ignore[arg-type]


class CentralService(DiagnosisQueryAPI):
    def __init__(self, window: int = 100, k: float = 2.0,
                 baseline_delta: float = 0.005,
                 robust_detector: bool = False,
                 fg_window: int = 16,
                 group_ttl_s: Optional[float] = 3600.0,
                 registry: Optional[ScenarioRegistry] = None,
                 min_root_lateness: float = 1e-4,
                 chips_per_node: int = 8,
                 retain: int = 512,
                 publish_stride: int = 1,
                 flap_damping: bool = True):
        self.symbol_repo = SymbolRepository()
        self.baselines = BaselineStore()
        # rule-set immutability after service start: pin a frozen snapshot
        # of the scenario registry, so diagnoses stay reproducible even if
        # scenarios/rules are registered later in the process
        self.rules = (registry if registry is not None
                      else default_registry()).snapshot()
        # one global interning table set: every columnar batch is re-mapped
        # into this id space at decode time, so flame graphs, waterlines and
        # kernel diffs from different agents are directly comparable
        self.tables = TraceTables()
        self._remaps = RemapCache(self.tables)
        # per-sender wire dictionary sessions (v3 delta frames): nonce ->
        # gather arrays mapping session-scope ids into self.tables
        self._wire_sessions: Dict[int, object] = {}
        self.detector = StragglerDetector(window=window, k=k,
                                          robust=robust_detector)
        self.waterlines: Dict[str, CPUWaterline] = defaultdict(
            lambda: CPUWaterline(window=window, k=k,
                                 names=self.tables.strings))
        self.window = window
        self.baseline_delta = baseline_delta
        # effective flame-graph memory in iterations: decay gamma such
        # that weight halves roughly every fg_window*ln2 iterations
        self.fg_window = max(2, fg_window)
        self._fg_decay = 1.0 - 1.0 / self.fg_window
        self.events: List[DiagnosticEvent] = []
        self._counts: Dict[str, int] = defaultdict(int)
        # latest per (group, rank) profile for differential diagnosis
        # (kernel timings + OS signals; bounded: one entry per live rank)
        self._latest: Dict[Tuple[str, int], ColumnarProfile] = {}
        # decayed per-(group, rank) flame graphs, merged at ingest
        self._rank_fg: Dict[Tuple[str, int], ColumnFlameGraph] = {}
        # iteration-time history: one ring buffer per group
        self._group_iter_time: Dict[str, Deque[float]] = defaultdict(
            lambda: deque(maxlen=window))
        # profiles with collectives defer materialization to process()
        self._pending_coll_profiles: List[ColumnarProfile] = []
        self._job_by_group: Dict[str, str] = {}
        # group -> live rank set, so per-group lookups never scan the
        # whole (group, rank) space at fleet scale
        self._group_ranks: Dict[str, set] = defaultdict(set)
        # groups idle longer than group_ttl_s are fully evicted at
        # process() time — transient jobs can't accumulate state forever
        self.group_ttl_s = group_ttl_s
        self._last_ingest: Dict[str, float] = {}
        self.groups_evicted = 0
        self.ingested = 0
        # significance floor for cascade localization: alerts below it
        # are windowed jitter (the same 100us threshold the network
        # fallback uses for "timing says slow"), not incidents worth a
        # root diagnosis
        self.min_root_lateness = min_root_lateness
        # node topology for provenance (rank -> node in cascade
        # evidence); mirror it in MitigationPlanner(chips_per_node=...)
        self.chips_per_node = chips_per_node
        # verdict flap-damping + confidence decay: every would-be
        # emission is proposed to the damper, which suppresses
        # unconfirmed cause flips on a standing (group, rank) verdict
        # and decays standing confidence while a verdict is contested
        # or absent.  First emissions and steady repeats pass through
        # unchanged, so single-incident scenarios emit exactly as
        # without damping (the scenario matrix holds with it on).
        self.damper: Optional[VerdictDamper] = (
            VerdictDamper(confirm=FLAP_CONFIRM, decay=FLAP_DECAY,
                          retire_after=FLAP_RETIRE)
            if flap_damping else None)
        self._tl_builder = TimelineBuilder(self.tables)
        # per-collective blame edges drained from the detector on the
        # most recent cycle (bounded); root diagnoses attach their
        # group's edges as evidence
        self.last_edges: List = []
        # most recent cycle's windowed blame summaries, by group id
        # (publish-time GroupView input); refreshed by collect_cycle
        self.last_summaries: Dict[str, object] = {}
        # ---- queryable diagnosis plane (repro.core.query) ----
        # retained per-(group, rank) history columns backing time-travel
        # queries; bounded by `retain` rows per column via copy-on-trim
        self.retain = retain
        self._history: Dict[Tuple[str, int], RankHistory] = {}
        # persistent per-group blame-root pointers from the most recent
        # cycle that localized a cascade touching the group
        self._blame_roots: Dict[str, BlameRoot] = {}
        # last group iteration whose timelines were recorded (skip
        # recomputation on idle groups)
        self._tl_recorded: Dict[str, int] = {}
        # publication striding: with stride s > 1, each analysis cycle
        # records timelines and refreshes waterline-top summaries for
        # 1/s of the groups (rotating, so every group refreshes every s
        # cycles).  Alerts, diagnoses, blame state and history ring
        # buffers are NOT strided — only the read-side publication work.
        # stride 1 (the default) is exactly the pre-stride behaviour.
        self.publish_stride = max(1, publish_stride)
        self._cycle_no = 0
        self._wl_top_cache: Dict[str, tuple] = {}
        self._init_query_api()
        # epoch 0: the empty snapshot, published at construction so
        # readers never see None; process() publishes 1, 2, ...
        self._epoch = 0
        self._snapshot = FleetSnapshot(
            epoch=0, published_at=time.monotonic(), groups=(),
            history={}, events=EventLog(self.events, 0),
            blame_roots={}, stats={})

    # -- ingestion -----------------------------------------------------------
    def _adopt(self, profile: ColumnarProfile) -> ColumnarProfile:
        """Re-map a foreign-table profile into the service's global id
        space (bounded cache of incremental gathers per source table)."""
        return remap_profile(profile, self._remaps.get(profile.tables))

    def ingest(self, profile, job_id: str = "job-0") -> None:
        """Ingest one per-rank iteration — a ``ColumnarProfile`` (hot
        path) or an ``IterationProfile`` (boundary schema), which is
        converted to columns over the service's tables here, at the door."""
        if isinstance(profile, IterationProfile):
            profile = profile_to_columnar(profile, self.tables)
        elif profile.tables is not self.tables:
            profile = self._adopt(profile)
        self.ingested += 1
        g = profile.group_id
        self._job_by_group[g] = job_id
        self._group_ranks[g].add(profile.rank)
        self._last_ingest[g] = time.monotonic()
        self._group_iter_time[g].append(profile.iter_time)
        hist = self._history.get((g, profile.rank))
        if hist is None:
            hist = self._history[(g, profile.rank)] = \
                RankHistory(self.retain)
        hist.append(profile.iteration, profile.iter_time)
        key = (g, profile.rank)
        self._latest[key] = profile
        if profile.coll_op.shape[0]:
            self._pending_coll_profiles.append(profile)
        ids, fracs = profile.function_fraction_sparse()
        self.waterlines[g].observe_sparse(profile.rank, ids, fracs)
        acc = self._rank_fg.get(key)
        if acc is None:
            acc = self._rank_fg[key] = ColumnFlameGraph(self.tables)
        acc.decay(self._fg_decay)
        acc.add_sid_weights(profile.stack_id, profile.stack_weight)

    def ingest_batch(self, batch) -> int:
        """One agent upload (§4's 30 s cycle) — a ``ProfileBatch`` or
        ``ColumnarBatch``; profiles may span groups."""
        for p in batch.profiles:
            self.ingest(p, job_id=batch.job_id)
        return len(batch.profiles)

    def ingest_encoded(self, data, *, detach: bool = False) -> int:
        """One wire-encoded columnar upload (``bytes`` or any buffer —
        no copy forced): decode straight into the service's global
        tables (one vectorized id gather per column), then ingest the
        column views.  v3 dictionary-delta frames resume their sender's
        session from ``_wire_sessions``; an out-of-sync frame raises
        ``WireFormatError`` back to the sender, which resyncs.

        ``detach=True`` when ``data`` is a view over transient storage
        (a shm ring slot): ingest retains column views in ``_latest``,
        so they must not alias a buffer that gets recycled."""
        return self.ingest_batch(decode_batch(data, tables=self.tables,
                                              sessions=self._wire_sessions,
                                              detach=detach))

    def ingest_log_line(self, job_id: str, line: str) -> Optional[DiagnosticEvent]:
        for pattern, cause in LOG_SOP_RULES:
            if pattern.lower() in line.lower():
                ev = DiagnosticEvent(
                    job_id=job_id, group_id="-", category="software",
                    root_cause=cause, verdict=None, straggler_rank=None,
                    detected_at=time.monotonic(), diagnosis_latency_s=0.0,
                    evidence={"log": line[:200]})
                self._record(ev)
                return ev
        return None

    def _record(self, ev: DiagnosticEvent) -> None:
        self.events.append(ev)
        self._counts[ev.category] += 1

    def _damp(self, ev: Optional[DiagnosticEvent]
              ) -> Optional[DiagnosticEvent]:
        """Propose one would-be emission to the verdict damper.  Returns
        the event (with any flap-damping evidence attached) or None when
        the damper suppresses it as an unconfirmed flip."""
        if ev is None or self.damper is None:
            return ev
        conf = ev.verdict.confidence if ev.verdict is not None else 1.0
        info = self.damper.propose(ev.group_id, ev.straggler_rank,
                                   ev.root_cause, conf)
        if info is None:
            return None
        if info:
            ev.evidence.update(info)
        return ev

    def standing_verdicts(self) -> Dict:
        """Live damped-verdict state keyed by (group, rank) — what an
        operator dashboard shows as standing/decaying diagnoses."""
        return (self.damper.standing_verdicts()
                if self.damper is not None else {})

    # -- group lifecycle -----------------------------------------------------
    def evict_group(self, g: str) -> None:
        """Drop every piece of per-group state (job retired or idle past
        TTL).  Historical baselines stay — BaselineStore is LRU-bounded."""
        for r in self._group_ranks.pop(g, ()):
            self._latest.pop((g, r), None)
            self._rank_fg.pop((g, r), None)
            self._history.pop((g, r), None)
        self.waterlines.pop(g, None)
        self._group_iter_time.pop(g, None)
        self._job_by_group.pop(g, None)
        self._last_ingest.pop(g, None)
        # the queryable plane forgets the group too: retained history
        # (above), blame-root pointers and exact-match SLO registrations
        # all go; already-published snapshots keep serving their own
        # captured views (copy-on-trim columns never dangle)
        self._blame_roots.pop(g, None)
        self._tl_recorded.pop(g, None)
        self._wl_top_cache.pop(g, None)
        self._drop_group_slos(g)
        self.detector.forget_group(g)
        if self.damper is not None:
            self.damper.forget_group(g)
        self.groups_evicted += 1

    def _evict_idle_groups(self, now: float) -> None:
        if self.group_ttl_s is None:
            return
        idle = [g for g, t in self._last_ingest.items()
                if now - t > self.group_ttl_s]
        for g in idle:
            self.evict_group(g)

    # -- analysis cycle (the "processed within minutes" loop) ----------------
    def _materialize_collectives(self) -> None:
        """Deferred collectives -> instance separation -> detector
        (blame-edge accumulation), once per cycle, with zero
        ``CollectiveEvent`` objects: channels are keyed by interned
        (group, op) ids straight off the columns and observed through
        the detector's array methods.  The order matches
        ``collective.separate_instances`` over the same events: channels
        form in first-seen order, events within a channel scan in stable
        entry order, instance members rank-sort the same way, and the
        final cross-channel pass sorts by the min-raw-entry key.

        Channel grouping is one stable argsort over the concatenated
        columns (profile order is the scan order), not a per-event
        Python walk — at 32k ranks the dict-of-lists channel build was
        ~15% of the analysis cycle."""
        P, self._pending_coll_profiles = self._pending_coll_profiles, []
        if not P:
            return
        lens = np.fromiter((p.coll_entry.shape[0] for p in P),
                           np.int64, len(P))
        if not int(lens.sum()):
            return
        gis = np.concatenate([p.coll_group for p in P]).astype(np.int64)
        ops = np.concatenate([p.coll_op for p in P]).astype(np.int64)
        ens = np.concatenate([p.coll_entry for p in P])
        exs = np.concatenate([p.coll_exit for p in P])
        rks = np.repeat(np.fromiter((p.rank for p in P), np.int64, len(P)),
                        lens)
        key = gis * np.int64(len(self.tables.strings) + 1) + ops
        uniq, first, inv = np.unique(key, return_index=True,
                                     return_inverse=True)
        by_key = np.argsort(key, kind="stable")     # scan order within key
        bounds = np.concatenate(([0], np.cumsum(np.bincount(inv))))
        insts = []
        # channels in first-seen order
        for ci in np.argsort(first, kind="stable").tolist():
            sl = by_key[bounds[ci]:bounds[ci + 1]]
            ea, xa, rlist = ens[sl], exs[sl], rks[sl].tolist()
            for start, idxs in separate_instance_indices(ea, xa, rlist):
                insts.append((start, int(gis[sl[0]]), int(ops[sl[0]]),
                              ea, xa, rlist, idxs))
        insts.sort(key=lambda t: t[0])      # stable: ties keep channel order
        name = self.tables.strings.get
        observe = self.detector.observe_instance_arrays
        for _start, gi, op, ea, xa, rks_c, idxs in insts:
            if len(idxs) < 2:
                continue
            observe(name(gi), name(op), [rks_c[j] for j in idxs],
                    ea[idxs], xa[idxs])

    def collect_cycle(self, t0: Optional[float] = None):
        """Run one cycle's *collection* half without emitting events:
        evict idle groups, materialize pending collectives into the
        detector, and return (alerts, blame summaries).  The sharded
        facade merges these fleet-wide before cascade localization —
        blame chains cross shard boundaries, diagnosis does not."""
        if t0 is None:
            t0 = time.monotonic()
        self._evict_idle_groups(t0)
        self._materialize_collectives()
        # one windowed-state walk per cycle: summaries feed both the
        # alert view and cascade localization
        summaries = self.detector.blame_summaries()
        alerts = [a for a in self.detector.check_windows(summaries)
                  if a.lateness >= self.min_root_lateness][:8]
        self.last_edges = self.detector.drain_edges()
        self.last_summaries = summaries
        return alerts, summaries

    def _temporal_cycle(self, flagged, t0: float) -> List[DiagnosticEvent]:
        """Uniform-degradation path (no straggler, iter time regressed)
        for every group not already flagged this cycle."""
        out: List[DiagnosticEvent] = []
        for g, times in self._group_iter_time.items():
            if g in flagged or len(times) < 4:
                continue
            ev = self._check_temporal(g, times, t0)
            if ev:
                out.append(ev)
        return out

    @staticmethod
    def _sequence(events: List[DiagnosticEvent], t0: float) -> None:
        """Strictly-increasing detected_at stamps in emission order, so
        merged multi-shard views sort back into exactly this order."""
        for i, ev in enumerate(events):
            ev.detected_at = t0 + i * 1e-9

    def process(self) -> List[DiagnosticEvent]:
        """One diagnosis cycle; its span carries the epoch it publishes."""
        with span("sysom.service.process", epoch=self._epoch + 1):
            return self._process()

    def _process(self) -> List[DiagnosticEvent]:
        t0 = time.monotonic()
        new_events: List[DiagnosticEvent] = []
        flagged: set = set()
        # 1. alerts -> cascade localization -> diagnose roots only
        alerts, summaries = self.collect_cycle(t0)
        locs, exports = localize_cascades(alerts, summaries)
        # retain this cycle's blame-root pointers for audit() walks
        # (stamped with the epoch the coming publish will carry)
        self._blame_roots.update(
            blame_roots_from(locs, exports, self._epoch + 1))
        for loc in locs:
            flagged.add(loc.root_group)
            flagged.update(loc.affected_groups)
            ev = self._diagnose_root(loc, t0)
            if ev:
                new_events.append(ev)
        for exp in exports:
            flagged.add(exp.group_id)
            ev = self._export_event(exp, t0)
            if ev:
                new_events.append(ev)
        # 2. uniform-degradation path
        new_events.extend(self._temporal_cycle(flagged, t0))
        if self.damper is not None:
            # end of cycle: decay standings that went unrefreshed
            self.damper.tick()
        self._sequence(new_events, t0)
        for ev in new_events:
            self._record(ev)
        # 3. read-side publication: record this cycle's blame timelines
        # into the retained history, then publish the epoch snapshot
        # (after _record, so the cycle's own events are queryable at
        # the epoch they were diagnosed)
        self._record_timelines()
        self._publish_snapshot(t0)
        return new_events

    # -- straggler path ---------------------------------------------------------
    def _diagnose_pair(self, g: str, rank: int, alert: StragglerAlert,
                       t0: float) -> Optional[DiagnosticEvent]:
        """Layered pairwise diff of a blame root ``rank`` against a
        healthy peer in its group: kernel columns of the latest profiles,
        then the decayed per-rank flame graphs, then OS signals."""
        ranks = sorted(self._group_ranks.get(g, ()))
        if len(ranks) < 2 or rank not in ranks:
            return None
        healthy_candidates = [r for r in ranks if r != rank]
        healthy = healthy_candidates[-1]
        sp = self._latest[(g, rank)]
        hp = self._latest[(g, healthy)]

        verdict = diagnose(
            sp, hp, self._rank_fg[(g, rank)], self._rank_fg[(g, healthy)],
            sp.os_signals, hp.os_signals, registry=self.rules)
        if verdict.layer == "inconclusive" and alert.lateness > 1e-4:
            # timing says slow but no layer diverges -> network path (§7)
            verdict = Verdict(layer="network",
                              root_cause="network_slow_collective",
                              confidence=0.5,
                              evidence={"lateness": alert.lateness},
                              action="inspect fabric counters / RDMA stats")
        return self._damp(DiagnosticEvent(
            job_id=self._job_by_group.get(g, "job-0"), group_id=g,
            category=self.rules.category_for(verdict.root_cause),
            root_cause=verdict.root_cause, verdict=verdict,
            straggler_rank=rank, detected_at=t0,
            diagnosis_latency_s=time.monotonic() - t0,
            evidence={"alert": dataclasses.asdict(alert)}))

    def _group_timelines(self, g: str):
        """Blame timelines of one group's latest iteration, computed
        over every rank's latest profile (instance starts need the whole
        group's aligned entries).  Empty when fewer than two ranks share
        the latest iteration — matching a stale iteration against current
        peers would read as a full-iteration wait."""
        ranks = sorted(self._group_ranks.get(g, ()))
        profiles = [p for p in (self._latest.get((g, r)) for r in ranks)
                    if p is not None]
        if len(profiles) < 2:
            return []
        latest_iter = max(p.iteration for p in profiles)
        profiles = [p for p in profiles if p.iteration == latest_iter]
        if len(profiles) < 2:
            return []
        tls, _ = iteration_timelines(
            profiles, skew=self.detector.aligner.skew,
            builder=self._tl_builder)
        return tls

    def _rank_timeline(self, g: str, rank: int):
        """Blame timeline of one rank's latest iteration (None when the
        group can't produce one — see ``_group_timelines``)."""
        return next((t for t in self._group_timelines(g)
                     if t.rank == rank), None)

    def _diagnose_root(self, loc: Localization,
                       t0: float) -> Optional[DiagnosticEvent]:
        """Diagnose a localized blame root: the pairwise diff runs at
        the root (group, rank) only, and the verdict carries culprit/
        victim provenance plus the root rank's blame timeline."""
        g, rank = loc.root_group, loc.root_rank
        ev = self._diagnose_pair(g, rank, loc.alert, t0)
        if ev is None or ev.verdict is None:
            return ev
        v = ev.verdict
        v.culprit_rank = rank
        v.culprit_group = g
        v.victim_ranks = loc.victim_ranks
        if len(loc.chain) > 1 or len(loc.affected_groups) > 1:
            ev.evidence["cascade"] = {
                "chain": list(loc.chain),
                "affected_groups": list(loc.affected_groups),
                "root_node": loc.node(self.chips_per_node),
                "victim_ranks": list(loc.victim_ranks)}
        tl = self._rank_timeline(g, rank)
        if tl is not None:
            ev.evidence["blame_timeline"] = tl.as_dict()
        edges = [e for e in self.last_edges if e.group_id == g]
        if edges:
            ev.evidence["blame_edges"] = [
                {"op": e.op, "culprit_rank": e.culprit_rank,
                 "victim_rank": e.victim_rank, "wait": e.wait}
                for e in edges[-8:]]
        return ev

    def _export_event(self, exp: CascadeExport,
                      t0: float) -> Optional[DiagnosticEvent]:
        """Victim-side event for a group whose blame localized in
        another group: no local diagnosis, provenance points at the
        root.  Consumers must not act on the victim (ft/mitigation)."""
        verdict = Verdict(
            layer="cascade", root_cause=CASCADE_EXPORT_CAUSE,
            confidence=0.8,
            evidence={"exported_to": exp.root_group,
                      "root_rank": exp.root_rank,
                      "root_node": exp.root_rank // self.chips_per_node,
                      "via_rank": exp.via_rank,
                      "observed_lateness": exp.wait},
            action=f"no local action: blame exported to group "
                   f"{exp.root_group} (root rank {exp.root_rank})",
            culprit_rank=exp.root_rank, culprit_group=exp.root_group,
            victim_ranks=(exp.via_rank,))
        return self._damp(DiagnosticEvent(
            job_id=self._job_by_group.get(exp.group_id, "job-0"),
            group_id=exp.group_id,
            category=self.rules.category_for(CASCADE_EXPORT_CAUSE),
            root_cause=CASCADE_EXPORT_CAUSE, verdict=verdict,
            straggler_rank=exp.via_rank, detected_at=t0,
            diagnosis_latency_s=time.monotonic() - t0,
            evidence={"exported_to": exp.root_group,
                      "root_rank": exp.root_rank}))

    # -- temporal path -------------------------------------------------------------
    def _check_temporal(self, g: str, times, t0: float
                        ) -> Optional[DiagnosticEvent]:
        job = self._job_by_group.get(g, "job-0")
        base_time = self.baselines.iter_time(job, g)
        n = min(3, len(times))
        recent = sum(times[len(times) - i - 1] for i in range(n)) / n
        if base_time is None:
            # bootstrap the baseline from the first healthy window
            fg = self._group_flamegraph(g)
            if fg is not None:
                self.baselines.save(job, g, fg, iter_time=recent)
            return None
        if recent < base_time * (1 + ITER_REGRESSION):
            return None
        baseline_fg = self.baselines.get(job, g)
        current_fg = self._group_flamegraph(g)
        if baseline_fg is None or current_fg is None:
            return None
        cands = compare_to_baseline(current_fg, baseline_fg,
                                    self.baseline_delta,
                                    sop_rules=self.rules.sop_rules)
        if not cands:
            return None
        top = next((c for c in cands if c.root_cause), cands[0])
        cause = top.root_cause or self.rules.cpu_rules.fallback_cause
        verdict = Verdict(layer="cpu", root_cause=cause,
                          confidence=min(1.0, top.delta /
                                         max(2 * self.baseline_delta,
                                             1e-12)),
                          evidence={"candidates": [
                              dataclasses.asdict(c) for c in cands[:8]]},
                          action=top.action)
        return self._damp(DiagnosticEvent(
            job_id=job, group_id=g,
            category=self.rules.category_for(cause),
            root_cause=cause, verdict=verdict, straggler_rank=None,
            detected_at=t0, diagnosis_latency_s=time.monotonic() - t0,
            evidence={"iter_time": (base_time, recent)}))

    def _group_flamegraph(self, g: str) -> Optional[ColumnFlameGraph]:
        """The group's decayed per-rank flame graphs summed; None while
        the group holds no live stack weight."""
        out = ColumnFlameGraph(self.tables)
        for r in self._group_ranks.get(g, ()):
            out.add_graph(self._rank_fg[(g, r)])
        return out if out.total else None

    # -- queryable diagnosis plane (publication side) ------------------------------
    def _record_timelines(self) -> None:
        """Append one blame-timeline row per (group, rank) to the
        retained query history — once per analysis cycle, one vectorized
        ``iteration_timelines`` pass per group that advanced since its
        last recording (idle groups cost a dict lookup).  With
        ``publish_stride`` s > 1 only the cycle's rotating 1/s of the
        groups record; the others keep their retained rows and catch up
        on their stride turn."""
        self._cycle_no += 1
        stride = self.publish_stride
        turn = self._cycle_no % stride
        for i, g in enumerate(self._group_ranks):
            if stride > 1 and i % stride != turn:
                continue
            latest = max(
                (p.iteration for p in
                 (self._latest.get((g, r)) for r in self._group_ranks[g])
                 if p is not None), default=None)
            if latest is None or self._tl_recorded.get(g) == latest:
                continue
            tls = self._group_timelines(g)
            if not tls:
                continue
            self._tl_recorded[g] = latest
            for tl in tls:
                hist = self._history.get((g, tl.rank))
                if hist is None:
                    hist = self._history[(g, tl.rank)] = \
                        RankHistory(self.retain)
                hist.append_timeline(
                    tl.iteration,
                    (tl.iter_time, tl.compute, tl.host, tl.blocked_wait,
                     tl.transfer, tl.residual))

    def _publish_snapshot(self, t0: float) -> None:
        """Publish one immutable epoch-stamped ``FleetSnapshot`` of the
        retained query state.  O(live groups + ranks) reference
        captures — history columns are never copied (copy-on-trim keeps
        captured views valid), and everything a view resolves (function
        names, summaries) is materialized here so nothing in a snapshot
        aliases mutable or interned service state."""
        self._epoch += 1
        hist = {key: h.view() for key, h in self._history.items()}
        summaries = self.last_summaries
        stride = self.publish_stride
        turn = self._cycle_no % stride
        groups = []
        for i, g in enumerate(sorted(self._group_ranks)):
            ranks = tuple(sorted(self._group_ranks[g]))
            last_it = -1
            for r in ranks:
                v = hist.get((g, r))
                if v is not None and v.n_it:
                    last_it = max(last_it, v.it[v.n_it - 1])
            wl = self.waterlines.get(g)
            s = summaries.get(g)
            # waterline top-5 extraction walks the group's function
            # accumulators; under striding it refreshes on the group's
            # rotation turn and republishes the cached tuple otherwise
            wl_top = self._wl_top_cache.get(g) if stride > 1 else None
            if wl_top is None or i % stride == turn:
                wl_top = (tuple(wl.top_functions(5))
                          if wl is not None else ())
                if stride > 1:
                    self._wl_top_cache[g] = wl_top
            groups.append(GroupView(
                group_id=g,
                job_id=self._job_by_group.get(g, "job-0"),
                ranks=ranks, last_iteration=last_it,
                waterline_top=wl_top,
                blame=s.as_dict() if s is not None else None))
        self._snapshot = FleetSnapshot(
            epoch=self._epoch, published_at=t0, groups=tuple(groups),
            history=hist, events=EventLog(self.events),
            blame_roots=dict(self._blame_roots), stats=self.stats())

    def snapshot(self) -> FleetSnapshot:
        """Current published snapshot — one GIL-atomic attribute read;
        readers on other threads never block ingest or process()."""
        return self._snapshot

    # -- reporting -----------------------------------------------------------------
    def event_counts(self) -> Dict[str, int]:
        return dict(self._counts)

    def stats(self) -> Dict[str, float]:
        """Bounded-state introspection for dashboards and benchmarks."""
        # n_live avoids materializing a per-rank counts dict: at 32k
        # ranks this sum runs twice per cycle (own snapshot + facade
        # merge) and was the single hottest reporting line
        live_stacks = sum(fg.n_live for fg in self._rank_fg.values())
        return {
            "ingested": self.ingested,
            "groups": len(self._group_iter_time),
            "ranks": len(self._latest),
            "live_stacks": live_stacks,
            "iter_time_entries": sum(len(t) for t in
                                     self._group_iter_time.values()),
            "events": len(self.events),
            "baselines": len(self.baselines),
            "groups_evicted": self.groups_evicted,
            "epoch": self._epoch,
            "verdicts_suppressed": (self.damper.suppressed
                                    if self.damper else 0),
            "verdict_flips_confirmed": (self.damper.flips_confirmed
                                        if self.damper else 0),
            "verdicts_retired": (self.damper.retired
                                 if self.damper else 0),
        }
