"""Sharded ingestion front-end (§5 regional deployments).

Hash-partitions communication groups across N independent
``CentralService`` shards.  Every per-group analysis (straggler windows,
waterlines, temporal baselines) only ever touches one group's state, so
routing by group id preserves each group's diagnoses while letting
ingestion scale out: shards share no mutable state and can be driven from
independent threads or processes, mirroring how the paper deploys one
service instance per region and merges at the reporting layer.

One deliberate capacity difference: the per-cycle straggler-alert cap
(8 per ``process()``) applies per shard, so an N-shard deployment can
diagnose up to N*8 concurrent incidents per cycle where a single service
defers the overflow to later cycles.  Sharding never diagnoses *fewer*
or *different* incidents per group — under <= 8 concurrent alerts the
outputs are identical (asserted over every registered scenario by the
``run_scenario_matrix`` tests in tests/test_scenarios.py).

The symbol repository is intentionally *shared* across shards — Build-ID
keyed symbolization is global, content-addressed, append-only state (§3.4)
and deduplicating uploads fleet-wide is the point.
"""
from __future__ import annotations

import time
import zlib
from collections import defaultdict
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

from repro.core.attribution import localize_cascades
from repro.core.events import IterationProfile, ProfileBatch
from repro.core.query import (DiagnosisQueryAPI, FleetSnapshot,
                              blame_roots_from)
from repro.core.service import CentralService, DiagnosticEvent
from repro.core.trace import decode_batch

__all__ = ["shard_of", "ShardedService"]


def shard_of(group_id: str, n_shards: int) -> int:
    """Stable group -> shard routing (crc32, not the salted builtin hash,
    so placement survives process restarts and is identical on every node)."""
    return zlib.crc32(group_id.encode()) % n_shards


class ShardedService(DiagnosisQueryAPI):
    """Drop-in ``CentralService`` facade over N group-partitioned shards."""

    def __init__(self, n_shards: int = 4, parallel: bool = False, **kwargs):
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.n_shards = n_shards
        self.parallel = parallel
        self.shards: List[CentralService] = [
            CentralService(**kwargs) for _ in range(n_shards)]
        # one global Build-ID-keyed symbol store (see module docstring),
        # and — same reasoning: append-only, content-addressed — one global
        # interning table set, so an encoded batch is decoded exactly once
        # and its column views route to shards without re-mapping
        self.symbol_repo = self.shards[0].symbol_repo
        self.tables = self.shards[0].tables
        # every shard already pinned an identical frozen registry snapshot
        # at construction (same source registry); share shard 0's so the
        # facade exposes one rule set and diagnoses stay shard-invariant
        self.rules = self.shards[0].rules
        for s in self.shards[1:]:
            s.symbol_repo = self.symbol_repo
            s.tables = self.tables
            s.rules = self.rules
            # per-table derived caches must follow the shared tables,
            # not each shard's discarded construction-time tables
            s._tl_builder = self.shards[0]._tl_builder
            s._remaps = self.shards[0]._remaps
        self._log_rr = 0
        # facade-level wire dictionary sessions: encoded uploads decode
        # ONCE at the facade (into the shared tables) before routing, so
        # the session store lives here, not in any shard
        self._wire_sessions: Dict[int, object] = {}
        # ---- queryable diagnosis plane (repro.core.query) ----
        # the facade holds its OWN SLO registry and epoch counter and
        # publishes a merged fleet snapshot per process() cycle, so the
        # query/audit surface is identical to CentralService's (same
        # epochs for the same call sequence — both start at the empty
        # epoch-0 snapshot and advance by one per cycle)
        self._init_query_api()
        self._epoch = 0
        self._known_groups: set = set()
        self._snapshot = FleetSnapshot(
            epoch=0, published_at=time.monotonic(), groups=(),
            history={}, events=(), blame_roots={}, stats={})

    # -- routing -------------------------------------------------------------
    def shard_for(self, group_id: str) -> CentralService:
        return self.shards[shard_of(group_id, self.n_shards)]

    # -- ingestion -----------------------------------------------------------
    def ingest(self, profile: IterationProfile, job_id: str = "job-0") -> None:
        self.shard_for(profile.group_id).ingest(profile, job_id=job_id)

    def ingest_encoded(self, data) -> int:
        """One wire-encoded columnar upload: decoded exactly once into the
        shared tables (v3 delta frames resume their sender's dictionary
        session), then the per-profile column views are routed to their
        group's shard (no per-shard re-decode or re-map)."""
        batch = decode_batch(data, tables=self.tables,
                             sessions=self._wire_sessions)
        return self.ingest_batch(batch)

    def ingest_batch(self, batch) -> int:
        """Split one agent upload (``ProfileBatch`` or ``ColumnarBatch``)
        by owning shard.  With ``parallel=True`` the per-shard sub-batches
        are ingested concurrently (safe: shards are independent)."""
        by_shard: Dict[int, List[IterationProfile]] = defaultdict(list)
        for p in batch.profiles:
            by_shard[shard_of(p.group_id, self.n_shards)].append(p)
        if self.parallel and len(by_shard) > 1:
            with ThreadPoolExecutor(max_workers=len(by_shard)) as ex:
                list(ex.map(
                    lambda kv: self.shards[kv[0]].ingest_batch(
                        ProfileBatch(batch.job_id, kv[1], batch.node_id)),
                    by_shard.items()))
        else:
            for idx, profiles in by_shard.items():
                self.shards[idx].ingest_batch(
                    ProfileBatch(batch.job_id, profiles, batch.node_id))
        return len(batch.profiles)

    def ingest_log_line(self, job_id: str, line: str
                        ) -> Optional[DiagnosticEvent]:
        # log lines carry no group; route round-robin so no shard becomes
        # the de-facto log shard under a chatty job
        shard = self.shards[self._log_rr % self.n_shards]
        self._log_rr += 1
        return shard.ingest_log_line(job_id, line)

    def evict_group(self, group_id: str) -> None:
        # facade-level exact-match SLO registrations retire with the
        # group (the owning shard drops its own state + registrations)
        self._drop_group_slos(group_id)
        self._known_groups.discard(group_id)
        self.shard_for(group_id).evict_group(group_id)

    @property
    def chips_per_node(self) -> int:
        return self.shards[0].chips_per_node

    # -- analysis ------------------------------------------------------------
    def process(self) -> List[DiagnosticEvent]:
        """Run one analysis cycle fleet-wide.

        The cycle splits: every shard runs its *collection* half
        (instance separation, blame accumulation, alerts + group blame
        summaries), the facade merges those summaries and runs cascade
        localization ONCE over the whole fleet — blame chains cross
        shard boundaries even though per-group diagnosis state never
        does — and then each root/export event is diagnosed and
        recorded on the shard owning its group."""
        t0 = time.monotonic()
        alerts, summaries = self._collect_fleet(t0)
        locs, exports = localize_cascades(alerts, summaries)
        # degraded-mode hook: a collection tier that knows parts of the
        # fleet are dark (repro.core.pod) vetoes conclusions it cannot
        # support — partial data must never cordon a healthy node
        locs, exports = self._filter_conclusions(locs, exports)
        # distribute this cycle's blame-root pointers to the shards
        # owning each group, so per-shard and merged snapshots carry the
        # same audit() walk state a single service would
        for g, br in blame_roots_from(locs, exports,
                                      self._epoch + 1).items():
            self.shard_for(g)._blame_roots[g] = br
        emitted = []                 # (owning shard, event) in order
        flagged = set()
        for loc in locs:
            flagged.add(loc.root_group)
            flagged.update(loc.affected_groups)
            shard = self.shard_for(loc.root_group)
            ev = shard._diagnose_root(loc, t0)
            if ev:
                emitted.append((shard, ev))
        for exp in exports:
            flagged.add(exp.group_id)
            shard = self.shard_for(exp.group_id)
            ev = shard._export_event(exp, t0)
            if ev:
                emitted.append((shard, ev))
        for s in self.shards:
            for ev in s._temporal_cycle(flagged, t0):
                emitted.append((s, ev))
            if s.damper is not None:
                # this path bypasses shard.process(), so the facade
                # drives each shard's per-cycle damper decay
                s.damper.tick()
        events = [ev for _s, ev in emitted]
        CentralService._sequence(events, t0)
        self._annotate_cycle(events)
        for shard, ev in emitted:
            shard._record(ev)
        # read-side publication: shard-local snapshots first (this path
        # bypasses shard.process(), so the facade drives them), then the
        # merged fleet snapshot
        for s in self.shards:
            s._record_timelines()
            s._publish_snapshot(t0)
        self._publish_merged(t0)
        return events

    # -- degraded-mode hooks -------------------------------------------------
    def _filter_conclusions(self, locs, exports):
        """Veto hook over this cycle's cascade conclusions, called
        right after localization.  The flat facade sees the whole fleet
        every cycle and filters nothing; the pod tier's bounded-
        staleness merge overrides this to suppress conclusions about
        ranks below its coverage floor."""
        return locs, exports

    def _annotate_cycle(self, events: List[DiagnosticEvent]) -> None:
        """Annotation hook over this cycle's sequenced events, called
        before they are recorded.  The pod tier stamps degraded-
        coverage evidence here; the flat facade has nothing to add."""

    def _facade_stats(self) -> Dict[str, float]:
        """Facade-only stats merged into ``stats()`` and the published
        snapshot on top of the per-shard sums (the pod tier reports
        coverage and fault-tolerance counters here)."""
        return {}

    # -- collection tier -----------------------------------------------------
    def _collect_fleet(self, t0: float):
        """Run every engine's *collection* half and merge fleet-wide into
        ``(alerts, summaries)`` for cascade localization.

        This is the scaling hook: the flat facade walks every engine
        itself; the pod tier (``repro.core.pod``) overrides it with a
        two-level pod -> pod-group tree merge so facade-visible work
        scales with pods, not engines.  Merge order is deterministic
        (engine index, then a stable lateness sort), so every override
        must preserve engine order to stay event-for-event identical."""
        if self.parallel and self.n_shards > 1:
            with ThreadPoolExecutor(max_workers=self.n_shards) as ex:
                collected = list(ex.map(lambda s: s.collect_cycle(t0),
                                        self.shards))
        else:
            collected = [s.collect_cycle(t0) for s in self.shards]
        alerts = [a for shard_alerts, _ in collected for a in shard_alerts]
        alerts.sort(key=lambda a: -a.lateness)
        summaries = {}
        for _, shard_summaries in collected:
            summaries.update(shard_summaries)
        return alerts, summaries

    # -- queryable diagnosis plane (merged publication) ----------------------
    def _publish_merged(self, t0: float) -> None:
        """Merge the shards' just-published snapshots into one facade
        ``FleetSnapshot``.  Groups partition cleanly across shards, so
        the merge is a union: group views re-sorted into the global
        group-id order, history/blame-root maps unioned, events merged
        by ``detected_at`` (strictly-increasing emission stamps make
        that exactly the single-service order)."""
        self._epoch += 1
        groups = []
        hist: Dict = {}
        roots: Dict = {}
        events: List[DiagnosticEvent] = []
        for s in self.shards:
            snap = s._snapshot
            groups.extend(snap.groups)
            hist.update(snap.history)
            roots.update(snap.blame_roots)
            events.extend(snap.events)
        groups.sort(key=lambda gv: gv.group_id)
        events.sort(key=lambda e: e.detected_at)
        # facade-level exact-match SLOs follow TTL evictions that
        # happened inside the shards' collection half
        live = {gv.group_id for gv in groups}
        for g in self._known_groups - live:
            self._drop_group_slos(g)
        self._known_groups = live
        # merged stats come from the stats each shard just froze into
        # its own snapshot (state hasn't changed since: same cycle, no
        # ingest in between) — re-walking every shard's per-rank flame
        # state via self.stats() doubled the fleet's reporting cost
        agg: Dict[str, float] = defaultdict(float)
        for s in self.shards:
            for k, v in s._snapshot.stats.items():
                agg[k] += v
        agg["shards"] = self.n_shards
        agg["epoch"] = self._epoch
        agg.update(self._facade_stats())
        self._snapshot = FleetSnapshot(
            epoch=self._epoch, published_at=t0, groups=tuple(groups),
            history=hist, events=tuple(events), blame_roots=roots,
            stats=dict(agg))

    def snapshot(self) -> FleetSnapshot:
        """Current merged snapshot — one GIL-atomic attribute read."""
        return self._snapshot

    # -- merged reporting view ----------------------------------------------
    @property
    def ingested(self) -> int:
        return sum(s.ingested for s in self.shards)

    @property
    def events(self) -> List[DiagnosticEvent]:
        out: List[DiagnosticEvent] = []
        for s in self.shards:
            out.extend(s.events)
        out.sort(key=lambda e: e.detected_at)
        return out

    def standing_verdicts(self) -> Dict:
        """Union of every shard's damped-verdict state (groups partition
        across shards, so keys never collide)."""
        merged: Dict = {}
        for s in self.shards:
            merged.update(s.standing_verdicts())
        return merged

    def event_counts(self) -> Dict[str, int]:
        counts: Dict[str, int] = defaultdict(int)
        for s in self.shards:
            for cat, n in s.event_counts().items():
                counts[cat] += n
        return dict(counts)

    def stats(self) -> Dict[str, float]:
        agg: Dict[str, float] = defaultdict(float)
        for s in self.shards:
            for k, v in s.stats().items():
                agg[k] += v
        agg["shards"] = self.n_shards
        # shard epochs advance in lockstep with the facade's — report
        # the facade epoch, not the meaningless per-shard sum
        agg["epoch"] = self._epoch
        agg.update(self._facade_stats())
        return dict(agg)
