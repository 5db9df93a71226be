"""Hierarchical pod aggregation tier (§5 scale-out to ~80k ranks).

The flat ``ShardedService`` facade walks every engine per ``process()``
cycle: collection fan-out, alert concatenation, and summary merging are
all O(engines), and at tens of thousands of ranks the facade itself
becomes the bottleneck even though each engine's work is tiny.  The pod
tier inserts one pre-reduction level between the agents and the facade:

    agents ──▶ pod engines ──▶ pod groups (slices) ──▶ facade

* A **pod** is one ``CentralService`` engine owning a group-partitioned
  slice of the fleet (same crc32 routing as flat sharding, so a group's
  diagnoses are bit-identical either way).  Per-rank flame graphs,
  CPU waterlines, and straggler windows accumulate *inside* the pod —
  the facade never touches per-rank state.
* A :class:`PodAggregator` runs the pod's collection half and pre-reduces
  it into a :class:`PodDigest`: the pod's straggler alerts, its
  ``GroupBlame`` summaries, and its per-rank flame columns merged into
  one deduplicated (stack id, weight) column pair
  (:func:`repro.core.aggregate.merge_stack_columns`).  The digest is the
  only thing that crosses the pod boundary (and it has a versioned wire
  codec — ``repro.core.transport`` — because at production scale that
  boundary is a real process/network boundary).
* Pods are sliced into fixed-size **pod groups** (``pods_per_shard``);
  each slice collects its pods' digests independently (in parallel when
  ``parallel=True``), and the facade merges per-pod digests in pod
  order.  The facade's per-cycle work — thread fan-out, list/dict
  merging — scales with pods, not with ranks.

**Bounded-staleness merge (fault tolerance).**  The facade never
barriers on its pods.  Each cycle it merges, per pod, the *freshest*
digest received within the last ``stale_after`` cycles; a pod that is
down, wedged, or past the watermark simply drops out of the merge.
The facade tracks what it can no longer see — ``coverage_fraction``
(fraction of known fleet ranks whose telemetry is within the
watermark), the missing pod list, and per-group coverage — and

* stamps every verdict emitted under partial coverage with a
  ``degraded`` coverage evidence block (also surfaced by ``audit()``
  and the snapshot ``stats``),
* **suppresses** straggler/cascade conclusions whose root rank's group
  coverage is below ``coverage_floor``: when the true root's pod is
  dark, cascade localization would otherwise walk a victim's blame to
  the bridge rank it *can* still see and blame a healthy node.
  Partial data degrades coverage; it never cordons a healthy machine.

Equivalence: with every pod responsive the merge concatenates alerts in
pod order and finishes with the same single stable lateness sort the
flat facade uses, and summaries merge in the same pod order, so
``process()`` output (and therefore the published snapshots and
``audit()``) is event-for-event identical to ``ShardedService`` with
``n_shards == n_pods`` — asserted across every registered scenario by
the "pod" column of ``run_scenario_matrix`` and by tests/test_pod.py.
:class:`MultiProcPodService` extends the same guarantee across real OS
process boundaries (tests/test_pod_ft.py).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.aggregate import merge_stack_columns
from repro.core.attribution import localize_cascades
from repro.core.events import ProfileBatch
from repro.core.query import (EventLog, FleetSnapshot, GroupView,
                              RankHistory, blame_roots_from)
from repro.core.service import LOG_SOP_RULES, CentralService, DiagnosticEvent
from repro.core.sharded import ShardedService, shard_of
from repro.core.shmring import ShmRingError
from repro.core.trace import ColumnarBatch, ColumnarProfile, WireEncoder
from repro.core.transport import (DigestFormatError, PodTransportError,
                                  decode_digest, spawn_pod_worker)

__all__ = ["PodDigest", "PodAggregator", "PodTierService",
           "MultiProcPodService", "merge_digests"]

#: In-process emulations of the two pod-worker failure modes the chaos
#: harness injects (``pod_kill`` stops a pod contributing entirely;
#: ``pod_slow`` makes it miss every collect deadline).  The multi-process
#: service maps ``pod_kill`` onto a real ``SIGKILL`` instead.
POD_FAULT_KINDS = ("pod_kill", "pod_slow")


@dataclasses.dataclass
class PodDigest:
    """Pre-reduced per-cycle view of one pod (or a merge of several).

    ``alerts`` keep pod order and are *unsorted* — ordering is the
    facade's job (one stable sort at the top, same as the flat facade),
    so merging digests is pure concatenation.  ``flame_sids`` /
    ``flame_weights`` are the pod's per-rank flame graphs collapsed into
    one deduplicated column pair.  ``group_ranks`` is the pod's group
    membership map — what the facade's coverage accounting needs to know
    about ranks it can no longer see — and ``seq`` the pod's collect
    counter (restarts from 1 in a respawned worker; the facade's
    staleness watermark, not seq, decides usability).
    """
    pod: int                       # pod index, -1 for a merged digest
    alerts: List                   # List[StragglerAlert], pod order
    summaries: Dict[str, object]   # group id -> GroupBlame
    groups: int                    # live groups in the pod
    ranks: int                     # ranks with a latest profile
    flame_sids: np.ndarray         # int64 stack ids, deduplicated
    flame_weights: np.ndarray      # float64 decayed sample weights
    group_ranks: Dict[str, Tuple[int, ...]] = \
        dataclasses.field(default_factory=dict)
    seq: int = 0                   # pod-local collect counter

    @property
    def flame_total(self) -> float:
        return float(self.flame_weights.sum()) if \
            self.flame_weights.shape[0] else 0.0


def merge_digests(digests: Sequence[PodDigest]) -> PodDigest:
    """Merge digests *in the given order* into one.

    Alert concatenation and summary update order follow the input order;
    callers must pass pods (or already-merged slices) in pod-index order
    to preserve the flat facade's deterministic merge (see
    ``ShardedService._collect_fleet``).
    """
    alerts: List = []
    summaries: Dict[str, object] = {}
    group_ranks: Dict[str, Tuple[int, ...]] = {}
    for d in digests:
        alerts.extend(d.alerts)
        summaries.update(d.summaries)
        group_ranks.update(d.group_ranks)
    sids, weights = merge_stack_columns(
        [(d.flame_sids, d.flame_weights) for d in digests])
    return PodDigest(
        pod=-1, alerts=alerts, summaries=summaries,
        groups=sum(d.groups for d in digests),
        ranks=sum(d.ranks for d in digests),
        flame_sids=sids, flame_weights=weights, group_ranks=group_ranks)


class PodAggregator:
    """Collection-side wrapper over one pod engine.

    ``collect`` runs the engine's collection half (eviction, collective
    materialization, straggler windows) and packages the result — plus
    the pod-merged flame columns — as a :class:`PodDigest`.  Ingestion
    still goes straight to the engine via the facade's routing; the
    aggregator only owns the upward-facing reduction.
    """

    def __init__(self, index: int, engine: CentralService):
        self.index = index
        self.engine = engine
        self.seq = 0

    def flame_columns(self) -> Tuple[np.ndarray, np.ndarray]:
        """All of the pod's per-rank columnar flame graphs merged into
        one deduplicated (stack id, weight) pair.  The rank vectors are
        dense and indexed by the shared stack id space, so the merge is
        one vector add per rank plus a single ``nonzero`` at the end —
        no per-rank sparsification (32k ``nonzero`` calls per cycle was
        a quarter of the facade's collection time)."""
        acc = None
        for fg in self.engine._rank_fg.values():
            vec = fg._vec
            if not vec.shape[0]:
                continue
            if acc is None or acc.shape[0] < vec.shape[0]:
                grown = np.zeros(vec.shape[0])
                if acc is not None:
                    grown[:acc.shape[0]] = acc
                acc = grown
            acc[:vec.shape[0]] += vec
        if acc is None:
            return merge_stack_columns([])
        nz = np.nonzero(acc)[0]
        return nz, acc[nz]

    def collect(self, t0: float) -> PodDigest:
        alerts, summaries = self.engine.collect_cycle(t0)
        sids, weights = self.flame_columns()
        self.seq += 1
        # membership tuples are handed over as-is (no per-cycle sort:
        # coverage accounting only needs membership, and sorting every
        # group at 32k ranks would tax the fault-free fast path)
        return PodDigest(
            pod=self.index, alerts=list(alerts), summaries=dict(summaries),
            groups=len(self.engine._group_ranks),
            ranks=len(self.engine._latest),
            flame_sids=sids, flame_weights=weights,
            group_ranks={g: tuple(rs) for g, rs in
                         self.engine._group_ranks.items()},
            seq=self.seq)


class PodTierService(ShardedService):
    """``ShardedService`` with the two-level pod -> pod-group collection
    tree and a bounded-staleness merge.  Routing, per-root diagnosis,
    temporal sequencing, publication, and the query/audit plane are all
    inherited unchanged — only the ``_collect_fleet`` hook is replaced
    (plus the coverage hooks it feeds), so everything downstream of
    collection is provably the flat facade's code path."""

    def __init__(self, n_pods: int = 8, pods_per_shard: int = 4,
                 parallel: bool = False, stale_after: int = 2,
                 coverage_floor: float = 0.75, respawn_warmup: int = 2,
                 **kwargs):
        if pods_per_shard < 1:
            raise ValueError("pods_per_shard must be >= 1")
        if stale_after < 0:
            raise ValueError("stale_after must be >= 0 cycles")
        if not 0.0 <= coverage_floor <= 1.0:
            raise ValueError("coverage_floor must be in [0, 1]")
        if respawn_warmup < 0:
            raise ValueError("respawn_warmup must be >= 0 cycles")
        super().__init__(n_shards=n_pods, parallel=parallel, **kwargs)
        self.n_pods = n_pods
        self.pods_per_shard = min(pods_per_shard, n_pods)
        self.stale_after = int(stale_after)
        self.coverage_floor = float(coverage_floor)
        self.respawn_warmup = int(respawn_warmup)
        self.pods: List[PodAggregator] = [
            PodAggregator(i, eng) for i, eng in enumerate(self.shards)]
        # fixed pod-index-order slices: pods collect inside a slice
        # worker, slice order is preserved at the facade => same total
        # merge order as the flat facade's engine walk
        self.pod_slices: List[List[PodAggregator]] = [
            self.pods[i:i + self.pods_per_shard]
            for i in range(0, n_pods, self.pods_per_shard)]
        self.last_digest: PodDigest = merge_digests([])
        # ---- bounded-staleness merge state ----
        self._cycle = 0
        self._digest_cache: Dict[int, PodDigest] = {}
        self._digest_cycle: Dict[int, int] = {}
        self._known_group_ranks: Dict[str, Tuple[int, ...]] = {}
        self._covered_groups: Set[str] = set()
        self._missing_pods: List[int] = []
        self._warming_pods: List[int] = []
        self._degraded_pods: List[int] = []
        self._warming: Dict[int, int] = {}   # pod -> warm until cycle
        self._coverage_fraction = 1.0
        # in-process fault emulation (chaos pod_kill / pod_slow)
        self._pod_down: Set[int] = set()
        self._pod_slow: Set[int] = set()
        # fault-tolerance counters surfaced via stats()/snapshots
        self._session_resyncs = 0
        # shm fast-path degradation counters (always 0 in-process; the
        # multi-process facade bumps them when an upload falls back
        # from its ring to the pipe)
        self._ring_overflows = 0
        self._ring_fallback_uploads = 0
        self.suppressed_low_coverage = 0

    # -- chaos fault injection ------------------------------------------------
    def inject_pod_fault(self, pod: int, kind: str) -> None:
        """Emulate one pod failure in-process: ``pod_kill`` stops the
        pod contributing digests entirely, ``pod_slow`` makes it miss
        every collect deadline.  Both present to the facade as "no
        fresh digest" — exactly how the multi-process transport
        surfaces a dead or wedged worker."""
        if kind not in POD_FAULT_KINDS:
            raise ValueError(f"unknown pod fault {kind!r}; "
                             f"choose from {POD_FAULT_KINDS}")
        if not 0 <= pod < self.n_pods:
            raise ValueError(f"pod {pod} out of range")
        (self._pod_down if kind == "pod_kill" else self._pod_slow).add(pod)

    def clear_pod_fault(self, pod: int) -> None:
        self._pod_down.discard(pod)
        self._pod_slow.discard(pod)

    # -- collection tier ------------------------------------------------------
    def _gather_digests(self, t0: float) -> Dict[int, PodDigest]:
        """Collect one fresh digest per *responsive* pod (the provider
        hook the multi-process facade replaces with deadline-bounded
        RPCs).  Slices still fan out concurrently under ``parallel``."""
        def slice_collect(pods: List[PodAggregator]) -> List[PodDigest]:
            return [p.collect(t0) for p in pods
                    if p.index not in self._pod_down
                    and p.index not in self._pod_slow]

        if self.parallel and len(self.pod_slices) > 1:
            with ThreadPoolExecutor(
                    max_workers=len(self.pod_slices)) as ex:
                parts = list(ex.map(slice_collect, self.pod_slices))
        else:
            parts = [slice_collect(s) for s in self.pod_slices]
        return {d.pod: d for part in parts for d in part}

    def _collect_fleet(self, t0: float):
        """Bounded-staleness merge: per pod, use the freshest digest no
        older than ``stale_after`` cycles; merge the usable ones in pod
        order and apply the single stable lateness sort.  Pods past the
        watermark drop out of the merge and into the coverage
        accounting.  With every pod responsive this is exactly the old
        barrier merge — event-for-event identical to the flat walk."""
        self._cycle += 1
        for i, d in self._gather_digests(t0).items():
            self._digest_cache[i] = d
            self._digest_cycle[i] = self._cycle
        usable: List[PodDigest] = []
        missing: List[int] = []
        for i in range(self.n_pods):
            d = self._digest_cache.get(i)
            if d is not None and \
                    self._cycle - self._digest_cycle[i] <= self.stale_after:
                usable.append(d)
            else:
                missing.append(i)
        self._missing_pods = missing
        for i in [p for p, until in self._warming.items()
                  if until < self._cycle]:
            del self._warming[i]
        self._warming_pods = [i for i in sorted(self._warming)
                              if i not in missing]
        self._degraded_pods = sorted(set(missing) | set(self._warming_pods))
        self._update_coverage(usable)
        top = merge_digests(usable)
        self.last_digest = top
        alerts = sorted(top.alerts, key=lambda a: -a.lateness)
        return alerts, top.summaries

    def _update_coverage(self, usable: List[PodDigest]) -> None:
        """Recompute what the merge can and cannot see.  *Known* state
        comes from every cached digest — a dark pod's last digest still
        tells us which groups/ranks exist behind it — plus whatever the
        facade knows independently (``_extra_known_group_ranks``);
        *covered* state only from usable digests of non-degraded pods.
        A freshly respawned worker is *warming*: its digests merge (the
        data it has is honest) but its groups stay uncovered until its
        detector windows have had ``respawn_warmup`` cycles to refill —
        an empty-windowed pod that "looks fresh" must not re-arm blame
        around ranks it cannot actually vouch for yet."""
        degraded = set(self._degraded_pods)
        self._covered_groups = {g for d in usable
                                if d.pod not in degraded
                                for g in d.group_ranks}
        known: Dict[str, Tuple[int, ...]] = {}
        for i in range(self.n_pods):
            d = self._digest_cache.get(i)
            if d is not None:
                known.update(d.group_ranks)
        for g, rs in self._extra_known_group_ranks().items():
            known.setdefault(g, rs)
        self._known_group_ranks = known
        if not degraded:
            self._coverage_fraction = 1.0
            return
        known_ranks: Set[int] = set()
        covered_ranks: Set[int] = set()
        for g, rs in known.items():
            known_ranks.update(rs)
            if g in self._covered_groups:
                covered_ranks.update(rs)
        self._coverage_fraction = (
            len(covered_ranks) / len(known_ranks) if known_ranks else 1.0)

    def note_pod_reset(self, pod: int) -> None:
        """Mark a pod as freshly restarted: its replacement engine's
        detector windows are empty, so the pod counts as degraded
        (uncovered, suppression-eligible) for ``respawn_warmup``
        collection cycles even though it answers RPCs immediately."""
        self._warming[pod] = self._cycle + self.respawn_warmup

    def _extra_known_group_ranks(self) -> Dict[str, Tuple[int, ...]]:
        """Membership the facade knows independently of pod digests.
        The in-process tier's digest cache is always complete (engines
        never lose state); the multi-process facade overrides this with
        its routed-profile bookkeeping so a respawned worker's empty
        first digest cannot erase what is known to exist behind it."""
        return {}

    def _rank_coverage(self, rank: int) -> float:
        """Fraction of the groups known to contain ``rank`` whose pod
        telemetry is within the staleness watermark."""
        known = covered = 0
        for g, rs in self._known_group_ranks.items():
            if rank in rs:
                known += 1
                if g in self._covered_groups:
                    covered += 1
        return covered / known if known else 1.0

    # -- degraded-mode hooks (see ShardedService.process) ---------------------
    def _filter_conclusions(self, locs, exports):
        """Coverage-floor suppression.  A localization's root rank must
        have enough of its own telemetry visible to be blamed: with the
        true root's pod dark, cascade localization walks a victim's
        blame chain to the nearest rank it *can* see — typically a
        bridge rank on a perfectly healthy node — and without this
        floor that node would be cordoned on partial data.  Exports
        whose root was suppressed go with it (a victim pointer at a
        suppressed root would resurrect the bad blame in audit())."""
        if not self._degraded_pods:
            return locs, exports
        kept = []
        dropped_roots = set()
        for loc in locs:
            if self._rank_coverage(loc.root_rank) < self.coverage_floor:
                dropped_roots.add(loc.root_group)
                self.suppressed_low_coverage += 1
            else:
                kept.append(loc)
        if dropped_roots:
            exports = [e for e in exports
                       if e.root_group not in dropped_roots]
        return kept, exports

    def _annotate_cycle(self, events: List[DiagnosticEvent]) -> None:
        """Every verdict emitted under partial coverage says so: the
        conclusion may be revised once the dark pods report again."""
        if not self._degraded_pods:
            return
        for ev in events:
            ev.evidence["coverage"] = {
                "degraded": True,
                "coverage_fraction": self._coverage_fraction,
                "missing_pods": list(self._missing_pods),
                "warming_pods": list(self._warming_pods),
            }

    def _facade_stats(self) -> Dict[str, float]:
        return {
            "coverage_fraction": self._coverage_fraction,
            "pods_live": float(self.n_pods - len(self._missing_pods)),
            "pods_dead": float(len(self._missing_pods)),
            "pods_warming": float(len(self._warming_pods)),
            "session_resyncs": float(self._session_resyncs),
            "ring_overflows": float(self._ring_overflows),
            "ring_fallback_uploads": float(self._ring_fallback_uploads),
            "pod_respawns": float(self._pod_respawns()),
            "pod_rpc_timeouts": float(self._pod_rpc_timeouts()),
            "suppressed_low_coverage": float(self.suppressed_low_coverage),
        }

    def _pod_respawns(self) -> int:
        return 0                   # in-process pods have no supervisor

    def _pod_rpc_timeouts(self) -> int:
        return 0                   # in-process pods have no RPC deadline

    # -- introspection --------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        agg = dict(super().stats())
        agg["pods"] = self.n_pods
        agg["pod_slices"] = len(self.pod_slices)
        agg["digest_ranks"] = self.last_digest.ranks
        agg["digest_stacks"] = int(self.last_digest.flame_sids.shape[0])
        return agg


def _silent_call(fn, *args):
    """Run one digest materialization, mapping any malformed frame or
    ring-protocol violation to "no digest this cycle" — the bounded-
    staleness merge treats it exactly like a missed deadline."""
    try:
        return fn(*args)
    except (DigestFormatError, ShmRingError):
        return None


def _silent_result(fut):
    try:
        return fut.result()
    except (DigestFormatError, ShmRingError):
        return None


class MultiProcPodService(PodTierService):
    """The pod tier over real OS processes.

    Every pod runs as a ``multiprocessing`` worker
    (``transport.pod_worker_main``): one ``CentralService`` engine plus
    its ``PodAggregator``, supervised by ``ft.supervisor.PodSupervisor``
    (dead workers respawn under their pod index; wedged workers fail
    their heartbeat and respawn).  All facade↔worker traffic crosses a
    deadline-bounded pipe: profile uploads go down as v3 wire sessions
    (one ``WireEncoder`` per pod; a respawned worker answers ``resync``
    and the facade re-opens the session), digests come back as SYPD
    frames into the same bounded-staleness merge as the in-process
    tier, and the diagnosis half (diagnose/export/temporal) runs as
    per-pod RPCs in exactly the in-process facade's order — so with no
    faults injected, ``process()`` is event-for-event equal to
    ``PodTierService`` (tests/test_pod_ft.py).

    With ``ring_bytes`` (the default), payload *bytes* skip the pipe:
    each worker maps a fork-inherited shared-memory ring pair
    (``repro.core.shmring``), uploads are wire v3 frames encoded
    directly into the up ring (zero intermediate ``bytes``; the tiny
    ``ingest_ring`` pipe message announces each record, so ordering /
    at-most-once / resync stay the pipe protocol's), digests come back
    over the down ring and decode as ``np.frombuffer`` views, and a
    full ring falls back to the pipe copy for that one payload rather
    than ever blocking ingest (counted in ``ring_overflows`` /
    ``ring_fallback_uploads``).  A respawned worker maps fresh rings —
    the dead incarnation's half-consumed records are unreachable.
    Facade-side digest decode parallelizes across pods over a small
    thread pool (``decode_workers``; numpy column decodes release the
    GIL) while the merge keeps the order-preserving two-level
    ``merge_digests`` reduction.

    Facade/worker state split: workers own the collection plane (flame
    graphs, waterlines, straggler windows, dampers); the facade owns
    the query plane (iteration-time history, the event log, blame-root
    pointers, SLOs, snapshots).  Two read-side features stay
    worker-local and are absent from facade snapshots: per-rank blame
    *timelines* and waterline summaries (both need per-rank profile
    state the facade deliberately never holds).

    Always ``close()`` (or use as a context manager) — workers are
    daemonic but deterministic teardown keeps tests hermetic."""

    def __init__(self, n_pods: int = 4, stale_after: int = 2,
                 coverage_floor: float = 0.75, respawn_warmup: int = 2,
                 rpc_timeout: float = 5.0, rpc_retries: int = 1,
                 ring_bytes: Optional[int] = 1 << 22,
                 decode_workers: Optional[int] = None,
                 supervisor_kwargs: Optional[Dict] = None, **kwargs):
        from repro.ft.supervisor import PodSupervisor
        self._worker_kwargs = dict(kwargs)
        super().__init__(n_pods=n_pods, pods_per_shard=1, parallel=False,
                         stale_after=stale_after,
                         coverage_floor=coverage_floor,
                         respawn_warmup=respawn_warmup, **kwargs)
        sup_kwargs = dict(call_timeout=rpc_timeout, retries=rpc_retries)
        if ring_bytes:
            sup_kwargs["spawn"] = functools.partial(
                spawn_pod_worker, ring_bytes=ring_bytes)
        sup_kwargs.update(supervisor_kwargs or {})
        self.supervisor = PodSupervisor(
            n_pods, service_kwargs=self._worker_kwargs, **sup_kwargs)
        # facade digest decode pool: per-pod decode is independent work
        # (numpy releases the GIL on the column passes), so it scales
        # with cores; <=1 worker or a 1-core box decodes serially
        if decode_workers is None:
            decode_workers = min(n_pods, os.cpu_count() or 1)
        self._decode_workers = max(1, int(decode_workers))
        self._decode_pool: Optional[ThreadPoolExecutor] = None
        # one uplink wire session per pod, bound to the facade tables
        self._encoders: Dict[int, WireEncoder] = {}
        # facade-side query plane (the in-process tier keeps this in
        # its shards; here the shards live in other processes)
        self._fl_history: Dict[Tuple[str, int], RankHistory] = {}
        self._fl_events: List[DiagnosticEvent] = []
        self._fl_counts: Dict[str, int] = {}
        self._fl_group_ranks: Dict[str, Set[int]] = {}
        self._fl_jobs: Dict[str, str] = {}
        self._fl_blame_roots: Dict[str, object] = {}
        self._fl_ingested = 0
        self._retain = self.shards[0].retain

    # -- lifecycle ------------------------------------------------------------
    def close(self) -> None:
        if self._decode_pool is not None:
            self._decode_pool.shutdown(wait=True)
            self._decode_pool = None
        self.supervisor.shutdown()

    def __enter__(self) -> "MultiProcPodService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _pod_respawns(self) -> int:
        return self.supervisor.respawns

    def _pod_rpc_timeouts(self) -> int:
        return self.supervisor.rpc_timeouts()

    # -- chaos fault injection ------------------------------------------------
    def inject_pod_fault(self, pod: int, kind: str) -> None:
        """``pod_kill`` is a real SIGKILL to the worker process (state
        loss and all); ``pod_slow`` makes the facade treat the pod's
        replies as missing their deadline (the deterministic stand-in
        for a wedged worker — the raw wedge path, a worker stuck in a
        blocking call, is exercised by the transport tests via the
        ``sleep`` verb)."""
        if kind not in POD_FAULT_KINDS:
            raise ValueError(f"unknown pod fault {kind!r}; "
                             f"choose from {POD_FAULT_KINDS}")
        if kind == "pod_kill":
            proc = self.supervisor.workers[pod].process
            proc.kill()
            proc.join(timeout=2.0)
        else:
            self._pod_slow.add(pod)

    def clear_pod_fault(self, pod: int) -> None:
        self._pod_slow.discard(pod)
        # a killed worker heals through supervision; run a pass now so
        # the schedule, not the next process() call, sets recovery time
        self._supervise()

    def _supervise(self) -> List[int]:
        """One supervision pass; every respawned pod starts its
        coverage warm-up (its replacement engine answers immediately
        but cannot vouch for its ranks until its windows refill)."""
        respawned = self.supervisor.supervise()
        for i in respawned:
            self.note_pod_reset(i)
        return respawned

    # -- ingestion ------------------------------------------------------------
    def ingest(self, profile, job_id: str = "job-0") -> None:
        self.ingest_batch(ProfileBatch(job_id, [profile], "node-0"))

    def ingest_batch(self, batch) -> int:
        by_pod: Dict[int, List] = {}
        for p in batch.profiles:
            by_pod.setdefault(
                shard_of(p.group_id, self.n_pods), []).append(p)
            self._note_profile(p, batch.job_id)
        for pod in sorted(by_pod):
            self._send_profiles(pod, batch.job_id, by_pod[pod],
                                batch.node_id)
        self._fl_ingested += len(batch.profiles)
        return len(batch.profiles)

    def _note_profile(self, p, job_id: str) -> None:
        """Facade-side query bookkeeping per routed profile (the cheap
        half of ``CentralService.ingest``: membership + iteration-time
        history; everything per-rank stays in the worker)."""
        g = p.group_id
        self._fl_jobs[g] = job_id
        self._fl_group_ranks.setdefault(g, set()).add(p.rank)
        hist = self._fl_history.get((g, p.rank))
        if hist is None:
            hist = self._fl_history[(g, p.rank)] = RankHistory(self._retain)
        hist.append(p.iteration, p.iter_time)

    def _send_profiles(self, pod: int, job_id: str, profiles: List,
                       node_id: str) -> None:
        """Ship one pod's sub-batch: a v3 delta frame when the profiles
        are columnar over the facade tables, pickled dataclasses
        otherwise.  A ``resync`` reply (fresh worker, no session) re-
        opens the session and resends; a dead/wedged pod drops the
        sub-batch — the coverage accounting, not an exception, reports
        the loss."""
        client = self.supervisor.client(pod)
        columnar = all(isinstance(p, ColumnarProfile)
                       and p.tables is self.tables for p in profiles)
        try:
            if columnar:
                enc = self._encoders.get(pod)
                if enc is None:
                    enc = self._encoders[pod] = WireEncoder(self.tables)
                batch = ColumnarBatch(job_id, profiles, node_id,
                                      self.tables)
                status, _ = self._upload_columnar(pod, client, enc, batch)
                if status == "resync":
                    enc.reset()
                    self._session_resyncs += 1
                    status, _ = self._upload_columnar(pod, client, enc,
                                                      batch)
                if status == "ok":
                    enc.commit()
                    self.supervisor.beat(pod)
            else:
                plain = [p.to_dataclasses()
                         if isinstance(p, ColumnarProfile) else p
                         for p in profiles]
                status, _ = client.call("ingest_profiles", (job_id, plain))
                if status == "ok":
                    self.supervisor.beat(pod)
        except PodTransportError:
            pass

    def _upload_columnar(self, pod: int, client, enc: WireEncoder,
                         batch: ColumnarBatch):
        """One delta-frame upload attempt, ring-first: encode directly
        into the pod's up ring and announce ``(record seq, nbytes)``
        over the pipe.  A full ring (``ring_overflows``) or a frame
        larger than the reservable span falls back to the pipe-copied
        byte path (``ring_fallback_uploads``) — the fallback carries
        the *identical* frame bytes, so session semantics don't fork."""
        rings = self.supervisor.rings(pod)
        if rings is not None:
            mv = rings.up.reserve_max()
            if mv is None:
                self._ring_overflows += 1
            else:
                try:
                    n = enc.encode_into(batch, mv)
                except BufferError:
                    rings.up.cancel()
                else:
                    return client.call("ingest_ring",
                                       (rings.up.commit(n), n))
            self._ring_fallback_uploads += 1
        return client.call("ingest_encoded", bytes(enc.encode(batch)))

    def ingest_log_line(self, job_id: str, line: str
                        ) -> Optional[DiagnosticEvent]:
        # log lines never carry per-rank state; match + record at the
        # facade (same rules, same event shape as the shard path)
        for pattern, cause in LOG_SOP_RULES:
            if pattern.lower() in line.lower():
                ev = DiagnosticEvent(
                    job_id=job_id, group_id="-", category="software",
                    root_cause=cause, verdict=None, straggler_rank=None,
                    detected_at=time.monotonic(), diagnosis_latency_s=0.0,
                    evidence={"log": line[:200]})
                self._fl_record(ev)
                return ev
        return None

    def evict_group(self, group_id: str) -> None:
        self._evict_facade_group(group_id)
        try:
            pod = shard_of(group_id, self.n_pods)
            self.supervisor.client(pod).call("evict_group", group_id)
        except PodTransportError:
            pass

    def _evict_facade_group(self, g: str) -> None:
        for r in self._fl_group_ranks.pop(g, ()):
            self._fl_history.pop((g, r), None)
        self._fl_jobs.pop(g, None)
        self._fl_blame_roots.pop(g, None)
        self._known_groups.discard(g)
        self._drop_group_slos(g)

    # -- collection over the wire ---------------------------------------------
    def _gather_digests(self, t0: float) -> Dict[int, PodDigest]:
        """Collect RPCs stay serial on the pipe (tiny control messages);
        the expensive half — decoding each pod's SYPD frame — fans out
        over the decode pool, one independent task per pod, and the
        caller's pod-index-ordered ``merge_digests`` reduction is
        untouched.  A pod's heartbeat only counts once its digest
        actually decoded, exactly as on the serial path."""
        replies: Dict[int, object] = {}
        for i in range(self.n_pods):
            if i in self._pod_slow:
                continue           # deadline-missing pod: no fresh digest
            try:
                status, data = self.supervisor.client(i).call(
                    "collect", t0, retries=0)
            except PodTransportError:
                continue
            if status == "ok":
                replies[i] = data
        out: Dict[int, PodDigest] = {}
        if len(replies) > 1 and self._decode_workers > 1:
            if self._decode_pool is None:
                self._decode_pool = ThreadPoolExecutor(
                    max_workers=self._decode_workers,
                    thread_name_prefix="digest-decode")
            futs = {i: self._decode_pool.submit(self._pop_digest, i, data)
                    for i, data in replies.items()}
            results = {i: _silent_result(f) for i, f in futs.items()}
        else:
            results = {i: _silent_call(self._pop_digest, i, data)
                       for i, data in replies.items()}
        for i, d in results.items():
            if d is not None:
                out[i] = d
                self.supervisor.beat(i)
        return out

    def _pop_digest(self, pod: int, data) -> PodDigest:
        """Materialize one collect reply: inline SYPD bytes, or a
        ``("ring", seq, nbytes)`` announcement — walk the pod's down
        ring to the announced record (releasing stale records whose
        replies were dropped by a timed-out collect) and decode it
        detached, so the slot can be recycled immediately."""
        if not (isinstance(data, tuple) and data and data[0] == "ring"):
            return decode_digest(data)
        _tag, rseq, nbytes = data
        rings = self.supervisor.rings(pod)
        if rings is None:
            raise DigestFormatError("ring digest reply but no rings mapped")
        while True:
            got = rings.down.pop()
            if got is None:
                raise DigestFormatError(
                    f"announced ring digest {rseq} not committed")
            seq, view = got
            try:
                if seq == rseq:
                    if len(view) != nbytes:
                        raise DigestFormatError(
                            "ring digest length mismatch")
                    return decode_digest(view, detach=True)
            finally:
                rings.down.release()
            if seq > rseq:
                raise DigestFormatError(
                    f"ring digest {rseq} already consumed (at {seq})")

    def _rpc_event(self, pod: int, kind: str,
                   payload) -> Optional[DiagnosticEvent]:
        try:
            status, ev = self.supervisor.client(pod).call(kind, payload)
        except PodTransportError:
            return None
        if status != "ok":
            return None
        self.supervisor.beat(pod)
        return ev

    # -- the analysis cycle ---------------------------------------------------
    def process(self) -> List[DiagnosticEvent]:
        """One fleet-wide cycle, mirroring ``ShardedService.process``'s
        attribution path RPC-for-call: collect → localize (facade) →
        filter by coverage → per-root diagnose / per-victim export on
        the owning pod → per-pod temporal + damper tick, in pod index
        order → sequence, annotate, record and publish at the facade.
        A pod that dies mid-cycle loses its contributions to this cycle
        only; the supervisor pass at the top respawns casualties
        immediately, and the respawned pod counts as degraded (warming)
        for ``respawn_warmup`` cycles while its windows refill."""
        t0 = time.monotonic()
        self._supervise()
        alerts, summaries = self._collect_fleet(t0)
        locs, exports = localize_cascades(alerts, summaries)
        locs, exports = self._filter_conclusions(locs, exports)
        for g, br in blame_roots_from(locs, exports,
                                      self._epoch + 1).items():
            self._fl_blame_roots[g] = br
        emitted: List[DiagnosticEvent] = []
        flagged: Set[str] = set()
        for loc in locs:
            flagged.add(loc.root_group)
            flagged.update(loc.affected_groups)
            ev = self._rpc_event(shard_of(loc.root_group, self.n_pods),
                                 "diagnose_root", (loc, t0))
            if ev:
                emitted.append(ev)
        for exp in exports:
            flagged.add(exp.group_id)
            ev = self._rpc_event(shard_of(exp.group_id, self.n_pods),
                                 "export_event", (exp, t0))
            if ev:
                emitted.append(ev)
        flag_list = sorted(flagged)
        for i in range(self.n_pods):
            try:
                status, evs = self.supervisor.client(i).call(
                    "temporal", (flag_list, t0))
            except PodTransportError:
                continue
            if status == "ok":
                emitted.extend(evs)
                self.supervisor.beat(i)
        CentralService._sequence(emitted, t0)
        self._annotate_cycle(emitted)
        for ev in emitted:
            self._fl_record(ev)
        self._publish_facade(t0)
        return emitted

    def _extra_known_group_ranks(self) -> Dict[str, Tuple[int, ...]]:
        return {g: tuple(rs)
                for g, rs in self._fl_group_ranks.items()}

    def _fl_record(self, ev: DiagnosticEvent) -> None:
        self._fl_events.append(ev)
        self._fl_counts[ev.category] = \
            self._fl_counts.get(ev.category, 0) + 1

    # -- publication ----------------------------------------------------------
    def _publish_facade(self, t0: float) -> None:
        """Facade-built ``FleetSnapshot``: groups/membership from the
        routed-profile bookkeeping, blame summaries from the merged
        digest, history/events/blame-roots from the facade query plane.
        Groups a *fresh* digest no longer mentions were evicted inside
        their worker (idle TTL) and retire here too; a dark pod's
        groups are never retired on its silence, and a *warming* pod's
        empty post-respawn digests carry no eviction authority either —
        its groups lost state, they did not go idle."""
        live = {g for d in self._digest_cache.values()
                for g in d.group_ranks}
        for g in list(self._fl_group_ranks):
            pod = shard_of(g, self.n_pods)
            if self._digest_cycle.get(pod) == self._cycle \
                    and pod not in self._warming and g not in live:
                self._evict_facade_group(g)
        self._epoch += 1
        hist = {k: h.view() for k, h in self._fl_history.items()}
        summaries = self.last_digest.summaries
        groups = []
        for g in sorted(self._fl_group_ranks):
            ranks = tuple(sorted(self._fl_group_ranks[g]))
            last_it = -1
            for r in ranks:
                v = hist.get((g, r))
                if v is not None and v.n_it:
                    last_it = max(last_it, v.it[v.n_it - 1])
            s = summaries.get(g)
            groups.append(GroupView(
                group_id=g, job_id=self._fl_jobs.get(g, "job-0"),
                ranks=ranks, last_iteration=last_it,
                waterline_top=(),
                blame=s.as_dict() if s is not None else None))
        self._known_groups = {gv.group_id for gv in groups}
        self._snapshot = FleetSnapshot(
            epoch=self._epoch, published_at=t0, groups=tuple(groups),
            history=hist, events=EventLog(self._fl_events),
            blame_roots=dict(self._fl_blame_roots), stats=self.stats())

    # -- merged reporting view ------------------------------------------------
    @property
    def ingested(self) -> int:
        return self._fl_ingested

    @property
    def events(self) -> List[DiagnosticEvent]:
        return sorted(self._fl_events, key=lambda e: e.detected_at)

    def event_counts(self) -> Dict[str, int]:
        return dict(self._fl_counts)

    def standing_verdicts(self) -> Dict:
        merged: Dict = {}
        for i in range(self.n_pods):
            try:
                status, sv = self.supervisor.client(i).call("standing")
            except PodTransportError:
                continue
            if status == "ok":
                merged.update(sv)
        return merged

    def stats(self) -> Dict[str, float]:
        d: Dict[str, float] = {
            "ingested": float(self._fl_ingested),
            "groups": float(len(self._fl_group_ranks)),
            "ranks": float(sum(dg.ranks
                               for dg in self._digest_cache.values())),
            "events": float(len(self._fl_events)),
            "epoch": float(self._epoch),
            "shards": float(self.n_pods),
            "pods": float(self.n_pods),
            "pod_slices": float(len(self.pod_slices)),
            "digest_ranks": float(self.last_digest.ranks),
            "digest_stacks": float(self.last_digest.flame_sids.shape[0]),
        }
        d.update(self._facade_stats())
        return d
