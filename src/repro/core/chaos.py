"""Seeded chaos harness over the fleet simulator (ROADMAP: closed-loop
mitigation under chaos; EROICA's online-troubleshooting framing).

A :class:`ChaosSchedule` composes a randomized *fault storm* from the
registered scenario injectors (``repro.core.scenarios``): flapping
on/off faults, overlapping multi-root incidents in different groups,
agent dropouts with late backfilled uploads, and mitigation blips that
themselves perturb the fleet.  The whole storm is generated from one
RNG seed into plain data (a sorted :class:`ChaosEvent` timeline), so a
storm replays bit-identically — on the same service path or across all
of them — from nothing but ``(seed, layout, links)``.

:class:`ChaosRunner` drives one schedule into one service path (the
same paths ``run_scenario_matrix`` exercises) and scores the
outcome: which true roots were localized, how often emitted verdicts
flipped causes, and the full event-tuple stream for cross-path
equality assertions.  ``benchmarks/bench_chaos.py`` gates a pinned
storm on exactly these scores.

Storm faults draw from the *stackless* scenario subset by default
(kernel/OS/entry-delay effects only), so a storm scores localization
and flap damping rather than how fast decayed flame graphs forget a
stack fault after a mid-run ``remove_fault``.
"""
from __future__ import annotations

import dataclasses
import random
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.simcluster import (Fault, MultiGroupSimCluster,
                                   cascade_fleet)

__all__ = [
    "CHAOS_SCENARIO_POOL", "ChaosEvent", "TrueRoot", "ChaosSchedule",
    "ChaosReport", "ChaosRunner", "restart_perturbation",
]

#: Scenario names safe for cross-path storms: rank-targeted and
#: stackless (see module docstring for why stack injectors stay out).
CHAOS_SCENARIO_POOL: Tuple[str, ...] = (
    "gpu_thermal_throttle", "memory_pressure_swap",
    "pcie_link_degradation", "cpu_frequency_downclock",
    "ecc_row_remap_stall", "numa_remote_allocation")


def restart_perturbation(name: str, ranks: Sequence[int], start: int,
                         duration: int = 3,
                         severity: float = 0.15) -> Fault:
    """The fleet-side cost of a mitigation: restarting/cordoning a node
    stalls its ranks' collective entries for ``duration`` iterations
    (process teardown, NCCL re-init).  Used both by chaos storms (a
    ``mitigate`` event) and by the mitigation replayer, which charges a
    planned action this same perturbation inside the forked what-if
    cluster before approving it."""
    return Fault(name=name, ranks=list(ranks),
                 entry_delay=lambda base: severity * base,
                 start_iteration=start, end_iteration=start + duration)


@dataclasses.dataclass(frozen=True)
class ChaosEvent:
    """One timeline entry.  ``kind`` is one of:

    * ``inject``  — add ``fault`` to group ``group_index``
    * ``clear``   — remove faults named ``name`` from ``group_index``
    * ``agent_down`` / ``agent_up`` — global rank ``rank`` stops /
      resumes uploading (held profiles backfill on resume)
    * ``mitigate`` — fleet-wide :func:`restart_perturbation`
    * ``pod_kill`` / ``pod_slow`` — collection-plane fault against pod
      ``pod``: the pod worker dies (state loss; the supervisor respawns
      it) or wedges (misses every collect deadline).  ``pod_up`` clears
      a ``pod_slow`` (a killed pod heals through supervision).  These
      target the *diagnosis system*, not the fleet: on service paths
      without a pod tier they are no-ops by design, so a storm with pod
      faults still replays on every path.
    """
    iteration: int
    kind: str
    name: str = ""
    group_index: Optional[int] = None
    rank: Optional[int] = None
    fault: Optional[Fault] = None
    pod: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class TrueRoot:
    """Ground truth for one storm fault: where the blame must land."""
    group_index: int
    rank: int
    cause: str
    scenario: str
    category: str
    flapping: bool

    def node(self, chips_per_node: int = 8) -> int:
        return self.rank // chips_per_node


@dataclasses.dataclass
class ChaosSchedule:
    """A replayable storm: pure data, generated once per seed."""
    seed: int
    layout: Tuple[Tuple[int, ...], ...]
    links: Tuple[Tuple[int, int], ...]
    horizon: int
    events: List[ChaosEvent]
    true_roots: List[TrueRoot]
    chips_per_node: int = 8

    def __post_init__(self) -> None:
        self._by_iter: Dict[int, List[ChaosEvent]] = {}
        for ev in sorted(self.events, key=lambda e: e.iteration):
            self._by_iter.setdefault(ev.iteration, []).append(ev)

    def events_at(self, iteration: int) -> List[ChaosEvent]:
        return self._by_iter.get(iteration, [])

    def dropout_ranks(self) -> List[int]:
        return sorted({ev.rank for ev in self.events
                       if ev.kind == "agent_down" and ev.rank is not None})

    # ------------------------------------------------------------------
    @classmethod
    def generate(cls, seed: int, layout: Sequence[Sequence[int]],
                 links: Sequence[Tuple[int, int]] = (), *,
                 n_faults: int = 5, horizon: int = 120,
                 onset: Tuple[int, int] = (25, 45),
                 flap_prob: float = 0.5,
                 burst_on: Tuple[int, int] = (10, 16),
                 burst_off: Tuple[int, int] = (4, 7),
                 n_dropouts: int = 1,
                 dropout_at: Tuple[int, int] = (20, 35),
                 dropout_len: Tuple[int, int] = (5, 9),
                 n_mitigation_blips: int = 1,
                 n_pod_faults: int = 0, n_pods: int = 0,
                 pod_fault_at: Tuple[int, int] = (55, 70),
                 pod_fault_len: Tuple[int, int] = (10, 18),
                 pod_kill_prob: float = 0.5,
                 chips_per_node: int = 8,
                 pool: Sequence[str] = CHAOS_SCENARIO_POOL,
                 registry=None) -> "ChaosSchedule":
        """Compose a storm from one seed.

        ``n_faults`` distinct groups each get one injector from
        ``pool``, retargeted (``dataclasses.replace``) onto a randomly
        chosen *non-bridge* rank of that group — bridge ranks belong to
        two groups, which would make the expected blame ambiguous.
        With probability ``flap_prob`` a fault flaps: alternating
        inject/clear bursts whose final burst stays on through the
        horizon, so every true root is live (and assertable) at the
        end.  Dropout ranks come from storm-free groups so a silent
        agent is unambiguously healthy.  Mitigation blips charge a
        :func:`restart_perturbation` to one culprit's node mid-run —
        the operator poking the fleet while it is already on fire.
        With ``n_pod_faults > 0`` (requires ``n_pods``) the storm also
        attacks the collection plane: distinct pods get killed
        (``pod_kill_prob``) or wedged mid-storm, each followed by a
        ``pod_up`` after ``pod_fault_len`` iterations — the diagnosis
        system being diagnosed while parts of it are down."""
        from repro.core.scenarios import default_registry
        registry = registry if registry is not None else default_registry()
        by_name = {s.name: s for s in registry.scenarios}
        missing = [n for n in pool if n not in by_name]
        if missing:
            raise ValueError(f"pool scenarios not registered: {missing}")
        if n_faults > len(layout):
            raise ValueError(
                f"n_faults={n_faults} needs at least that many groups "
                f"(got {len(layout)}): one storm fault per group")
        rng = random.Random(seed)
        member_count = Counter(r for g in layout for r in g)
        events: List[ChaosEvent] = []
        roots: List[TrueRoot] = []
        storm_groups = sorted(rng.sample(range(len(layout)), n_faults))
        for gi in storm_groups:
            scen = by_name[rng.choice(list(pool))]
            candidates = [r for r in layout[gi] if member_count[r] == 1]
            if not candidates:
                candidates = list(layout[gi])
            rank = rng.choice(candidates)
            start = rng.randint(*onset)
            name = f"chaos/{scen.name}@g{gi}r{rank}"
            base = dataclasses.replace(
                scen.make_fault(), name=name, ranks=[rank],
                end_iteration=None)
            flapping = rng.random() < flap_prob
            if not flapping:
                events.append(ChaosEvent(
                    iteration=start, kind="inject", name=name,
                    group_index=gi,
                    fault=dataclasses.replace(base,
                                              start_iteration=start)))
            else:
                t = start
                while True:
                    events.append(ChaosEvent(
                        iteration=t, kind="inject", name=name,
                        group_index=gi,
                        fault=dataclasses.replace(base,
                                                  start_iteration=t)))
                    on = rng.randint(*burst_on)
                    if t + on >= horizon - burst_on[1]:
                        break      # final burst rides out the horizon
                    events.append(ChaosEvent(
                        iteration=t + on, kind="clear", name=name,
                        group_index=gi))
                    t = t + on + rng.randint(*burst_off)
            roots.append(TrueRoot(
                group_index=gi, rank=rank, cause=scen.expected_cause,
                scenario=scen.name, category=scen.category,
                flapping=flapping))
        # agent dropouts: silent-but-healthy ranks in storm-free groups
        quiet_groups = [i for i in range(len(layout))
                        if i not in set(storm_groups)] or \
            list(range(len(layout)))
        culprit_ranks = {r.rank for r in roots}
        for k in range(n_dropouts):
            gi = quiet_groups[rng.randrange(len(quiet_groups))]
            candidates = [r for r in layout[gi]
                          if member_count[r] == 1
                          and r not in culprit_ranks] or list(layout[gi])
            rank = rng.choice(candidates)
            d0 = rng.randint(*dropout_at)
            dlen = rng.randint(*dropout_len)
            events.append(ChaosEvent(iteration=d0, kind="agent_down",
                                     name=f"dropout#{k}", rank=rank))
            events.append(ChaosEvent(iteration=d0 + dlen, kind="agent_up",
                                     name=f"dropout#{k}", rank=rank))
        # mitigation blips: the fix itself perturbs the culprit's node
        for k in range(n_mitigation_blips):
            root = roots[rng.randrange(len(roots))]
            node = root.node(chips_per_node)
            node_ranks = sorted({r for g in layout for r in g
                                 if r // chips_per_node == node})
            at = rng.randint(onset[1] + 10,
                             max(onset[1] + 11, horizon - 20))
            # softer than a real restart (see restart_perturbation's
            # defaults, which the replayer charges): a storm blip must
            # perturb the fleet without drowning a root fault whose
            # windowed lateness is still emerging
            events.append(ChaosEvent(
                iteration=at, kind="mitigate",
                name=f"chaos/mitigate-node{node}#{k}",
                fault=restart_perturbation(
                    f"chaos/mitigate-node{node}#{k}", node_ranks, at,
                    duration=2, severity=0.05)))
        # collection-plane faults: kill/wedge distinct pod workers
        if n_pod_faults:
            if n_pod_faults > n_pods:
                raise ValueError(
                    f"n_pod_faults={n_pod_faults} needs n_pods >= that "
                    f"(got {n_pods}): one fault per distinct pod")
            for k, pod in enumerate(sorted(
                    rng.sample(range(n_pods), n_pod_faults))):
                kind = ("pod_kill" if rng.random() < pod_kill_prob
                        else "pod_slow")
                at = rng.randint(*pod_fault_at)
                events.append(ChaosEvent(
                    iteration=at, kind=kind,
                    name=f"chaos/{kind}-pod{pod}#{k}", pod=pod))
                events.append(ChaosEvent(
                    iteration=at + rng.randint(*pod_fault_len),
                    kind="pod_up",
                    name=f"chaos/{kind}-pod{pod}#{k}", pod=pod))
        return cls(seed=seed,
                   layout=tuple(tuple(g) for g in layout),
                   links=tuple(tuple(l) for l in links),
                   horizon=horizon, events=events, true_roots=roots,
                   chips_per_node=chips_per_node)


@dataclasses.dataclass
class ChaosReport:
    """Scored outcome of one storm on one service path."""
    path: str
    schedule: ChaosSchedule
    events: List                       # emitted DiagnosticEvents, in order
    event_tuples: List[Tuple[str, str, str, Optional[int]]]
    flips: int                         # emitted cause changes per (g, rank)
    localized: Dict[Tuple[int, int], bool]   # true root -> blamed correctly
    service: object
    cluster: MultiGroupSimCluster

    @property
    def flip_rate(self) -> float:
        return self.flips / max(1, len(self.events))

    @property
    def all_roots_localized(self) -> bool:
        return all(self.localized.values())

    def missed_roots(self) -> List[TrueRoot]:
        return [r for r in self.schedule.true_roots
                if not self.localized[(r.group_index, r.rank)]]


class ChaosRunner:
    """Drive one :class:`ChaosSchedule` into one service path.

    The runner emulates the collection tier's failure modes itself:
    profiles of a dropped-out rank are held in a per-rank buffer (the
    agent's ring) and delivered in original order when the agent comes
    back, *before* that cycle's fresh profiles — the late/partial
    upload shape the aligner and straggler windows must tolerate."""

    def __init__(self, schedule: ChaosSchedule, path: str = "streaming",
                 *, n_shards: int = 4, window: int = 50,
                 process_every: int = 10, registry=None,
                 service_kwargs: Optional[Dict] = None,
                 cluster_kwargs: Optional[Dict] = None):
        from repro.core.scenarios import default_registry
        from repro.core.simcluster import SERVICE_PATHS
        # "podproc" — the pod tier over real OS processes — is a chaos/
        # bench-only path: it is deliberately not in SERVICE_PATHS so
        # the scenario matrix stays fork-free and fast.
        if path not in SERVICE_PATHS + ("podproc",):
            raise ValueError(
                f"unknown service path {path!r}; choose from "
                f"{SERVICE_PATHS + ('podproc',)}")
        self.schedule = schedule
        self.path = path
        self.process_every = process_every
        self.registry = (registry if registry is not None
                         else default_registry())
        columnar = path in ("columnar", "pod", "podproc")
        # cluster_kwargs lets scale tests thin the simulation (e.g.
        # samples_per_iter=64 for a 1k-rank storm) without a new path
        self.cluster = cascade_fleet(
            [list(g) for g in schedule.layout],
            [tuple(l) for l in schedule.links],
            seed=schedule.seed, columnar=columnar,
            native_unwind=columnar, **(cluster_kwargs or {}))
        kwargs = dict(window=window, registry=self.registry,
                      chips_per_node=schedule.chips_per_node)
        kwargs.update(service_kwargs or {})
        self.service = self._make_service(path, n_shards, kwargs)
        self._down: set = set()
        self._held: Dict[int, List] = {}

    @staticmethod
    def _make_service(path: str, n_shards: int, kwargs: Dict):
        from repro.core.pod import MultiProcPodService, PodTierService
        from repro.core.service import CentralService
        from repro.core.sharded import ShardedService
        if path in ("streaming", "columnar"):
            return CentralService(**kwargs)
        if path == "sharded":
            return ShardedService(n_shards=n_shards, **kwargs)
        if path == "podproc":
            return MultiProcPodService(n_pods=n_shards, **kwargs)
        return PodTierService(n_pods=n_shards, pods_per_shard=2, **kwargs)

    def close(self) -> None:
        """Tear down the service (the multi-process path forks real
        workers; benches and tests must not leak them)."""
        close = getattr(self.service, "close", None)
        if close is not None:
            close()

    # ------------------------------------------------------------------
    def _apply(self, ev: ChaosEvent, released: List[int]) -> None:
        cl = self.cluster
        if ev.kind == "inject":
            cl.add_fault(ev.group_index, ev.fault)
        elif ev.kind == "clear":
            cl.remove_fault(ev.name, ev.group_index)
        elif ev.kind == "agent_down":
            self._down.add(ev.rank)
        elif ev.kind == "agent_up":
            self._down.discard(ev.rank)
            released.append(ev.rank)
        elif ev.kind == "mitigate":
            cl.add_fleet_fault(ev.fault)
        elif ev.kind in ("pod_kill", "pod_slow"):
            # collection-plane fault: meaningful only on pod-tier paths;
            # elsewhere a no-op so the storm replays on every path
            if hasattr(self.service, "inject_pod_fault"):
                self.service.inject_pod_fault(ev.pod, ev.kind)
        elif ev.kind == "pod_up":
            if hasattr(self.service, "clear_pod_fault"):
                self.service.clear_pod_fault(ev.pod)
        else:
            raise ValueError(f"unknown chaos event kind {ev.kind!r}")

    def _ingest(self, profiles: List, enc) -> None:
        if not profiles:
            return
        from repro.core.trace import ColumnarBatch, encode_batch
        if enc is not None:
            self.service.ingest_encoded(enc.encode(ColumnarBatch(
                "job-0", profiles, "node-0", self.cluster.tables)))
            enc.commit()
        elif self.path == "columnar":
            self.service.ingest_encoded(encode_batch(ColumnarBatch(
                "job-0", profiles, "node-0", self.cluster.tables)))
        else:
            for p in profiles:
                self.service.ingest(p)

    def run(self) -> ChaosReport:
        from repro.core.trace import WireEncoder
        cl, svc, sched = self.cluster, self.service, self.schedule
        enc = (WireEncoder(cl.tables)
               if self.path in ("pod", "podproc") else None)
        emitted: List = []
        for it in range(sched.horizon):
            released: List[int] = []
            for ev in sched.events_at(it):
                self._apply(ev, released)
            profiles = cl.step()
            deliver: List = []
            for r in sorted(released):
                deliver.extend(self._held.pop(r, []))
            for p in profiles:
                if p.rank in self._down:
                    self._held.setdefault(p.rank, []).append(p)
                else:
                    deliver.append(p)
            self._ingest(deliver, enc)
            if cl.iteration % self.process_every == 0:
                emitted.extend(svc.process())
        emitted.extend(svc.process())
        return self._report(emitted)

    # ------------------------------------------------------------------
    def _report(self, emitted: List) -> ChaosReport:
        gids = self.cluster.group_ids()
        last: Dict[Tuple[str, Optional[int]], str] = {}
        flips = 0
        for e in emitted:
            key = (e.group_id, e.straggler_rank)
            if key in last and last[key] != e.root_cause:
                flips += 1
            last[key] = e.root_cause
        localized = {}
        for root in self.schedule.true_roots:
            g = gids[root.group_index]
            localized[(root.group_index, root.rank)] = any(
                e.group_id == g and e.straggler_rank == root.rank
                and e.root_cause == root.cause for e in emitted)
        return ChaosReport(
            path=self.path, schedule=self.schedule, events=emitted,
            event_tuples=[(e.group_id, e.root_cause, e.category,
                           e.straggler_rank) for e in emitted],
            flips=flips, localized=localized,
            service=self.service, cluster=self.cluster)
