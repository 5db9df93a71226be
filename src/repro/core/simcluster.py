"""Multi-rank training-cluster simulation + fault injection (§5.4).

Generates per-iteration IterationProfiles for an N-rank communication
group running a synchronous training loop: realistic CPU flame graphs
(the Fig 6 forward/softmax/dropout paths), per-kernel GPU timings, NCCL
collective entry/exit events with per-rank clock skew and jitter, and OS
signal counters.

Faults are *pluggable*: a :class:`Fault` describes an incident by its
per-layer effects (kernel slowdown factor, CPU-stack rewrite, OS-counter
perturbation, collective entry delay) rather than by name, so a new
production scenario is one factory function plus a registry entry
(``repro.core.scenarios``) — no simulator edits.  The factories below
cover the paper's five §5.4 case studies plus six further production
incidents; :func:`run_scenario_matrix` drives every registered scenario
through the streaming, columnar, sharded and pod service paths and
checks the expected diagnosis.

Wall-clock here is simulated (the cluster "runs" at arbitrary speed), so
diagnosis latency is measured in iterations + real analysis time.
"""
from __future__ import annotations

import dataclasses
import hashlib
import random
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.collective.introspect import CommStructCodec
from repro.core.events import (CollectiveEvent, IterationProfile, KernelEvent,
                               OSSignals, StackSample)
from repro.core.symbols.resolver import CentralResolver
from repro.core.trace import ColumnarProfile, TraceTables
from repro.core.unwind import HybridUnwinder, SimProcess, SimThread
from repro.core.unwind.procmodel import Binary, FunctionDef

__all__ = [
    "Fault", "StackRow", "NativeStackFeed",
    "thermal_throttle", "nic_softirq", "vfs_lock_contention",
    "logging_overhead", "io_bottleneck", "dataloader_starvation",
    "swap_thrash", "pcie_link_degradation", "cpu_downclock",
    "ecc_row_remap", "numa_remote_alloc",
    "SimCluster", "MultiGroupSimCluster",
    "cascade_fleet", "expect_cascade_export",
    "SERVICE_PATHS", "ScenarioResult", "run_scenario_matrix",
]

# ---------------------------------------------------------------------------
# baseline workload model (Fig 6's python/c++ mixed stacks)
# ---------------------------------------------------------------------------

_BASE_STACKS: List[Tuple[Tuple[str, ...], float]] = [
    (("py::train_loop", "py::forward", "py::_wrapped_call_impl", "py::softmax",
      "torch::autograd::THPVariable_softmax", "at::_ops::_softmax::call",
      "at::native::softmax", "cudaLaunchKernel"), 0.21),
    (("py::train_loop", "py::forward", "py::dropout",
      "torch::autograd::THPVariable_dropout", "at::_ops::native_dropout::call",
      "at::native::dropout_cuda", "cudaLaunchKernel"), 0.16),
    (("py::train_loop", "py::forward", "py::attention_mask_func",
      "at::_ops::masked_fill_::call", "at::native::masked_fill"), 0.10),
    (("py::train_loop", "py::backward", "torch::autograd::Engine::execute",
      "at::native::matmul_backward", "cudaLaunchKernel"), 0.25),
    (("py::train_loop", "py::optimizer_step", "at::_ops::_foreach_add_::call",
      "at::native::foreach_tensor_add"), 0.08),
    (("py::train_loop", "py::data_next", "py::collate",
      "PyObject_CallFunctionObjArgs"), 0.06),
    (("ncclAllReduce", "ncclGroupEnd", "ncclProxyService"), 0.09),
    (("py::train_loop", "py::log_metrics", "py::format"), 0.05),
]

_BASE_KERNELS: List[Tuple[str, float]] = [
    ("gemm_bf16_128x128", 38e-3),
    ("flash_attention_fwd", 21e-3),
    ("elementwise_softmax", 8e-3),
    ("dropout_kernel", 6e-3),
    ("layernorm_fwd", 5e-3),
    ("gemm_bf16_bwd", 52e-3),
    ("ncclDevKernel_ReduceScatter", 14e-3),
    ("adam_update", 4e-3),
]

# Fault stack fragments -------------------------------------------------------

_NIC_SOFTIRQ_STACK = (
    "asm_common_interrupt", "common_interrupt", "irq_exit_rcu", "do_softirq",
    "net_rx_action", "napi_poll", "virtnet_poll", "virtnet_receive",
    "napi_gro_receive")

_VFS_STACKS = [
    (("py::data_next", "py::open", "do_sys_openat2", "path_openat",
      "link_path_walk", "__legitimize_path", "lockref_get_not_dead",
      "queued_spin_lock_slowpath"), 0.65),
    (("py::data_next", "py::open", "do_sys_openat2", "path_openat",
      "terminate_walk", "dput", "queued_spin_lock_slowpath"), 0.24),
    (("py::data_next", "py::open", "do_sys_openat2", "path_openat",
      "lookup_fast", "unlazy_child", "queued_spin_lock_slowpath"), 0.11),
]

_LOGGING_STACK = ("py::train_loop", "py::log_metrics", "SLS::LogClient::Send",
                  "protobuf::Serialize", "memcpy")

_IO_STACKS = [
    (("py::data_next", "py::read_shard", "cpfs::Client::Read",
      "cpfs::RpcChannel::Call"), 0.6),
    (("py::data_next", "py::fetch_object", "ossutils::GetObject",
      "ossutils::HttpTransfer"), 0.4),
]


# ---------------------------------------------------------------------------
# fault injectors
# ---------------------------------------------------------------------------

# (stack, weight) rows as produced by SimCluster._cpu_rows
StackRow = Tuple[Tuple[str, ...], float]


@dataclasses.dataclass
class Fault:
    """One injected incident, described by its per-layer *effects*.

    Each hook perturbs one layer of the simulated iteration; ``None``
    (or 1.0 for ``kernel_factor``) means "no effect at that layer".  The
    simulator applies every active fault generically — adding a scenario
    never requires editing ``SimCluster`` itself:

      kernel_factor  multiplies every GPU kernel duration (thermal caps,
                     ECC-induced downclocks, MIG contention, ...)
      stack_effect   rewrites the (stack, weight) rows a rank samples
                     (host-side interference visible in flame graphs)
      os_effect      mutates the draft OS-counter dict *in place* before
                     ``OSSignals`` is built (events too brief to sample)
      entry_delay    seconds of extra compute before the gradient
                     collective, as a function of the base iteration time
                     (what makes the rank a straggler at the barrier)

    Faults are stateless per step (every hook re-derives its effect from
    the current iteration), so teardown is exact: once a fault stops
    applying — ``end_iteration`` reached, or removed via
    ``SimCluster.remove_fault`` — the very next iteration is
    baseline-identical at every layer.  That is what makes flapping
    faults (chaos harness on/off windows) representable as plain
    inject/remove pairs.
    """
    name: str
    ranks: Sequence[int]               # affected ranks ([] = all)
    start_iteration: int = 0
    kernel_factor: float = 1.0
    stack_effect: Optional[Callable[[List[StackRow]], List[StackRow]]] = None
    os_effect: Optional[
        Callable[[Dict[str, object], random.Random], None]] = None
    entry_delay: Optional[Callable[[float], float]] = None
    # first iteration the fault no longer applies (None = open-ended)
    end_iteration: Optional[int] = None

    def applies(self, rank: int, iteration: int) -> bool:
        if iteration < self.start_iteration:
            return False
        if self.end_iteration is not None and iteration >= self.end_iteration:
            return False
        return not self.ranks or rank in self.ranks


def thermal_throttle(rank: int, start: int = 0, factor: float = 1.075) -> Fault:
    """§5.4 Case 1: one GPU clocks down — uniform kernel slowdown."""
    return Fault("gpu_thermal_throttle", [rank], start, kernel_factor=factor)


def nic_softirq(rank: int, start: int = 0, fraction: float = 0.0174) -> Fault:
    """§5.4 Case 2: NET_RX softirqs share the training cores of one rank."""
    def stacks(rows: List[StackRow]) -> List[StackRow]:
        return rows + [(_NIC_SOFTIRQ_STACK, fraction / (1 - fraction))]

    def os_fx(sig: Dict[str, object], rng: random.Random) -> None:
        sig["interrupts"]["NET_RX"] = 95_000 + rng.randint(-2000, 2000)
        sig["sched_latency_p99"] *= 4.0

    return Fault("nic_softirq_contention", [rank], start,
                 stack_effect=stacks, os_effect=os_fx,
                 entry_delay=lambda base: 0.6e-3)


def vfs_lock_contention(ranks: Sequence[int], start: int = 0,
                        slow: float = 1.6) -> Fault:
    """§5.4 Case 3: dcache invalidation serializes opens on some nodes."""
    def stacks(rows: List[StackRow]) -> List[StackRow]:
        rows = [(s, w * 0.25) for s, w in rows]
        return rows + [(s, w * 3.0) for s, w in _VFS_STACKS]

    def os_fx(sig: Dict[str, object], rng: random.Random) -> None:
        sig["sched_latency_p99"] *= 8.0

    return Fault("vfs_dentry_lock_contention", list(ranks), start,
                 stack_effect=stacks, os_effect=os_fx,
                 entry_delay=lambda base: (slow - 1) * base)


def logging_overhead(start: int = 0, fraction: float = 0.10) -> Fault:
    """§5.4 Case 4: DEBUG logging serializes on every training thread."""
    return Fault(
        "logging_overhead", [], start,
        stack_effect=lambda rows: rows + [(_LOGGING_STACK,
                                           fraction / (1 - fraction))],
        entry_delay=lambda base: fraction * base)


def io_bottleneck(start: int = 0, fraction: float = 0.12) -> Fault:
    """§5.4 Case 5: saturated storage tier stalls every data loader."""
    def stacks(rows: List[StackRow]) -> List[StackRow]:
        return rows + [(s, w * fraction / (1 - fraction)) for s, w in _IO_STACKS]

    return Fault("storage_io_bottleneck", [], start, stack_effect=stacks,
                 entry_delay=lambda base: fraction * base * 2.5)


# -- production scenarios beyond the five case studies -----------------------

_DATALOADER_STACK = ("py::train_loop", "py::data_next",
                     "py::_worker_queue_get", "pthread_cond_timedwait")


def dataloader_starvation(start: int = 0, fraction: float = 0.10) -> Fault:
    """Input-pipeline starvation: every rank blocks on an empty prefetch
    queue — uniform slowdown, new wait stacks under ``py::data_next``."""
    return Fault(
        "dataloader_starvation", [], start,
        stack_effect=lambda rows: rows + [(_DATALOADER_STACK,
                                           fraction / (1 - fraction))],
        entry_delay=lambda base: fraction * base * 2.0)


def swap_thrash(rank: int, start: int = 0,
                faults_per_window: int = 6000,
                delay_s: float = 1.5e-3) -> Fault:
    """Memory pressure on one node: the training process takes major page
    faults (swap-in) — too brief for sampled stacks, loud in vmstat.
    ``delay_s`` scales the collective entry delay (cascade benches raise
    it so the *victim* group's diluted share of the delay still clears
    their noise floor); the diagnosis signal is ``major_faults`` either
    way."""
    def os_fx(sig: Dict[str, object], rng: random.Random) -> None:
        sig["major_faults"] = faults_per_window + rng.randint(-500, 500)

    return Fault("memory_pressure_swap", [rank], start, os_effect=os_fx,
                 entry_delay=lambda base: delay_s)


def pcie_link_degradation(rank: int, start: int = 0, replays: int = 600) -> Fault:
    """One GPU's PCIe/NVLink link retrains: replay/CRC error counters climb
    while CPU and kernel profiles stay clean."""
    def os_fx(sig: Dict[str, object], rng: random.Random) -> None:
        sig["pcie_replays"] = replays + rng.randint(-50, 50)

    return Fault("pcie_link_degradation", [rank], start, os_effect=os_fx,
                 entry_delay=lambda base: 1.2e-3)


def cpu_downclock(rank: int, start: int = 0, mhz: float = 1200.0) -> Fault:
    """Frequency-governor downclock (powersave / failed turbo) on one
    node's cores — visible only as a lower effective frequency."""
    def os_fx(sig: Dict[str, object], rng: random.Random) -> None:
        sig["cpu_freq_mhz"] = mhz + rng.uniform(-25.0, 25.0)

    return Fault("cpu_frequency_downclock", [rank], start, os_effect=os_fx,
                 entry_delay=lambda base: 2.0e-3)


def ecc_row_remap(rank: int, start: int = 0, rows: int = 8) -> Fault:
    """GPU ECC row-remap events stall one rank between kernels: kernel
    timings match the fleet, the remap counter does not."""
    def os_fx(sig: Dict[str, object], rng: random.Random) -> None:
        sig["ecc_remapped_rows"] = rows

    return Fault("ecc_row_remap_stall", [rank], start, os_effect=os_fx,
                 entry_delay=lambda base: 1.0e-3)


def numa_remote_alloc(rank: int, start: int = 0,
                      remote_ratio: float = 0.6) -> Fault:
    """Dataloader workers pinned to the wrong socket: most memory traffic
    crosses the interconnect, no new code paths appear."""
    def os_fx(sig: Dict[str, object], rng: random.Random) -> None:
        sig["numa_remote_ratio"] = remote_ratio + rng.uniform(-0.05, 0.05)

    return Fault("numa_remote_allocation", [rank], start, os_effect=os_fx,
                 entry_delay=lambda base: 0.8e-3)


# ---------------------------------------------------------------------------
# native collection feed: stacks through the real batch unwinder
# ---------------------------------------------------------------------------


class NativeStackFeed:
    """Routes simulator stack rows through the REAL collection path:
    each unique stack is laid out as a machine stack image in a
    ``SimProcess`` (functions synthesized per frame name, a mix of
    FP-preserving and ``-fomit-frame-pointer``), unwound by the batch
    ``HybridUnwinder``, symbolized by the ``CentralResolver`` and
    interned into the shared ``TraceTables`` — exactly what a node agent
    does at 99 Hz.  Frame names discovered mid-run (fault injections)
    arrive as freshly ``dlopen``'d binaries, i.e. the §4 maps-poll path.

    The recovered stack must equal the source row byte-for-byte (the
    hybrid unwinder + full central tables are exact on this workload);
    any mismatch raises immediately rather than silently skewing a
    diagnosis.  Steady state is one memoized dict hit per unique stack —
    fleet-rate benchmarks pay the real unwind cost exactly once per
    stack, like production's in-kernel stack dedup."""

    _FUNC_SIZE = 512

    def __init__(self, tables: TraceTables, seed: int = 0):
        self.tables = tables
        self.proc = SimProcess()
        self.unwinder = HybridUnwinder()
        self.resolver = CentralResolver()
        self.rng = random.Random(seed ^ 0x5EED_FEED)
        self._fn: Dict[str, Tuple[Binary, FunctionDef]] = {}
        self._sids: Dict[Tuple[str, ...], int] = {}
        self._binary_seq = 0

    def _ensure_functions(self, names: Sequence[str]) -> None:
        new = [n for n in dict.fromkeys(names) if n not in self._fn]
        if not new:
            return
        off, funcs = 0x1000, []
        for n in new:
            h = int(hashlib.sha1(n.encode()).hexdigest()[:8], 16)
            funcs.append(FunctionDef(
                name=n, offset=off, size=self._FUNC_SIZE,
                omits_fp=(h & 3) == 0,          # deterministic ~25% -O2 mix
                frame_size=(32, 48, 64, 96)[h >> 2 & 3], exported=True))
            off += self._FUNC_SIZE
        seq = self._binary_seq
        self._binary_seq += 1
        b = Binary(name=f"sim_workload_{seq}",
                   build_id=hashlib.sha1(
                       f"sim_workload:{seq}:{new[0]}".encode()).hexdigest(),
                   functions=funcs, size=off)
        self.proc.mmap_binary(b)
        self.unwinder.register_binary(b)     # dlopen'd mid-profile (§4)
        self.resolver.ensure_uploaded(b)
        for f in funcs:
            self._fn[f.name] = (b, f)

    def sids(self, stacks: Sequence[Tuple[str, ...]]) -> List[int]:
        """Interned stack ids for root..leaf name tuples, unwinding any
        not-yet-seen stack through the batch pipeline."""
        missing = [s for s in dict.fromkeys(stacks) if s not in self._sids]
        if missing:
            self._ensure_functions([n for s in missing for n in s])
            threads = []
            for s in missing:
                t = SimThread(self.proc, self.rng)
                t.call_chain([self._fn[n] for n in s])
                threads.append(t)
            pcs_lists = self.unwinder.unwind_batch(threads)
            resolve = self.proc.resolve
            for s, pcs in zip(missing, pcs_lists):
                frames = [resolve(pc)[:2] for pc in pcs]     # leaf..root
                recovered = tuple(reversed(
                    self.resolver.resolve_frames_batch(frames)))
                if recovered != s:
                    raise AssertionError(
                        f"native feed mis-unwound {s!r} -> {recovered!r}")
                self._sids[s] = self.tables.intern_stack(recovered)
        return [self._sids[s] for s in stacks]


# ---------------------------------------------------------------------------
# the simulated cluster
# ---------------------------------------------------------------------------


class SimCluster:
    def __init__(self, n_ranks: int = 8, group_hash: int = 0xAB54A98CEB1F0AD2,
                 comm_version: str = "nccl-2.18", seed: int = 0,
                 samples_per_iter: int = 400, iter_time: float = 0.1,
                 columnar: bool = False,
                 tables: Optional[TraceTables] = None,
                 stack_variants: int = 1,
                 native_unwind: bool = False,
                 native_feed: Optional[NativeStackFeed] = None,
                 rank_ids: Optional[Sequence[int]] = None,
                 coll_phase: float = 0.7):
        self.n_ranks = n_ranks
        self.rng = random.Random(seed)
        self.samples_per_iter = samples_per_iter
        self.base_iter_time = iter_time
        self.iteration = 0
        self.faults: List[Fault] = []
        self.group_hash = group_hash
        self.comm_version = comm_version
        # global rank identity: a rank that belongs to several groups
        # (a cascade bridge) carries the same id in each — defaults to
        # the group-local 0..n-1 numbering
        if rank_ids is not None:
            if len(rank_ids) != n_ranks:
                raise ValueError("rank_ids must name exactly n_ranks ranks")
            if len(set(rank_ids)) != n_ranks:
                raise ValueError("rank_ids must be unique within a group "
                                 "(duplicates would silently collapse "
                                 "per-rank simulation state)")
        self.rank_ids: List[int] = (list(rank_ids) if rank_ids is not None
                                    else list(range(n_ranks)))
        # where in the iteration this group's blocking collective sits
        # (fraction of base iter time); cascade fleets stagger phases so
        # downstream groups' collectives follow their upstream ones
        self.coll_phase = coll_phase
        # delay imported from an upstream group's barrier this iteration
        # (set by MultiGroupSimCluster cascade links, keyed by rank id;
        # consumed and cleared by step())
        self.imported_delay: Dict[int, float] = {}
        # barrier delay this group exported on its last step
        self.last_exit_delay = 0.0
        # per-rank clock skew (us-scale) — exercised by ClockAligner
        self.skew = {self.rank_ids[r]: self.rng.uniform(-2e-4, 2e-4)
                     for r in range(n_ranks)}
        self.group_id = f"{group_hash:016x}"
        # columnar mode: step() emits ColumnarProfiles natively — the same
        # RNG stream and values, interned against `tables` (shareable
        # across the groups of a fleet, like one node agent's tables)
        self.columnar = columnar
        self.tables = tables if tables is not None else TraceTables()
        # native_unwind: stack rows reach the tables through the real
        # batch collection path (machine-stack layout -> batch hybrid
        # unwinding -> central symbolization) instead of direct interning
        # — identical resulting profiles, real collection cost model
        self.native_feed = native_feed if native_feed is not None else (
            NativeStackFeed(self.tables, seed=seed) if native_unwind
            else None)
        self._sid_cache: Dict[Tuple[str, ...], int] = {}
        self._fid_cache: Dict[str, int] = {}
        # stack diversity: production 30 s windows carry dozens-to-hundreds
        # of unique stacks, not the 8 canonical Fig 6 paths — variants
        # split each base path into per-leaf specializations (e.g. shape-
        # specialized kernels) so benches can ingest realistic row counts.
        # Default 1 reproduces the base workload exactly.
        if stack_variants > 1:
            self._base_stacks = [
                (stack[:-1] + (f"{stack[-1]}#v{v}",), w / stack_variants)
                for stack, w in _BASE_STACKS
                for v in range(stack_variants)]
        else:
            self._base_stacks = list(_BASE_STACKS)

    # -- registration handshake payloads --------------------------------------
    def comm_snapshots(self, rank: int) -> List[bytes]:
        return [CommStructCodec.pack(
            self.comm_version, comm_hash=self.group_hash, rank=rank,
            n_ranks=self.n_ranks, local_rank=rank % 8)]

    def add_fault(self, fault: Fault) -> None:
        self.faults.append(fault)

    def remove_fault(self, name: str) -> int:
        """Remove every fault with ``name`` mid-run; returns how many
        were removed.  Faults are stateless per step, so removal fully
        restores baseline kernel/OS/stack/entry effects from the next
        iteration on (the teardown contract the chaos harness's
        flapping windows rely on)."""
        kept = [f for f in self.faults if f.name != name]
        removed = len(self.faults) - len(kept)
        self.faults = kept
        return removed

    def clear_faults(self) -> int:
        """Remove every injected fault; returns how many were removed."""
        n = len(self.faults)
        self.faults = []
        return n

    def fork(self) -> "SimCluster":
        """Deep-enough copy for what-if replay: the fork steps the same
        RNG stream forward from the parent's current state, carries its
        own fault list / skew / imported-delay maps, and SHARES the
        append-only interning tables and native feed (forks of one
        fleet intern against one id space, like agents of one node).
        Stepping the fork never perturbs the parent — the mitigation
        replayer scores a planned action on a fork before committing."""
        cl = SimCluster.__new__(SimCluster)
        cl.__dict__.update(self.__dict__)
        cl.rng = random.Random()
        cl.rng.setstate(self.rng.getstate())
        cl.faults = list(self.faults)
        cl.rank_ids = list(self.rank_ids)
        cl.skew = dict(self.skew)
        cl.imported_delay = dict(self.imported_delay)
        cl._sid_cache = dict(self._sid_cache)
        cl._fid_cache = dict(self._fid_cache)
        return cl

    # -- one iteration ---------------------------------------------------------
    def _cpu_rows(self, rank: int) -> List[Tuple[Tuple[str, ...], int]]:
        """(stack, count) rows for one rank-iteration — the single source
        of truth for both the dataclass and columnar materializations."""
        stacks = list(self._base_stacks)
        for f in self.faults:
            if f.stack_effect is not None and f.applies(rank, self.iteration):
                stacks = f.stack_effect(stacks)
        total = sum(w for _, w in stacks)
        rows = []
        n = self.samples_per_iter
        for stack, w in stacks:
            cnt = round(n * w / total)
            # Poisson-ish jitter so sigma is non-degenerate
            cnt = max(0, cnt + self.rng.randint(-2, 2))
            if cnt:
                rows.append((stack, cnt))
        return rows

    def _cpu_samples(self, rank: int, t: float) -> List[StackSample]:
        return [StackSample(rank=rank, timestamp=t, frames=stack, weight=cnt)
                for stack, cnt in self._cpu_rows(rank)]

    def _sid(self, stack: Tuple[str, ...]) -> int:
        sid = self._sid_cache.get(stack)
        if sid is None:
            if self.native_feed is not None:
                sid = self.native_feed.sids([stack])[0]
            else:
                sid = self.tables.intern_stack(stack)
            self._sid_cache[stack] = sid
        return sid

    def _sids(self, stacks: Sequence[Tuple[str, ...]]) -> List[int]:
        """Batch variant of ``_sid``: unseen stacks go through the native
        feed (one ``unwind_batch`` call for all of them) when enabled."""
        cache = self._sid_cache
        missing = [s for s in stacks if s not in cache]
        if missing:
            if self.native_feed is not None:
                for s, sid in zip(missing, self.native_feed.sids(missing)):
                    cache[s] = sid
            else:
                for s in missing:
                    cache[s] = self.tables.intern_stack(s)
        return [cache[s] for s in stacks]

    def _fid(self, name: str) -> int:
        fid = self._fid_cache.get(name)
        if fid is None:
            fid = self._fid_cache[name] = self.tables.strings.intern(name)
        return fid

    def _kernel_rows(self, rank: int, t: float
                     ) -> Tuple[List[Tuple[str, float, float]], float]:
        factor = 1.0
        for f in self.faults:
            if f.applies(rank, self.iteration):
                factor *= f.kernel_factor
        rows, extra = [], 0.0
        cursor = t
        for name, dur in _BASE_KERNELS:
            d = dur * factor * self.rng.uniform(0.995, 1.005)
            rows.append((name, cursor, d))
            cursor += d
            extra += d - dur
        return rows, extra

    def _kernels(self, rank: int, t: float) -> Tuple[List[KernelEvent], float]:
        rows, extra = self._kernel_rows(rank, t)
        return [KernelEvent(rank=rank, name=n, start=s, duration=d)
                for n, s, d in rows], extra

    def _os_signals(self, rank: int, t: float) -> OSSignals:
        """Healthy-node baseline counters, then every active fault's
        ``os_effect`` mutates the draft in place."""
        rng = self.rng
        draft: Dict[str, object] = {
            "rank": rank, "timestamp": t,
            "interrupts": {"LOC": 100_000 + rng.randint(-500, 500),
                           "NET_RX": 2_000 + rng.randint(-100, 100)},
            "softirq_residency": {},
            "sched_latency_p99": 80e-6 * rng.uniform(0.9, 1.1),
            "numa_migrations": 0,
            "cpu_steal": 0.0,
            "major_faults": rng.randint(0, 3),
            "cpu_freq_mhz": 2600.0 + rng.uniform(-20.0, 20.0),
            "pcie_replays": rng.randint(0, 2),
            "ecc_remapped_rows": 0,
            "numa_remote_ratio": 0.02 + rng.uniform(0.0, 0.02),
        }
        for f in self.faults:
            if f.os_effect is not None and f.applies(rank, self.iteration):
                f.os_effect(draft, rng)
        return OSSignals(**draft)  # type: ignore[arg-type]

    def _columnar_profile(self, rank: int, t0: float, iter_time: float,
                          cpu_rows, kernel_rows, entry: float, exit_v: float,
                          coll_dur: float, sig: OSSignals) -> ColumnarProfile:
        n = len(cpu_rows)
        return ColumnarProfile(
            rank=rank, iteration=self.iteration, group_id=self.group_id,
            iter_time=iter_time, tables=self.tables,
            stack_ts=np.full(n, t0),
            stack_weight=np.array([c for _, c in cpu_rows], dtype=np.int64),
            stack_kind=np.full(n, self._fid("cpu"), dtype=np.int64),
            stack_id=np.array(self._sids([s for s, _ in cpu_rows]),
                              dtype=np.int64),
            kern_name=np.array([self._fid(nm) for nm, _, _ in kernel_rows],
                               dtype=np.int64),
            kern_start=np.array([s for _, s, _ in kernel_rows]),
            kern_dur=np.array([d for _, _, d in kernel_rows]),
            kern_stream=np.zeros(len(kernel_rows), dtype=np.int64),
            coll_op=np.array([self._fid("ReduceScatter")], dtype=np.int64),
            coll_group=np.array([self._fid(self.group_id)], dtype=np.int64),
            coll_entry=np.array([entry]), coll_exit=np.array([exit_v]),
            coll_nbytes=np.array([512 * 1024 * 1024], dtype=np.int64),
            coll_dev_dur=np.array([coll_dur]),
            coll_instance=np.array([-1], dtype=np.int64),
            coll_seq=np.array([-1], dtype=np.int64),
            os_signals=sig)

    def step(self) -> List[IterationProfile]:
        """Simulate one synchronous iteration across all ranks.  Emits
        ``IterationProfile``s, or native ``ColumnarProfile``s in columnar
        mode — same RNG stream, same values, different representation.
        Ranks are reported under their global ``rank_ids``; any delay a
        cascade link imported for a rank id is added to that rank's
        collective entry (and cleared)."""
        t0 = self.iteration * self.base_iter_time
        profiles = []
        gids = self.rank_ids
        imported, self.imported_delay = self.imported_delay, {}
        # per-rank compute time before entering the gradient collective
        entry_delay: Dict[int, float] = {}
        kernel_rows: Dict[int, List[Tuple[str, float, float]]] = {}
        for r in range(self.n_ranks):
            gid = gids[r]
            rows, gpu_extra = self._kernel_rows(gid, t0)
            kernel_rows[gid] = rows
            delay = gpu_extra + self.rng.gauss(0, 12e-6)
            for f in self.faults:
                if f.entry_delay is not None and f.applies(gid,
                                                           self.iteration):
                    delay += f.entry_delay(self.base_iter_time)
            delay += imported.get(gid, 0.0)
            entry_delay[gid] = max(0.0, delay)

        # blocking collective: starts when the last rank arrives
        base_entry = t0 + self.coll_phase * self.base_iter_time
        entries = {gid: base_entry + entry_delay[gid] for gid in gids}
        start = max(entries.values())
        self.last_exit_delay = max(entry_delay.values()) \
            if entry_delay else 0.0
        coll_dur = 9e-3
        exit_t = start + coll_dur
        iter_end = exit_t + 0.05 * self.base_iter_time

        for r in range(self.n_ranks):
            gid = gids[r]
            entry = entries[gid] + self.skew[gid]
            exit_v = exit_t + self.skew[gid] + self.rng.gauss(0, 3e-6)
            cpu_rows = self._cpu_rows(gid)
            sig = self._os_signals(gid, t0)
            if self.columnar:
                profiles.append(self._columnar_profile(
                    gid, t0, iter_end - t0, cpu_rows, kernel_rows[gid],
                    entry, exit_v, coll_dur, sig))
            else:
                ev = CollectiveEvent(
                    rank=gid, group_id=self.group_id, op="ReduceScatter",
                    entry=entry, exit=exit_v,
                    nbytes=512 * 1024 * 1024, device_duration=coll_dur)
                profiles.append(IterationProfile(
                    rank=gid, iteration=self.iteration,
                    group_id=self.group_id,
                    iter_time=iter_end - t0,
                    cpu_samples=[StackSample(rank=gid, timestamp=t0,
                                             frames=stack, weight=cnt)
                                 for stack, cnt in cpu_rows],
                    kernel_events=[KernelEvent(rank=gid, name=nm, start=s,
                                               duration=d)
                                   for nm, s, d in kernel_rows[gid]],
                    collectives=[ev],
                    os_signals=sig))
        self.iteration += 1
        return profiles

    def run(self, service, iterations: int, job_id: str = "job-0",
            process_every: int = 10) -> List:
        """Drive the cluster into a CentralService; returns new events."""
        events = []
        for _ in range(iterations):
            for p in self.step():
                service.ingest(p, job_id=job_id)
            if self.iteration % process_every == 0:
                events.extend(service.process())
        events.extend(service.process())
        return events


# ---------------------------------------------------------------------------
# fleet-scale simulation: many communication groups, 1000+ ranks
# ---------------------------------------------------------------------------


class MultiGroupSimCluster:
    """Dozens of communication groups stepped in lockstep — the fleet
    shape the sharded service ingests (1,000+ ranks).  Each group is one
    ``SimCluster`` with its own comm hash, clock skews, RNG stream and
    (possibly concurrent, heterogeneous) fault injections.

    Cascade mode: ``rank_ids`` assigns per-group *global* rank ids (a
    rank id appearing in two groups is the same physical rank — a
    bridge), ``coll_phases`` staggers the groups' collectives within
    the iteration, and each ``cascade_links`` pair (upstream,
    downstream) propagates the upstream group's barrier delay — minus
    ``cascade_slack`` of schedule headroom — onto the bridge ranks'
    entries into the downstream group.  A root fault in one group then
    produces observable pure-victim stragglers in the groups behind it,
    which is exactly what the attribution layer must see through.
    """

    def __init__(self, n_groups: int = 32, ranks_per_group: int = 32,
                 seed: int = 0, samples_per_iter: int = 400,
                 iter_time: float = 0.1, base_hash: int = 0x51A0_0000_0000_0001,
                 columnar: bool = False,
                 tables: Optional[TraceTables] = None,
                 stack_variants: int = 1,
                 native_unwind: bool = False,
                 rank_ids: Optional[Sequence[Sequence[int]]] = None,
                 coll_phases: Optional[Sequence[float]] = None,
                 cascade_links: Sequence[Tuple[int, int]] = (),
                 cascade_slack: float = 6e-4):
        # columnar mode shares ONE table set fleet-wide: the groups run the
        # same workload, so their stacks/kernel names intern once, ever —
        # and with native_unwind, one shared feed means the fleet unwinds
        # each unique stack exactly once, like one node agent would
        self.tables = tables if tables is not None else TraceTables()
        feed = NativeStackFeed(self.tables, seed=seed) if native_unwind \
            else None
        if rank_ids is not None:
            n_groups = len(rank_ids)
        self.groups: List[SimCluster] = [
            SimCluster(n_ranks=(len(rank_ids[i]) if rank_ids is not None
                                else ranks_per_group),
                       group_hash=(base_hash + 0x9E3779B97F4A7C15 * i)
                       & 0xFFFFFFFFFFFFFFFF,
                       seed=seed * 1000 + i,
                       samples_per_iter=samples_per_iter,
                       iter_time=iter_time,
                       columnar=columnar, tables=self.tables,
                       stack_variants=stack_variants,
                       native_feed=feed,
                       rank_ids=(rank_ids[i] if rank_ids is not None
                                 else None),
                       coll_phase=(coll_phases[i] if coll_phases is not None
                                   else 0.7))
            for i in range(n_groups)
        ]
        self.n_groups = n_groups
        self.ranks_per_group = ranks_per_group
        self.columnar = columnar
        self.cascade_slack = cascade_slack
        self.cascade_links: List[Tuple[int, int]] = list(cascade_links)
        self._shared_ranks: Dict[Tuple[int, int], List[int]] = {}
        for u, d in self.cascade_links:
            if not 0 <= u < d < n_groups:
                raise ValueError(
                    f"cascade link ({u}, {d}) must satisfy "
                    f"0 <= upstream < downstream < {n_groups} "
                    "(groups step in index order)")
            shared = sorted(set(self.groups[u].rank_ids)
                            & set(self.groups[d].rank_ids))
            if not shared:
                raise ValueError(
                    f"cascade link ({u}, {d}) has no bridge rank "
                    "(no shared rank ids)")
            self._shared_ranks[(u, d)] = shared

    @property
    def n_ranks(self) -> int:
        """Total rank-*slots* across groups.  A bridge rank (member of
        several groups) is counted once per group; dedupe the groups'
        ``rank_ids`` for a physical machine count."""
        return sum(g.n_ranks for g in self.groups)

    @property
    def iteration(self) -> int:
        return self.groups[0].iteration if self.groups else 0

    def group_ids(self) -> List[str]:
        return [g.group_id for g in self.groups]

    def add_fault(self, group_index: int, fault: Fault) -> None:
        """Inject ``fault`` into one group (ranks are group-local)."""
        self.groups[group_index].add_fault(fault)

    def add_fleet_fault(self, fault: Fault) -> None:
        """Inject ``fault`` fleet-wide: every group carries it, and it
        takes effect wherever its target rank ids actually live —
        including a bridge rank's membership in several groups."""
        for g in self.groups:
            g.add_fault(fault)

    def remove_fault(self, name: str,
                     group_index: Optional[int] = None) -> int:
        """Remove faults named ``name`` from one group (or, with
        ``group_index=None``, from every group — the fleet-fault
        inverse).  Returns the number of fault entries removed."""
        if group_index is not None:
            return self.groups[group_index].remove_fault(name)
        return sum(g.remove_fault(name) for g in self.groups)

    def fork(self) -> "MultiGroupSimCluster":
        """What-if replay fork: every group forked (own RNG stream /
        fault list, shared append-only tables), topology copied.  See
        :meth:`SimCluster.fork`."""
        fl = MultiGroupSimCluster.__new__(MultiGroupSimCluster)
        fl.__dict__.update(self.__dict__)
        fl.groups = [g.fork() for g in self.groups]
        fl.cascade_links = list(self.cascade_links)
        fl._shared_ranks = {k: list(v)
                            for k, v in self._shared_ranks.items()}
        return fl

    def step(self) -> List[IterationProfile]:
        """One synchronous fleet iteration: profiles from every group.
        Groups step in index order; after an upstream group steps, its
        barrier delay (beyond the schedule slack) is imported onto the
        bridge ranks of every linked downstream group."""
        profiles: List[IterationProfile] = []
        for i, g in enumerate(self.groups):
            profiles.extend(g.step())
            for (u, d) in self.cascade_links:
                if u != i:
                    continue
                exported = max(0.0, g.last_exit_delay - self.cascade_slack)
                if exported <= 0.0:
                    continue
                downstream = self.groups[d].imported_delay
                for rid in self._shared_ranks[(u, d)]:
                    downstream[rid] = downstream.get(rid, 0.0) + exported
        return profiles

    def run(self, service, iterations: int, job_id: str = "job-0",
            process_every: int = 10) -> List:
        """Drive the fleet into a (sharded or plain) service."""
        events = []
        for _ in range(iterations):
            for p in self.step():
                service.ingest(p, job_id=job_id)
            if self.iteration % process_every == 0:
                events.extend(service.process())
        events.extend(service.process())
        return events


# ---------------------------------------------------------------------------
# cascade fleet construction + validation helpers
# ---------------------------------------------------------------------------


def cascade_fleet(layout: Sequence[Sequence[int]],
                  links: Sequence[Tuple[int, int]] = ((0, 1),), *,
                  seed: int = 0, columnar: bool = False,
                  native_unwind: bool = False,
                  samples_per_iter: int = 400, iter_time: float = 0.1,
                  slack: float = 6e-4, phase_step: float = 0.12,
                  tables: Optional[TraceTables] = None,
                  stack_variants: int = 1) -> MultiGroupSimCluster:
    """A fleet with explicit cross-group topology.

    ``layout`` lists each group's *global* rank ids; a rank id shared
    between two groups is a bridge rank.  ``links`` are (upstream,
    downstream) cascade edges; group i's collective is phased
    ``phase_step`` later per index so downstream collectives follow
    their upstream ones within the iteration.  The signature matches
    what ``run_scenario_matrix`` passes to ``Scenario.make_cluster``.
    """
    return MultiGroupSimCluster(
        ranks_per_group=len(layout[0]), seed=seed,
        samples_per_iter=samples_per_iter, iter_time=iter_time,
        columnar=columnar, tables=tables, stack_variants=stack_variants,
        native_unwind=native_unwind,
        rank_ids=[list(g) for g in layout],
        coll_phases=[0.7 + phase_step * i for i in range(len(layout))],
        cascade_links=links, cascade_slack=slack)


def expect_cascade_export(victim_index: int, root_index: int):
    """Scenario ``validate`` hook: the victim group must have yielded a
    ``cascade_blame_exported`` verdict pointing at the root group."""
    def _validate(events, cluster) -> Optional[str]:
        from repro.core.attribution import CASCADE_EXPORT_CAUSE
        gids = cluster.group_ids()
        vg, rg = gids[victim_index], gids[root_index]
        for e in events:
            if e.root_cause == CASCADE_EXPORT_CAUSE and e.group_id == vg:
                to = (e.verdict.evidence.get("exported_to")
                      if e.verdict else None)
                if to != rg:
                    return (f"export from group {vg} points at {to!r}, "
                            f"want {rg}")
                return None
        return f"no cascade_blame_exported event for victim group {vg}"
    return _validate


def fleet_slos(cluster, margin: float = 0.2, window: int = 8,
               prefix: str = "iter-time") -> List:
    """Per-group iteration-time SLOs for a simulated fleet: each group's
    threshold is its base iteration time plus ``margin`` headroom, so a
    healthy fleet is breach-free and an injected slowdown breaches
    exactly the affected groups.  Register the returned ``SLO`` objects
    on any ``DiagnosisService`` before calling ``audit()``."""
    from repro.core.query import SLO
    groups = (cluster.groups if isinstance(cluster, MultiGroupSimCluster)
              else [cluster])
    return [SLO(name=f"{prefix}/{g.group_id}", metric="iter_time",
                threshold=g.base_iter_time * (1.0 + margin),
                group_id=g.group_id, window=window)
            for g in groups]


# ---------------------------------------------------------------------------
# scenario matrix: every registered scenario x every service path
# ---------------------------------------------------------------------------

#: The four ingest/analysis paths a diagnosis must survive unchanged:
#: dataclass ingest (converted to columns at the service's door),
#: wire-encoded columnar upload, the group-partitioned sharded
#: front-end, and the hierarchical pod tier (wire v3 dictionary-delta
#: session uploads into ``PodTierService``'s two-level collection tree).
SERVICE_PATHS: Tuple[str, ...] = ("streaming", "columnar", "sharded", "pod")


@dataclasses.dataclass
class ScenarioResult:
    """Outcome of one scenario on one service path.  ``event_tuples``
    carries every diagnosis as (group_id, root_cause, category,
    straggler_rank) in emission order, so callers can assert
    event-for-event equivalence *across* paths from one matrix run.
    ``detail`` holds the scenario ``validate`` hook's failure message
    (empty on success)."""
    scenario: str
    path: str
    ok: bool
    expected_cause: str
    expected_rank: Optional[int]
    first_cause: Optional[str]
    first_rank: Optional[int]
    causes: List[str]
    n_events: int
    event_tuples: List[Tuple[str, str, str, Optional[int]]] = \
        dataclasses.field(default_factory=list)
    detail: str = ""


def _drive_scenario(scenario, path: str, *, n_ranks: int, seed: int,
                    baseline_iters: int, fault_iters: int,
                    process_every: int, n_shards: int, window: int,
                    registry) -> ScenarioResult:
    from repro.core.pod import PodTierService
    from repro.core.service import CentralService
    from repro.core.sharded import ShardedService
    from repro.core.trace import ColumnarBatch, WireEncoder, encode_batch

    kwargs = dict(window=window, robust_detector=scenario.robust_detector,
                  registry=registry)
    if path in ("streaming", "columnar"):
        svc = CentralService(**kwargs)
    elif path == "sharded":
        svc = ShardedService(n_shards=n_shards, **kwargs)
    elif path == "pod":
        # same engine count/routing as "sharded" (so diagnoses match
        # event-for-event), merged through the two-level pod tree
        svc = PodTierService(n_pods=n_shards, pods_per_shard=2, **kwargs)
    else:
        raise ValueError(
            f"unknown service path {path!r}; choose from {SERVICE_PATHS}")
    # the columnar path doubles as the batched-collection gate: its
    # stacks reach the tables through the real batch unwinder + central
    # symbolization (NativeStackFeed), so every registered scenario's
    # verdict is asserted end-to-end through the production-shaped path;
    # the pod path rides the same columnar cluster but ships every
    # upload as a wire v3 dictionary-delta frame over one persistent
    # encoder session (tables cross the wire incrementally, once)
    columnar = path in ("columnar", "pod")
    make_cluster = getattr(scenario, "make_cluster", None)
    if make_cluster is not None:
        # cascade scenarios bring their own fleet topology (overlapping
        # groups, bridge ranks, staggered collective phases)
        cl = make_cluster(seed=seed, columnar=columnar,
                          native_unwind=columnar)
    else:
        cl = SimCluster(n_ranks=n_ranks, seed=seed, columnar=columnar,
                        native_unwind=columnar)
    enc = WireEncoder(cl.tables) if path == "pod" else None

    def run(iterations: int) -> None:
        for _ in range(iterations):
            profiles = cl.step()
            if enc is not None:
                svc.ingest_encoded(enc.encode(
                    ColumnarBatch("job-0", profiles, "node-0", cl.tables)))
                enc.commit()
            elif columnar:
                svc.ingest_encoded(encode_batch(
                    ColumnarBatch("job-0", profiles, "node-0", cl.tables)))
            else:
                for p in profiles:
                    svc.ingest(p)
            if cl.iteration % process_every == 0:
                svc.process()
        svc.process()

    run(baseline_iters)
    fault = scenario.make_fault()
    if isinstance(cl, MultiGroupSimCluster):
        # fleet-wide injection: the fault bites wherever its target
        # rank ids live, including a bridge rank's several groups
        cl.add_fleet_fault(fault)
    else:
        cl.add_fault(fault)
    run(fault_iters)
    events = svc.events
    first = events[0] if events else None
    if first is None or first.verdict is None:
        layer_ok = False
    elif scenario.expected_layer == "temporal":
        # the temporal-baseline path emits a cpu-layer verdict with no
        # straggler (uniform degradation)
        layer_ok = (first.verdict.layer == "cpu"
                    and first.straggler_rank is None)
    else:
        layer_ok = first.verdict.layer == scenario.expected_layer
    group_ok = True
    if (first is not None
            and getattr(scenario, "expected_group_index", None) is not None):
        # cascade scenarios pin which group the root diagnosis names
        group_ok = (first.group_id
                    == cl.group_ids()[scenario.expected_group_index])
    detail = ""
    validate = getattr(scenario, "validate", None)
    if validate is not None:
        detail = validate(events, cl) or ""
    ok = (first is not None and layer_ok and group_ok and not detail
          and first.root_cause == scenario.expected_cause
          and (scenario.expected_rank is None
               or first.straggler_rank == scenario.expected_rank))
    return ScenarioResult(
        scenario=scenario.name, path=path, ok=ok,
        expected_cause=scenario.expected_cause,
        expected_rank=scenario.expected_rank,
        first_cause=first.root_cause if first else None,
        first_rank=first.straggler_rank if first else None,
        causes=sorted({e.root_cause for e in events}), n_events=len(events),
        event_tuples=[(e.group_id, e.root_cause, e.category,
                       e.straggler_rank) for e in events],
        detail=detail)


def run_scenario_matrix(registry=None, scenarios=None,
                        paths: Sequence[str] = SERVICE_PATHS, *,
                        n_ranks: int = 8, seed: int = 7,
                        baseline_iters: int = 30, fault_iters: int = 60,
                        process_every: int = 10, n_shards: int = 4,
                        window: int = 50, strict: bool = False
                        ) -> Dict[str, Dict[str, ScenarioResult]]:
    """Drive every registered scenario through every service path.

    For each (scenario, path) pair: run a healthy baseline, inject the
    scenario's fault, and record whether the first diagnosis matches the
    scenario's expected root cause, diagnosis layer ("temporal" expects
    a cpu-layer verdict with no straggler) and straggler rank, where the
    scenario pins one.  Returns ``{scenario name: {path: result}}``;
    with ``strict=True`` raises ``AssertionError`` listing every miss —
    the acceptance gate used by tests and ``benchmarks/bench_scenarios``.

    ``scenarios`` narrows the run to an explicit scenario list;
    ``registry`` defaults to :func:`repro.core.scenarios.default_registry`.
    """
    from repro.core.scenarios import default_registry
    registry = registry if registry is not None else default_registry()
    chosen = list(scenarios) if scenarios is not None \
        else list(registry.scenarios)
    results: Dict[str, Dict[str, ScenarioResult]] = {}
    misses: List[ScenarioResult] = []
    for scen in chosen:
        per_path: Dict[str, ScenarioResult] = {}
        for path in paths:
            res = _drive_scenario(
                scen, path, n_ranks=n_ranks, seed=seed,
                baseline_iters=baseline_iters, fault_iters=fault_iters,
                process_every=process_every, n_shards=n_shards,
                window=window, registry=registry)
            per_path[path] = res
            if not res.ok:
                misses.append(res)
        results[scen.name] = per_path
    if strict and misses:
        detail = "\n".join(
            f"  {m.scenario}/{m.path}: expected {m.expected_cause}"
            f"@rank{m.expected_rank} got {m.first_cause}@rank{m.first_rank}"
            f" ({m.n_events} events: {m.causes})"
            + (f" [{m.detail}]" if m.detail else "") for m in misses)
        raise AssertionError(f"scenario matrix misses:\n{detail}")
    return results
