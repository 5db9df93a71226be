"""Node agent (§4): per-node collection, aggregation, batched upload.

Production shape: eBPF programs + Rust daemon per node, Unix-socket
registration from training processes, 30 s upload batches, chunked symbol
uploads keyed by Build ID, ~200 MB resident budget.  Here the agent is a
Python object with the same lifecycle; collectors are pluggable (real
SamplingProfiler, SimCluster feeds, or a replayed trace).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional

from repro.core.aggregate import StackAggregator
from repro.core.collective.tracer import CollectiveTracer
from repro.core.events import IterationProfile, ProfileBatch
from repro.core.samplers import SamplingProfiler
from repro.core.spans import span
from repro.core.symbols.resolver import CentralResolver
from repro.core.trace import (ColumnarBatch, ColumnarProfile, RemapCache,
                              TraceTables, WireEncoder, WireFormatError,
                              profile_to_columnar, remap_profile,
                              stacks_profile)


@dataclasses.dataclass
class AgentConfig:
    rank: int = 0
    job_id: str = "job-0"
    node_id: str = "node-0"
    hz: float = 99.0
    sampling_rate: float = 0.10
    drain_interval_s: float = 5.0
    upload_interval_s: float = 30.0
    buffer_limit_s: float = 3600.0   # local buffering if service is down (§7)


@dataclasses.dataclass
class RegisteredProcess:
    pid: int
    rank: int
    job_id: str
    group_ids: List[str]


class NodeAgent:
    """One per node.  ``service`` is duck-typed: needs ``ingest(profile)``
    and ``symbol_repo`` — the central service or a test double."""

    def __init__(self, cfg: AgentConfig, service=None):
        self.cfg = cfg
        self.service = service
        # agent-lifetime interning tables: repeated stacks/kernel names
        # across the job's 30 s upload cycles intern once, ever — the
        # sampler and aggregator fold straight into them (no per-sample
        # dataclasses anywhere on the collection path)
        self._tables = TraceTables()
        self.aggregator = StackAggregator(tables=self._tables)
        self.sampler = SamplingProfiler(
            hz=cfg.hz, sampling_rate=cfg.sampling_rate, rank=cfg.rank,
            aggregator=self.aggregator)
        self.tracer = CollectiveTracer(rank=cfg.rank)
        self.resolver: Optional[CentralResolver] = (
            CentralResolver(service.symbol_repo) if service is not None
            and hasattr(service, "symbol_repo") else None)
        self._procs: Dict[int, RegisteredProcess] = {}
        self._buffer: List[IterationProfile] = []
        self._lock = threading.Lock()
        self._remaps = RemapCache(self._tables)
        # lazy stateful wire encoder: reusable output buffer + cross-
        # batch dictionary session over the agent-lifetime tables, so
        # string/stack tables ship once per agent lifetime, not per batch
        self._wire: Optional[WireEncoder] = None
        self.uploads = 0
        self.dropped = 0
        self.upload_failures = 0
        self.encoded_uploads = 0
        self.bytes_uploaded = 0
        self.session_resyncs = 0

    # -- the SYSOM_SOCK_PATH handshake (§4) ----------------------------------
    def register_process(self, pid: int, rank: int, job_id: str,
                         comm_snapshots: List[bytes]) -> RegisteredProcess:
        """Training process registration: pid + packed communicator
        snapshots (parsed without symbols)."""
        groups = []
        for blob in comm_snapshots:
            info = self.tracer.register_comm_snapshot(blob)
            groups.append(info.group_id)
        rp = RegisteredProcess(pid, rank, job_id, groups)
        self._procs[pid] = rp
        return rp

    def register_binary(self, binary) -> None:
        """Build-ID dedup'd symbol upload."""
        if self.resolver is not None:
            self.resolver.ensure_uploaded(binary)

    # -- profile submission ----------------------------------------------------
    def submit(self, profile: IterationProfile) -> None:
        with self._lock:
            self._buffer.append(profile)
            # local buffering bound: drop oldest beyond ~1 h at 1 iter/s
            limit = int(self.cfg.buffer_limit_s)
            if len(self._buffer) > limit:
                self.dropped += len(self._buffer) - limit
                self._buffer = self._buffer[-limit:]

    def _columnar_batch(self, profiles) -> ColumnarBatch:
        """Build the upload as columns over the agent's lifetime tables;
        foreign-table columnar profiles (e.g. simulator feeds) are
        re-mapped, dataclass profiles are interned."""
        cols = []
        for p in profiles:
            if isinstance(p, ColumnarProfile):
                if p.tables is not self._tables:
                    p = remap_profile(p, self._remaps.get(p.tables))
            else:
                p = profile_to_columnar(p, self._tables)
            cols.append(p)
        return ColumnarBatch(self.cfg.job_id, cols, self.cfg.node_id,
                             self._tables)

    def flush(self) -> int:
        """Upload one batch to the central service (the 30 s cycle).

        If the service is unreachable — absent, or raising mid-upload —
        the not-yet-ingested remainder is re-buffered *in front of*
        anything submitted meanwhile, so a later flush preserves original
        submission order and nothing is lost.  Services exposing
        ``ingest_encoded`` get the batch as a wire v3 dictionary-delta
        frame encoded into the agent's reusable buffer (zero copies, and
        table entries ship once per agent lifetime); what gets
        re-buffered on failure is the already-interned *columnar* view,
        so a retry re-encodes the identical bytes without re-interning
        or allocating new columns.  Services exposing only
        ``ingest_batch`` (legacy sharded front-ends) get the dataclass
        batch in one call; plain services get per-profile ``ingest``.
        """
        with self._lock:
            batch, self._buffer = self._buffer, []
        with span("sysom.agent.flush",
                  step=batch[-1].iteration if batch else -1):
            return self._upload(batch)

    def _upload(self, batch) -> int:
        if self.service is None:
            with self._lock:
                self._buffer = batch + self._buffer
            return 0
        sent = 0
        try:
            if hasattr(self.service, "ingest_encoded"):
                cols = self._columnar_batch(batch)
                # re-buffer columnar views on failure: the retry path is
                # allocation-free (interning already happened) and its
                # re-encode is byte-identical (session watermarks only
                # advance on commit)
                batch = cols.profiles
                if self._wire is None:
                    self._wire = WireEncoder(self._tables)
                data = self._wire.encode(cols)
                try:
                    self.service.ingest_encoded(data)
                except WireFormatError:
                    # receiver lost (or never had) our dictionary
                    # session: reopen fresh — the next flush sends a
                    # self-contained frame under a new nonce
                    self.session_resyncs += 1
                    self._wire.reset()
                    raise
                self._wire.commit()
                sent = len(batch)
                self.encoded_uploads += 1
                self.bytes_uploaded += len(data)
            elif hasattr(self.service, "ingest_batch"):
                self.service.ingest_batch(
                    ProfileBatch(self.cfg.job_id, batch))
                sent = len(batch)
            else:
                for p in batch:
                    self.service.ingest(p)
                    sent += 1
        except Exception:
            self.upload_failures += 1
            with self._lock:
                self._buffer = batch[sent:] + self._buffer
            self.uploads += sent
            return sent
        self.uploads += sent
        return sent

    def counters(self) -> Dict[str, int]:
        """Upload-path counters; ``upload_failures`` counts flushes the
        service refused (their profiles were re-buffered, not lost)."""
        return {"uploads": self.uploads,
                "upload_failures": self.upload_failures,
                "dropped": self.dropped,
                "encoded_uploads": self.encoded_uploads,
                "bytes_uploaded": self.bytes_uploaded,
                "session_resyncs": self.session_resyncs,
                "buffered": len(self._buffer)}

    # -- real-profiling lifecycle ------------------------------------------------
    def start(self) -> None:
        self.sampler.start()

    def stop(self) -> None:
        self.sampler.stop()

    def drain_stacks(self):
        """Legacy dataclass-view drain: [(frames, count)].  With the
        interned sampler (the default since the batched collection path)
        ``frames`` are root..leaf ``"filename:name"`` strings, not the
        old ``(filename, hashed name)`` pairs — prefer
        :meth:`drain_profile` for anything feeding the columnar world."""
        return self.aggregator.drain()

    def drain_profile(self, iteration: int = 0, iter_time: float = 0.0,
                      group_id: Optional[str] = None,
                      timestamp: Optional[float] = None) -> ColumnarProfile:
        """Drain the aggregator straight into a ``ColumnarProfile`` over
        the agent-lifetime tables — the hot upload path: aggregated
        (stack id, count) columns in, wire-encodable profile out, no
        per-sample dataclass in between.  ``submit`` it like any other
        profile; ``flush`` ships it as encoded columns."""
        sids, weights = self.aggregator.drain_columns()
        return stacks_profile(
            self._tables, rank=self.cfg.rank, iteration=iteration,
            group_id=group_id if group_id is not None else self.cfg.node_id,
            iter_time=iter_time, sids=sids, weights=weights,
            timestamp=time.monotonic() if timestamp is None else timestamp)
