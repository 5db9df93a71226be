"""SysOM-AI core: continuous cross-layer performance diagnosis.

Modules map 1:1 to the paper's mechanisms:

  events        — cross-layer event schema (CPU stacks, kernel timings,
                  collective events, OS signals) — the boundary types
  trace         — columnar hot-path twin of events: interned structure-of-
                  arrays columns + the versioned binary wire codec
  flamegraph    — folded-stack profiles, merge/diff
  waterline     — per-communication-group CPU waterline (§3.1)
  straggler     — slow-rank detection w/ barrier-semantics clock alignment (§3.1)
  diffdiag      — layered differential diagnosis GPU→CPU→OS (§3.1)
  baseline      — temporal baseline comparison (§3.1)
  aggregate     — in-kernel-style stack aggregation + drain (§4)
  unwind/       — adaptive hybrid FP+DWARF unwinding, Algorithm 1 (§3.3)
  symbols/      — centralized Build-ID-keyed symbol resolution (§3.4)
  collective/   — framework-agnostic collective observability (§3.2)
  stitch        — Python↔native stack stitching (§4)
  samplers      — real in-process sampling profiler (overhead benchmark)
  agent         — node agent (collection, aggregation, upload)
  spans         — named host spans on the profiler's clock
  scenarios     — pluggable scenario + diagnosis-rule registry (SOP
                  signatures, OS thresholds, fault bundles; docs are
                  generated from it)
  service       — central analysis service (streaming, bounded state)
  sharded       — group-partitioned multi-shard ingestion front-end
  query         — queryable diagnosis plane: epoch/snapshot read state,
                  SLOs with wildcard targets, time-travel queries and
                  the fleet audit() walk (DiagnosisService protocol)
  simcluster    — multi-rank simulation + pluggable fault injection
                  (§5.4 case studies and beyond; run_scenario_matrix)
"""
