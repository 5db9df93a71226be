"""Scenario/diagnosis-rule registry: registration validation, service
snapshot immutability, rule-driven diffdiag behaviour, wire-format
version negotiation for the extended OS counters, and the scenario
matrix — every registered scenario must produce its expected diagnosis
on all four service paths (streaming, columnar, sharded, pod tier over
wire v3 sessions)."""
import dataclasses

import pytest

from repro.core import simcluster as sc
from repro.core.diffdiag import cpu_diff, os_diff
from repro.core.events import OSSignals, ProfileBatch
from repro.core.flamegraph import FlameGraph
from repro.core.scenarios import (CPURules, OSRule, RegistryError, Scenario,
                                  SOPRule, ScenarioRegistry,
                                  build_default_registry, default_registry)
from repro.core.service import CentralService
from repro.core.simcluster import SERVICE_PATHS, run_scenario_matrix
from repro.core.trace import (WIRE_VERSION, WireFormatError, decode_batch,
                              encode_batch)


# ---------------------------------------------------------------------------
# registration validation
# ---------------------------------------------------------------------------


def _scenario(name="s1", cause="c1", **kw):
    defaults = dict(
        name=name, description="d", make_fault=lambda: sc.swap_thrash(0),
        expected_cause=cause, expected_layer="os", category="os_interference")
    defaults.update(kw)
    return Scenario(**defaults)


def test_duplicate_scenario_name_raises():
    reg = ScenarioRegistry()
    reg.register_scenario(_scenario())
    with pytest.raises(RegistryError, match="duplicate"):
        reg.register_scenario(_scenario(cause="c2"))


def test_empty_sop_signature_raises():
    reg = ScenarioRegistry()
    with pytest.raises(RegistryError, match="empty signature"):
        reg.register_sop_rule(SOPRule((), "c", "a"))
    with pytest.raises(RegistryError, match="empty signature"):
        reg.register_sop_rule(SOPRule(("fn", ""), "c", "a"))


def test_empty_os_rule_field_raises():
    reg = ScenarioRegistry()
    with pytest.raises(RegistryError):
        reg.register_os_rule(OSRule(cause="c", field="", ratio=2.0))
    with pytest.raises(RegistryError):
        reg.register_os_rule(OSRule(cause="", field="f", ratio=2.0))
    with pytest.raises(RegistryError, match="positive ratio"):
        reg.register_os_rule(OSRule(cause="c", field="f", ratio=0.0))
    # eager validation: a typo'd field must fail at registration, not be
    # silently skipped at diagnosis time
    with pytest.raises(RegistryError, match="unknown OSSignals field"):
        reg.register_os_rule(OSRule(cause="c", field="majro_faults",
                                    ratio=2.0))
    reg.register_os_rule(OSRule(cause="c", field="major_faults", ratio=2.0))


def test_conflicting_category_raises():
    reg = ScenarioRegistry()
    reg.register_scenario(_scenario(cause="c1", category="software"))
    with pytest.raises(RegistryError, match="already mapped"):
        reg.register_scenario(
            _scenario(name="s2", cause="c1", category="network"))


def test_category_lookup_defaults_unknown():
    reg = ScenarioRegistry()
    assert reg.category_for("never_registered") == "unknown"
    assert reg.category_for("logging_overhead") == "software"  # legacy seed


def test_default_registry_has_ten_plus_scenarios():
    reg = build_default_registry()
    assert len(reg) >= 10
    names = {s.name for s in reg}
    # the five §5.4 case studies stay registered
    assert {"gpu_thermal_throttle", "nic_softirq_contention",
            "vfs_dentry_lock_contention", "logging_overhead",
            "storage_io_bottleneck"} <= names
    # plus at least five production-style scenarios
    assert {"memory_pressure_swap", "pcie_link_degradation",
            "cpu_frequency_downclock", "ecc_row_remap_stall",
            "numa_remote_allocation", "dataloader_starvation"} <= names


# ---------------------------------------------------------------------------
# snapshot immutability: a started service is isolated from later edits
# ---------------------------------------------------------------------------


def test_snapshot_is_frozen_and_isolated():
    reg = build_default_registry()
    snap = reg.snapshot()
    assert snap.frozen and not reg.frozen
    with pytest.raises(RegistryError, match="frozen"):
        snap.register_scenario(_scenario(name="late"))
    n = len(snap)
    reg.register_scenario(_scenario(name="late", cause="late_cause"))
    assert len(snap) == n and "late" not in snap


def test_service_pins_registry_at_construction():
    reg = build_default_registry()
    svc = CentralService(registry=reg)
    reg.register_sop_rule(SOPRule(("post_start_fn",), "post_start_cause",
                                  "act"))
    assert svc.rules.frozen
    assert all(r.cause != "post_start_cause" for r in svc.rules.sop_rules)
    assert any(r.cause == "post_start_cause" for r in reg.sop_rules)


# ---------------------------------------------------------------------------
# rule-driven diffdiag: thresholds are data, pinned legacy behaviour
# ---------------------------------------------------------------------------


def test_os_diff_legacy_thresholds_pinned():
    """Regression pin for the original inline thresholds: irq 2x + 1000
    absolute, scheduler 2x (severity = ratio/threshold), numa 4x."""
    h = OSSignals(rank=7, timestamp=0, interrupts={"NET_RX": 2000},
                  sched_latency_p99=80e-6, numa_migrations=10)
    # just below every threshold: quiet
    quiet = OSSignals(rank=0, timestamp=0, interrupts={"NET_RX": 2999},
                      sched_latency_p99=159e-6, numa_migrations=40)
    assert os_diff(quiet, h) is None
    # irq needs BOTH 2x and +1000 absolute: 1900 vs 900 is >2x but +1000
    small_abs = OSSignals(rank=0, timestamp=0, interrupts={"NET_RX": 1900},
                          sched_latency_p99=80e-6)
    assert os_diff(small_abs, dataclasses.replace(h, interrupts={"NET_RX": 900})) is None
    v = os_diff(OSSignals(rank=0, timestamp=0, interrupts={"NET_RX": 8000},
                          sched_latency_p99=80e-6), h)
    assert v and v.root_cause == "irq_imbalance"
    assert v.evidence["irq:NET_RX"] == (8000, 2000)
    assert v.evidence["causes"][0]["severity"] == pytest.approx(2.0)  # 4x/2x
    # scheduler severity normalized by its own 2x threshold
    v = os_diff(dataclasses.replace(h, rank=0, sched_latency_p99=800e-6), h)
    assert v and v.root_cause == "scheduler_contention"
    assert v.evidence["causes"][0]["severity"] == pytest.approx(5.0)  # 10x/2x


def test_os_diff_custom_rules_override_defaults():
    h = OSSignals(rank=7, timestamp=0, sched_latency_p99=80e-6)
    s = dataclasses.replace(h, rank=0, sched_latency_p99=800e-6)
    strict = [OSRule(cause="sched_paranoid", field="sched_latency_p99",
                     ratio=100.0, baseline_floor=1e-6)]
    assert os_diff(s, h, rules=strict) is None
    loose = [OSRule(cause="sched_paranoid", field="sched_latency_p99",
                    ratio=1.5, baseline_floor=1e-6, action="page oncall")]
    v = os_diff(s, h, rules=loose)
    assert v and v.root_cause == "sched_paranoid" and v.action == "page oncall"


def test_os_diff_extended_counters():
    h = OSSignals(rank=7, timestamp=0, major_faults=2, cpu_freq_mhz=2600.0,
                  pcie_replays=1, ecc_remapped_rows=0, numa_remote_ratio=0.03)
    cases = [
        (dict(major_faults=6000), "memory_pressure_swap"),
        (dict(pcie_replays=600), "pcie_link_degradation"),
        (dict(cpu_freq_mhz=1200.0), "cpu_frequency_downclock"),
        (dict(ecc_remapped_rows=8), "ecc_row_remap_stall"),
        (dict(numa_remote_ratio=0.6), "numa_remote_allocation"),
    ]
    for overrides, cause in cases:
        s = dataclasses.replace(h, rank=0, **overrides)
        v = os_diff(s, h)
        assert v and v.root_cause == cause, (overrides, v)
    # healthy-vs-healthy jitter on the extended counters stays quiet
    s = dataclasses.replace(h, rank=0, major_faults=4, pcie_replays=2,
                            cpu_freq_mhz=2580.0, numa_remote_ratio=0.045)
    assert os_diff(s, h) is None


def test_os_diff_unreported_gauge_is_not_a_downclock():
    """A v1 agent reports no cpu_freq_mhz (schema default 0).  The
    lower-is-worse rule must treat 0 as 'unreported' on EITHER side —
    not as an extreme downclock that out-severities every real cause."""
    h = OSSignals(rank=7, timestamp=0, cpu_freq_mhz=2600.0)
    v1_straggler = OSSignals(rank=0, timestamp=0, cpu_freq_mhz=0.0,
                             major_faults=6000)
    v = os_diff(v1_straggler, h)
    assert v is not None and v.root_cause == "memory_pressure_swap"
    assert all(c["cause"] != "cpu_frequency_downclock"
               for c in v.evidence["causes"])
    # unreported on the healthy side is equally not a verdict
    assert os_diff(OSSignals(rank=0, timestamp=0, cpu_freq_mhz=1200.0),
                   OSSignals(rank=7, timestamp=0, cpu_freq_mhz=0.0)) is None


def test_os_diff_dict_rule_honors_lower_is_worse():
    """Dict-valued fields go through the same evaluator as scalars, so
    direction applies per key (e.g. residency where a drop is the fault)."""
    rules = [OSRule(cause="residency_drop", field="softirq_residency",
                    ratio=2.0, baseline_floor=1e-3, lower_is_worse=True)]
    s = OSSignals(rank=0, timestamp=0, softirq_residency={"RCU": 0.01})
    h = OSSignals(rank=7, timestamp=0, softirq_residency={"RCU": 0.10})
    v = os_diff(s, h, rules=rules)
    assert v and v.root_cause == "residency_drop"
    assert v.evidence["softirq_residency:RCU"] == (0.01, 0.10)
    assert os_diff(h, s, rules=rules) is None
    # the extreme case: the counter vanished entirely on the straggler —
    # keys present only on the healthy side still evaluate
    gone = OSSignals(rank=0, timestamp=0, softirq_residency={})
    v = os_diff(gone, h, rules=rules)
    assert v and v.root_cause == "residency_drop"
    assert v.evidence["softirq_residency:RCU"] == (0, 0.10)


def test_cpu_diff_unclassified_noise_descends():
    """Diffuse unclassified deltas below unclassified_min are sampling
    noise, not a CPU diagnosis — the walk must descend to the OS layer."""
    base = {("main", "forward", "softmax"): 400,
            ("main", "backward", "matmul"): 400}
    noisy = {("main", "forward", "softmax"): 404,
             ("main", "backward", "matmul"): 397}
    fg = FlameGraph
    a, b = fg(), fg()
    for st, w in base.items():
        b.add(st, w)
    for st, w in noisy.items():
        a.add(st, w)
    assert cpu_diff(a, b) is None
    # ...but a large unclassified divergence still fires
    a.add(("main", "mystery_daemon"), 40)
    v = cpu_diff(a, b)
    assert v and v.root_cause == "cpu_host_interference"
    # and the floor itself is rule data
    v = cpu_diff(a, b, rules=CPURules(unclassified_min=0.9))
    assert v is None
    # raising the noise floor must NOT deflate confidence on verdicts
    # that clear it — confidence has its own scale
    v = cpu_diff(a, b, rules=CPURules(unclassified_min=0.04))
    assert v and v.confidence == pytest.approx(
        min(1.0, max(a.diff(b).values()) / 0.02))


# ---------------------------------------------------------------------------
# wire-format version negotiation (SYTC v1 <-> v2)
# ---------------------------------------------------------------------------


def _batch(sig):
    cl = sc.SimCluster(n_ranks=1, seed=3)
    prof = cl.step()[0]
    prof.os_signals = sig
    return ProfileBatch("job-v", [prof], "node-v")


def test_wire_v2_round_trips_extended_fields():
    sig = OSSignals(rank=0, timestamp=1.0, interrupts={"LOC": 5},
                    sched_latency_p99=1e-4, major_faults=77,
                    cpu_freq_mhz=1234.5, pcie_replays=9,
                    ecc_remapped_rows=3, numa_remote_ratio=0.25)
    batch = _batch(sig)
    data = encode_batch(batch)
    assert data[4:6] == WIRE_VERSION.to_bytes(2, "little")
    out = decode_batch(data).to_dataclasses()
    assert out.profiles[0].os_signals == sig


def test_wire_v1_downlevel_round_trips_default_fields():
    sig = OSSignals(rank=0, timestamp=1.0, interrupts={"LOC": 5},
                    sched_latency_p99=1e-4)
    batch = _batch(sig)
    data = encode_batch(batch, version=1)
    assert data[4:6] == (1).to_bytes(2, "little")
    out = decode_batch(data).to_dataclasses()
    assert out.profiles[0].os_signals == sig
    assert out == batch


def test_wire_v1_refuses_extended_fields():
    batch = _batch(OSSignals(rank=0, timestamp=0.0, major_faults=5000))
    with pytest.raises(WireFormatError, match="extended OS counters"):
        encode_batch(batch, version=1)


def test_wire_unsupported_versions_rejected():
    batch = _batch(None)
    with pytest.raises(WireFormatError, match="cannot encode"):
        encode_batch(batch, version=0)
    with pytest.raises(WireFormatError, match="cannot encode"):
        encode_batch(batch, version=WIRE_VERSION + 1)
    data = bytearray(encode_batch(batch))
    data[4:6] = (WIRE_VERSION + 1).to_bytes(2, "little")
    with pytest.raises(WireFormatError, match="unsupported wire version"):
        decode_batch(bytes(data))


# ---------------------------------------------------------------------------
# the scenario matrix: every scenario, every service path
# ---------------------------------------------------------------------------

_REGISTRY = default_registry()


@pytest.mark.parametrize(
    "name", sorted(s.name for s in _REGISTRY.scenarios))
def test_scenario_diagnoses_on_all_service_paths(name):
    """The acceptance gate, generalized from the old hand-enumerated
    five-case equivalence tests: each registered scenario's first
    diagnosis is the expected root cause (and straggler rank, where
    pinned) on the streaming, columnar, sharded and pod paths alike —
    and all four paths agree event for event."""
    scen = _REGISTRY.get(name)
    results = run_scenario_matrix(scenarios=[scen], strict=True)
    per_path = results[name]
    assert set(per_path) == set(SERVICE_PATHS)
    assert all(r.ok for r in per_path.values())
    # every path agrees on the cause AND the category is the scenario's
    causes = {r.first_cause for r in per_path.values()}
    assert causes == {scen.expected_cause}
    assert _REGISTRY.category_for(scen.expected_cause) == scen.category
    # cross-path equivalence: identical diagnoses, event for event
    reference = per_path["streaming"].event_tuples
    assert reference
    for path in SERVICE_PATHS:
        assert per_path[path].event_tuples == reference, path


def test_zero_baseline_delta_does_not_crash_temporal_path():
    """'Report any regression' tuning: baseline_delta=0 must still emit a
    (fully confident) temporal diagnosis, not divide by zero."""
    svc = CentralService(window=50, baseline_delta=0.0)
    cl = sc.SimCluster(n_ranks=8, seed=7)
    cl.run(svc, 30)
    cl.add_fault(sc.logging_overhead())
    events = cl.run(svc, 60)
    assert events and events[0].root_cause == "logging_overhead"
    assert events[0].verdict.confidence == 1.0


def test_matrix_strict_reports_misses():
    bad = _scenario(name="impossible", cause="never_this_cause")
    with pytest.raises(AssertionError, match="impossible/streaming"):
        run_scenario_matrix(scenarios=[bad], paths=("streaming",),
                            strict=True)


def test_registered_scenario_flows_through_custom_registry():
    """A user-registered scenario (new fault + new OS rule) is diagnosed
    end-to-end by a service built from that registry — no core edits."""
    reg = build_default_registry()
    reg.register_os_rule(OSRule(
        cause="cpu_steal_storm", field="cpu_steal", ratio=3.0,
        min_abs_delta=0.05, baseline_floor=0.01,
        action="evict the noisy neighbour VM"))

    def steal_fault():
        def os_fx(sig, rng):
            sig["cpu_steal"] = 0.4 + rng.uniform(-0.02, 0.02)
        return sc.Fault("cpu_steal_storm", [3], os_effect=os_fx,
                        entry_delay=lambda base: 1.0e-3)

    reg.register_scenario(Scenario(
        name="noisy_neighbour_steal",
        description="hypervisor steals cycles from one node",
        make_fault=steal_fault, expected_cause="cpu_steal_storm",
        expected_layer="os", category="os_interference", expected_rank=3))
    res = run_scenario_matrix(
        registry=reg, scenarios=[reg.get("noisy_neighbour_steal")],
        paths=("streaming", "sharded"), strict=True)
    assert all(r.ok for r in res["noisy_neighbour_steal"].values())
