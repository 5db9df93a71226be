"""Layered differential diagnosis (§3.1).

Given a flagged straggler and a healthy reference rank, generate
layer-by-layer differential profiles and walk them in order:

  (1) GPU diff   — uniform kernel slowdown => hardware (thermal/frequency);
                   specific-kernel slowdown => software (operator change).
  (2) CPU diff   — if GPU matches, diff flame graphs; new hot paths reveal
                   host-side interference, classified by SOP signature rules.
  (3) OS diff    — if CPU profiles match, compare OS/node counters
                   (interrupts, scheduler latency, NUMA migrations, major
                   faults, link replays, core frequency, ...) — signals too
                   brief to appear in sampled flame graphs.

Every threshold and signature is *data* from the scenario registry
(``repro.core.scenarios``): SOP signatures, per-counter OS severity
thresholds and the GPU/CPU layer thresholds all live on registered rule
objects; each layer function takes an optional rules override and falls
back to ``default_registry()``.  ``SOP_RULES`` remains as the legacy
tuple view of the default SOP set for backwards compatibility.

Invariant: the walk is deterministic in its inputs and rule set — a
service diagnoses with the frozen registry snapshot it pinned at
construction, so verdicts are reproducible after later registrations.

Each verdict carries the evidence that produced it, mirroring the paper's
case studies (§5.4); uniform degradations go through the temporal-baseline
path (baseline.py).
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.events import KernelEvent, OSSignals
from repro.core.flamegraph import FlameGraph
from repro.core.scenarios import (CPURules, EXTENDED_SOP_RULES, GPURules,
                                  LEGACY_SOP_RULES, OSRule, SOPRule,
                                  default_registry)

__all__ = [
    "Verdict", "SOP_RULES", "classify_functions", "per_kernel_means",
    "gpu_diff", "cpu_diff", "os_diff", "diagnose",
    "StandingVerdict", "VerdictDamper",
]

# Backwards-compatible tuple view of the *default* SOP registration set
# (the paper's production rule set, §5 "log-based SOP rule matching") —
# built from the pure constants, so its value never depends on what was
# registered on the live registry before this module imported.  New
# rules belong in the registry, not here.
SOP_RULES: List[Tuple[Tuple[str, ...], str, str]] = [
    (r.pattern, r.cause, r.action)
    for r in LEGACY_SOP_RULES + EXTENDED_SOP_RULES]


@dataclasses.dataclass
class Verdict:
    """One layered-diagnosis outcome.  The provenance fields separate
    *culprit* from *victim* (ARGUS/EROICA-style): ``culprit_rank``/
    ``culprit_group`` name where the blame actually localized, and
    ``victim_ranks`` the ranks that merely blocked in collectives
    waiting on it.  On a victim-side verdict (``layer == "cascade"``)
    ``culprit_group`` differs from the event's own group — consumers
    (``ft/mitigation.py``) must never cordon the victim."""
    layer: str                    # gpu | cpu | os | cascade | inconclusive
    root_cause: str
    confidence: float
    evidence: Dict[str, object]
    action: str = ""
    culprit_rank: Optional[int] = None
    culprit_group: Optional[str] = None
    victim_ranks: Tuple[int, ...] = ()

    def to_dict(self) -> Dict[str, object]:
        """Stable wire form (query-envelope contract: field names match
        the dataclass; ``victim_ranks`` is a list)."""
        d = dataclasses.asdict(self)
        d["victim_ranks"] = list(self.victim_ranks)
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, object]) -> "Verdict":
        d = dict(d)
        d["victim_ranks"] = tuple(d.get("victim_ranks", ()))
        return cls(**d)  # type: ignore[arg-type]


def classify_functions(functions: Sequence[str],
                       rules: Optional[Sequence[SOPRule]] = None
                       ) -> Optional[Tuple[str, str]]:
    """First SOP rule whose every pattern element substring-matches some
    hot function -> (cause, action); None when nothing matches."""
    if rules is None:
        rules = default_registry().sop_rules
    for rule in rules:
        if all(any(p in fn for fn in functions) for p in rule.pattern):
            return rule.cause, rule.action
    return None


# ---------------------------------------------------------------------------
# verdict flap-damping + confidence decay
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StandingVerdict:
    """The damper's memory of one (group, rank) diagnosis stream: the
    cause currently considered standing, how decayed its confidence is,
    and any not-yet-confirmed flip candidate."""
    cause: str
    confidence: float
    confirmed: int = 1         # cycles the standing cause has been proposed
    absent: int = 0            # consecutive cycles with no proposal
    pending_cause: str = ""    # unconfirmed flip candidate
    pending_count: int = 0     # consecutive cycles the candidate proposed

    def as_dict(self) -> Dict[str, object]:
        return {"cause": self.cause, "confidence": self.confidence,
                "confirmed": self.confirmed, "absent": self.absent,
                "pending_cause": self.pending_cause or None,
                "pending_count": self.pending_count}


class VerdictDamper:
    """Per-(group, rank) verdict state machine: flap damping and
    confidence decay (chaos-harness robustness, EROICA's online
    troubleshooting framing).

    Under a flapping fault the layered walk flickers: during an OFF
    window the straggler's windowed lateness still alerts, but the
    latest profiles are healthy, every layer matches, and the network
    fallback (or a different layer) wins — a verdict *flip* that an
    un-damped consumer would act on (e.g. cordon a node over a single
    noisy cycle).  The damper's rules:

      * first diagnosis for a (group, rank): emit immediately and
        establish the standing verdict (single-incident behaviour is
        unchanged — every registered scenario emits exactly as before);
      * proposal matching the standing cause: emit (a refresh), reset
        absence, restore confidence;
      * proposal with a DIFFERENT cause: suppressed until it repeats
        ``confirm`` consecutive cycles; a transient single-cycle
        anomaly never flips a standing verdict.  A confirmed flip emits
        carrying ``flap_damping`` evidence (what it replaced, how many
        cycles were suppressed);
      * no proposal for a standing (group, rank) this cycle
        (:meth:`tick`): confidence decays by ``decay`` per absent
        cycle; after ``retire_after`` absent cycles the standing
        verdict retires and the next diagnosis starts fresh.

    Determinism: decisions depend only on the proposal stream, so the
    streaming/columnar/sharded/pod paths (which feed identical
    streams per group) damp identically — the scenario-matrix
    event-for-event equality holds with damping on.
    """

    def __init__(self, confirm: int = 2, decay: float = 0.7,
                 retire_after: int = 4):
        self.confirm = max(1, confirm)
        self.decay = decay
        self.retire_after = max(1, retire_after)
        self._standing: Dict[Tuple[str, Optional[int]], StandingVerdict] = {}
        self._seen: set = set()
        self.suppressed = 0        # proposals suppressed as unconfirmed flips
        self.flips_confirmed = 0   # standing-cause changes that confirmed
        self.retired = 0           # standings retired by absence decay

    def propose(self, group: str, rank: Optional[int], cause: str,
                confidence: float) -> Optional[Dict[str, object]]:
        """One cycle's diagnosis proposal for (group, rank).  Returns
        None to suppress the emission, or an evidence dict (possibly
        empty) to attach to the emitted event."""
        key = (group, rank)
        self._seen.add(key)
        st = self._standing.get(key)
        if st is None:
            self._standing[key] = StandingVerdict(cause, confidence)
            return {}
        if cause == st.cause:
            st.confirmed += 1
            st.absent = 0
            st.confidence = confidence
            st.pending_cause = ""
            st.pending_count = 0
            return {}
        # flip candidate: hold the standing verdict until confirmed
        if cause == st.pending_cause:
            st.pending_count += 1
        else:
            st.pending_cause = cause
            st.pending_count = 1
        st.absent = 0
        if st.pending_count >= self.confirm:
            evidence = {"replaced": st.cause,
                        "suppressed_cycles": st.pending_count - 1,
                        "standing_confirmed": st.confirmed}
            self._standing[key] = StandingVerdict(cause, confidence)
            self.flips_confirmed += 1
            return {"flap_damping": evidence}
        # decay the standing verdict's confidence while contested
        st.confidence *= self.decay
        self.suppressed += 1
        return None

    def tick(self) -> None:
        """End of one analysis cycle: decay every standing verdict that
        got no proposal this cycle; retire after ``retire_after``
        consecutive absent cycles."""
        gone = []
        for key, st in self._standing.items():
            if key in self._seen:
                continue
            st.absent += 1
            st.confidence *= self.decay
            if st.absent >= self.retire_after:
                gone.append(key)
        for key in gone:
            del self._standing[key]
            self.retired += 1
        self._seen.clear()

    def standing(self, group: str, rank: Optional[int]
                 ) -> Optional[StandingVerdict]:
        return self._standing.get((group, rank))

    def standing_verdicts(self) -> Dict[Tuple[str, Optional[int]],
                                        StandingVerdict]:
        """Live standing verdicts keyed by (group, rank) — the
        operator's view of what is damped or decaying right now."""
        return dict(self._standing)

    def forget_group(self, group: str) -> None:
        for key in [k for k in self._standing if k[0] == group]:
            del self._standing[key]


# ---------------------------------------------------------------------------
# layer 1: GPU diff
# ---------------------------------------------------------------------------


def per_kernel_means(evs) -> Dict[str, float]:
    """Mean duration per kernel name.  Accepts a sequence of
    ``KernelEvent`` or anything with interned kernel columns
    (``kern_name`` id array + ``kern_dur`` + ``tables`` — see
    ``repro.core.trace.ColumnarProfile``); the columnar path aggregates
    with one bincount over the interned-id space instead of a per-event
    dict walk."""
    names = getattr(evs, "kern_name", None)
    if names is not None:
        if names.shape[0] == 0:
            return {}
        sums = np.bincount(names, weights=evs.kern_dur)
        counts = np.bincount(names)
        get = evs.tables.strings.get
        nz = np.nonzero(counts)[0]
        return {get(int(i)): float(sums[i] / counts[i]) for i in nz}
    acc: Dict[str, List[float]] = {}
    for e in evs:
        acc.setdefault(e.name, []).append(e.duration)
    return {k: sum(v) / len(v) for k, v in acc.items()}


def gpu_diff(straggler: Sequence[KernelEvent], healthy: Sequence[KernelEvent],
             rules: Optional[GPURules] = None) -> Optional[Verdict]:
    if rules is None:
        rules = default_registry().gpu_rules
    a, b = per_kernel_means(straggler), per_kernel_means(healthy)
    common = sorted(set(a) & set(b))
    if not common:
        return None
    ratios = {k: a[k] / b[k] for k in common if b[k] > 0}
    vals = list(ratios.values())
    med = statistics.median(vals)
    cv = (statistics.pstdev(vals) / med) if med > 0 else 0.0

    if med >= rules.slow_ratio and cv <= rules.uniform_cv:
        return Verdict(
            layer="gpu", root_cause=rules.uniform_cause,
            confidence=min(1.0, (med - 1) * 20),
            evidence={"median_ratio": med, "ratio_cv": cv,
                      "kernels": len(common), "per_kernel_ratio": ratios},
            action=rules.uniform_action)
    slow = {k: r for k, r in ratios.items() if r >= rules.slow_ratio}
    if slow and med < rules.slow_ratio:
        return Verdict(
            layer="gpu", root_cause=rules.specific_cause,
            confidence=0.8,
            evidence={"slow_kernels": slow, "median_ratio": med},
            action=rules.specific_action)
    return None  # GPU profiles match -> descend to CPU layer


# ---------------------------------------------------------------------------
# layer 2: CPU diff
# ---------------------------------------------------------------------------


def cpu_diff(straggler: FlameGraph, healthy: FlameGraph,
             rules: Optional[CPURules] = None,
             sop_rules: Optional[Sequence[SOPRule]] = None
             ) -> Optional[Verdict]:
    if rules is None:
        rules = default_registry().cpu_rules
    deltas = straggler.diff(healthy)
    hot = {fn: d for fn, d in deltas.items() if d >= rules.min_delta}
    if not hot:
        return None
    cls = classify_functions(list(hot), sop_rules)
    if cls:
        cause, action = cls
    else:
        # unexplained diffuse deltas: only a real CPU-layer diagnosis
        # above the (higher) unclassified floor; below it the walk
        # descends to the OS layer instead of crying wolf on noise
        if max(hot.values()) < rules.unclassified_min:
            return None
        cause, action = rules.fallback_cause, rules.fallback_action
    return Verdict(
        layer="cpu", root_cause=cause,
        confidence=min(1.0, max(hot.values()) / rules.confidence_scale),
        evidence={"hot_deltas": dict(sorted(hot.items(), key=lambda kv: -kv[1])[:12])},
        action=action)


# ---------------------------------------------------------------------------
# layer 3: OS diff
# ---------------------------------------------------------------------------


def _eval_scalar(rule: OSRule, s: float, h: float
                 ) -> Optional[Tuple[float, Tuple[float, float]]]:
    """(severity, (straggler, healthy)) when the rule fires, else None."""
    if s < rule.min_valid or h < rule.min_valid:
        return None     # one side unreported (schema default): no verdict
    if rule.lower_is_worse:
        worse, base = h, s
    else:
        worse, base = s, h
    floor = max(base, rule.baseline_floor)
    if worse > floor * rule.ratio and worse - base > rule.min_abs_delta:
        return worse / floor / rule.ratio, (s, h)
    return None


def os_diff(straggler: OSSignals, healthy: OSSignals,
            rules: Optional[Sequence[OSRule]] = None) -> Optional[Verdict]:
    """Compare OS/node counters; every divergent subsystem becomes a cause.

    Each registered :class:`~repro.core.scenarios.OSRule` carries its own
    thresholds (ratio, absolute floor, direction).  Co-occurring signals
    (an IRQ storm usually drags scheduler latency up with it) are ALL
    reported, ranked by severity — the measured ratio normalized by that
    rule's own threshold, so severities are comparable across subsystems.
    ``root_cause`` is the top-ranked cause; ``evidence["causes"]`` carries
    the full ranking."""
    if rules is None:
        rules = default_registry().os_rules
    evidence: Dict[str, object] = {}
    scored: List[Tuple[float, OSRule]] = []
    for rule in rules:
        s = getattr(straggler, rule.field, None)
        h = getattr(healthy, rule.field, None)
        if s is None or h is None:
            continue
        key = rule.evidence_key or rule.field
        if isinstance(s, dict):
            worst = 0.0
            # union of keys, straggler order first: a counter that exists
            # only on the healthy side is the *extreme* case for a
            # lower-is-worse rule (the signal vanished) and must still
            # evaluate; for higher-is-worse rules a missing straggler key
            # can never fire, so legacy behaviour is unchanged
            counters = list(s) + [c for c in h if c not in s]
            for counter in counters:
                hit = _eval_scalar(rule, s.get(counter, 0),
                                   h.get(counter, 0))
                if hit is not None:
                    severity, pair = hit
                    worst = max(worst, severity)
                    evidence[f"{key}:{counter}"] = pair
            if worst:
                scored.append((worst, rule))
        else:
            hit = _eval_scalar(rule, s, h)
            if hit is not None:
                severity, pair = hit
                scored.append((severity, rule))
                evidence[key] = pair
    if not scored:
        return None
    scored.sort(key=lambda sc: -sc[0])       # stable: ties keep rule order
    evidence["causes"] = [
        {"cause": rule.cause, "severity": round(sev, 3)}
        for sev, rule in scored]
    top = scored[0][1]
    return Verdict(layer="os", root_cause=top.cause, confidence=0.7,
                   evidence=evidence, action=top.action)


# ---------------------------------------------------------------------------
# the layered walk
# ---------------------------------------------------------------------------


def diagnose(straggler_kernels, healthy_kernels,
             straggler_cpu: FlameGraph, healthy_cpu: FlameGraph,
             straggler_os: Optional[OSSignals] = None,
             healthy_os: Optional[OSSignals] = None,
             registry=None) -> Verdict:
    """Walk the layers in order with one rule source.  ``registry`` is
    any object exposing ``gpu_rules``/``cpu_rules``/``os_rules``/
    ``sop_rules`` (a ``ScenarioRegistry`` or a frozen snapshot); default
    is the process-wide registry."""
    if registry is None:
        registry = default_registry()
    v = gpu_diff(straggler_kernels, healthy_kernels, registry.gpu_rules)
    if v:
        return v
    v = cpu_diff(straggler_cpu, healthy_cpu, registry.cpu_rules,
                 registry.sop_rules)
    if v:
        return v
    if straggler_os and healthy_os:
        v = os_diff(straggler_os, healthy_os, registry.os_rules)
        if v:
            return v
    return Verdict(layer="inconclusive", root_cause="unknown", confidence=0.0,
                   evidence={}, action="escalate with raw profiles attached")
