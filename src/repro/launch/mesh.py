"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import dataclasses

import jax

from repro.parallel.sharding import ShardingRules


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_rules(*, multi_pod: bool = False, **overrides) -> ShardingRules:
    return ShardingRules(pod_axis="pod" if multi_pod else None, **overrides)


def make_local_mesh(data: int = 1, model: int = 1):
    """Degenerate mesh over however many devices exist (tests / examples)."""
    return jax.make_mesh((data, model), ("data", "model"))


CHIPS_PER_POD = 256
# the production mesh above is a pod of these (jax ``Device.device_kind``)
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks used by the roofline analysis."""
    flops_bf16: float     # FLOP/s
    hbm_bw: float         # bytes/s
    hbm_bytes: float
    ici_bw: float         # bytes/s per link
    source: str


# Keyed by ``jax.Device.device_kind``.  A kind missing here is an error,
# never a borrowed default.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def device_peaks(kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}") from None
