"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's table (``repro.launch.mesh.DEVICE_PEAKS``) so
that no change to the program moves the yardstick.  A kind missing here is
an error, never a borrowed default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,        # FLOP/s
        "hbm_bw": 819e9,             # bytes/s
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peak(kind: str, what: str = "flops_bf16") -> float:
    try:
        return PEAKS[kind][what]
    except KeyError:
        raise KeyError(f"no published {what} for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
