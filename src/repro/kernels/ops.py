"""Public jit'd kernel entry points.

Models call these; each runs its Pallas kernel, compiled on a TPU and
through the Pallas interpreter on the CPU backend (the tests).  The
choice is made at trace time from ``jax.default_backend()``; any other
backend raises rather than silently interpreting.  The pure-jnp oracles
are re-exported for the tests and the non-Pallas model paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ref as _ref
from repro.kernels import ssd as _ssd


def pallas_interpret() -> bool:
    """True on the CPU backend (interpret), False on TPU (compile)."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' or interpret on 'cpu'; the "
        f"default backend is {backend!r}")


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         sliding_window: int = 0):
    """(B, Hq, S, D) layout."""
    return _fa.flash_attention_fwd(q, k, v, causal=causal,
                                   sliding_window=sliding_window,
                                   interpret=pallas_interpret())


def flash_attention(q, k, v, *, causal: bool = True, sliding_window: int = 0):
    """(B, S, H, D) layout (model-side convention) -> same layout."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal,
                               sliding_window=sliding_window)
    return jnp.swapaxes(out, 1, 2)


@jax.jit
def ssd_chunk(x, dt, A, B, C):
    return _ssd.ssd_chunk_fwd(x, dt, A, B, C, interpret=pallas_interpret())


@functools.partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x, weight, eps: float = 1e-6):
    shape = x.shape
    out = _rn.rmsnorm_fwd(x.reshape(-1, shape[-1]), weight,
                          eps=eps, interpret=pallas_interpret())
    return out.reshape(shape)


# re-exported oracles (tests, fallback paths)
flash_attention_ref = _ref.flash_attention_ref
ssd_chunk_ref = _ref.ssd_chunk_ref
rmsnorm_ref = _ref.rmsnorm_ref
