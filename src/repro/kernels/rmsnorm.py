"""Pallas TPU fused RMSNorm: one HBM round-trip for norm+scale.

Row-blocked: grid over (rows/block_rows); each program loads a
(block_rows, d) tile into VMEM, reduces in f32, writes the scaled tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * (1.0 + w)[None, :]).astype(o_ref.dtype)


def rmsnorm_fwd(x, weight, *, eps: float = 1e-6, block_rows: int = 256,
                interpret: bool):
    """x: (rows, d) (callers flatten batch dims); weight: (d,)."""
    rows, d = x.shape
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, weight)
