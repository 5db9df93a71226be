"""Pallas TPU kernel for the Mamba2 SSD chunk-local computation.

The SSD scan splits into (a) a quadratic chunk-local term + per-chunk state
summaries — O(S*L) compute, the hot spot — and (b) a cheap sequential
recurrence across chunks.  The kernel computes (a) per (batch, chunk, head)
grid cell entirely in VMEM: the (L, L) decay matrix, gated scores, y_diag,
and the (P, N) chunk state.  The host keeps (b) as a lax.scan plus the
off-diagonal einsum (repro.models.ssm consumes these exact contracts).

Layout is head-major, so every block's last two dims are either a full
array dim or tile-aligned (L=256 chunks): x/y blocks are (L, P), dt and
the in-chunk decay are (1, L) rows, states (P, N).  B/C tiles are shared
across heads via index maps (no HBM duplication); the per-head decay
rates A sit whole in SMEM and are read as scalars.  The inclusive cumsum
is built from masked reductions over the (L, L) lower triangle, which
Mosaic lowers (it has no cumsum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                y_ref, st_ref, id_ref, *, L: int):
    f32 = jnp.float32
    a = a_ref[pl.program_id(2)]                       # SMEM scalar decay rate
    x = x_ref[0, 0, 0].astype(f32)                    # (L, P)
    dt_row = dt_ref[0, 0, 0].astype(f32)              # (1, L)
    bm = b_ref[0, 0].astype(f32)                      # (L, N)
    cm = c_ref[0, 0].astype(f32)                      # (L, N)

    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    lower, diag = li >= lj, li == lj
    # row <-> column through the diagonal: dt_col[i] = dt_row[i]
    dt_col = jnp.sum(jnp.where(diag, dt_row, 0.0), axis=1, keepdims=True)
    # inclusive cumsum of dA = dt * a, as a column and as a row
    dA_row = dt_row * a                               # (1, L)
    cum_col = jnp.sum(jnp.where(lower, dA_row, 0.0), axis=1,
                      keepdims=True)                  # (L, 1)
    cum_row = jnp.sum(jnp.where(diag, cum_col, 0.0), axis=0,
                      keepdims=True)                  # (1, L)
    cum_last = jnp.sum(dA_row, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk decay matrix: exp(segsum) lower-tri
    decay = jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0)  # (L, L)

    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
    gated = scores * decay                            # (L, L)
    y = jax.lax.dot_general(gated, x * dt_col, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)  # (L, P)

    decay_to_end = jnp.exp(cum_last - cum_col)        # (L, 1)
    weighted_b = bm * (decay_to_end * dt_col)         # (L, N)
    state = jax.lax.dot_general(x, weighted_b, (((0,), (0,)), ((), ())),
                                preferred_element_type=f32)  # (P, N)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0] = state
    id_ref[0, 0, 0] = jnp.exp(cum_row)


def ssd_chunk_fwd(x, dt, A, B, C, *, interpret: bool):
    """Chunk-local SSD terms, head-major.

    x: (b, nc, h, L, p); dt: (b, nc, h, L); A: (h,); B, C: (b, nc, L, n)
    Returns (y_diag (b,nc,h,L,p), states (b,nc,h,p,n), in_decay (b,nc,h,L))
    matching ref.ssd_chunk_ref; the chunk decay is ``in_decay[..., -1]``.
    """
    b, nc, h, L, p = x.shape
    n = B.shape[-1]
    kernel = functools.partial(_ssd_kernel, L=L)

    out_shapes = (
        jax.ShapeDtypeStruct((b, nc, h, L, p), x.dtype),
        jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        jax.ShapeDtypeStruct((b, nc, h, 1, L), jnp.float32),
    )
    y, states, in_decay = pl.pallas_call(
        kernel,
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, p), lambda bb, c, hh: (bb, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda bb, c, hh: (bb, c, hh, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, L, n), lambda bb, c, hh: (bb, c, 0, 0)),
            pl.BlockSpec((1, 1, L, n), lambda bb, c, hh: (bb, c, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, 1, L, p), lambda bb, c, hh: (bb, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda bb, c, hh: (bb, c, hh, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda bb, c, hh: (bb, c, hh, 0, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(x, dt.reshape(b, nc, h, 1, L), A.astype(jnp.float32), B, C)
    return y, states, in_decay.reshape(b, nc, h, L)
