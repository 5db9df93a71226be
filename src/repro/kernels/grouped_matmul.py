"""Grouped matrix products over the experts a chip holds, for the dropless
MoE layer.

``grouped_matmul(lhs, rhs, sizes)``: lhs (M, K) rows sorted by group, rhs
(G, K, N) one matrix per held group, sizes (G,) the rows of each held
group from the top; rows past the held groups come out zero, and neither
they nor their cotangents enter any product.  The MoE layer sizes M by
the held experts' pairs, twice their even share, so the rows past the
held groups are few; a step whose held pairs overflow that runs with M at
the bound on the held pairs instead.  On a TPU it is the megablox grouped
matmul that ships with JAX (``jax.experimental.pallas.ops.tpu.megablox``,
forward ``gmm``, backward ``gmm`` and ``tgmm``): it visits only the tiles
of the held groups' rows, and, being a Pallas kernel, it carries the
model's scope in its op name.  Elsewhere it is ``jax.lax.ragged_dot``, the
kernel's oracle in the tests (interpret mode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

# (rows, contraction, columns) of each kernel tile
TILING = (256, 512, 512)


def _megablox(lhs, rhs, sizes, interpret: bool = False):
    m = lhs.shape[0]
    pad = -m % TILING[0]          # the kernel takes whole row tiles
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    # one more group for the rows past the held ones: the kernel computes
    # the first rhs.shape[0] groups and zeroes the rows of the others
    rest = jnp.int32(m + pad) - jnp.sum(sizes)
    out = megablox.gmm(lhs, rhs, jnp.append(sizes, rest), lhs.dtype,
                       TILING, None, None, False, interpret)
    return out[:m] if pad else out


def grouped_matmul(lhs, rhs, sizes, *, interpret: bool = False):
    """(M, K) x (G, K, N) by the row groups ``sizes`` -> (M, N)."""
    if interpret:
        return _megablox(lhs, rhs, sizes, interpret=True)
    return jax.lax.platform_dependent(
        lhs, rhs, sizes, tpu=_megablox,
        default=lambda lhs, rhs, sizes: jax.lax.ragged_dot(lhs, rhs, sizes))
