"""What the plain references share: weight draws, RMSNorm, the vocabulary
head.  Imports nothing of the system under test."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def padded_vocab(m) -> int:
    mult = m["vocab_pad_multiple"]
    return -(-m["vocab_size"] // mult) * mult


def normal(key, shape, fan_in, dtype):
    """A normal draw scaled by ``fan_in ** -0.5``, stored in ``dtype``."""
    w = jax.random.normal(key, shape, dtype=jnp.float32) * fan_in ** -0.5
    return w.astype(dtype)


def rms_norm(x, w, eps):
    """RMSNorm whose weight is stored as ``w - 1``."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def head_matrix(params, m):
    e = params["embed"]
    return e["embedding"].T if m["tie_embeddings"] else e["lm_head"]


def mm(eq, a, b, q):
    """A matrix product whose operands and result pass through ``q``, as
    the system holds both in its compute type."""
    return q(jnp.einsum(eq, q(a), q(b)))
