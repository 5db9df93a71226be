"""Pure-jnp oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        sliding_window: int = 0, scale: float | None = None):
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D); GQA via head grouping.
    Returns (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, hkv, g, s, d)
    scores = jnp.einsum("bkgqd,bksd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qi = jnp.arange(s)[:, None]
    kj = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), dtype=bool)
    if causal:
        mask &= kj <= qi
    if sliding_window:
        mask &= kj > qi - sliding_window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, s, d).astype(q.dtype)


def ssd_chunk_ref(x, dt, A, B, C):
    """Chunk-local SSD terms (the Pallas kernel's contract), head-major.

    x: (b, nc, h, l, p); dt: (b, nc, h, l); A: (h,); B, C: (b, nc, l, n)
    Returns (y_diag (b,nc,h,l,p), states (b,nc,h,p,n), in_decay (b,nc,h,l));
    the chunk decay is ``in_decay[..., -1]``.
    """
    f32 = jnp.float32
    xc, dtc = x.astype(f32), dt.astype(f32)
    Bc, Cc = B.astype(f32), C.astype(f32)
    dA = dtc * A.astype(f32)[:, None]              # (b,nc,h,l)
    dA_cum = jnp.cumsum(dA, axis=-1)

    L = dA_cum[..., :, None] - dA_cum[..., None, :]
    l_idx = jnp.arange(x.shape[3])
    tri = l_idx[:, None] >= l_idx[None, :]
    # mask before exp: above the diagonal L is positive and can overflow,
    # and exp(inf) * 0 would turn the gradient into NaN
    L = jnp.exp(jnp.where(tri, L, -jnp.inf))       # (b,nc,h,l,l)

    scores = jnp.einsum("bcln,bcmn->bclm", Cc, Bc)
    gated = L * scores[:, :, None, :, :]           # (b,nc,h,l,m)
    y_diag = jnp.einsum("bchlm,bchm,bchmp->bchlp", gated, dtc, xc)

    decay_to_end = jnp.exp(dA_cum[..., -1:] - dA_cum)
    states = jnp.einsum("bcln,bchl,bchl,bchlp->bchpn", Bc, decay_to_end,
                        dtc, xc)
    in_decay = jnp.exp(dA_cum)
    return (y_diag.astype(x.dtype), states, in_decay)


def rmsnorm_ref(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * (1.0 + weight.astype(jnp.float32))).astype(dt)
