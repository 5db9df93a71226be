"""Plain reference of a dense decoder-only LM (Qwen2 family), in float32.

Pre-norm blocks: RMSNorm -> grouped-query attention with q/k/v biases and
rotary positions (rotate-half, theta from the config) -> residual, RMSNorm
-> SwiGLU MLP -> residual; final RMSNorm; logits against the embedding
(tied) or an untied head; mean next-token cross-entropy over the real
vocabulary (padded columns masked).  Written from the published
description (arXiv:2407.10671) in straightforward ``jax.numpy``.

Departures from the published model, each because the system under test
departs the same way and the reference has to compute the same function:

- an RMSNorm weight is stored as ``w - 1`` (zero-initialised), so the
  norm multiplies by ``1 + w``;
- the vocabulary is padded to ``vocab_pad_multiple`` rows and the padded
  logits are masked out of the softmax.

``init_params`` draws the starting weights from the seed with the same
``jax.random`` calls, in the same order, as the configuration's
initialiser: normal draws scaled by ``fan_in ** -0.5``, stored in the
configuration's parameter type.  It takes nothing the system made.

Every value the system holds in its compute type (matrix products, their
operands, the residual stream, norms' outputs, the softmax) passes through
``q``: the identity for the float32 reference, a rounding to a lower
precision for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from lm_common import head_matrix, mm, normal, padded_vocab, rms_norm  # noqa: F401

NEG_INF = -1e30


def head_dim(m) -> int:
    return m["head_dim"] or m["d_model"] // m["num_heads"]


def _layer_init(key, m):
    d, f, h = m["d_model"], m["d_ff"], head_dim(m)
    nq, nkv = m["num_heads"], m["num_kv_heads"]
    dt = jnp.dtype(m["param_dtype"])
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    attn = {
        "wq": normal(ka[0], (d, nq, h), d, dt),
        "wk": normal(ka[1], (d, nkv, h), d, dt),
        "wv": normal(ka[2], (d, nkv, h), d, dt),
        "wo": normal(ka[3], (nq, h, d), nq * h, dt),
    }
    if m["qkv_bias"]:
        attn["bq"] = jnp.zeros((nq, h), dt)
        attn["bk"] = jnp.zeros((nkv, h), dt)
        attn["bv"] = jnp.zeros((nkv, h), dt)
    return {
        "attn_norm": jnp.zeros((d,), dt),
        "attn": attn,
        "mlp_norm": jnp.zeros((d,), dt),
        "mlp": {"w_gate": normal(km[0], (d, f), d, dt),
                "w_in": normal(km[1], (d, f), d, dt),
                "w_out": normal(km[2], (f, d), f, dt)},
    }


def init_params(key, m):
    """Starting weights from ``key``: layers stacked on a leading axis."""
    dt = jnp.dtype(m["param_dtype"])
    k_embed, k_layers, _ = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, m["num_layers"])
    layers = jax.vmap(lambda k: _layer_init(k, m))(layer_keys)
    v, d = padded_vocab(m), m["d_model"]
    embed = {"embedding": normal(k_embed, (v, d), d, dt)}
    if not m["tie_embeddings"]:
        embed["lm_head"] = normal(jax.random.fold_in(k_embed, 1), (d, v), d,
                                  dt)
    return {"embed": embed, "layers": layers,
            "final_norm": jnp.zeros((d,), dt)}


def rope(x, theta):
    """Rotate-half rotary embedding; x (b, s, heads, h)."""
    s, h = x.shape[1], x.shape[-1]
    half = h // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs   # (s, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(lp, x, m, q):
    """One block, float32: x (b, s, d) -> (b, s, d)."""
    b, s, _ = x.shape
    h, nq, nkv = head_dim(m), m["num_heads"], m["num_kv_heads"]
    eps = m["norm_eps"]
    a = lp["attn"]
    n = q(rms_norm(x, lp["attn_norm"], eps))
    qh = mm("bsd,dnh->bsnh", n, a["wq"], q)
    kh = mm("bsd,dnh->bsnh", n, a["wk"], q)
    vh = mm("bsd,dnh->bsnh", n, a["wv"], q)
    if m["qkv_bias"]:
        qh, kh, vh = q(qh + a["bq"]), q(kh + a["bk"]), q(vh + a["bv"])
    qh = q(rope(qh, m["rope_theta"]))
    kh = q(rope(kh, m["rope_theta"]))
    g = nq // nkv
    qg = qh.reshape(b, s, nkv, g, h)
    scores = mm("bqkgh,bskh->bkgqs", qg, kh, q) * h ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = mm("bkgqs,bskh->bqkgh", probs, vh, q).reshape(b, s, nq, h)
    x = q(x + mm("bsnh,nhd->bsd", out, a["wo"], q))
    n = q(rms_norm(x, lp["mlp_norm"], eps))
    mp = lp["mlp"]
    hid = q(jax.nn.silu(mm("bsd,df->bsf", n, mp["w_gate"], q))
            * mm("bsd,df->bsf", n, mp["w_in"], q))
    return q(x + mm("bsf,fd->bsd", hid, mp["w_out"], q))


def hidden(params, tokens, m, q):
    x = q(jnp.take(params["embed"]["embedding"], tokens, axis=0))
    body = jax.checkpoint(lambda c, lp: (layer(lp, c, m, q), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["final_norm"], m["norm_eps"])

