"""Multi-head attention: GQA/MQA, latent attention (MLA), sliding windows,
qk-norm, KV-cache decode.

``attention_impl="ref"`` is exact softmax attention.  Full-sequence causal
self-attention with no sliding window, at a length the kernels' blocks
divide, lowers on a TPU to the fused kernels of
``repro.kernels.fused_attention`` (forward and backward, the scores kept
in VMEM); every other call, and every other platform, materialises the
scores with einsums, which are the fused kernels' oracle in the tests.
``cfg.use_pallas`` selects the forward-only Pallas flash kernel
(``repro.kernels.flash_attention``) instead.

Latent attention (``cfg.kv_lora_rank > 0``, DeepSeek-V2/V3 with no query
LoRA) only changes how q, k and v are made: k and v come from one normed
latent per token, and all heads share one rotary key.  Its query/key head
(nope + rope) is wider than its value head; every path takes that.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import fused_attention
from repro.models.config import ModelConfig
from repro.models import layers

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig):
    if cfg.is_mla:
        return init_mla(key, cfg)
    d, h = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, nq, h), ("embed", "q_heads", "head_dim"), cfg, fan_in=d),
        "wk": layers.dense_init(ks[1], (d, nkv, h), ("embed", "kv_heads", "head_dim"), cfg, fan_in=d),
        "wv": layers.dense_init(ks[2], (d, nkv, h), ("embed", "kv_heads", "head_dim"), cfg, fan_in=d),
        "wo": layers.dense_init(ks[3], (nq, h, d), ("q_heads", "head_dim", "embed"), cfg, fan_in=nq * h),
    }
    if cfg.qkv_bias:
        p["bq"] = layers.zeros_init((nq, h), ("q_heads", "head_dim"), cfg)
        p["bk"] = layers.zeros_init((nkv, h), ("kv_heads", "head_dim"), cfg)
        p["bv"] = layers.zeros_init((nkv, h), ("kv_heads", "head_dim"), cfg)
    if cfg.qk_norm:
        p["q_norm"] = layers.zeros_init((h,), ("head_dim",), cfg)
        p["k_norm"] = layers.zeros_init((h,), ("head_dim",), cfg)
    return p


def init_mla(key, cfg: ModelConfig):
    d, nq, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.resolved_v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": layers.dense_init(ks[0], (d, nq, dn + dr), ("embed", "q_heads", "head_dim"), cfg, fan_in=d),
        "wkv_a": layers.dense_init(ks[1], (d, r + dr), ("embed", "kv_lora"), cfg, fan_in=d),
        "kv_norm": layers.zeros_init((r,), ("kv_lora",), cfg),
        "wkv_b": layers.dense_init(ks[2], (r, nq, dn + dv), ("kv_lora", "q_heads", "head_dim"), cfg, fan_in=r),
        "wo": layers.dense_init(ks[3], (nq, dv, d), ("q_heads", "head_dim", "embed"), cfg, fan_in=nq * dv),
    }


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------


def _rope(x, positions, cfg: ModelConfig):
    return layers.apply_rope(x, positions, cfg.rope_theta, cfg.mrope_sections,
                             interleaved=cfg.rope_type == "interleaved")


def _project_qkv(params, x, cfg: ModelConfig, positions):
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = layers.rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, params["k_norm"], cfg.norm_eps)
    if cfg.rope_theta > 0:
        q = _rope(q, positions, cfg)
        k = _rope(k, positions, cfg)
    return q, k, v


@jax.named_scope("mla")
def _project_mla(params, x, cfg: ModelConfig, positions):
    """q (B,S,H,nope+rope), k (B,S,H,nope+rope), v (B,S,H,v) from the
    query projection and the normed latent; the rope part of k is one
    head's, repeated."""
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], positions, cfg)],
                        axis=-1)
    kv_a = x @ params["wkv_a"]
    latent = layers.rms_norm(kv_a[..., :r], params["kv_norm"], cfg.norm_eps)
    k_rope = _rope(kv_a[..., None, r:], positions, cfg)      # (B,S,1,rope)
    kv = jnp.einsum("bsr,rnh->bsnh", latent, params["wkv_b"])
    k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(k_rope, kv.shape[:3] + k_rope.shape[-1:])],
        axis=-1)
    return q, k, kv[..., dn:]


def _project(params, x, cfg: ModelConfig, positions):
    return (_project_mla if cfg.is_mla else _project_qkv)(params, x, cfg,
                                                          positions)


def _group_query(q, num_kv: int):
    """(B,S,nq,h) -> (B,S,nkv,group,h)"""
    b, s, nq, h = q.shape
    return q.reshape(b, s, num_kv, nq // num_kv, h)


# ---------------------------------------------------------------------------
# full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------


def _attention_chunked(q, k, v, cfg: ModelConfig, causal: bool,
                       block: int = 512) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure jnp: scan over KV blocks
    with running (m, l, acc) so the (S, S) score matrix never materializes.

    This is the *lowering stand-in* for the Pallas TPU kernel on dry runs
    (pallas_call cannot compile for the CPU backend): same O(S*d) memory
    profile, same flops — so the roofline memory term reflects the fused
    TPU program instead of an unfused S^2 intermediate.
    """
    b, s, nq, hd = q.shape
    kv, dv = k.shape[2], v.shape[-1]
    g = nq // kv
    scale = hd ** -0.5
    blk = min(block, s)
    while s % blk:        # largest divisor of s <= block (e.g. whisper 1500)
        blk -= 1
    nb = s // blk
    qg = q.reshape(b, s, kv, g, hd).astype(jnp.float32)
    kb = k.reshape(b, nb, blk, kv, hd).astype(jnp.float32)
    vb = v.reshape(b, nb, blk, kv, dv).astype(jnp.float32)
    q_pos = jnp.arange(s)

    def step(carry, inp):
        m_run, l_run, acc = carry
        j, k_j, v_j = inp
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_j) * scale  # (b,kv,g,S,blk)
        k_pos = j * blk + jnp.arange(blk)
        mask = jnp.ones((s, blk), dtype=bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if cfg.sliding_window:
            mask &= k_pos[None, :] > q_pos[:, None] - cfg.sliding_window
        scores = jnp.where(mask, scores, NEG_INF)
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m_run, m_cur)
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p, v_j)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, s), jnp.float32)
    a0 = jnp.zeros((b, kv, g, s, dv), jnp.float32)
    (m_f, l_f, acc_f), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(nb), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
    out = acc_f / jnp.maximum(l_f, 1e-30)[..., None]
    out = jnp.moveaxis(out, -2, 1).reshape(b, s, nq, dv)
    return out.astype(q.dtype)


def _attention_materialized(q, k, v, cfg: ModelConfig,
                            causal: bool) -> jnp.ndarray:
    """Exact softmax attention over the whole (S, S) score matrix."""
    b, s, nq, h = q.shape
    qg = _group_query(q, cfg.num_kv_heads)          # (b,s,kv,g,h)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    scores = scores * (h ** -0.5)
    qi = jnp.arange(s)[:, None]
    kj = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), dtype=bool)
    if causal:
        mask &= kj <= qi
    if cfg.sliding_window:
        mask &= kj > qi - cfg.sliding_window
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(b, s, nq, v.shape[-1])


def _attention_fused(q, k, v):
    with jax.named_scope("flash"):
        return fused_attention.causal_attention(q, k, v)


def attention(params, x, cfg: ModelConfig, positions,
              causal: bool = True) -> jnp.ndarray:
    """Attention for training/prefill; (B, S, d) -> (B, S, d)."""
    s = x.shape[1]
    q, k, v = _project(params, x, cfg, positions)

    if cfg.use_pallas:
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.flash_attention(
            q, k, v, causal=causal, sliding_window=cfg.sliding_window)
    elif cfg.attention_impl == "chunked":
        out = _attention_chunked(q, k, v, cfg, causal)
    elif causal and not cfg.sliding_window and fused_attention.fits(
            s, q.shape[-1]):
        # the platform the program lowers for picks the branch: the other
        # one is traced but never lowered
        out = jax.lax.platform_dependent(
            q, k, v, tpu=_attention_fused,
            default=lambda q, k, v: _attention_materialized(q, k, v, cfg,
                                                            True))
    else:
        out = _attention_materialized(q, k, v, cfg, causal)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, num_layers: int,
                  dtype=jnp.bfloat16) -> Tuple[dict, dict]:
    """Cache layout (L, B, S, kv, h): seq dim shardable over the model axis
    (context-parallel decode) when kv %% model_axis != 0."""
    seq = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    shape = (num_layers, batch, seq, cfg.num_kv_heads)
    specs = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    # latent attention caches its k and v expanded, per head
    cache = {
        "k": jnp.zeros(shape + (cfg.qk_head_dim,), dtype=dtype),
        "v": jnp.zeros(shape + (cfg.resolved_v_head_dim,), dtype=dtype),
    }
    return cache, {"k": specs, "v": specs}


def decode_attention(params, x, cfg: ModelConfig, layer_cache, pos):
    """One-token decode.  x: (B, 1, d); layer_cache k/v: (B, S, kv, h);
    pos: (B,) absolute position of the new token.  Returns (out, new_cache).

    With a sliding window the cache is a ring buffer of size ``window``.
    """
    b, _, _ = x.shape
    k_cache, v_cache = layer_cache["k"], layer_cache["v"]
    s_cache = k_cache.shape[1]

    q, k_new, v_new = _project(params, x, cfg, positions=pos[:, None])
    h = q.shape[-1]

    slot = (pos % s_cache) if cfg.sliding_window else pos  # (B,)
    b_idx = jnp.arange(b)
    k_cache = k_cache.at[b_idx, slot].set(k_new[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[b_idx, slot].set(v_new[:, 0].astype(v_cache.dtype))

    qg = _group_query(q, cfg.num_kv_heads)[:, 0]          # (b,kv,g,h)
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache).astype(jnp.float32)
    scores = scores * (h ** -0.5)

    # valid = cache slots holding tokens <= pos (and within the window)
    idx = jnp.arange(s_cache)[None, :]                    # (1, S)
    if cfg.sliding_window:
        age = pos[:, None] - (idx + (pos[:, None] // s_cache) * s_cache)
        age = jnp.where(age < 0, age + s_cache, age)      # ring-buffer age
        valid = age < jnp.minimum(pos[:, None] + 1, s_cache)
    else:
        valid = idx <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v_cache)
    out = out.reshape(b, 1, cfg.num_heads, v_cache.shape[-1])
    proj = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return proj, {"k": k_cache, "v": v_cache}
