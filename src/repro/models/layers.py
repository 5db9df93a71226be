"""Shared neural-net layers: norms, rotary embeddings, gated MLPs.

Everything is pure-functional: ``init_*`` returns ``(params, logical_specs)``
where the spec tree mirrors the param tree with tuples of *logical* axis
names (mapped to mesh axes by ``repro.parallel.sharding``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, axes, cfg: ModelConfig, fan_in: Optional[int] = None):
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = fan_in ** -0.5
    w = jax.random.normal(key, shape, dtype=jnp.float32) * scale
    return w.astype(_dtype(cfg)), axes


def zeros_init(shape, axes, cfg: ModelConfig):
    return jnp.zeros(shape, dtype=_dtype(cfg)), axes


def ones_init(shape, axes, cfg: ModelConfig):
    return jnp.ones(shape, dtype=_dtype(cfg)), axes


def _is_pair(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 2 and hasattr(x[0], "dtype")
            and isinstance(x[1], tuple))


def split_tree(pairs):
    """Tree of (array, logical_axes) pairs -> (params tree, specs tree)."""
    params = jax.tree.map(lambda p: p[0], pairs, is_leaf=_is_pair)
    specs = jax.tree.map(lambda p: p[1], pairs, is_leaf=_is_pair)
    return params, specs


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def init_rms_norm(d: int, cfg: ModelConfig):
    # stored as (weight - 1) so zero-init == identity (gemma convention)
    return zeros_init((d,), ("embed",), cfg)


# ---------------------------------------------------------------------------
# rotary embeddings (default + M-RoPE)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float,
               mrope_sections: Tuple[int, ...] = (),
               interleaved: bool = False):
    """x: (B, S, H, D); positions: (B, S) or (3, B, S) for M-RoPE.

    The default rotates dimension i with i + D/2 (rotate-half);
    ``interleaved`` rotates the adjacent pairs (2i, 2i+1) and keeps them in
    place, as DeepSeek-V3's published code does (its rotate-half over the
    de-interleaved pairs gives the same scores)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, max(theta, 1.0))  # (d/2,)
    if mrope_sections and positions.ndim == 3:
        # M-RoPE: frequency bands are driven by (t, h, w) position streams.
        sec = jnp.concatenate([
            jnp.full((s,), i, dtype=jnp.int32)
            for i, s in enumerate(mrope_sections)
        ])  # (d/2,) stream selector per frequency band
        pos = positions.astype(jnp.float32)           # (3, B, S)
        angles_all = pos[..., None] * freqs           # (3, B, S, d/2)
        select = jax.nn.one_hot(sec, len(mrope_sections), dtype=jnp.float32)
        angles = jnp.einsum("kbsd,dk->bsd", angles_all, select)
    else:
        if positions.ndim == 3:
            positions = positions[0]
        angles = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, d/2)
    cos = jnp.cos(angles)[..., None, :]  # (B, S, 1, d/2)
    sin = jnp.sin(angles)[..., None, :]
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d: int):
    """Whisper-style fixed sinusoidal embeddings (S, d)."""
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / (half - 1))
    args = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d, f), ("embed", "ffn"), cfg),
        "w_in": dense_init(k2, (d, f), ("embed", "ffn"), cfg),
        "w_out": dense_init(k3, (f, d), ("ffn", "embed"), cfg, fan_in=f),
    }


def mlp(params, x, cfg: ModelConfig):
    act = jax.nn.gelu if cfg.activation == "gelu" else jax.nn.silu
    h = act(x @ params["w_gate"]) * (x @ params["w_in"])
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# embedding + distributed cross-entropy head
# ---------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig):
    v, d = cfg.padded_vocab, cfg.d_model
    out = {"embedding": dense_init(key, (v, d), ("vocab", "embed"), cfg, fan_in=d)}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        out["lm_head"] = dense_init(k2, (d, v), ("embed", "vocab"), cfg)
    return out


@jax.named_scope("embed")
def embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embedding"], tokens, axis=0)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype=x.dtype)
    return x


def logits_head(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return x @ params["embedding"].T
    return x @ params["lm_head"]


@jax.named_scope("head_loss")
def lm_loss(params, hidden, labels, cfg) -> jnp.ndarray:
    """Mean next-token CE from final hidden states.

    ``ce_impl="chunked"`` computes logits + CE over sequence blocks so the
    (tokens, vocab) logits tensor never fully materializes — the LM-head
    analog of flash attention (peak-memory + HBM-traffic optimization,
    EXPERIMENTS §Perf).  The block loop unrolls when ``scan_layers`` is off
    (the accurate-cost lowering convention).
    """
    if cfg.ce_impl != "chunked":
        logits = logits_head(params["embed"] if "embed" in params else params,
                             hidden, cfg)
        return jnp.mean(cross_entropy(logits, labels, cfg.vocab_size))

    b, s, d = hidden.shape
    blk = min(cfg.ce_block_tokens, s)
    assert s % blk == 0, (s, blk)
    nb = s // blk
    hs = jnp.moveaxis(hidden.reshape(b, nb, blk, d), 1, 0)   # (nb, b, blk, d)
    ls = jnp.moveaxis(labels.reshape(b, nb, blk), 1, 0)

    embed_params = params["embed"] if "embed" in params else params

    def body(carry, inp):
        h_b, l_b = inp
        logits = logits_head(embed_params, h_b, cfg)
        ce = cross_entropy(logits, l_b, cfg.vocab_size)
        return carry + jnp.sum(ce), None

    if cfg.scan_layers:
        total, _ = jax.lax.scan(body, jnp.float32(0), (hs, ls))
    else:
        total = jnp.float32(0)
        for i in range(nb):
            total, _ = body(total, (hs[i], ls[i]))
    return total / (b * s)


def cross_entropy(logits, labels, vocab_size: int):
    """Cross-entropy that stays correct when logits are vocab-sharded.

    Written with max/logsumexp so GSPMD lowers partial reductions + psum
    instead of all-gathering the (tokens, vocab) logits tensor.  Padded
    vocab entries are masked to a large negative before the reduction.
    """
    logits = logits.astype(jnp.float32)
    padded_v = logits.shape[-1]
    if padded_v != vocab_size:
        col = jnp.arange(padded_v)
        logits = jnp.where(col[None, None, :] < vocab_size, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - label_logit  # (B, S)
