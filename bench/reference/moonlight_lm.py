"""Plain reference of a DeepSeek-V3-block LM (Moonlight-16B-A3B), in
float32.

Written from the published description (arXiv:2502.16982, the model's
config.json, DeepSeek-V2 arXiv:2405.04434 for latent attention and
DeepSeek-V3 arXiv:2412.19437 for the routing and its balance loss) in
straightforward ``jax.numpy``:

- pre-norm blocks: RMSNorm -> attention -> residual, RMSNorm -> FFN ->
  residual; final RMSNorm; an untied head; mean next-token cross-entropy;
- attention is multi-head latent attention with no query LoRA: q = x W_q
  per head (nope and rope parts); [c, k_rope] = x W_kv_a, the latent c
  RMS-normed; [k_nope, v] = c W_kv_b per head; k_rope is one head's,
  shared by all; RoPE (theta from the config) on the rope parts, rotating
  adjacent pairs as DeepSeek-V3's code does; scores over the concatenated
  nope and rope parts, scaled by (nope + rope) ** -0.5, causal; values of
  their own width; computed a block of queries at a time, each block's
  softmax over every key exact;
- the leading ``first_dense_layers`` have a SwiGLU MLP of width
  ``dense_d_ff``; the others an MoE FFN: float32 sigmoid scores of the
  router; the top-k of score plus the per-expert selection bias; the
  chosen scores over their sum (plus 1e-20) times ``routed_scaling_factor``
  as weights; each held expert (a SwiGLU of width ``d_ff``) computed on
  every token and its output weighted by the token's weight for it (zero
  where the token did not choose it); plus the shared experts, one SwiGLU
  of width ``num_shared_experts * d_ff``, on every token;
- ``extra_loss``: DeepSeek-V3's sequence-wise balance loss, per row
  alpha * sum_i f_i P_i (f_i: the row's top-k picks of expert i times E /
  (k S); P_i: the row's mean of each token's scores over their sum),
  summed over the MoE layers.

Departures from the published model, each because the system under test
departs the same way and the reference has to compute the same function:

- one chip's share (``experts_held`` of ``num_experts``, the first ones):
  the router scores all experts, and only the held experts' part of the
  routed result is added; the vocabulary is the configuration's slice;
- an RMSNorm weight is stored as ``w - 1`` (zero-initialised), so the
  norm multiplies by ``1 + w``; the vocabulary is padded to
  ``vocab_pad_multiple`` rows and the padded logits are masked out;
- the selection bias is a stored leaf that starts at zero; the gradient
  does not reach it (it moves no weight) and nothing else updates it.

``init_params`` draws the starting weights from the seed with the same
``jax.random`` calls, in the same order, as the configuration's
initialiser: normal draws scaled by ``fan_in ** -0.5``, stored in the
configuration's parameter type.  It takes nothing the system made.

Every value the system holds in its compute type (matrix products, their
operands, the residual stream, norms' outputs, the softmax) passes through
``q``: the identity for the float32 reference, a rounding to a lower
precision for the control.  The router's logits and scores the system
holds in float32, and so they skip ``q``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from lm_common import head_matrix, mm, normal, padded_vocab, rms_norm  # noqa: F401

NEG_INF = -1e30
QUERY_BLOCK = 512


def _held(m) -> int:
    return m["experts_held"] or m["num_experts"]


def _swiglu_init(key, d, f, dt):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": normal(k1, (d, f), d, dt),
            "w_in": normal(k2, (d, f), d, dt),
            "w_out": normal(k3, (f, d), f, dt)}


def _layer_init(key, m, dense: bool):
    d, nq, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], \
        m["v_head_dim"]
    f, e, held = m["d_ff"], m["num_experts"], _held(m)
    dt = jnp.dtype(m["param_dtype"])
    k_attn, k_ffn = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    layer = {
        "attn_norm": jnp.zeros((d,), dt),
        "attn": {
            "wq": normal(ka[0], (d, nq, dn + dr), d, dt),
            "wkv_a": normal(ka[1], (d, r + dr), d, dt),
            "kv_norm": jnp.zeros((r,), dt),
            "wkv_b": normal(ka[2], (r, nq, dn + dv), r, dt),
            "wo": normal(ka[3], (nq, dv, d), nq * dv, dt),
        },
        "mlp_norm": jnp.zeros((d,), dt),
    }
    if dense:
        layer["mlp"] = _swiglu_init(k_ffn, d, m["dense_d_ff"], dt)
        return layer
    km = jax.random.split(k_ffn, 4)
    layer["moe"] = {
        "router": normal(km[0], (d, e), d, dt),
        "router_bias": jnp.zeros((e,), dt),
        "w_gate": normal(km[1], (held, d, f), d, dt),
        "w_in": normal(km[2], (held, d, f), d, dt),
        "w_out": normal(km[3], (held, f, d), f, dt),
        "shared": _swiglu_init(jax.random.fold_in(k_ffn, 1), d,
                               m["num_shared_experts"] * f, dt),
    }
    return layer


def init_params(key, m):
    """Starting weights from ``key``: each stack's layers on a leading
    axis, the leading dense layers in ``dense_layers``."""
    dt = jnp.dtype(m["param_dtype"])
    k_embed, k_layers, k_dense = jax.random.split(key, 3)
    n_dense = m["first_dense_layers"]
    stack = lambda k, n, dense: jax.vmap(
        lambda kk: _layer_init(kk, m, dense))(jax.random.split(k, n))
    v, d = padded_vocab(m), m["d_model"]
    params = {
        "embed": {"embedding": normal(k_embed, (v, d), d, dt),
                  "lm_head": normal(jax.random.fold_in(k_embed, 1), (d, v),
                                    d, dt)},
        "layers": stack(k_layers, m["num_layers"] - n_dense, False),
        "final_norm": jnp.zeros((d,), dt),
    }
    if n_dense:
        params["dense_layers"] = stack(k_dense, n_dense, True)
    return params


def rope(x, theta):
    """Rotary embedding over adjacent pairs (2i, 2i+1), kept in place;
    x (b, s, heads, h)."""
    s, h = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs   # (s, h/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def attention(a, n, m, q):
    """Latent attention of one block: n (b, s, d) normed -> (b, s, d)."""
    b, s, _ = n.shape
    nq, r, dn = m["num_heads"], m["kv_lora_rank"], m["qk_nope_head_dim"]
    qh = mm("bsd,dnh->bsnh", n, a["wq"], q)
    qh = jnp.concatenate([qh[..., :dn], q(rope(qh[..., dn:],
                                                m["rope_theta"]))], -1)
    kv_a = mm("bsd,dr->bsr", n, a["wkv_a"], q)
    latent = q(rms_norm(kv_a[..., :r], a["kv_norm"], m["norm_eps"]))
    k_rope = q(rope(kv_a[..., None, r:], m["rope_theta"]))     # one head
    kv = mm("bsr,rnh->bsnh", latent, a["wkv_b"], q)
    kh = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, nq, k_rope.shape[-1]))],
        -1)
    vh = kv[..., dn:]
    scale = qh.shape[-1] ** -0.5
    blk = min(QUERY_BLOCK, s)
    nb = s // blk

    def block(args):
        i, qb = args                                   # qb (b, blk, H, h)
        scores = mm("bqnh,bknh->bnqk", qb, kh, q) * scale
        causal = jnp.arange(s)[None, :] <= (i * blk + jnp.arange(blk))[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, NEG_INF), axis=-1)
        return mm("bnqk,bknh->bqnh", probs, vh, q)

    qb = jnp.moveaxis(qh.reshape(b, nb, blk, nq, -1), 1, 0)
    out = jax.lax.map(jax.checkpoint(block), (jnp.arange(nb), qb))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, nq, -1)
    return mm("bsnh,nhd->bsd", out, a["wo"], q)


def swiglu(p, n, q):
    hid = q(jax.nn.silu(mm("bsd,df->bsf", n, p["w_gate"], q))
            * mm("bsd,df->bsf", n, p["w_in"], q))
    return mm("bsf,fd->bsd", hid, p["w_out"], q)


def route(p, n, m):
    """(weights (b,s,k), ids (b,s,k), scores (b,s,E)), float32."""
    logits = jnp.einsum("bsd,de->bse", n, p["router"])
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + p["router_bias"], m["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * m["routed_scaling_factor"], ids, scores


def balance_loss(ids, scores, m):
    """DeepSeek-V3's sequence-wise balance loss of each row: (b,)."""
    _, s, e = scores.shape
    k = m["num_experts_per_tok"]
    picks = jnp.sum(jax.nn.one_hot(ids, e), axis=(1, 2))          # (b, E)
    f = picks * e / (k * s)
    p = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    return m["router_aux_loss_coef"] * jnp.sum(f * p, axis=-1)


def moe(p, n, m, q):
    """The held experts' part of the routed result plus the shared
    experts: n (b, s, d) normed -> ((b, s, d), balance loss (b,))."""
    w, ids, scores = route(p, n, m)
    held = _held(m)
    # each token's weight for each held expert, zero where not chosen
    gate = jnp.sum(jax.nn.one_hot(ids, held) * w[..., None], axis=2)
    hid = q(jax.nn.silu(mm("bsd,edf->bsef", n, p["w_gate"], q))
            * mm("bsd,edf->bsef", n, p["w_in"], q))
    y = mm("bsef,efd->bsed", hid, p["w_out"], q)
    routed = q(jnp.sum(y * gate[..., None], axis=2))
    return q(routed + swiglu(p["shared"], n, q)), balance_loss(ids, scores, m)


def layer(lp, x, m, q):
    """One block, float32: x (b, s, d) -> (x, the row's balance loss)."""
    eps = m["norm_eps"]
    x = q(x + attention(lp["attn"], q(rms_norm(x, lp["attn_norm"], eps)),
                        m, q))
    n = q(rms_norm(x, lp["mlp_norm"], eps))
    if "mlp" in lp:
        return q(x + swiglu(lp["mlp"], n, q)), jnp.zeros(x.shape[0])
    f, aux = moe(lp["moe"], n, m, q)
    return q(x + f), aux


def trunk(params, tokens, m, q):
    """(the last block's output, the balance loss summed over layers).

    The layers run one after another in Python, each recomputed for its
    gradient: a layer then reads its slice of the stacked weights, and its
    gradient goes into theirs, without a float32 copy of every layer's
    weights or gradients held at once (a scan would hold both)."""
    x = q(jnp.take(params["embed"]["embedding"], tokens, axis=0))
    body = jax.checkpoint(lambda lp, c: layer(lp, c, m, q))
    aux = jnp.zeros(tokens.shape[0])
    for name in ("dense_layers", "layers"):
        stack = params.get(name)
        for i in range(len(jax.tree.leaves(stack)[0]) if stack else 0):
            x, a = body(jax.tree.map(lambda p: p[i], stack), x)
            aux = aux + a
    return x, aux


# A loss traces ``hidden`` and ``extra_loss`` on the same params and
# tokens: the second call takes the first one's trunk, so that its
# gradient is traced once (a second trunk's gradient takes another 2 GB
# at the benchmark's size).  Held by identity, with the inputs, so a later
# trace never takes an earlier one's values.
_LAST_TRUNK: list = []


def _shared_trunk(params, tokens, m, q):
    key = (params, tokens, m, q)
    if _LAST_TRUNK and all(a is b for a, b in zip(_LAST_TRUNK[0], key)):
        return _LAST_TRUNK[1]
    out = trunk(params, tokens, m, q)
    _LAST_TRUNK[:] = [key, out]
    return out


def hidden(params, tokens, m, q):
    x, _ = _shared_trunk(params, tokens, m, q)
    return rms_norm(x, params["final_norm"], m["norm_eps"])


def extra_loss(params, tokens, m, q):
    return _shared_trunk(params, tokens, m, q)[1]
