"""Plain reference of a Mamba-2 language model, in float32.

Each layer: RMSNorm -> Mamba-2 block -> residual; final RMSNorm; logits
against the embedding (tied) or an untied head; mean next-token
cross-entropy over the real vocabulary.  The block (arXiv:2405.21060):
one input projection to (z, x, B, C, dt); a causal depthwise convolution
of width ``ssm_conv_width`` over (x, B, C) with a bias, then SiLU;
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the selective scan
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``
with one group of (B, C) shared by every head; ``RMSNorm(y * silu(z))``;
an output projection.

The scan is the paper's minimal SSD listing ("ssd_minimal_discrete"):
quadratic within blocks of ``REF_BLOCK`` positions, with the stable
segment sum (a cumulative sum of masked copies, so no two large cumulative
sums are subtracted), and a recurrence over the blocks' states.  Its block
length differs from the configuration's ``ssm_chunk_size`` on purpose:
the chunking is not part of the function, so a reference that chunks
differently checks it.

Departures from the published model, shared with the system under test:
RMSNorm weights stored as ``w - 1``; the vocabulary padded to
``vocab_pad_multiple`` rows with the padded logits masked.

``init_params`` draws the starting weights from the seed with the same
``jax.random`` calls, in the same order, as the configuration's
initialiser.  Every value the system holds in its compute type passes
through ``q`` (see ``dense_lm``); the scan's own products in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from lm_common import head_matrix, mm, normal, padded_vocab, rms_norm  # noqa: F401

REF_BLOCK = 64


def dims(m):
    di = m["ssm_expand"] * m["d_model"]
    return di, m["ssm_state_size"], di // m["ssm_head_dim"]


def _layer_init(key, m):
    d, dt = m["d_model"], jnp.dtype(m["param_dtype"])
    di, ns, nh = dims(m)
    w = m["ssm_conv_width"]
    ks = jax.random.split(key, 5)
    return {
        "norm": jnp.zeros((d,), dt),
        "mamba": {
            "in_proj": normal(ks[0], (d, 2 * di + 2 * ns + nh), d, dt),
            "conv_w": normal(ks[1], (w, di + 2 * ns), w, dt),
            "conv_b": jnp.zeros((di + 2 * ns,), dt),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh,
                                          dtype=jnp.float32)).astype(dt),
            "D": jnp.ones((nh,), dt),
            "dt_bias": jnp.zeros((nh,), dt),
            "norm": jnp.zeros((di,), dt),
            "out_proj": normal(ks[4], (di, d), di, dt),
        },
    }


def init_params(key, m):
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


def segsum(x):
    """x (..., T) -> (..., T, T): the sum of x[j+1..i] on and below the
    diagonal, -inf above it."""
    t = x.shape[-1]
    rep = jnp.broadcast_to(x[..., None], x.shape + (t,))      # [..., i, j]
    rep = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), rep, 0.0)
    seg = jnp.cumsum(rep, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd(x, a, b, c, q):
    """x (b, s, h, p) already times dt; a (b, s, h) = dt * A; b, c (b, s, n).
    Returns y (b, s, h, p)."""
    bs, s, h, p = x.shape
    n = b.shape[-1]
    blk = REF_BLOCK
    nc = s // blk
    x = x.reshape(bs, nc, blk, h, p)
    b = b.reshape(bs, nc, blk, n)
    c = c.reshape(bs, nc, blk, n)
    a = jnp.moveaxis(a.reshape(bs, nc, blk, h), 3, 1)         # (b, h, c, l)
    a_cum = jnp.cumsum(a, axis=-1)
    decay = jnp.exp(segsum(a))                                # (b,h,c,l,l)
    cb = jnp.einsum("bcln,bcsn->bcls", q(c), q(b))
    y_diag = jnp.einsum("bcls,bhcls,bcshp->bclhp", cb, decay, q(x))
    to_end = jnp.exp(a_cum[..., -1:] - a_cum)                 # (b,h,c,l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", q(b), to_end, q(x))
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(a_cum[..., -1],
                                         ((0, 0), (0, 0), (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", q(c), q(states),
                       jnp.exp(a_cum))
    return (y_diag + y_off).reshape(bs, s, h, p)


def layer(lp, x, m, q):
    bs, s, _ = x.shape
    di, ns, nh = dims(m)
    hp = m["ssm_head_dim"]
    mp = lp["mamba"]
    eps = m["norm_eps"]
    proj = mm("bsd,de->bse", q(rms_norm(x, lp["norm"], eps)), mp["in_proj"],
              q)
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * ns],
                  proj[..., 2 * di + 2 * ns:])
    w = mp["conv_w"]
    width = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = q(sum(xp[:, i:i + s] * w[i] for i in range(width)))
    conv = q(jax.nn.silu(conv + mp["conv_b"]))
    xs, b, c = conv[..., :di], conv[..., di:di + ns], conv[..., di + ns:]
    dt = jax.nn.softplus(dt + mp["dt_bias"])                  # (b, s, h)
    a = -jnp.exp(mp["A_log"])
    xh = xs.reshape(bs, s, nh, hp)
    y = q(ssd(xh * dt[..., None], dt * a, b, c, q))
    y = q(y + xh * mp["D"][:, None]).reshape(bs, s, di)
    y = q(rms_norm(q(y * q(jax.nn.silu(z))), mp["norm"], eps))
    return q(x + mm("bse,ed->bsd", y, mp["out_proj"], q))


def hidden(params, tokens, m, q):
    x = q(jnp.take(params["embed"]["embedding"], tokens, axis=0))
    body = jax.checkpoint(lambda h, lp: (layer(lp, h, m, q), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["final_norm"], m["norm_eps"])
