"""Mixture-of-experts FFN: routing, expert dispatch, shared experts.

Routing (``cfg.router``):

- ``softmax`` (Mixtral, Qwen3-MoE): softmax over the router's logits,
  top-k renormalised, Switch-style load-balancing loss;
- ``sigmoid`` (DeepSeek-V3, Moonlight): float32 sigmoid scores, top-k of
  score plus a stored per-expert selection bias, the chosen (unbiased)
  scores renormalised and scaled by ``routed_scaling_factor``, and the
  sequence-wise balance loss.

Dispatch:

- capacity (``moe_capacity_factor > 0``, MegaBlocks/MaxText-style): instead
  of the GShard one-hot (T, E, C) dispatch tensor, tokens are ranked
  within their expert via a stable argsort + first-occurrence subtraction,
  then scattered into an (E*C, d) buffer.  Under pjit this lowers to
  all-to-all-style collectives on the expert-parallel axis.  Tokens beyond
  capacity are dropped (contribute zero), standard for capacity-based MoE.
- dropless (``moe_capacity_factor == 0``): the (token, expert) pairs are
  sorted by expert and the held experts' rows go through grouped products
  (``repro.kernels.grouped_matmul``), so every token routed to a held
  expert is computed.  Each held pair takes a row of its own, and the
  row buffers are sized by the held pairs, not by all T k pairs: M rows,
  twice the held experts' even share in whole kernel row tiles (T k / 4
  for a chip holding 8 of 64 experts at top-6).  A step whose held pairs
  outnumber M runs the same dispatch with the full-size buffer instead,
  T min(k, held) rows, which no step's held pairs exceed, so no pair is
  ever dropped; the layer's ``dispatch_full`` says which ran.  Where M
  would reach that size (a chip holding every expert) there is one path,
  the full-size one.  The choice is made apart in the forward and in the
  backward, so that neither path's intermediates are kept for the other.

Expert parallelism: a layer holds the routed experts ``[lo, lo +
experts_held)`` of the router's ``num_experts``.  It routes over all of
them and returns the part of the result its own experts give (plus the
shared experts, which every chip computes alike); the other chips' parts
are theirs to add.  On one chip nothing is exchanged.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.grouped_matmul import TILING, grouped_matmul
from repro.models.config import ModelConfig
from repro.models import layers


def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    held = cfg.resolved_experts_held
    ks = jax.random.split(key, 4)
    p = {
        "router": layers.dense_init(ks[0], (d, e), ("embed", "experts_r"), cfg),
        "w_gate": layers.dense_init(ks[1], (held, d, f), ("experts", "embed", "ffn"), cfg, fan_in=d),
        "w_in": layers.dense_init(ks[2], (held, d, f), ("experts", "embed", "ffn"), cfg, fan_in=d),
        "w_out": layers.dense_init(ks[3], (held, f, d), ("experts", "ffn", "embed"), cfg, fan_in=f),
    }
    if cfg.router == "sigmoid":
        p["router_bias"] = layers.zeros_init((e,), ("experts_r",), cfg)
    if cfg.num_shared_experts:
        p["shared"] = layers.init_mlp(jax.random.fold_in(key, 1), cfg,
                                      d_ff=cfg.num_shared_experts * f)
    return p


def _capacity(num_tokens: int, cfg: ModelConfig) -> int:
    c = int(cfg.moe_capacity_factor * num_tokens * cfg.num_experts_per_tok
            / cfg.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def route(params, x_flat, cfg: ModelConfig):
    """Softmax routing.  x_flat (T, d) -> (weights (T,k), ids (T,k),
    aux_loss)."""
    logits = (x_flat @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # Switch-style load-balancing auxiliary loss
    e = cfg.num_experts
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * mean_prob) * e * cfg.router_aux_loss_coef
    return weights.astype(x_flat.dtype), ids, aux


def route_sigmoid(params, x, cfg: ModelConfig):
    """DeepSeek-V3 routing.  x (B, S, d) -> (weights (B,S,k) float32,
    ids (B,S,k), aux_loss).

    The balance loss is the sequence-wise one of arXiv:2412.19437 (eqs.
    17-20): per row, alpha * sum_i f_i P_i, with f_i the row's share of
    top-k picks of expert i times E / k and P_i the row's mean of the
    scores normalised over the experts; the mean over rows is returned."""
    b, s, _ = x.shape
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    # the bias steers which experts are chosen, not their weights
    _, ids = jax.lax.top_k(
        scores + params["router_bias"].astype(jnp.float32), k)
    # the chosen scores, by one-hot and not by a gather: the gather's
    # gradient is a scatter, slow on a TPU
    chosen = jax.nn.one_hot(ids, e, dtype=jnp.float32)           # (B,S,k,E)
    weights = jnp.sum(chosen * scores[:, :, None, :], axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * cfg.routed_scaling_factor
    picks = jnp.sum(chosen, axis=(1, 2))
    share = picks * e / (k * s)                                   # (B, E)
    mean_score = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True),
                          axis=1)                                 # (B, E)
    aux = cfg.router_aux_loss_coef * jnp.mean(
        jnp.sum(share * mean_score, axis=-1))
    return weights, ids, aux


def expert_tokens(ids, num_experts: int):
    """The tokens routed to each expert: (..., k) ids -> (E,) int32."""
    return jnp.sum(jax.nn.one_hot(ids.reshape(-1), num_experts,
                                  dtype=jnp.int32), axis=0)


def moe_ffn(params, x, cfg: ModelConfig, lo: int = 0
            ) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss, counters): the counters
    are ``expert_tokens`` (E,), and for the dropless dispatch
    ``dispatch_full``, int32 1 where the full-size dispatch ran.

    ``lo`` is the first routed expert this layer holds (dropless dispatch).
    With ``moe_dispatch_groups = G > 0`` the token stream of the capacity
    dispatch splits into G independent dispatch groups (leading dim aligned
    with the data-parallel batch shards): capacity and the scatter/gather
    stay LOCAL to each shard, so the (E, C, d) buffers never cross the data
    axis — the GSPMD lowering loses its dispatch all-reduces (EXPERIMENTS
    §Perf, mixtral iteration).  G=0 keeps one global dispatch.
    """
    b, s, d = x.shape
    k = cfg.num_experts_per_tok
    with jax.named_scope("router"):
        if cfg.router == "sigmoid":
            weights, ids, aux = route_sigmoid(params, x, cfg)
        else:
            weights, ids, aux = route(params, x.reshape(b * s, d), cfg)
        weights, ids = weights.reshape(b * s, k), ids.reshape(b * s, k)
        counters = {"expert_tokens": expert_tokens(ids, cfg.num_experts)}
    with jax.named_scope("experts"):
        x_flat = x.reshape(b * s, d)
        g = cfg.moe_dispatch_groups
        if cfg.moe_capacity_factor <= 0:
            out, counters["dispatch_full"] = _dropless(
                params, x_flat, weights, ids, cfg, lo)
        elif g and b % g == 0:
            from repro.parallel.context import constrain
            split = lambda a: a.reshape((g, (b // g) * s) + a.shape[1:])
            xg = constrain(split(x_flat), ("batch", None, None))
            out = jax.vmap(lambda xx, ww, ii: _capacity_dispatch(
                params, xx, ww, ii, cfg))(xg, split(weights), split(ids))
            out = constrain(out, ("batch", None, None))
        else:
            out = _capacity_dispatch(params, x_flat, weights, ids, cfg)
        out = out.reshape(b, s, d)
    if "shared" in params:
        with jax.named_scope("shared_experts"):
            out = out + layers.mlp(params["shared"], x, cfg)
    return out, aux, counters


def _expert_act(cfg: ModelConfig):
    return jax.nn.gelu if cfg.activation == "gelu" else jax.nn.silu


def _dropless(params, x_flat, weights, ids, cfg: ModelConfig, lo: int):
    """The held experts' part of the result for every token routed to
    them, and whether the full-size dispatch ran: (T, d) -> ((T, d),
    int32)."""
    t = x_flat.shape[0]
    k = ids.shape[-1]
    held = cfg.resolved_experts_held
    local = ids.reshape(t * k) - lo
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)        # the others sort last
    order = jnp.argsort(group, stable=True)     # sorted position -> pair
    back = jnp.argsort(order)                   # pair -> sorted position
    sizes = jnp.sum(group[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)
    args = (x_flat, weights, (params["w_gate"], params["w_in"],
                              params["w_out"]), order, back, sizes, mine)
    act = _expert_act(cfg)
    bound = t * min(k, held)                    # no step's held pairs exceed
    m = _compact_rows(t, k, held, cfg.num_experts)
    if m >= bound:
        return _dispatch(act, bound, *args), jnp.int32(1)
    full = jnp.sum(sizes) > m
    return _either(act, m, bound, full, *args), full.astype(jnp.int32)


def _compact_rows(t: int, k: int, held: int, num_experts: int) -> int:
    """Twice the held experts' even share of the t k pairs, in whole row
    tiles of the grouped kernel."""
    tile = TILING[0]
    return -(-2 * t * k * held // (num_experts * tile)) * tile


def _dispatch(act, m, x_flat, weights, experts, order, back, sizes, mine):
    """The held experts' part of the result through (m, d) row buffers:
    the first m pairs in sorted order, a row each.  The held pairs sort
    first, so it is exact where they number at most m."""
    t, k = weights.shape
    w_gate, w_in, w_out = experts
    pair = order[:m]                                # row -> pair
    slot = jnp.where(mine, back, m).reshape(t, k)   # pair -> row; m: none
    rows = _gather_rows(x_flat, pair, slot)
    # the kernels leave the rows past the held groups out, and zero them
    h = act(grouped_matmul(rows, w_gate, sizes))
    h = h * grouped_matmul(rows, w_in, sizes)
    y = grouped_matmul(h, w_out, sizes)
    return _combine(y, weights, pair, slot)


# The compact dispatch or, where the held pairs outnumber its rows
# (``full``), the full-size one.  The choice is made apart in the forward
# and in the backward, which keep only the inputs: differentiating one
# ``lax.cond`` keeps the residuals of both branches, at Moonlight's widths
# 2.6 times the full-size dispatch's temporaries.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _either(act, m, bound, full, *args):
    return jax.lax.cond(full, lambda: _dispatch(act, bound, *args),
                        lambda: _dispatch(act, m, *args))


def _either_fwd(act, m, bound, full, *args):
    return _either(act, m, bound, full, *args), (full, args)


def _either_bwd(act, m, bound, res, g):
    full, (x_flat, weights, experts, *rest) = res

    def grads(rows):
        _, vjp = jax.vjp(lambda *a: _dispatch(act, rows, *a, *rest),
                         x_flat, weights, experts)
        return vjp(g)

    dx, dw, de = jax.lax.cond(full, lambda: grads(bound), lambda: grads(m))
    return (None, dx, dw, de) + (None,) * len(rest)


_either.defvjp(_either_fwd, _either_bwd)


# The row gather and the combine, each the other's transpose, so that no
# gradient is a scatter-add, which on a TPU took most of the dispatch's
# time: every row belongs to one (token, pick) pair, ``pair`` maps rows to
# pairs and ``slot`` (T, k) pairs to rows, past the rows for a pair that
# has none.

@jax.custom_vjp
def _gather_rows(x_flat, pair, slot):
    """The token row of each row's pair: x_flat[pair // k]."""
    return x_flat[pair // slot.shape[1]]


def _gather_rows_fwd(x_flat, pair, slot):
    return _gather_rows(x_flat, pair, slot), slot


def _gather_rows_bwd(slot, g):
    # each token's rows, summed
    rows = g.at[slot].get(mode="fill", fill_value=0)
    return jnp.sum(rows, axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def _combine(y, weights, pair, slot):
    """Each token's rows weighted by its routing weights and summed, in
    float32: (m, d), (T, k) -> (T, d)."""
    rows = y.at[slot].get(mode="fill", fill_value=0).astype(jnp.float32)
    out = jnp.sum(rows * weights.astype(jnp.float32)[..., None], axis=1)
    return out.astype(y.dtype)


def _combine_fwd(y, weights, pair, slot):
    return _combine(y, weights, pair, slot), (y, weights, pair, slot)


def _combine_bwd(res, g):
    y, weights, pair, slot = res
    k = weights.shape[-1]
    g_rows = g[pair // k].astype(jnp.float32)       # each row's token's
    w_rows = weights.reshape(-1)[pair].astype(jnp.float32)
    # the rows of other chips' experts get cotangents too: the grouped
    # kernels leave them out
    dy = (g_rows * w_rows[:, None]).astype(y.dtype)
    # the weights' gradient by row, back to (T, k) as scalars
    dw_rows = jnp.sum(g_rows * y.astype(jnp.float32), axis=-1)
    dw = dw_rows.at[slot].get(mode="fill", fill_value=0)
    return dy, dw.astype(weights.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _capacity_dispatch(params, x_flat, weights, ids, cfg: ModelConfig):
    """Dispatch + expert FFN over a flat (T, d) token group."""
    t, d = x_flat.shape
    k = cfg.num_experts_per_tok
    e = cfg.num_experts
    cap = _capacity(t, cfg)

    # ---- rank within expert via stable sort ------------------------------
    flat_ids = ids.reshape(t * k)                       # (N,)
    order = jnp.argsort(flat_ids, stable=True)          # (N,)
    sorted_ids = flat_ids[order]
    first = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    rank_sorted = jnp.arange(t * k) - first             # rank within expert
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)

    keep = rank < cap
    slot = jnp.where(keep, flat_ids * cap + rank, e * cap)  # overflow row

    # ---- scatter tokens into (E*C, d) expert buffers ----------------------
    token_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e * cap + 1, d), dtype=x_flat.dtype)
    buf = buf.at[slot].set(x_flat[token_idx], mode="drop")
    expert_in = buf[:-1].reshape(e, cap, d)

    # ---- expert FFN (batched over E; EP-sharded over the model axis) ------
    act = _expert_act(cfg)
    h = act(jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"])
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])

    # ---- gather back & combine with routing weights -----------------------
    out_buf = jnp.concatenate(
        [expert_out.reshape(e * cap, d),
         jnp.zeros((1, d), dtype=expert_out.dtype)], axis=0)
    per_slot = out_buf[slot] * weights.reshape(t * k)[:, None]
    per_slot = jnp.where(keep[:, None], per_slot, 0)
    return jnp.sum(per_slot.reshape(t, k, d), axis=1)
