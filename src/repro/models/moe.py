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
  expert is computed.

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

from repro.kernels.grouped_matmul import grouped_matmul
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
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss, expert_tokens (E,)).

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
        tokens = expert_tokens(ids, cfg.num_experts)
    with jax.named_scope("experts"):
        x_flat = x.reshape(b * s, d)
        g = cfg.moe_dispatch_groups
        if cfg.moe_capacity_factor <= 0:
            out = _dropless(params, x_flat, weights, ids, cfg, lo)
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
    return out, aux, tokens


def _expert_act(cfg: ModelConfig):
    return jax.nn.gelu if cfg.activation == "gelu" else jax.nn.silu


def _dropless(params, x_flat, weights, ids, cfg: ModelConfig, lo: int):
    """The held experts' part of the result for every token routed to
    them: (T, d) -> (T, d)."""
    t, d = x_flat.shape
    k = ids.shape[-1]
    held = cfg.resolved_experts_held
    local = ids.reshape(t * k) - lo
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)        # the others sort last
    order = jnp.argsort(group, stable=True)     # sorted position -> pair
    back = jnp.argsort(order)                   # pair -> sorted position
    sizes = jnp.sum(group[:, None] == jnp.arange(held), axis=0,
                    dtype=jnp.int32)
    kept = mine[order][:, None]
    # the rows past the held experts' groups are zero both ways
    rows = jnp.where(kept, _pair_rows(x_flat, order, back, k), 0)
    act = _expert_act(cfg)
    h = act(grouped_matmul(rows, params["w_gate"], sizes))
    h = h * grouped_matmul(rows, params["w_in"], sizes)
    y = jnp.where(kept, grouped_matmul(h, params["w_out"], sizes), 0)
    y = _permute(y, back, order).reshape(t, k, d).astype(jnp.float32)
    out = jnp.sum(y * weights.astype(jnp.float32)[..., None], axis=1)
    return out.astype(x_flat.dtype)


# Row gathers by a permutation whose gradients are gathers by the inverse
# permutation: autodiff alone would scatter-add them, which on a TPU took
# most of the dispatch's time.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pair_rows(x_flat, order, back, k):
    """The token row of each (token, pick) pair, pairs in ``order``:
    x_flat[order // k]."""
    return x_flat[order // k]


def _pair_rows_fwd(x_flat, order, back, k):
    return x_flat[order // k], back


def _pair_rows_bwd(k, back, g):
    # each token's k pairs, back in pair order, summed
    g = g[back].reshape(-1, k, g.shape[-1])
    return jnp.sum(g, axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_pair_rows.defvjp(_pair_rows_fwd, _pair_rows_bwd)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """x[perm], ``inverse`` the inverse permutation of ``perm``."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], inverse


def _permute_bwd(inverse, g):
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


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
