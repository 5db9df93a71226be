"""Decoder-only LM covering the dense / MoE / MLA-MoE / VLM families.

Layers are stacked on a leading ``layers`` axis and executed with
``jax.lax.scan`` so compile time is depth-independent; remat policy is
selectable per config.  An MoE model's leading dense layers
(``first_dense_layers``) are a stack of their own, ``dense_layers``, run
before it.  The same stacked layout carries the KV cache for decode:
(L, B, S, kv, h), the leading dense layers first.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import fused_attention
from repro.models.config import ModelConfig
from repro.models import attention, layers, moe


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, dense: bool = False):
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": layers.init_rms_norm(cfg.d_model, cfg),
        "attn": attention.init_attention(k1, cfg),
        "mlp_norm": layers.init_rms_norm(cfg.d_model, cfg),
    }
    if dense:
        p["mlp"] = layers.init_mlp(k2, cfg, d_ff=cfg.dense_d_ff)
    elif cfg.is_moe:
        p["moe"] = moe.init_moe(k2, cfg)
    else:
        p["mlp"] = layers.init_mlp(k2, cfg)
    return p


def stack_layer_params(init_one, key, num_layers: int):
    """vmap-stack per-layer params; specs come from a single trace (vmap
    cannot carry the string axis tuples)."""
    layer_keys = jax.random.split(key, num_layers)
    _, layer_specs = layers.split_tree(init_one(layer_keys[0]))
    stacked = jax.vmap(lambda k: layers.split_tree(init_one(k))[0])(layer_keys)
    layer_specs = jax.tree.map(
        lambda s: ("layers",) + s, layer_specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    return stacked, layer_specs


def init_params(key, cfg: ModelConfig):
    """Returns (params, logical_specs) with stacked layer params."""
    k_embed, k_layers, k_dense = jax.random.split(key, 3)
    n_dense = cfg.first_dense_layers
    stacked, layer_specs = stack_layer_params(
        lambda k: init_layer(k, cfg), k_layers, cfg.num_layers - n_dense)

    embed_params, embed_specs = layers.split_tree(layers.init_embedding(k_embed, cfg))
    fn_param, fn_spec = layers.init_rms_norm(cfg.d_model, cfg)
    params = {"embed": embed_params, "layers": stacked, "final_norm": fn_param}
    specs = {"embed": embed_specs, "layers": layer_specs, "final_norm": fn_spec}
    if n_dense:
        params["dense_layers"], specs["dense_layers"] = stack_layer_params(
            lambda k: init_layer(k, cfg, dense=True), k_dense, n_dense)
    return params, specs


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _ffn(layer_params, x, cfg: ModelConfig):
    """(out, (aux_loss, counters)); a dense layer routes nothing."""
    if "moe" in layer_params:
        f, aux, counters = moe.moe_ffn(layer_params["moe"], x, cfg)
        return f, (aux, counters)
    return layers.mlp(layer_params["mlp"], x, cfg), (jnp.float32(0), {})


def _layer_forward(layer_params, x, cfg: ModelConfig, positions):
    with jax.named_scope("attention"):
        h = attention.attention(
            layer_params["attn"],
            layers.rms_norm(x, layer_params["attn_norm"], cfg.norm_eps),
            cfg, positions)
    x = x + h
    with jax.named_scope("mlp"):
        normed = layers.rms_norm(x, layer_params["mlp_norm"], cfg.norm_eps)
        f, routed = _ffn(layer_params, normed, cfg)
    return x + f, routed


def _unrolled_scan(body, carry, xs, length: int):
    """Python-unrolled scan (cost-extrapolation dry runs + perf variants:
    XLA cost analysis counts a while-loop body ONCE, so unrolled lowering
    is the accurate-cost path)."""
    ys = []
    for i in range(length):
        x_i = jax.tree.map(lambda p: p[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and all(y is not None for y in ys):
        stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        stacked = None
    return carry, stacked


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        # the fused attention's inputs, output and log-sum-exp are kept
        # too: the backward then runs neither its forward kernel nor the
        # layout work before it again.  The grouped expert kernels' outputs
        # are not dots: the dropless dispatch's backward recomputes them
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                fused_attention.RESIDUALS))
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(params, x_or_tokens, cfg: ModelConfig,
            positions: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, dict]:
    """Embeds (if needed), runs the trunk, returns (hidden, routing): the
    routers' ``aux_loss`` summed over layers and, for an MoE model,
    ``expert_tokens`` (MoE layers, experts), the tokens routed to each
    expert, and for a dropless one ``dispatch_full`` (MoE layers,), 1
    where a layer ran the full-size dispatch."""
    if cfg.embeds_as_input:
        x = x_or_tokens.astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = layers.embed(params["embed"], x_or_tokens, cfg)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if cfg.rope_type == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, b, s))

    from repro.parallel.context import constrain
    x = constrain(x, ("batch", "seq", None))  # SP: seq over model if enabled

    body = functools.partial(_layer_forward, cfg=cfg, positions=positions)
    wrapped = _remat(lambda carry, lp: body(lp, carry), cfg)

    def scan_body(carry, lp):
        new_x, routed = wrapped(carry, lp)
        return constrain(new_x, ("batch", "seq", None)), routed

    routing = {"aux_loss": jnp.float32(0)}
    for stack in _stacks(params):
        if cfg.scan_layers:
            x, (auxs, counters) = jax.lax.scan(scan_body, x, stack)
        else:
            x, (auxs, counters) = _unrolled_scan(
                lambda c, lp: body(lp, c), x, stack,
                jax.tree.leaves(stack)[0].shape[0])
        routing["aux_loss"] = routing["aux_loss"] + jnp.sum(auxs)
        routing.update(counters)
    return layers.rms_norm(x, params["final_norm"], cfg.norm_eps), routing


def _stacks(params):
    """The layer stacks in the order they run."""
    return [params[k] for k in ("dense_layers", "layers") if k in params]


def loss_fn(params, batch, cfg: ModelConfig):
    """batch: {tokens|embeds, labels} -> (loss, metrics)."""
    inputs = batch["embeds"] if cfg.embeds_as_input else batch["tokens"]
    hidden, routing = forward(params, inputs, cfg)
    loss = layers.lm_loss(params, hidden, batch["labels"], cfg)
    return loss + routing["aux_loss"], dict(routing, loss=loss)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    return attention.init_kv_cache(cfg, batch, seq_len, cfg.num_layers)


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    """One decode step.  tokens (B, 1) int32 (or embeds (B,1,d));
    pos (B,) int32.  Returns (logits (B,1,V), new_cache)."""
    if cfg.embeds_as_input:
        x = tokens.astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = layers.embed(params["embed"], tokens, cfg)

    def body(carry, scanned):
        lp, layer_cache = scanned
        h, new_lc = attention.decode_attention(
            lp["attn"],
            layers.rms_norm(carry, lp["attn_norm"], cfg.norm_eps),
            cfg, layer_cache, pos)
        carry = carry + h
        normed = layers.rms_norm(carry, lp["mlp_norm"], cfg.norm_eps)
        f, _ = _ffn(lp, normed, cfg)
        return carry + f, new_lc

    new_caches, start = [], 0
    for stack in _stacks(params):
        n = jax.tree.leaves(stack)[0].shape[0]
        part = jax.tree.map(lambda c: c[start:start + n], cache)
        if cfg.scan_layers:
            x, new_part = jax.lax.scan(body, x, (stack, part))
        else:
            x, new_part = _unrolled_scan(body, x, (stack, part), n)
        new_caches.append(new_part)
        start += n
    new_cache = jax.tree.map(lambda *cs: jnp.concatenate(cs), *new_caches) \
        if len(new_caches) > 1 else new_caches[0]
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.logits_head(params["embed"], x, cfg)
    return logits, new_cache
