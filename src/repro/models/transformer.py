"""Decoder-only LM covering the dense / MoE / VLM families.

Layers are stacked on a leading ``layers`` axis and executed with
``jax.lax.scan`` so compile time is depth-independent; remat policy is
selectable per config.  The same stacked layout carries the KV cache for
decode: (L, B, S, kv, h).
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


def init_layer(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": layers.init_rms_norm(cfg.d_model, cfg),
        "attn": attention.init_attention(k1, cfg),
        "mlp_norm": layers.init_rms_norm(cfg.d_model, cfg),
    }
    if cfg.is_moe:
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
    k_embed, k_layers, _ = jax.random.split(key, 3)
    stacked, layer_specs = stack_layer_params(
        lambda k: init_layer(k, cfg), k_layers, cfg.num_layers)

    embed_params, embed_specs = layers.split_tree(layers.init_embedding(k_embed, cfg))
    fn_param, fn_spec = layers.init_rms_norm(cfg.d_model, cfg)
    params = {"embed": embed_params, "layers": stacked, "final_norm": fn_param}
    specs = {"embed": embed_specs, "layers": layer_specs, "final_norm": fn_spec}
    return params, specs


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def _layer_forward(layer_params, x, cfg: ModelConfig, positions):
    with jax.named_scope("attention"):
        h = attention.attention(
            layer_params["attn"],
            layers.rms_norm(x, layer_params["attn_norm"], cfg.norm_eps),
            cfg, positions)
    x = x + h
    with jax.named_scope("mlp"):
        normed = layers.rms_norm(x, layer_params["mlp_norm"], cfg.norm_eps)
        if cfg.is_moe:
            f, aux = moe.moe_ffn(layer_params["moe"], normed, cfg)
        else:
            f = layers.mlp(layer_params["mlp"], normed, cfg)
            aux = jnp.float32(0)
    return x + f, aux


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
        # layout work before it again
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                fused_attention.RESIDUALS))
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(params, x_or_tokens, cfg: ModelConfig,
            positions: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Embeds (if needed), runs the trunk, returns (hidden, aux_loss)."""
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
    if cfg.scan_layers:
        wrapped = _remat(lambda carry, lp: body(lp, carry), cfg)

        def scan_body(carry, lp):
            new_x, aux = wrapped(carry, lp)
            return constrain(new_x, ("batch", "seq", None)), aux

        x, auxs = jax.lax.scan(scan_body, x, params["layers"])
        aux = jnp.sum(auxs)
    else:
        aux = jnp.float32(0)
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda p: p[i], params["layers"])
            x, a = body(lp, x)
            aux = aux + a
    return layers.rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def loss_fn(params, batch, cfg: ModelConfig):
    """batch: {tokens|embeds, labels} -> (loss, metrics)."""
    inputs = batch["embeds"] if cfg.embeds_as_input else batch["tokens"]
    hidden, aux = forward(params, inputs, cfg)
    loss = layers.lm_loss(params, hidden, batch["labels"], cfg)
    return loss + aux, {"loss": loss, "aux_loss": aux}


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
        if cfg.is_moe:
            f, _ = moe.moe_ffn(lp["moe"], normed, cfg)
        else:
            f = layers.mlp(lp["mlp"], normed, cfg)
        return carry + f, new_lc

    if cfg.scan_layers:
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    else:
        x, new_cache = _unrolled_scan(body, x, (params["layers"], cache),
                                      cfg.num_layers)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.logits_head(params["embed"], x, cfg)
    return logits, new_cache
