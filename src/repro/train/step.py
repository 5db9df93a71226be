"""train_step / prefill_step / serve_step factories.

All three are pure functions meant for ``jax.jit`` with explicit
in/out_shardings (pjit).  State is a plain dict pytree:
``{"params", "opt": {"m","v"}, "step"}`` so checkpointing and sharding
stay framework-free.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import Model
from repro.optim import adamw_init, adamw_update, clip_by_global_norm
from repro.optim.adamw import AdamWConfig
from repro.parallel.sharding import ShardingRules, tree_pspecs


# ---------------------------------------------------------------------------
# abstract init (shapes + logical specs, no allocation)
# ---------------------------------------------------------------------------


def abstract_init(model: Model, key=None):
    """(param ShapeDtypeStructs, logical specs) without allocating.

    The logical-spec tree is built statically during tracing, so we capture
    it via closure side-effect while eval_shape computes the shapes.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    holder = {}

    def f(k):
        params, specs = model.init(k)
        holder["specs"] = specs
        return params

    shapes = jax.eval_shape(f, key)
    return shapes, holder["specs"]


def init_train_state(model: Model, key) -> Dict[str, Any]:
    params, _ = model.init(key)
    return {"params": params, "opt": adamw_init(params),
            "step": jnp.zeros((), dtype=jnp.int32)}


def abstract_train_state(model: Model):
    """ShapeDtypeStructs for the full train state + its logical specs."""
    p_shapes, p_specs = abstract_init(model)
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    state_shapes = {
        "params": p_shapes,
        "opt": {"m": jax.tree.map(f32, p_shapes),
                "v": jax.tree.map(f32, p_shapes)},
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state_specs = {
        "params": p_specs,
        "opt": {"m": p_specs, "v": p_specs},
        "step": (),
    }
    return state_shapes, state_specs


def train_state_pspecs(state_shapes, state_specs, mesh, rules: ShardingRules):
    pspecs = tree_pspecs(state_specs, state_shapes, mesh, rules)
    pspecs["step"] = P()
    return pspecs


# ---------------------------------------------------------------------------
# step factories
# ---------------------------------------------------------------------------


def make_train_step(model: Model, schedule: Callable,
                    adamw_cfg: AdamWConfig = AdamWConfig(),
                    max_grad_norm: float = 1.0) -> Callable:
    def train_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(state["params"], batch)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
            lr = schedule(state["step"])
            new_params, new_opt = adamw_update(grads, state["opt"],
                                               state["params"], lr,
                                               state["step"], adamw_cfg)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return new_state, metrics

    return train_step


def make_prefill_step(model: Model) -> Callable:
    """Inference prefill: full forward, last-token logits."""
    def prefill_step(params, batch):
        cfg = model.cfg
        if cfg.is_enc_dec:
            from repro.models import whisper
            enc = whisper.encode(params, batch["embeds"], cfg)
            hidden = whisper.decode_train(params, batch["tokens"], enc, cfg)
        else:
            from repro.models import ssm_lm, transformer
            mod = ssm_lm if cfg.family in ("ssm", "hybrid") else transformer
            inputs = batch["embeds"] if cfg.embeds_as_input else batch["tokens"]
            hidden, _ = mod.forward(params, inputs, cfg)
        from repro.models import layers
        logits = layers.logits_head(params["embed"], hidden[:, -1:], cfg)
        return logits

    return prefill_step


def make_serve_step(model: Model) -> Callable:
    """One batched decode step with a KV/SSM cache (donated)."""
    def serve_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    return serve_step
