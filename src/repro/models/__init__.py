"""Model zoo facade: one interface over all architecture families."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.models.config import ModelConfig, ShapeConfig, SHAPES, smoke_shape
from repro.models import ssm_lm, transformer, whisper

__all__ = ["ModelConfig", "ShapeConfig", "SHAPES", "smoke_shape",
           "build_model", "Model"]


@dataclasses.dataclass(frozen=True)
class Model:
    """Family-dispatched functional model bundle."""
    cfg: ModelConfig
    init: Callable          # key -> (params, logical_specs)
    loss_fn: Callable       # (params, batch) -> (loss, metrics)
    init_cache: Callable    # (batch, seq_len) -> (cache, cache_specs)
    decode_step: Callable   # (params, cache, tokens, pos) -> (logits, cache)

    def batch_spec(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for a *training/prefill* batch."""
        import jax
        b, s = shape.global_batch, shape.seq_len
        cfg = self.cfg
        batch: Dict[str, Any] = {}
        if cfg.is_enc_dec:
            batch["embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
            batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        elif cfg.embeds_as_input:
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.float32)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        batch["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        return batch


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family in ("dense", "moe", "mla_moe", "vlm"):
        mod = transformer
    elif cfg.family in ("ssm", "hybrid"):
        mod = ssm_lm
    elif cfg.family == "audio":
        mod = whisper
    else:
        raise ValueError(f"unknown family {cfg.family!r}")

    return Model(
        cfg=cfg,
        init=lambda key: mod.init_params(key, cfg),
        loss_fn=lambda params, batch: mod.loss_fn(params, batch, cfg),
        init_cache=lambda batch, seq_len: mod.init_cache(cfg, batch, seq_len),
        decode_step=lambda params, cache, tokens, pos: mod.decode_step(
            params, cache, tokens, pos, cfg),
    )
