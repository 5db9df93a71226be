"""Model configuration for every architecture family the framework hosts.

A single dataclass covers dense / MoE / SSM / hybrid / VLM / enc-dec LMs.
Family-specific fields default to "off" values so dense configs stay terse.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # -- identity -----------------------------------------------------------
    name: str = "unnamed"
    # dense | moe | mla_moe (latent attention + MoE, DeepSeek-V3 style) |
    # ssm | hybrid | vlm | audio
    family: str = "dense"

    # -- transformer trunk --------------------------------------------------
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 2
    num_kv_heads: int = 2
    head_dim: int = 0          # 0 -> d_model // num_heads
    d_ff: int = 256            # per-expert FFN width for MoE families
    vocab_size: int = 1024
    activation: str = "silu"   # silu (swiglu) | gelu (geglu)
    norm_eps: float = 1e-6
    qkv_bias: bool = False
    qk_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    # default (rotate-half) | interleaved (adjacent pairs, as DeepSeek-V3's
    # published code rotates) | mrope
    rope_type: str = "default"
    mrope_sections: Tuple[int, ...] = ()  # head_dim splits for M-RoPE
    sliding_window: int = 0    # 0 -> full causal attention

    # -- multi-head latent attention (DeepSeek-V2/V3), no query LoRA ---------
    kv_lora_rank: int = 0      # >0 -> MLA: k and v from a normed latent
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0  # one rotary key of this width for all heads
    v_head_dim: int = 0

    # -- MoE ----------------------------------------------------------------
    num_experts: int = 0       # the router's width
    experts_held: int = 0      # routed experts this chip holds; 0 -> all
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0    # one gated MLP of this many experts' width
    # softmax: Switch aux loss | sigmoid: DeepSeek-V3 (float32 scores,
    # top-k of score + selection bias, renormalised and scaled weights,
    # sequence-wise balance loss)
    router: str = "softmax"
    routed_scaling_factor: float = 1.0
    # 0 -> dropless: every routed token computed (sort + grouped products)
    moe_capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.001
    first_dense_layers: int = 0    # leading dense layers of an MoE stack
    dense_d_ff: int = 0            # their FFN width
    moe_dispatch_groups: int = 0   # >0: shard-local dispatch groups (SP/EP)

    # -- SSM (Mamba2 / SSD) --------------------------------------------------
    ssm_state_size: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk_size: int = 256

    # -- hybrid (zamba2-style: SSM trunk + shared attention block) ----------
    attn_every: int = 0        # apply the shared attention block every N layers
    shared_attention: bool = False

    # -- encoder-decoder (whisper-style) -------------------------------------
    encoder_layers: int = 0    # >0 -> enc-dec model; num_layers = decoder layers
    encoder_seq_len: int = 1500  # stub frontend output length (audio frames)

    # -- modality stub -------------------------------------------------------
    embeds_as_input: bool = False  # vlm/audio: inputs are precomputed embeddings

    # -- numerics / runtime ---------------------------------------------------
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: str = "dots"        # none | dots | full
    scan_layers: bool = True
    # Pallas kernels: compiled on TPU, interpreted on CPU.  Flash attention
    # is forward-only, so a model that trains keeps this off.
    use_pallas: bool = False
    # ref: exact softmax attention, lowered on a TPU to the fused kernels
    # (forward and backward) where the shape fits and materialised
    # elsewhere | chunked: flash-style jnp scan over key blocks
    attention_impl: str = "ref"
    ce_impl: str = "ref"         # ref | chunked (blockwise logits+CE)
    ce_block_tokens: int = 512
    vocab_pad_multiple: int = 128

    # ------------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // max(self.num_heads, 1)

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        if self.is_mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.resolved_head_dim

    @property
    def resolved_v_head_dim(self) -> int:
        return self.v_head_dim or self.resolved_head_dim

    @property
    def resolved_experts_held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, self.vocab_pad_multiple)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_ssm_only(self) -> bool:
        return self.family == "ssm"

    @property
    def is_hybrid(self) -> bool:
        return self.family == "hybrid"

    @property
    def is_enc_dec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_num_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    # -- parameter counting (for roofline MODEL_FLOPS) -----------------------
    def param_count(self, active_only: bool = False) -> int:
        """Parameter count of the stored model (the experts this chip
        holds); active_only counts top-k experts."""
        d, h = self.d_model, self.resolved_head_dim
        n_q, n_kv = self.num_heads, self.num_kv_heads
        embed = self.padded_vocab * d * (1 if self.tie_embeddings else 2)

        def attn_params() -> int:
            if self.is_mla:
                r = self.kv_lora_rank
                return (d * n_q * self.qk_head_dim              # wq
                        + d * (r + self.qk_rope_head_dim)       # wkv_a
                        + r                                     # kv_norm
                        + r * n_q * (self.qk_nope_head_dim
                                     + self.resolved_v_head_dim)  # wkv_b
                        + n_q * self.resolved_v_head_dim * d)   # wo
            p = d * (n_q * h) + 2 * d * (n_kv * h) + (n_q * h) * d
            if self.qkv_bias:
                p += n_q * h + 2 * n_kv * h
            return p

        def dense_ffn(width: int) -> int:
            return 3 * d * width  # gated MLP: w_in, w_gate, w_out

        def ssm_params() -> int:
            di, ns, nh = self.ssm_d_inner, self.ssm_state_size, self.ssm_num_heads
            # B and C are per-GROUP (ngroups=1), shared across heads (Mamba2)
            in_proj = d * (2 * di + 2 * ns + nh)        # x, z, B, C, dt
            conv = self.ssm_conv_width * (di + 2 * ns)
            out = di * d
            return in_proj + conv + out + nh + nh        # + A_log, D

        per_layer, n_dense = 0, 0
        if self.family in ("dense", "vlm", "audio"):
            per_layer = attn_params() + dense_ffn(self.d_ff) + 2 * d
        elif self.family in ("moe", "mla_moe"):
            e = self.num_experts
            n_e = self.num_experts_per_tok if active_only else \
                self.resolved_experts_held
            router = d * e + (e if self.router == "sigmoid" else 0)  # + bias
            per_layer = (attn_params() + n_e * dense_ffn(self.d_ff) + router
                         + dense_ffn(self.num_shared_experts * self.d_ff)
                         + 2 * d)
            n_dense = self.first_dense_layers
        elif self.family == "ssm":
            per_layer = ssm_params() + 2 * d
        elif self.family == "hybrid":
            per_layer = ssm_params() + 2 * d

        total = embed + (self.num_layers - n_dense) * per_layer + d
        total += n_dense * (attn_params() + dense_ffn(self.dense_d_ff) + 2 * d)
        if self.is_hybrid and self.shared_attention:
            total += attn_params() + 2 * d  # one shared block
        if self.is_enc_dec:
            enc_layer = attn_params() + dense_ffn(self.d_ff) + 2 * d
            cross = attn_params() + 2 * d
            total += self.encoder_layers * enc_layer + self.num_layers * cross
        return int(total)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One (input-shape) cell: what the dry-run lowers."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def smoke_shape(kind: str = "train") -> ShapeConfig:
    return ShapeConfig(f"smoke_{kind}", 64, 2, kind)
