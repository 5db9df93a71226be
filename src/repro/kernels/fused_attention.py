"""Fused causal self-attention with a backward, for the TPU.

The splash attention kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.splash_attention``) in their MQA form,
vmapped over batch x KV heads, so that each KV head meets its group of
query heads without K and V being repeated in HBM.  Score tiles stay in
VMEM, key blocks wholly above the diagonal are skipped, and the backward's
``dq`` and ``dkv`` kernels read the forward's output and log-sum-exp.
Those and the kernels' inputs carry the checkpoint name ``RESIDUALS``, so
that a remat policy can keep them.  Numerics: QK^T and every backward
product take the operands' dtype with float32 accumulation; the running
max and sum, and dQ, dK and dV until written, are float32.

``attention.attention`` lowers to this on a TPU where ``fits`` holds; the
materialised einsum path is its oracle in the tests (interpret mode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash,
    splash_attention_mask as masks,
)

RESIDUALS = "attention_residuals"


def block(seq: int) -> int:
    """Query and key block of every kernel: the largest of 512, 256 and
    128 that divides ``seq``, or 0 where none does.  (On a TPU v5e, 512
    trained qwen2-0.5b at 4 x 1024 21 ms a step faster than 256.)"""
    return next((b for b in (512, 256, 128) if seq % b == 0), 0)


def fits(seq: int, head_dim: int) -> bool:
    """Whether the kernels take a sequence of ``seq`` at ``head_dim``."""
    return block(seq) > 0 and head_dim % 64 == 0 and head_dim <= 256


def _kernel(seq: int, group: int, interpret: bool):
    b = block(seq)
    # sequence-minor inputs are not padded to 128 lanes at head_dim 64
    sizes = splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                              block_q_dkv=b, block_kv_dkv=b,
                              block_kv_dkv_compute=b,
                              block_q_dq=b, block_kv_dq=b,
                              q_layout=splash.QKVLayout.SEQ_MINOR,
                              k_layout=splash.QKVLayout.SEQ_MINOR,
                              v_layout=splash.QKVLayout.SEQ_MINOR)
    mask = masks.MultiHeadMask([masks.CausalMask((seq, seq))] * group)
    return splash.make_splash_mqa_single_device(
        mask, block_sizes=sizes, residual_checkpoint_name=RESIDUALS,
        interpret=interpret)


def causal_attention(q, k, v, *, interpret: bool = False):
    """softmax(q k^T / sqrt(D), causal) v.  q (B, S, Hq, D), k (B, S, Hkv,
    D) and v (B, S, Hkv, Dv), Hq a multiple of Hkv -> (B, S, Hq, Dv).  The
    value head may be narrower than the query/key head (latent attention's
    192 and 128)."""
    b, s, nq, d = q.shape
    nkv, dv = k.shape[2], v.shape[-1]
    g = nq // nkv
    qt = q.reshape(b, s, nkv, g, d).transpose(0, 2, 3, 1, 4).reshape(
        b * nkv, g, s, d)
    qt = (qt.astype(jnp.float32) * d ** -0.5).astype(q.dtype)
    kt, vt = (x.transpose(0, 2, 1, 3).reshape(b * nkv, s, x.shape[-1])
              for x in (k, v))
    qt, kt, vt = (checkpoint_name(x, RESIDUALS) for x in (qt, kt, vt))
    out = jax.vmap(_kernel(s, g, interpret))(qt, kt, vt)
    return out.reshape(b, nkv, g, s, dv).transpose(0, 3, 1, 2, 4).reshape(
        b, s, nq, dv)
