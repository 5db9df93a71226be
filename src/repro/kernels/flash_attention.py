"""Pallas TPU flash attention (fwd): blocked online-softmax.

TPU-adapted tiling: the grid is (B, Hq, S/bq, S/bk) with the kv-block axis
innermost — on TPU grid steps execute sequentially per core, so the f32
running (m, l, acc) state lives in VMEM scratch across the kv sweep and the
output block is written once on the last kv step.  Block shapes keep the
MXU happy (bq x bk x D matmuls, D and bk multiples of 128 on real configs);
q/k/v tiles stream HBM->VMEM per BlockSpec.

Supports causal masking, sliding windows (Mixtral SWA) and GQA (kv head =
q head // group) directly in the index maps — no KV repetition in HBM.
Validated in interpret mode against ref.flash_attention_ref; forward only
(no backward rule, so training uses the jnp attention paths).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int, bq: int, bk: int,
               n_kv_blocks: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    i = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                # (bq, 1)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        sliding_window: int = 0, scale: float | None = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool):
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) -> (B, Hq, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_kv = s // bk
    scale = scale if scale is not None else d ** -0.5

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=sliding_window,
        bq=bq, bk=bk, n_kv_blocks=n_kv)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, s // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, j: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
