"""The fused causal attention kernels (``repro.kernels.fused_attention``)
against the materialised einsum path, in Pallas interpret mode, and the
dispatch in ``attention.attention`` that picks one of them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import fused_attention
from repro.models import attention, layers


def _qkv(b, s, nq, nkv, d, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, nq, d), dtype),
            jax.random.normal(ks[1], (b, s, nkv, d), dtype),
            jax.random.normal(ks[2], (b, s, nkv, d), dtype),
            jax.random.normal(ks[3], (b, s, nq, d), dtype))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _out_and_grads(fn, q, k, v, ct):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(ct)


@pytest.mark.parametrize("s,nq,nkv,d", [
    (256, 4, 2, 64),      # GQA, two KV heads
    (256, 3, 1, 128),     # MQA
    (512, 4, 2, 128),
    (512, 2, 1, 64),
])
def test_kernel_matches_materialized_path(s, nq, nkv, d):
    cfg = dataclasses.replace(configs.get("qwen2-0.5b"), num_heads=nq,
                              num_kv_heads=nkv, head_dim=d)
    q, k, v, ct = _qkv(2, s, nq, nkv, d)
    fused = _out_and_grads(
        lambda q, k, v: fused_attention.causal_attention(q, k, v,
                                                         interpret=True),
        q, k, v, ct)
    materialized = _out_and_grads(
        lambda q, k, v: attention._attention_materialized(q, k, v, cfg,
                                                          True),
        q, k, v, ct)
    with jax.default_matmul_precision("highest"):
        exact = _out_and_grads(
            lambda q, k, v: attention._attention_materialized(q, k, v, cfg,
                                                              True),
            *(x.astype(jnp.float32) for x in (q, k, v, ct)))
    for name, f, m, e in zip(("out", "dq", "dk", "dv"), fused, materialized,
                             exact):
        assert f.shape == m.shape and f.dtype == m.dtype, name
        # bf16 rounding apart, and no further from float32 than the
        # materialised path, which rounds the scores to bf16
        assert _rel(f, m) < 1e-2, name
        assert _rel(f, e) <= 1.25 * _rel(m, e), name


def _layer(cfg, s):
    params, _ = layers.split_tree(
        attention.init_attention(jax.random.PRNGKey(1), cfg))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, s, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (2, s))
    return params, x, pos


def _materialized_before(params, x, cfg, pos, causal):
    q, k, v = attention._project_qkv(params, x, cfg, pos)
    out = attention._attention_materialized(q, k, v, cfg, causal)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"])


@pytest.mark.parametrize("s,window,causal,fused", [
    (256, 0, True, True),       # full-sequence causal: the kernel on a TPU
    (200, 0, True, False),      # no block divides the length
    (256, 64, True, False),     # sliding window
    (256, 0, False, False),     # encoder self-attention
])
def test_dispatch(s, window, causal, fused):
    cfg = dataclasses.replace(configs.get("qwen2-0.5b"), num_heads=4,
                              num_kv_heads=2, head_dim=64, d_model=128,
                              sliding_window=window)
    params, x, pos = _layer(cfg, s)
    fn = jax.jit(lambda p, x, pos: attention.attention(p, x, cfg, pos,
                                                       causal=causal))
    traced = fn.trace(params, x, pos)
    for platform, kernel in (("cpu", False), ("tpu", fused)):
        text = traced.lower(lowering_platforms=(platform,)).as_text()
        assert ("tpu_custom_call" in text) == kernel, platform
    # where it runs here, every shape gives the numbers it gave before
    np.testing.assert_array_equal(
        np.asarray(fn(params, x, pos), np.float32),
        np.asarray(jax.jit(_materialized_before, static_argnums=(2, 4))(
            params, x, cfg, pos, causal), np.float32))


def test_block_and_fits():
    assert [fused_attention.block(s) for s in (1024, 1536, 384, 1500)] == \
        [512, 512, 128, 0]
    assert fused_attention.fits(1024, 64) and fused_attention.fits(256, 256)
    assert not fused_attention.fits(1500, 64)
    assert not fused_attention.fits(1024, 80)
