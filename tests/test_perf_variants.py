"""Numerical equivalence of the §Perf optimization variants vs reference
paths (the optimizations must not change model math)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import build_model


def _batch(cfg, b=2, s=64):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                                         cfg.vocab_size)}


def _f32(cfg):
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


@pytest.mark.parametrize("arch", ["qwen3-4b", "gemma-2b", "mixtral-8x22b"])
def test_chunked_attention_equals_ref(arch):
    cfg_r = _f32(configs.tiny(arch))
    cfg_c = dataclasses.replace(cfg_r, attention_impl="chunked")
    mr, mc = build_model(cfg_r), build_model(cfg_c)
    params, _ = mr.init(jax.random.PRNGKey(0))
    batch = _batch(cfg_r)
    lr = float(mr.loss_fn(params, batch)[0])
    lc = float(mc.loss_fn(params, batch)[0])
    assert abs(lr - lc) < 2e-5, (lr, lc)


def test_chunked_attention_sliding_window():
    cfg_r = _f32(configs.tiny("mixtral-8x22b"))      # sliding_window=32
    assert cfg_r.sliding_window
    cfg_c = dataclasses.replace(cfg_r, attention_impl="chunked")
    mr, mc = build_model(cfg_r), build_model(cfg_c)
    params, _ = mr.init(jax.random.PRNGKey(0))
    batch = _batch(cfg_r, s=96)
    assert abs(float(mr.loss_fn(params, batch)[0])
               - float(mc.loss_fn(params, batch)[0])) < 2e-5


def test_chunked_ce_equals_ref():
    cfg_r = _f32(configs.tiny("qwen2-0.5b"))
    cfg_c = dataclasses.replace(cfg_r, ce_impl="chunked", ce_block_tokens=16)
    mr, mc = build_model(cfg_r), build_model(cfg_c)
    params, _ = mr.init(jax.random.PRNGKey(0))
    batch = _batch(cfg_r)
    assert abs(float(mr.loss_fn(params, batch)[0])
               - float(mc.loss_fn(params, batch)[0])) < 2e-5


def test_grouped_moe_dispatch_ce_exact_in_nodrop_regime():
    cfg_r = dataclasses.replace(_f32(configs.tiny("qwen3-moe-30b-a3b")),
                                moe_capacity_factor=8.0)
    cfg_g = dataclasses.replace(cfg_r, moe_dispatch_groups=2)
    mr, mg = build_model(cfg_r), build_model(cfg_g)
    params, _ = mr.init(jax.random.PRNGKey(0))
    batch = _batch(cfg_r, b=4, s=32)
    _, m_r = mr.loss_fn(params, batch)
    _, m_g = mg.loss_fn(params, batch)
    # pure CE identical; only the (per-group) aux loss may differ
    assert abs(float(m_r["loss"]) - float(m_g["loss"])) < 1e-5


def test_unrolled_equals_scanned():
    """The cost-extrapolation lowering (scan_layers=False) is numerically
    the same program."""
    cfg_s = _f32(configs.tiny("qwen3-4b"))
    cfg_u = dataclasses.replace(cfg_s, scan_layers=False)
    ms, mu = build_model(cfg_s), build_model(cfg_u)
    params, _ = ms.init(jax.random.PRNGKey(0))
    batch = _batch(cfg_s)
    assert abs(float(ms.loss_fn(params, batch)[0])
               - float(mu.loss_fn(params, batch)[0])) < 2e-5


def test_unrolled_decode_equals_scanned():
    cfg_s = _f32(configs.tiny("zamba2-2.7b"))
    cfg_u = dataclasses.replace(cfg_s, scan_layers=False)
    ms, mu = build_model(cfg_s), build_model(cfg_u)
    params, _ = ms.init(jax.random.PRNGKey(0))
    cache_s, _ = ms.init_cache(2, 32)
    cache_u, _ = mu.init_cache(2, 32)
    tok = jnp.ones((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    ls, _ = ms.decode_step(params, cache_s, tok, pos)
    lu, _ = mu.decode_step(params, cache_u, tok, pos)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lu),
                               rtol=1e-5, atol=1e-5)


def test_constrain_is_noop_outside_context():
    from repro.parallel.context import constrain
    x = jnp.ones((4, 8))
    y = constrain(x, ("batch", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sharding_context_applies_spec():
    from jax.sharding import AbstractMesh
    from repro.parallel.context import sharding_context, constrain
    from repro.parallel.sharding import ShardingRules
    mesh = AbstractMesh((1, 1), ("data", "model"))
    rules = ShardingRules(seq_parallel=True)

    def f(x):
        return constrain(x, ("batch", "seq", None)) * 2

    with sharding_context(mesh, rules):
        jaxpr = jax.make_jaxpr(f)(jnp.ones((2, 4, 8)))
    assert "sharding_constraint" in str(jaxpr)
