"""Moonlight-16B-A3B's mechanisms in the program, at tiny sizes: latent
attention through the fused kernels (interpret mode) and through the
decode cache, the grouped expert kernels (interpret mode), DeepSeek-V3
routing with dropless dispatch over the experts a chip holds (compact and
full-size), the per-expert token and full-size dispatch counters, and the
parameter count of the published model and of one chip's share."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import fused_attention
from repro.models import attention, build_model, layers, moe
from repro.optim import make_schedule
from repro.train import init_train_state, make_train_step
from repro.train.step import abstract_init


def _f32(cfg):
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("s,nq,nkv,dqk,dv", [
    (256, 4, 4, 192, 128),    # latent attention's widths, one k per head
    (256, 4, 2, 128, 64),     # grouped queries, a narrower value head
])
def test_kernel_takes_a_narrower_value_head(s, nq, nkv, dqk, dv):
    cfg = dataclasses.replace(configs.get("qwen2-0.5b"), num_heads=nq,
                              num_kv_heads=nkv)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, s, nq, dqk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, s, nkv, dqk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, s, nkv, dv), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (2, s, nq, dv), jnp.bfloat16)

    def run(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(ct.astype(out.dtype))

    fused = run(lambda q, k, v: fused_attention.causal_attention(
        q, k, v, interpret=True), q, k, v)
    materialized = run(lambda q, k, v: attention._attention_materialized(
        q, k, v, cfg, True), q, k, v)
    with jax.default_matmul_precision("highest"):
        exact = run(lambda q, k, v: attention._attention_materialized(
            q, k, v, cfg, True), *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, f, m, e in zip(("out", "dq", "dk", "dv"), fused, materialized,
                             exact):
        assert f.shape == m.shape and f.dtype == m.dtype, name
        assert _rel(f, m) < 1e-2, name
        assert _rel(f, e) <= 1.25 * _rel(m, e), name
    assert fused[0].shape == (2, s, nq, dv)


def test_grouped_kernel_matches_ragged_dot_and_zeroes_other_rows():
    """The megablox kernels (interpret mode) against ``ragged_dot``: the
    held groups' rows, forward and both gradients; the rows past them, of
    experts other chips hold, zero, and their cotangents left out."""
    from repro.kernels.grouped_matmul import grouped_matmul
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (1024, 256), jnp.bfloat16)
    w = jax.random.normal(ks[1], (4, 256, 384), jnp.bfloat16)
    ct = jax.random.normal(ks[2], (1024, 384), jnp.bfloat16)
    sizes = jnp.array([100, 0, 300, 37], jnp.int32)

    def run(interpret):
        out, vjp = jax.vjp(lambda x, w: grouped_matmul(
            x, w, sizes, interpret=interpret), x, w)
        return (out,) + vjp(ct)

    got = {interpret: run(interpret) for interpret in (True, False)}
    for name, f, r in zip(("out", "dx", "dw"), got[True], got[False]):
        assert f.shape == r.shape and f.dtype == r.dtype, name
        assert _rel(f, r) < 1e-2, name
    out, dx, _ = got[True]
    assert not np.any(np.asarray(out[437:], np.float32))
    assert not np.any(np.asarray(dx[437:], np.float32))
    # nor do those rows' cotangents reach the experts' gradient
    ct = ct.at[437:].set(0)
    for interpret in (True, False):
        np.testing.assert_array_equal(run(interpret)[2], got[interpret][2])


def test_latent_attention_lowers_to_the_kernels_on_a_tpu():
    cfg = configs.tiny("moonlight-16b-a3b-ep8")
    cfg = dataclasses.replace(cfg, qk_nope_head_dim=128, qk_rope_head_dim=64,
                              v_head_dim=128)
    params, _ = layers.split_tree(
        attention.init_attention(jax.random.PRNGKey(1), cfg))
    x = jnp.zeros((2, 256, cfg.d_model), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(256)[None], (2, 256))
    traced = jax.jit(lambda p, x, pos: attention.attention(
        p, x, cfg, pos)).trace(params, x, pos)
    for platform, kernel in (("cpu", False), ("tpu", True)):
        text = traced.lower(lowering_platforms=(platform,)).as_text()
        assert ("tpu_custom_call" in text) == kernel, platform


def test_decode_through_the_latent_cache_matches_the_full_forward():
    cfg = _f32(configs.tiny("moonlight-16b-a3b-ep8"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    s = 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, s), 0,
                                cfg.vocab_size)
    from repro.models import transformer
    with jax.default_matmul_precision("highest"):
        hidden, _ = transformer.forward(params, tokens, cfg)
        full = layers.logits_head(params["embed"], hidden, cfg)
        # float32 throughout, the cache too (it is bf16 by default)
        cache, _ = attention.init_kv_cache(cfg, 2, 16, cfg.num_layers,
                                           dtype=jnp.float32)
        assert cache["k"].shape[-1] == cfg.qk_head_dim
        assert cache["v"].shape[-1] == cfg.v_head_dim
        step = jax.jit(model.decode_step)
        for pos in range(s):
            logits, cache = step(params, cache, tokens[:, pos:pos + 1],
                                 jnp.full((2,), pos, jnp.int32))
            np.testing.assert_allclose(logits[:, 0], full[:, pos],
                                       rtol=1e-4, atol=1e-4)


def _moe_params(cfg, seed=0):
    params, _ = layers.split_tree(moe.init_moe(jax.random.PRNGKey(seed), cfg))
    return params


def _held_part_by_hand(params, x, cfg, lo):
    """Every token through every held expert, weighted by its routing
    weight for it (zero where it did not choose it)."""
    b, s, d = x.shape
    w, ids, _ = moe.route_sigmoid(params, x, cfg)
    out = jnp.zeros((b, s, d), jnp.float32)
    for j in range(cfg.resolved_experts_held):
        gate = jnp.sum(jnp.where(ids == lo + j, w, 0.0), axis=-1)
        h = jax.nn.silu(x @ params["w_gate"][j]) * (x @ params["w_in"][j])
        out = out + gate[..., None] * (h @ params["w_out"][j])
    return out


def _skew_onto_the_first_experts(params, cfg):
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    return dict(params, router_bias=jnp.where(jnp.arange(e) < k, 10.0, 0.0))


@pytest.mark.parametrize("s", [
    16,     # the compact buffer would be no smaller than the full-size one
    256,    # the held pairs overflow the compact buffer's 512 rows
])
def test_no_token_routed_to_a_held_expert_is_dropped(s):
    """A router skewed so that every token picks the first experts: the
    two held ones get every token of the batch, which a capacity of 1.25
    times the mean would cut to a fraction, and which overflows the
    compact dispatch; the full-size one runs."""
    cfg = _f32(configs.tiny("moonlight-16b-a3b-ep8"))
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    params = _skew_onto_the_first_experts(_moe_params(cfg), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, s, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, _, counters = moe.moe_ffn(params, x, cfg, lo=0)
        shared = layers.mlp(params["shared"], x, cfg)
        want = _held_part_by_hand(params, x, cfg, 0)
    assert counters["expert_tokens"].tolist() == [2 * s] * k + [0] * (e - k)
    assert int(counters["dispatch_full"]) == 1
    np.testing.assert_allclose(out - shared, want, rtol=1e-5, atol=1e-5)
    # the selection bias steers the choice, not the weights
    w, ids, _ = moe.route_sigmoid(params, x, cfg)
    scores = jax.nn.sigmoid(x @ params["router"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling_factor,
        rtol=1e-5)


@pytest.mark.parametrize("kernel", ["ragged_dot", "megablox"])
@pytest.mark.parametrize("routing,full", [
    ("initialised", 0),         # 269 of the 1024 pairs held, 512 rows
    ("skewed", 1),              # all 1024 held
])
def test_compact_dispatch_matches_the_full_size_one(monkeypatch, kernel,
                                                    routing, full):
    """2 x 256 tokens at top-4 with 2 of 16 experts held: the compact
    buffer has 512 rows, the full-size one 1024.  Forward and gradients
    with respect to the tokens, the routing weights and the experts, in
    float32, against the full-size dispatch alone."""
    from repro.kernels.grouped_matmul import grouped_matmul
    if kernel == "megablox":
        monkeypatch.setattr(moe, "grouped_matmul", functools.partial(
            grouped_matmul, interpret=True))
    cfg = _f32(configs.tiny("moonlight-16b-a3b-ep8"))
    params = _moe_params(cfg)
    if routing == "skewed":
        params = _skew_onto_the_first_experts(params, cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, cfg.d_model))
    weights, ids, _ = moe.route_sigmoid(params, x, cfg)
    k = cfg.num_experts_per_tok
    x, weights, ids = (x.reshape(512, -1), weights.reshape(512, k),
                       ids.reshape(512, k))
    experts = {n: params[n] for n in ("w_gate", "w_in", "w_out")}
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def run():
        with jax.default_matmul_precision("highest"):
            (out, ran_full), vjp = jax.vjp(
                lambda x, w, e: moe._dropless(e, x, w, ids, cfg, 0),
                x, weights, experts)
            no_ct = np.zeros((), jax.dtypes.float0)
            return ran_full, (out,) + vjp((ct, no_ct))

    ran_full, got = run()
    assert int(ran_full) == full
    # the full-size dispatch alone, as where the compact buffer is no
    # smaller than it
    monkeypatch.setattr(moe, "_compact_rows", lambda *a: 1 << 30)
    ran_full, want = run()
    assert int(ran_full) == 1
    for name, g, w in zip(("out", "x", "weights", "w_gate", "w_in",
                           "w_out"), jax.tree.leaves(got),
                          jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert _rel(g, w) < 1e-5, name


def test_expert_tokens_count_every_routed_token_of_every_moe_layer():
    cfg = configs.tiny("moonlight-16b-a3b-ep8")
    model = build_model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(0))
    b, s = 2, 32
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                          cfg.vocab_size)}
    batch["labels"] = jnp.roll(batch["tokens"], -1, axis=1)
    step = jax.jit(make_train_step(model, make_schedule("cosine",
                                                        peak_lr=1e-3)))
    _, metrics = step(state, batch)
    tokens = np.asarray(metrics["expert_tokens"])
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    assert tokens.dtype == np.int32
    assert tokens.shape == (moe_layers, cfg.num_experts)
    assert tokens.sum(axis=1).tolist() == [b * s * cfg.num_experts_per_tok] \
        * moe_layers
    full = np.asarray(metrics["dispatch_full"])
    assert full.dtype == np.int32 and full.shape == (moe_layers,)
    assert float(metrics["aux_loss"]) > 0


def test_the_loop_logs_the_expert_load_at_log_steps(capsys):
    from repro.data import DataPipeline, SyntheticCorpus
    from repro.train.loop import LoopConfig, train_loop
    cfg = configs.tiny("moonlight-16b-a3b-ep8")
    pipeline = DataPipeline(SyntheticCorpus(cfg.vocab_size, seq_len=16,
                                            seed=0), global_batch=2)
    train_loop(build_model(cfg), pipeline,
               LoopConfig(total_steps=4, log_every=2, observability=False,
                          warmup_steps=1))
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("step ")]
    assert len(lines) == 2
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    for line in lines:
        loads = line.split("expert_load_max/mean=")[1].split()[0].split(",")
        assert len(loads) == moe_layers
        assert all(float(x) >= 1.0 for x in loads)
        # at 2 x 16 tokens the full-size dispatch is the only one
        full = line.split("dispatch_full=")[1].split()[0].split(",")
        assert full == ["1"] * moe_layers


def _leaf_count(cfg) -> int:
    shapes, _ = abstract_init(build_model(cfg))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def test_param_count_knows_latent_attention_shared_experts_and_dense_prefix():
    published = configs.get("moonlight-16b-a3b")
    # about 16 B as published; 8 of 64 experts and an eighth of the
    # vocabulary over the dense layer and 5 MoE layers: 668.9 M
    assert published.param_count() == 15_960_110_208
    assert configs.get("moonlight-16b-a3b-ep8").param_count() == 668_890_432
    for name in ("moonlight-16b-a3b-ep8", "moonlight-16b-a3b", "qwen2-0.5b",
                 "mixtral-8x22b"):
        cfg = configs.tiny(name)
        assert cfg.param_count() == _leaf_count(cfg), name
