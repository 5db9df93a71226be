"""Compile the main path's kernels and the chip smoke test's train step
for a described (not attached) TPU v5e chip, at real widths.

Nothing runs: these tests catch what interpret mode cannot (tile
alignment, VMEM and HBM limits) at no chip time.  The topology is
described only inside the module fixture, so that under several test
workers only the one given this file loads the TPU library.
"""
import dataclasses
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro import configs  # noqa: E402
from repro.kernels import flash_attention as fa  # noqa: E402
from repro.kernels import fused_attention  # noqa: E402
from repro.kernels import rmsnorm as rn  # noqa: E402
from repro.kernels import ssd  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import make_schedule  # noqa: E402
from repro.train.step import (abstract_init, abstract_train_state,  # noqa: E402
                              make_prefill_step, make_train_step)

HBM_LIMIT = 15.75e9    # what the v5e compiler reports it may allocate


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_qwen2_widths(one_chip):
    b, s, d = 4, 2048, 64
    q, k = (b, 14, s, d), (b, 2, s, d)
    _compile(lambda q, k, v: fa.flash_attention_fwd(q, k, v, interpret=False),
             *_on(one_chip, [jax.ShapeDtypeStruct(q, jnp.bfloat16),
                             jax.ShapeDtypeStruct(k, jnp.bfloat16),
                             jax.ShapeDtypeStruct(k, jnp.bfloat16)]))


def test_fused_attention_forward_and_backward_compile_at_qwen2_widths(
        one_chip):
    b, s, d = 4, 1024, 64
    q, k = (b, s, 14, d), (b, s, 2, d)

    def forward_and_backward(q, k, v, ct):
        out, vjp = jax.vjp(fused_attention.causal_attention, q, k, v)
        return out, vjp(ct)

    text = _compile(forward_and_backward,
                    *_on(one_chip, [jax.ShapeDtypeStruct(q, jnp.bfloat16),
                                    jax.ShapeDtypeStruct(k, jnp.bfloat16),
                                    jax.ShapeDtypeStruct(k, jnp.bfloat16),
                                    jax.ShapeDtypeStruct(q, jnp.bfloat16)])
                    ).as_text()
    for kernel in ("splash_mqa_fwd", "splash_mqa_dq", "splash_mqa_dkv"):
        assert kernel in text


def test_fused_attention_compiles_at_moonlight_latent_widths(one_chip):
    # latent attention: query/key heads of 128 + 64, value heads of 128
    b, s, h = 2, 4096, 16
    qk, v = (b, s, h, 192), (b, s, h, 128)

    def forward_and_backward(q, k, v, ct):
        out, vjp = jax.vjp(fused_attention.causal_attention, q, k, v)
        return out, vjp(ct)

    _compile(forward_and_backward,
             *_on(one_chip, [jax.ShapeDtypeStruct(qk, jnp.bfloat16),
                             jax.ShapeDtypeStruct(qk, jnp.bfloat16),
                             jax.ShapeDtypeStruct(v, jnp.bfloat16),
                             jax.ShapeDtypeStruct(v, jnp.bfloat16)]))


def test_grouped_matmul_compiles_at_moonlight_expert_widths(one_chip):
    # 2 x 4096 tokens x 6 picks, 8 held experts of 2048 -> 1408
    from repro.kernels.grouped_matmul import grouped_matmul

    def forward_and_backward(x, w, sizes, ct):
        out, vjp = jax.vjp(lambda x, w: grouped_matmul(x, w, sizes), x, w)
        return out, vjp(ct)

    text = _compile(forward_and_backward, *_on(one_chip, [
        jax.ShapeDtypeStruct((49152, 2048), jnp.bfloat16),
        jax.ShapeDtypeStruct((8, 2048, 1408), jnp.bfloat16),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((49152, 1408), jnp.bfloat16)])).as_text()
    assert "gmm" in text and "tgmm" in text


def test_moonlight_dispatch_keeps_one_paths_temporaries(one_chip,
                                                       monkeypatch):
    # one MoE layer's routed part, forward and backward, at the cell's
    # widths: 2 x 4096 tokens, top-6, experts 0-7 of 64, 2048 -> 1408
    from repro.models import moe
    cfg = configs.get("moonlight-16b-a3b-ep8")
    t, k, d, f, held = 8192, 6, 2048, 1408, 8

    def forward_and_backward(x, weights, ids, experts, ct):
        (out, full), vjp = jax.vjp(
            lambda x, w, e: moe._dropless(e, x, w, ids, cfg, 0),
            x, weights, experts)
        return out, full, vjp((ct, np.zeros((), jax.dtypes.float0)))

    args = _on(one_chip, [
        jax.ShapeDtypeStruct((t, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((t, k), jnp.float32),
        jax.ShapeDtypeStruct((t, k), jnp.int32),
        {"w_gate": jax.ShapeDtypeStruct((held, d, f), jnp.bfloat16),
         "w_in": jax.ShapeDtypeStruct((held, d, f), jnp.bfloat16),
         "w_out": jax.ShapeDtypeStruct((held, f, d), jnp.bfloat16)},
        jax.ShapeDtypeStruct((t, d), jnp.bfloat16)])

    def temp():
        # a new function each time: jit's cache does not see the patch
        compiled = _compile(lambda *a: forward_and_backward(*a), *args)
        assert "tgmm" in compiled.as_text()
        return compiled.memory_analysis().temp_size_in_bytes

    either = temp()
    # the full-size dispatch alone, as where the compact buffer is no
    # smaller than it
    monkeypatch.setattr(moe, "_compact_rows", lambda *a: 1 << 40)
    full = temp()
    # differentiating one lax.cond over both keeps both paths' residuals,
    # 2.6 times the full-size dispatch's temporaries; the choice made
    # apart in the forward and the backward keeps one path's, and the
    # conditionals' own buffers (0.3 %)
    assert either <= 1.01 * full, (either, full)


def test_rmsnorm_compiles_at_d896(one_chip):
    _compile(lambda x, w: rn.rmsnorm_fwd(x, w, interpret=False),
             *_on(one_chip, [jax.ShapeDtypeStruct((4096, 896), jnp.bfloat16),
                             jax.ShapeDtypeStruct((896,), jnp.float32)]))


def test_ssd_compiles_at_mamba2_370m_widths(one_chip):
    cfg = configs.get("mamba2-370m")
    b, nc, L = 4, 8, cfg.ssm_chunk_size
    h, p, n = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size
    assert (h, p, n, L) == (32, 64, 128, 256)
    f32 = jnp.float32
    _compile(lambda *a: ssd.ssd_chunk_fwd(*a, interpret=False),
             *_on(one_chip, [jax.ShapeDtypeStruct((b, nc, h, L, p), f32),
                             jax.ShapeDtypeStruct((b, nc, h, L), f32),
                             jax.ShapeDtypeStruct((h,), f32),
                             jax.ShapeDtypeStruct((b, nc, L, n), f32),
                             jax.ShapeDtypeStruct((b, nc, L, n), f32)]))


@pytest.mark.parametrize("arch,batch,seq", chip_smoke.PREFILL_CELLS)
def test_smoke_prefill_compiles_with_kernels(one_chip, monkeypatch,
                                             arch, batch, seq):
    # jax.default_backend() still says cpu here; steer repro.kernels.ops
    # to the compiled kernels as it would choose on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model(dataclasses.replace(configs.get(arch),
                                            use_pallas=True))
    params, _ = abstract_init(model)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    _compile(make_prefill_step(model), _on(one_chip, params),
             _on(one_chip, {"tokens": tokens}))


def test_smoke_train_step_fits_one_chip(one_chip):
    model = build_model(configs.get(chip_smoke.TRAIN_ARCH))
    state, _ = abstract_train_state(model)
    b, s = chip_smoke.TRAIN_BATCH, chip_smoke.TRAIN_SEQ
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    schedule = make_schedule("cosine", peak_lr=3e-4, warmup_steps=5,
                             total_steps=chip_smoke.TRAIN_STEPS)
    step = jax.jit(make_train_step(model, schedule), donate_argnums=(0,))
    compiled = step.lower(_on(one_chip, state), _on(one_chip, batch)).compile()
    # attention runs the fused kernels
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the donated state aliases the new state, so arguments + temporaries
    # is the whole program's footprint
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_LIMIT
