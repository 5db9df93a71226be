"""Moonlight-16B-A3B's benchmark files on the CPU, at a tiny size: the
program against its plain reference (``bench/reference/moonlight_lm.py``)
through the readings a run takes, one chip's share of the experts tied to
the uncut layer, and the hand counts of the FLOP and roofline files."""
import dataclasses
import json

import jax
import numpy as np
import pytest

import tinycell
from benchlib import flops, harness, reftrain, spec
from repro import configs

ARCH = "moonlight-16b-a3b"
CONFIG = json.loads((tinycell.BENCH / "configs" / f"{ARCH}.json").read_text())


@pytest.fixture
def tiny_share(monkeypatch):
    """The registry's tiny preset of one chip's share stands for the
    configuration in ``tinycell``'s cell."""
    real = configs.tiny
    monkeypatch.setattr(configs, "tiny", lambda n: real(f"{ARCH}-ep8")
                        if n == ARCH else real(n))
    # the float32 run below is judged by its own assertions on each gap
    monkeypatch.setitem(tinycell.TINY_LIMITS, ARCH, {
        "loss_gap": 1.0, "grad_norm_gap": 1.0, "update_norm_gap": 1.0})
    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    yield
    jax.config.update(name, was)


def test_the_reference_computes_the_programs_function(monkeypatch, tmp_path,
                                                      tiny_share):
    name = tinycell.make_root(tmp_path, ARCH, "agent", "float32")
    tinycell.use_cpu(monkeypatch, tmp_path, ARCH, "float32")
    cell = spec.find_cell(name, tmp_path)
    assert cell.config["model"]["experts_held"] < cell.config["model"][
        "num_experts"]
    res = harness.run(cell, 2**31 + 9, 0.5, False, 0.0)
    # in float32 the program and the reference differ by rounding alone:
    # the losses, each leaf's first gradient and its change
    for check in ("loss_gap", "grad_norm_gap", "update_norm_gap"):
        assert res["checks"][check]["value"] < 2e-5, (check, res["checks"])


def test_the_shares_of_eight_chips_add_up_to_the_uncut_layer():
    """Eight shares of the routed experts, each computed by the program's
    layer that holds them, with the shared experts (which every chip
    computes alike) counted once, give the reference's uncut layer."""
    from repro.models import layers, moe
    whole = dataclasses.replace(configs.tiny(ARCH), param_dtype="float32",
                                compute_dtype="float32")
    share = dataclasses.replace(whole, experts_held=whole.num_experts // 8)
    params, _ = layers.split_tree(moe.init_moe(jax.random.PRNGKey(3), whole))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole.d_model))
    m = {f.name: getattr(whole, f.name) for f in dataclasses.fields(whole)}
    held = share.experts_held
    with jax.default_matmul_precision("highest"):
        ref = reftrain.load_reference("moonlight_lm")
        want, _ = ref.moe(params, x, m, reftrain.identity)
        shared = layers.mlp(params["shared"], x, whole)
        total = shared
        for lo in range(0, whole.num_experts, held):
            part = dict(params, **{
                k: params[k][lo:lo + held] for k in ("w_gate", "w_in",
                                                     "w_out")})
            out, _, _ = moe.moe_ffn(part, x, share, lo=lo)
            total = total + (out - shared)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_flops_are_the_hand_count():
    m, seq = CONFIG["model"], CONFIG["seq_len"]
    layer = flops.family(CONFIG)
    mla = 2 * (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256
               + 16 * 128 * 2048)
    scores = 2 * seq * 16 * (192 + 128)
    dense = 6 * 2048 * 11264
    # router, shared experts, 6 of 64 experts a token of which 8 are here
    moe_ffn = 2 * 2048 * 64 + 6 * 2048 * 2816 + 6 * 8 / 64 * 6 * 2048 * 1408
    assert (mla, scores, dense) == (27525120, 41943040, 138412032)
    assert layer(m, seq) == pytest.approx(
        mla + scores + (dense + 5 * moe_ffn) / 6, rel=1e-12)
    per_token = flops.forward_flops_per_token(m, seq, layer)
    assert per_token == pytest.approx(
        6 * (mla + scores) + dense + 5 * moe_ffn + 2 * 2048 * 20480,
        rel=1e-12)
    assert 0.87e9 < per_token < 0.89e9


def test_the_latent_attention_kernels_work_is_the_hand_count():
    roofline = spec.metric_module("mla_flash_attention_roofline")
    m, b, s = CONFIG["model"], CONFIG["batch"], CONFIG["seq_len"]
    # QK^T, dQ, dK at 192 and PV, dP, dV at 128, the causal half, over 16
    # heads, 2 rows and 6 layers
    assert roofline.flops(m, b, s) == 3 * s * s * (192 + 128) * 16 * b * 6 \
        == 3092376453120
    # q, k, dq, dk at 192 and v, o, dO, dv at 128 over 16 heads in bf16,
    # the log-sum-exp of 16 heads in f32
    assert roofline.bytes_moved(m, b, s) == 6 * b * s * (
        2 * 16 * (4 * 192 + 4 * 128) + 4 * 16) == 2016411648


def test_a_traced_run_reads_the_new_scopes_inside_their_layers(
        monkeypatch, tmp_path, tiny_share):
    from benchlib import peaks
    name = tinycell.make_root(tmp_path, ARCH)
    tinycell.use_cpu(monkeypatch, tmp_path, ARCH)
    tinycell.cpu_ops_as_device(monkeypatch)
    cell = spec.find_cell(name, tmp_path)
    cell.traffic.update(trace_from_step=2, trace_steps=6)
    # a stand-in peak, so that the reduction runs; never a device number
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"flops_bf16": 1e12,
                                             "hbm_bw": 1e11})
    contexts = []
    real = harness.read_trace

    def read_trace(*args):
        ctx, breakdown = real(*args)
        contexts.append(ctx)
        return ctx, breakdown

    monkeypatch.setattr(harness, "read_trace", read_trace)
    res = harness.run(cell, 13, 2.0, True, 0.0)
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    for scope in ("mla", "router", "experts", "shared_experts"):
        assert metrics[f"{scope}_ms_per_step"] > 0, scope
    # no operation runs under ``flash`` off the TPU
    assert "mla_flash_attention_roofline" not in metrics
    ctx, = contexts
    assert ctx.in_scope("mla") < ctx.in_scope("attention")
    assert sum(ctx.in_scope(s) for s in ("router", "experts",
                                         "shared_experts")) \
        < ctx.in_scope("mlp")
    assert 1e3 * ctx.in_scope("mla") / ctx.steps == metrics["mla_ms_per_step"]


def test_the_expert_kernels_work_is_the_hand_count():
    roofline = spec.metric_module("gmm_roofline")
    m, b, s = CONFIG["model"], CONFIG["batch"], CONFIG["seq_len"]
    # 8192 tokens x 6 picks x 8 / 64 experts = 6144 pairs in each of 5
    # layers; gate, in and out, forward and two backward products each
    pairs = b * s * 6 * 8 // 64
    assert pairs == 6144
    assert roofline.flops(m, b, s) == 9 * 2 * pairs * 2048 * 1408 * 5
    assert roofline.bytes_moved(m, b, s) == 9 * 2 * 5 * (
        pairs * 2048 + pairs * 1408 + 8 * 2048 * 1408)
