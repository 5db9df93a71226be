"""The benchmark's yardstick on the CPU: the trace reduction on a small
recorded trace, the FLOPs function against the configurations' parameter
counts, the table of peaks, and the plain references against the
program's own arithmetic."""
import json
import math

import pytest

import tinycell
from benchlib import flops, peaks, tracereduce

BENCH = tinycell.BENCH

# a recorded stretch (ns): three steps of device work with host spans
DEVICE = [(0, 40, "fusion.1"), (30, 90, "dot.2"), (100, 180, "dot.2"),
          (185, 200, "fusion.1"), (260, 300, "dot.2")]
SPANS = [(85, 102, "loss_sync"), (195, 262, "service_process"),
         (200, 210, "agent_submit")]


def test_busy_is_the_union_of_overlapping_intervals():
    assert tracereduce.merge([(s, e) for s, e, _ in DEVICE], 0, 300) == [
        (0, 90), (100, 180), (185, 200), (260, 300)]
    assert tracereduce.busy([(s, e) for s, e, _ in DEVICE], 0, 300) == 225
    # clipped to the window
    assert tracereduce.busy([(s, e) for s, e, _ in DEVICE], 20, 110) == 80


def test_idle_gaps_are_named_by_the_span_that_overlaps_most():
    idle = tracereduce.gaps([(s, e) for s, e, _ in DEVICE], 0, 320)
    assert idle == [(90, 100), (180, 185), (200, 260), (300, 320)]
    named = tracereduce.name_gaps(idle, SPANS)
    assert named == [("service_process", 60), ("unspanned", 20),
                     ("loss_sync", 10), ("unspanned", 5)]


def test_top_ops_sum_by_name_inside_the_window():
    assert tracereduce.top_ops(DEVICE, 0, 300) == [("dot.2", 180),
                                                   ("fusion.1", 55)]


def _matmul_params(cfg) -> float:
    """Parameters that enter a matrix product once per token: the
    registry's count less norms, biases, per-head scalars and an untied
    embedding's lookup table."""
    d, L = cfg.d_model, cfg.num_layers
    n = cfg.param_count() - d - 2 * d * L          # final + per-layer norms
    if cfg.family == "dense":
        h = cfg.resolved_head_dim
        n -= L * (cfg.num_heads + 2 * cfg.num_kv_heads) * h   # q/k/v biases
    else:
        n -= L * 2 * cfg.ssm_num_heads                         # A_log, D
    if not cfg.tie_embeddings:
        n -= cfg.padded_vocab * d                              # lookup only
    return n


# each configuration's step FLOPs as counted before the count of a layer
# moved to its family's file: the move changes no bit
STEP_FLOPS = {"qwen2-0.5b": 13221922603008.0,
              "mamba2-370m": 20642820784128.0}


@pytest.mark.parametrize("config", ["qwen2-0.5b", "mamba2-370m"])
def test_flops_match_the_parameter_count(config):
    from repro import configs
    name = config
    config = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    m = config["model"]
    cfg = configs.get(config["registry"])
    seq = config["seq_len"]
    layer_flops = flops.family(config)
    per_token = flops.forward_flops_per_token(m, seq, layer_flops)
    assert flops.train_step_flops(m, config["batch"], seq, layer_flops) \
        == STEP_FLOPS[name]
    if m["family"] == "dense":
        mixing = m["num_layers"] * 4 * seq * m["num_heads"] * (
            m["d_model"] // m["num_heads"])
    else:
        di = m["ssm_expand"] * m["d_model"]
        nh, p, n = di // m["ssm_head_dim"], m["ssm_head_dim"], \
            m["ssm_state_size"]
        q = m["ssm_chunk_size"]
        mixing = m["num_layers"] * (2 * q * n + nh * (2 * q * p + 4 * p * n))
    # what is left is 2 FLOPs per matmul parameter (the head counts the
    # real vocabulary, the parameter count the padded one)
    assert (per_token - mixing) / (2 * _matmul_params(cfg)) == \
        pytest.approx(1.0, rel=2e-3)
    assert flops.train_step_flops(m, 4, seq, layer_flops) == \
        3 * 4 * seq * per_token


def test_a_family_file_is_found_under_the_cells_root(tmp_path):
    tinycell.make_root(tmp_path, "qwen2-0.5b")
    # the shipped count, copied into the root, is found by the family
    config = {"model": {"family": "dense"}}
    assert flops.family(config, tmp_path)({"d_model": 4, "d_ff": 2,
                                           "num_heads": 1,
                                           "num_kv_heads": 1,
                                           "head_dim": 0}, 8) == (
        2 * 4 * 12 + 2 * 4 * 4 + 4 * 8 * 4 + 6 * 4 * 2)
    # a new family fails and names the file to add, until it is added
    config["model"]["family"] = "moe"
    with pytest.raises(FileNotFoundError, match="bench/flops/moe.py"):
        flops.family(config, tmp_path)
    (tmp_path / "bench/flops/moe.py").write_text(
        "def layer_flops(m, seq):\n    return 2 * seq\n")
    assert flops.family(config, tmp_path)({}, 8) == 16


def test_the_attention_kernels_work_is_the_hand_count():
    from benchlib import spec
    kernels = spec.metric_module("flash_attention_roofline")
    config = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    m, b, s = config["model"], config["batch"], config["seq_len"]
    # 3 x 2 S^2 h x B x heads x layers: six products at the causal half
    assert kernels.flops(m, b, s) == 3 * 2 * s * s * 64 * b * 14 * 24 \
        == 541165879296
    # q, o, dO, dq over 14 heads and k, v, dk, dv over 2, in bf16; the
    # log-sum-exp of the 14 heads in f32; in each of 24 layers
    assert kernels.bytes_moved(m, b, s) == 24 * b * s * (
        2 * (4 * 14 * 64 + 4 * 2 * 64) + 4 * 14) == 810811392


def test_peaks_are_keyed_by_device_kind_and_refuse_unknown_kinds():
    assert peaks.peak("TPU v5 lite") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bw") == 819e9
    assert "TPU v5e" in peaks.PEAKS["TPU v5 lite"]["source"]
    with pytest.raises(KeyError, match="no published"):
        peaks.peak("TPU v9000")


def test_fp8_rounding_keeps_three_mantissa_bits():
    import jax.numpy as jnp
    from benchlib import reftrain
    x = jnp.array([1.0, 1.0625, 1.09375, 1.125, -3.3, 0.0], jnp.float32)
    got = [float(v) for v in reftrain.fp8_round(x)]
    assert got == [1.0, 1.0, 1.125, 1.125, -3.25, 0.0]
    assert not math.isnan(got[-1])
