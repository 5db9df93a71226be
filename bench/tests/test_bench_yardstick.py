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


@pytest.mark.parametrize("config", ["qwen2-0.5b", "mamba2-370m"])
def test_flops_match_the_parameter_count(config):
    from repro import configs
    config = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    m = config["model"]
    cfg = configs.get(config["registry"])
    seq = config["seq_len"]
    per_token = flops.forward_flops_per_token(m, seq)
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
    assert flops.train_step_flops(m, 4, seq) == 3 * 4 * seq * per_token


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
