"""The benchmark's harness on the CPU, at a tiny size: cells found by name
from data files, no run without a TPU, the result line's keys, the step
period tail, and ``correct`` against the plain reference: true for the
program as it is, false for the control and for each fault a training
cell can have."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import tinycell
from benchlib import harness, peaks, reftrain, spec

BENCH = spec.load_benchmark()


@pytest.fixture
def jax_config():
    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    yield
    jax.config.update(name, was)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = spec.find_cell(workload)
    assert cell.config_name and cell.traffic_name
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer
    ref = reftrain.load_reference(cell.config["reference"])
    for fn in ("init_params", "hidden", "head_matrix"):
        assert callable(getattr(ref, fn))
    assert cell.config["limits"]
    assert set(cell.config["limits"]) <= {
        "loss_gap", "loss_gap_first", "grad_norm_gap", "grad_norm_gap_median",
        "update_norm_gap", "update_norm_gap_median"}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(spec.metric_reader(metric))


def test_agent_metrics_stay_out_of_a_cell_without_the_agent(tmp_path):
    name = tinycell.make_root(tmp_path, "qwen2-0.5b", "bare")
    bare = spec.find_cell(name, tmp_path)
    names = {m["name"] for m in bare.per_layer}
    assert "agent_ms_per_step" not in names
    assert {"device_idle_share", "step_mfu"} <= names


def test_a_cell_added_as_data_files_alone_is_picked_up(tmp_path):
    name = tinycell.make_root(tmp_path, "qwen2-0.5b")
    traffic = json.loads((tmp_path / "bench/traffic/agent.json").read_text())
    traffic["sampling_rate"] = 1.0
    (tmp_path / "bench/traffic/agent-full.json").write_text(
        json.dumps(traffic))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.agent-full", "config": "tiny",
                               "traffic": "agent-full", "chips": 1,
                               "why": "sampler at every tick"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.find_cell("tiny.agent-full", tmp_path)
    assert cell.traffic["sampling_rate"] == 1.0
    assert cell.config["registry"] == "tiny-qwen2-0.5b"
    assert spec.find_cell(name, tmp_path).traffic["sampling_rate"] == 0.1
    with pytest.raises(KeyError, match="no workload"):
        spec.find_cell("tiny.nothing", tmp_path)


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(tinycell.BENCH / "run.py"), "--workload",
         "qwen2-0.5b.train.agent", "--seed", "3", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=tmp_path)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_step_p95_is_taken_over_every_step_period():
    probe = harness.Probe(warmup=0, seconds=1, tracing=False, trace_from=0,
                          trace_steps=0, trace_dir="", b1=0.9)
    # 18 steps of 100 ms, then two flush steps of 500 ms
    t, times = 0.0, [0.0]
    for period in [0.1] * 18 + [0.5] * 2:
        t += period
        times.append(t)
    probe.request_times, probe.t_open, probe.t_close = times, 0.0, t
    cell = spec.find_cell("qwen2-0.5b.train.agent")
    e2e = harness.end_to_end(cell, probe, -2.0)
    # numpy's percentile of all 20 periods: rank 18.05 of 0..19, on the
    # flush steps (a median of chunks would read 100)
    assert e2e["step_ms_p95"] == pytest.approx(500)
    assert harness.percentile([0.1] * 19 + [0.5], 95) == pytest.approx(
        0.1 + 0.05 * 0.4)
    assert e2e["tokens_per_s"] == pytest.approx(20 * 4 * 1024 / t)
    assert e2e["setup_s"] == 2.0


def _run(monkeypatch, tmp_path, arch, traffic="agent", tracing=False,
         param_dtype="bfloat16"):
    name = tinycell.make_root(tmp_path, arch, traffic, param_dtype)
    tinycell.use_cpu(monkeypatch, tmp_path, arch, param_dtype)
    cell = spec.find_cell(name, tmp_path)
    return harness.run(cell, 2**31 + 5, 0.5, tracing, 0.0)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-370m"])
def test_the_reference_computes_the_programs_function(monkeypatch, tmp_path,
                                                      jax_config, arch):
    # in float32 the program and the reference differ by rounding alone
    res = _run(monkeypatch, tmp_path, arch, param_dtype="float32")
    for name in ("loss_gap", "grad_norm_gap", "update_norm_gap"):
        assert res["checks"][name]["value"] < 2e-5, (name, res["checks"])


def test_a_sound_run_is_correct_and_its_last_line_has_the_result_keys(
        monkeypatch, tmp_path, jax_config, capsys):
    name = tinycell.make_root(tmp_path, "qwen2-0.5b")
    tinycell.use_cpu(monkeypatch, tmp_path, "qwen2-0.5b")
    real_find = spec.find_cell
    monkeypatch.setattr(spec, "find_cell", lambda w: real_find(w, tmp_path))
    assert harness.main(["--workload", name, "--seed", "7", "--seconds",
                         "0.5", "--trace", "0"]) == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms_p95",
                                      "setup_s"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert out.err.strip().splitlines()[-1].startswith("[bench] check ")
    for check in result["checks"].values():
        assert set(check) == {"value", "limit"}


# A configuration added as files alone: its family's count of FLOPs in the
# cell's own root (here in place of the dense count copied there); a
# reference with an extra loss term (zero, as the dense program adds none);
# and metrics that read the step's FLOPs and a scope's device time.
PLANTED = {
    "flops/dense.py": (
        "def layer_flops(m, seq):\n"
        "    return 1000 * m['d_model'] + seq\n"),
    "reference/dense_lm_extra.py": (
        "import jax.numpy as jnp\n"
        "from dense_lm import head_matrix, hidden, init_params  # noqa\n\n\n"
        "def extra_loss(params, tokens, m, q):\n"
        "    return 0.0 * jnp.sum(params['final_norm']) + jnp.zeros(\n"
        "        tokens.shape[0])\n"),
    "metrics/planted_step_flops.py": (
        "def read(ctx):\n"
        "    return ctx.step_flops\n"),
    "metrics/planted_attention_ms.py": (
        "def read(ctx):\n"
        "    busy = ctx.in_scope('attention')\n"
        "    return 1e3 * busy / ctx.steps if busy > 0 else None\n"),
}


def _plant(root, name):
    for rel, text in PLANTED.items():
        (root / "bench" / rel).write_text(text)
    config_path = root / "bench/configs/tiny.json"
    config = json.loads(config_path.read_text())
    config.update(reference="dense_lm_extra")
    config_path.write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for metric, unit in (("planted_step_flops", "FLOP"),
                         ("planted_attention_ms", "ms")):
        bench["per_layer"].append({
            "name": metric, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "model step",
            "moves": "tokens_per_s", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return config


@pytest.mark.parametrize("cpu_ops", [False, True],
                         ids=["no_device_plane", "cpu_ops_as_device"])
def test_a_traced_run_reads_the_host_spans(monkeypatch, tmp_path,
                                           jax_config, cpu_ops):
    name = tinycell.make_root(tmp_path, "qwen2-0.5b")
    config = _plant(tmp_path, name)
    tinycell.use_cpu(monkeypatch, tmp_path, "qwen2-0.5b")
    if cpu_ops:
        tinycell.cpu_ops_as_device(monkeypatch)
    cell = spec.find_cell(name, tmp_path)
    cell.traffic.update(trace_from_step=2, trace_steps=12)
    # a stand-in peak, so that the reduction runs; never a device number
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"flops_bf16": 1e12,
                                             "hbm_bw": 1e11})
    res = harness.run(cell, 11, 3.0, True, 0.0)
    assert res["correct"] is True, res["checks"]
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    for name in ("input_wait_ms_per_step", "agent_ms_per_step",
                 "sampler_cpu_share", "step_mfu"):
        assert metrics[name] >= 0, name
    m, b, s = config["model"], config["batch"], config["seq_len"]
    assert metrics["planted_step_flops"] == 3.0 * b * s * (
        m["num_layers"] * (1000 * m["d_model"] + s)
        + 2 * m["d_model"] * m["vocab_size"])
    # no operation runs under ``flash`` off the TPU
    assert "flash_attention_roofline" not in metrics
    if cpu_ops:
        scopes = [metrics[f"{n}_ms_per_step"] for n in
                  ("attention", "mlp", "head_loss", "optimizer")]
        assert min(scopes) > 0
        assert sum(scopes) < metrics["device_ms_per_step"]
        assert metrics["planted_attention_ms"] == metrics[
            "attention_ms_per_step"]
        for name in ("dispatch", "step_wait", "loss_fetch", "observe"):
            assert metrics[f"{name}_idle_ms_per_step"] >= 0, name
    else:
        # the CPU has no device plane: device metrics are left out, not 0
        for name in ("device_idle_share", "planted_attention_ms",
                     "attention_ms_per_step", "dispatch_idle_ms_per_step"):
            assert name not in metrics, name
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not (tmp_path / ".bench_trace").exists() or not any(
        (tmp_path / ".bench_trace").iterdir())


def test_the_bare_cell_calls_no_agent(monkeypatch, tmp_path, jax_config):
    res = _run(monkeypatch, tmp_path, "qwen2-0.5b", traffic="bare")
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["agent_calls"]["value"] == 0


def _break_step(monkeypatch, how):
    from repro.train import loop as loop_mod
    real = loop_mod.make_train_step

    def make(model, schedule, *args, **kwargs):
        if how == "half_batch":
            def half_loss(params, batch, loss_fn=model.loss_fn):
                return loss_fn(params, {
                    k: v[:v.shape[0] // 2] for k, v in batch.items()})
            model = dataclasses.replace(model, loss_fn=half_loss)
        inner = real(model, schedule, *args, **kwargs)
        if how != "state_unchanged":
            return inner

        def train_step(state, batch):
            return state, inner(state, batch)[1]
        return train_step

    monkeypatch.setattr(loop_mod, "make_train_step", make)


@pytest.mark.parametrize("how", ["state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(monkeypatch, tmp_path, jax_config,
                                      how):
    _break_step(monkeypatch, how)
    res = _run(monkeypatch, tmp_path, "qwen2-0.5b")
    assert res["correct"] is False, res["checks"]


def test_labels_that_are_not_the_next_token_are_not_correct(
        monkeypatch, tmp_path, jax_config):
    from repro.data import DataPipeline
    real = DataPipeline.build_batch

    def build_batch(self, cursor):
        batch = real(self, cursor)
        return dict(batch, labels=batch["tokens"])

    monkeypatch.setattr(DataPipeline, "build_batch", build_batch)
    res = _run(monkeypatch, tmp_path, "qwen2-0.5b")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["labels_not_next_token"]["value"] > 0


def test_a_configuration_without_limits_is_refused(monkeypatch, tmp_path):
    name = tinycell.make_root(tmp_path, "mamba2-370m")
    tinycell.use_cpu(monkeypatch, tmp_path, "mamba2-370m")
    cell = spec.find_cell(name, tmp_path)
    del cell.config["limits"]
    with pytest.raises(SystemExit, match="no limits"):
        harness.run(cell, 3, 0.5, False, 0.0)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-370m"])
def test_the_control_is_not_correct(monkeypatch, tmp_path, jax_config, arch):
    """The reference in fp8, in the program's place, fails the limits."""
    name = tinycell.make_root(tmp_path, arch)
    cell = spec.find_cell(name, tmp_path)
    from repro.data import SyntheticCorpus
    corpus = SyntheticCorpus(cell.config["model"]["vocab_size"],
                             seq_len=cell.config["seq_len"], seed=11)
    batches = []
    for step in range(harness.CAPTURE_STEPS):
        seqs = np.stack([corpus.sequence(step * 4 + i) for i in range(4)])
        batches.append({"tokens": seqs[:, :-1], "labels": seqs[:, 1:]})
    ref = harness.reference_readings(cell, 11, batches)
    ctl = harness.reference_readings(cell, 11, batches, q=reftrain.fp8)
    gaps = reftrain.gaps(ctl, ref)
    limits = cell.config["limits"]
    assert any(gaps[k] > limits[k] for k in limits), gaps


def _tiny_batches(cell, seed):
    from repro.data import SyntheticCorpus
    corpus = SyntheticCorpus(cell.config["model"]["vocab_size"],
                             seq_len=cell.config["seq_len"], seed=seed)
    batches = []
    for step in range(harness.CAPTURE_STEPS):
        seqs = np.stack([corpus.sequence(step * 4 + i) for i in range(4)])
        batches.append({"tokens": seqs[:, :-1], "labels": seqs[:, 1:]})
    return batches


def _with_extra_loss(ref, name, extra_loss):
    import types
    mod = types.ModuleType(name)
    for fn in ("init_params", "hidden", "head_matrix"):
        setattr(mod, fn, getattr(ref, fn))
    mod.extra_loss = extra_loss
    return mod


def test_a_references_extra_loss_moves_the_gradient_not_the_loss(tmp_path):
    import jax.numpy as jnp
    name = tinycell.make_root(tmp_path, "qwen2-0.5b")
    cell = spec.find_cell(name, tmp_path)
    config = cell.config
    ref = reftrain.load_reference(config["reference"], tmp_path)
    opt = dict(config["train"], peak_lr=cell.traffic["peak_lr"],
               warmup_steps=max(cell.traffic["total_steps"] // 20, 5))
    batches = _tiny_batches(cell, 5)

    def readings(module):
        return reftrain.reference_steps(module, config["model"], opt,
                                        batches, jax.random.PRNGKey(5))

    plain = readings(ref)
    zero = readings(_with_extra_loss(
        ref, "zero_extra", lambda p, t, m, q: jnp.zeros(t.shape[0])))
    assert zero == plain
    # a term on the final norm's weight moves its gradient and the norms
    # compared, and leaves the compared loss, the cross-entropy, alone
    pull = _with_extra_loss(ref, "norm_pull", lambda p, t, m, q: jnp.sum(
        p["final_norm"]) * jnp.ones(t.shape[0]))
    moved = readings(pull)
    assert moved.losses[0] == plain.losses[0]
    assert reftrain.gaps(moved, plain)["grad_norm_gap"] > 0.1
    # summed over the rows and divided by the token count, the term's
    # gradient is that of its mean over rows: 1 on each weight
    tokens, labels = batches[0]["tokens"], batches[0]["labels"]
    grads = []
    for module in (ref, pull):
        init, accumulate, _ = reftrain._programs(
            module, config["model"], opt, reftrain.identity)
        params = init(jax.random.PRNGKey(5), reftrain._Frozen(
            config["model"]))
        acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                           params)
        for r in range(tokens.shape[0]):
            acc, _ = accumulate(params, acc, tokens[r:r + 1],
                                labels[r:r + 1])
        grads.append(np.asarray(acc["final_norm"]) / tokens.size)
    np.testing.assert_allclose(grads[1] - grads[0], 1.0, rtol=1e-5)
