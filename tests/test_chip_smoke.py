"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
train phase's checks hold for the launcher at a tiny size on the CPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_smoke_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_smoke_train_phase_checks_hold_on_a_tiny_run(capsys, monkeypatch,
                                                     tmp_path):
    # set, so the launcher leaves the compile cache to JAX, which read
    # the variable (unset) when it was imported: no cache is written
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    clock = chip_smoke._CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    failures = []
    try:
        chip_smoke.train_phase(["--arch", "qwen2-0.5b", "--steps", "12",
                                "--batch", "2", "--seq", "32", "--seed", "0"],
                               clock, failures)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock.on_duration)
        jax.monitoring.unregister_event_listener(clock.on_event)
    assert failures == []
    out = capsys.readouterr().out
    assert "12 profiles ingested" in out and "1 service cycles" in out
    agent = json.loads(out.split("agent ", 1)[1].splitlines()[0])
    assert agent["upload_failures"] == 0 and agent["uploads"] == 12
    assert any(name == "jit(train_step)" for name, _ in clock.compiles)


def test_compile_cache_location(monkeypatch):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_device_peaks_table():
    from repro.launch.mesh import (PRODUCTION_DEVICE_KIND, device_peaks)
    v5e = device_peaks(PRODUCTION_DEVICE_KIND)
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                            16e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="TPU v9"):
        device_peaks("TPU v9")


def test_roofline_refuses_an_unknown_device_kind():
    from repro.roofline.analyze import analyze_record
    rec = {"ok": True, "arch": "qwen2-0.5b", "shape": "train_4k",
           "kind": "train", "seq_len": 4096, "global_batch": 256,
           "devices": 256, "cost_analysis": {"flops": 1e12,
                                             "bytes accessed": 1e9}}
    with pytest.raises(KeyError):
        analyze_record(dict(rec, device_kind="cpu"))
    row = analyze_record(dict(rec, device_kind="TPU v5 lite"))
    assert row.compute_s == pytest.approx(1e12 * 256 / (256 * 197e12))
