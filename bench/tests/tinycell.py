"""A benchmark cell at a tiny size, for the benchmark's own tests on the CPU.

``make_root`` writes a checkout-like directory holding ``BENCHMARK.json``
and the files of one cell whose configuration is the registry's tiny
preset of an architecture; ``use_cpu`` steers the harness onto the CPU,
and ``cpu_ops_as_device`` reads the CPU's XLA operations in a trace as
the operations of a device.  The steering lives here, in the tests, and
not in options of the harness.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import jax  # noqa: E402

from benchlib import harness, spec  # noqa: E402

# The tiny presets' own limits, between what the program (bf16) and the
# fp8 control read against the reference on the CPU over six seeds:
# qwen2 loss 1.3e-4..2.3e-4 / 7.8e-4.., grad 9.5e-4..4.1e-3 / 0.018..,
# update 8.6e-3..0.026 / 0.047..; mamba2 loss 1.3e-4..3.3e-4 / 1.2e-3..,
# grad 8e-3..0.02 / 0.038.., update 5.3e-3..0.031 / 0.022..
TINY_LIMITS = {
    "qwen2-0.5b": {"loss_gap": 6e-4, "grad_norm_gap": 0.01,
                   "update_norm_gap": 0.04},
    "mamba2-370m": {"loss_gap": 6e-4, "grad_norm_gap": 0.03,
                    "update_norm_gap": 0.04},
}


def tiny_config(arch: str, param_dtype: str = "bfloat16"):
    from repro import configs
    return dataclasses.replace(configs.tiny(arch), param_dtype=param_dtype,
                               compute_dtype=param_dtype)


def make_root(tmp: Path, arch: str, traffic: str = "agent",
              param_dtype: str = "bfloat16") -> str:
    """A root with one cell ``tiny.<traffic>``; returns the cell's name."""
    bench = spec.load_benchmark(ROOT)
    real = json.loads((BENCH / "configs" / f"{arch}.json").read_text())
    tiny = tiny_config(arch, param_dtype)
    config = copy.deepcopy(real)
    config["registry"] = f"tiny-{arch}"
    config["model"] = {k: getattr(tiny, k) for k in config["model"]}
    config["batch"], config["seq_len"] = 4, (64 if tiny.family == "ssm"
                                             else 32)
    config["limits"] = TINY_LIMITS[arch]
    (tmp / "bench" / "configs").mkdir(parents=True)
    for part in ("traffic", "metrics", "flops", "reference"):
        shutil.copytree(BENCH / part, tmp / "bench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    name = f"tiny.{traffic}"
    bench["configs"] = [{"name": "tiny", "source": real["source"],
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "tiny preset"}]
    bench["workloads"] = [{"name": name, "config": "tiny",
                           "traffic": traffic, "chips": 1,
                           "why": "tiny preset"}]
    # the metrics of the real cell of this architecture and traffic
    real_cell = f"{arch}.train.{traffic}"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [name] if real_cell in m["workloads"] else []
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def use_cpu(monkeypatch, tmp: Path, arch: str, param_dtype="bfloat16"):
    """Run the harness on the CPU with the tiny preset under the registry
    name ``tiny-<arch>``, compiling into no persistent cache."""
    from repro import configs
    tiny = tiny_config(arch, param_dtype)
    real_get = configs.get
    monkeypatch.setattr(configs, "get", lambda n: tiny if n == f"tiny-{arch}"
                        else real_get(n))
    monkeypatch.setattr(harness, "check_devices",
                        lambda chips: jax.devices())
    # set, so the program leaves the cache to JAX, which read it (unset)
    # when it was imported: nothing is written
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "cache"))


def cpu_ops_as_device(monkeypatch):
    """Trace readings in which the operations that XLA's CPU threads ran
    stand as the operations of one device, ``/device:TPU:0``; the host
    plane is read as before."""
    from jax.profiler import ProfileData

    from benchlib import tracereduce
    real = tracereduce.read_xplane

    def read_xplane(trace_dir, span_names):
        devices, spans = real(trace_dir, span_names)
        path, = Path(trace_dir).glob("**/*.xplane.pb")
        devices["/device:TPU:0"] = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            if line.name.startswith("tf_XLA")
            for e in line.events
            if not e.name.startswith(("ThreadpoolListener", "ThunkExecutor",
                                      "end: ")))
        return devices, spans

    monkeypatch.setattr(tracereduce, "read_xplane", read_xplane)
