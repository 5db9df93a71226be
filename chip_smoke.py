"""Bring-up smoke test of the diagnosed training job on one TPU chip.

    python3 chip_smoke.py          # from the repository root, on a TPU host

One process holds the chip for every phase:

  train    the real launcher (``repro.launch.train.main``) trains the full
           published qwen2-0.5b for TRAIN_STEPS steps with the SysOM-AI
           agent and central service attached: every loss finite, every
           step's profile ingested, no failed upload, >=1 service cycle.
  prefill  ``make_prefill_step`` with the Pallas kernels (flash
           attention for qwen2-0.5b, SSD for mamba2-370m): the published
           config (bf16, full depth) gives finite logits from a program
           holding a ``tpu_custom_call``; then, at full width and
           AGREE_LAYERS layers in float32, the Pallas program and a jnp
           program with no kernel (attention's scores materialised) agree
           within PREFILL_REL_L2 on the same params and tokens.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.  Without a TPU (or outside the repository) the
script exits non-zero before printing it.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
import sys
import time
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TRAIN_ARCH = "qwen2-0.5b"
# batch x seq of the train phase: the compiler's peak for the whole step
# is ~11.5 GB of the chip's 15.75 GB; 8 x 1024 does not fit
TRAIN_BATCH, TRAIN_SEQ = 4, 1024
TRAIN_STEPS = 20
WARMUP_STEPS = 2          # left out of the median step time (compile)
# (arch, batch, seq) of the prefill phase
PREFILL_CELLS = (("qwen2-0.5b", 4, 2048), ("mamba2-370m", 4, 2048))
# Agreement is checked in float32 (float32 XLA matmuls) over AGREE_LAYERS
# layers.  Random-weight mamba2-370m is chaotic in depth: bfloat16
# rounding of an exact kernel moves its logits by up to 0.3 relative L2
# over 48 layers, and bfloat16 matmul passes inside the kernels alone by
# up to 0.2 (CPU emulation, narrow widths).  Over 2 layers the latter
# stays under 5e-3 for both models, and a wrong kernel lands near 1;
# 2e-2 is about five bfloat16 ulps (2^-8).
AGREE_LAYERS = 2
PREFILL_REL_L2 = 2e-2
SEED = 0


class _CompileClock:
    """JAX's compile monitoring events since the last ``reset()``."""
    BACKEND = "/jax/core/compile/backend_compile_duration"
    FRONT = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.reset()

    def reset(self):
        # (program, seconds) per backend compile; a persistent-cache hit
        # is a compile whose seconds are the cache read
        self.compiles = []
        self.cache_hits = 0
        self.front_s = 0.0      # tracing + lowering; nested jits count again

    def on_duration(self, event, duration_secs, fun_name="?", **kwargs):
        if event == self.BACKEND:
            self.compiles.append((fun_name, duration_secs))
        elif event in self.FRONT:
            self.front_s += duration_secs

    def on_event(self, event, **kwargs):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    def report(self) -> str:
        name, slowest = max(self.compiles, key=lambda c: c[1],
                            default=("-", 0.0))
        return (f"backend compile {sum(s for _, s in self.compiles):.3f} s "
                f"over {len(self.compiles)} programs ({self.cache_hits} "
                f"from the persistent cache; slowest {name} {slowest:.3f} s),"
                f" trace+lower {self.front_s:.3f} s")


def train_phase(argv, clock, failures):
    from repro import configs
    from repro.launch import train

    def arg(name):
        return argv[argv.index(name) + 1]

    arch = arg("--arch")
    cfg = configs.get(arch) if "--full" in argv else configs.tiny(arch)
    clock.reset()
    res = train.main(argv)
    steps = len(res.losses)
    warm = res.step_times[WARMUP_STEPS:] or res.step_times
    print(f"[smoke] train: {cfg.name} {cfg.param_count():,} params, "
          f"batch {arg('--batch')} x seq {arg('--seq')}, launcher argv {argv}")
    print(f"[smoke] train: {clock.report()}")
    print(f"[smoke] train: first step {res.step_times[0]:.3f} s, median "
          f"step after {WARMUP_STEPS} warm-up "
          f"{statistics.median(warm) * 1e3:.3f} ms (host clock, ends on the "
          f"loss transfer)")
    print(f"[smoke] train: loss {res.losses[0]:.4f} -> {res.losses[-1]:.4f} "
          f"over {steps} steps; {res.profiles_ingested} profiles ingested, "
          f"{len(res.diagnostics)} events, {res.service_cycles} service "
          f"cycles, agent {json.dumps(res.agent)}")
    want = int(arg("--steps"))
    if steps != want or not all(math.isfinite(x) for x in res.losses):
        failures.append(f"train: {steps}/{want} steps, losses {res.losses}")
    if res.profiles_ingested != steps:
        failures.append(f"train: {res.profiles_ingested} profiles ingested "
                        f"for {steps} steps")
    if res.agent.get("upload_failures") != 0 or res.agent.get("buffered"):
        failures.append(f"train: agent upload path failed {res.agent}")
    if res.service_cycles < 1:
        failures.append("train: no service.process() cycle ran")


def run_prefill(cfg, batch, seq, clock, tag):
    """Compile and run ``make_prefill_step`` on seeded random params and
    tokens; return (logits as float32 numpy, tpu_custom_call present)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.train.step import make_prefill_step

    model = build_model(cfg)
    params = jax.jit(lambda k: model.init(k)[0])(jax.random.PRNGKey(SEED))
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (batch, seq),
                                0, cfg.vocab_size, dtype=jnp.int32)
    clock.reset()
    compiled = jax.jit(make_prefill_step(model)).lower(
        params, {"tokens": tokens}).compile()
    kernel = "tpu_custom_call" in compiled.as_text()
    t0 = time.monotonic()
    out = compiled(params, {"tokens": tokens}).block_until_ready()
    run_s = time.monotonic() - t0
    print(f"[smoke] prefill {cfg.name} {tag} use_pallas={cfg.use_pallas}: "
          f"{cfg.num_layers} layers {cfg.param_dtype}, batch {batch} x seq "
          f"{seq}, first run {run_s:.3f} s, tpu_custom_call={kernel}; "
          f"{clock.report()}")
    return np.asarray(out, np.float32), kernel


def prefill_phase(arch, batch, seq, clock, failures):
    import jax
    import numpy as np

    from repro import configs
    from repro.models import attention

    cfg = dataclasses.replace(configs.get(arch), use_pallas=True)
    logits, kernel = run_prefill(cfg, batch, seq, clock, "published")
    if logits.shape != (batch, 1, cfg.padded_vocab) or \
            not np.isfinite(logits).all() or not kernel:
        failures.append(f"prefill {arch}: logits {logits.shape}, finite "
                        f"{np.isfinite(logits).all()}, tpu_custom_call "
                        f"{kernel}")

    small = dataclasses.replace(cfg, num_layers=AGREE_LAYERS,
                                param_dtype="float32",
                                compute_dtype="float32")
    with jax.default_matmul_precision("float32"):
        # the reference materialises the scores: on a TPU, attention()
        # would otherwise lower it to the fused kernels too
        with mock.patch.object(attention.fused_attention, "fits",
                               lambda seq, head_dim: False):
            ref, ref_kernel = run_prefill(
                dataclasses.replace(small, use_pallas=False), batch, seq,
                clock, "agreement")
        got, got_kernel = run_prefill(small, batch, seq, clock, "agreement")
    diff = got - ref
    rel_l2 = float(np.linalg.norm(diff) / np.linalg.norm(ref))
    print(f"[smoke] prefill {arch} agreement: max|pallas - ref| "
          f"{float(np.abs(diff).max()):.6g} (max|ref| "
          f"{float(np.abs(ref).max()):.6g}), relative L2 {rel_l2:.6g} "
          f"(limit {PREFILL_REL_L2})")
    if not rel_l2 <= PREFILL_REL_L2:
        failures.append(f"prefill {arch}: relative L2 {rel_l2:.6g} > "
                        f"{PREFILL_REL_L2}")
    if not got_kernel or ref_kernel:
        failures.append(f"prefill {arch}: tpu_custom_call in the Pallas "
                        f"program {got_kernel}, in the reference {ref_kernel}")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] FAIL: no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this smoke test runs only on a TPU",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    cache = Path(jax.config.jax_compilation_cache_dir)
    print(f"[smoke] device {dev.platform} {dev.device_kind} x "
          f"{jax.device_count()}, jax {jax.__version__}, compile cache "
          f"{cache} ({len(list(cache.glob('*'))) if cache.is_dir() else 0}"
          f" entries at start)")
    failures: list = []
    train_phase(["--arch", TRAIN_ARCH, "--full", "--steps", str(TRAIN_STEPS),
                 "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
                 "--seed", str(SEED)], clock, failures)
    for arch, batch, seq in PREFILL_CELLS:
        prefill_phase(arch, batch, seq, clock, failures)
    if failures:
        for f in failures:
            print(f"[smoke] FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
