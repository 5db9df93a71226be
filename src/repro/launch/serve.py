"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Batched KV/SSM-cache decoding with per-step latency tracing through the
SysOM-AI collective tracer (the serving-side observability path).  Reduced
config executes locally; --lower-only compiles the full decode_32k cell on
the production mesh via the dry-run driver.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser(description="repro serving launcher")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--lower-only", action="store_true")
    args = ap.parse_args()

    if args.lower_only:
        import subprocess
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", "decode_32k"]
        raise SystemExit(subprocess.call(cmd))

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core.agent import AgentConfig, NodeAgent
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model
    from repro.train import make_serve_step

    use_compile_cache()
    cfg = dataclasses.replace(configs.tiny(args.arch),
                              param_dtype="float32",
                              compute_dtype="float32")
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    cache, _ = model.init_cache(args.batch, args.cache_len)
    if cfg.is_enc_dec:
        from repro.models import whisper
        frames = jnp.zeros((args.batch, cfg.encoder_seq_len, cfg.d_model),
                           jnp.float32)
        cache = whisper.prime_cross_cache(params, cache, frames, cfg)
    serve = jax.jit(make_serve_step(model), donate_argnums=(1,))

    agent = NodeAgent(AgentConfig(rank=0, sampling_rate=0.1))
    agent.start()
    group = "serve-group"
    if cfg.embeds_as_input and not cfg.is_enc_dec:
        tok = jnp.zeros((args.batch, 1, cfg.d_model), jnp.float32)
    else:
        tok = jnp.ones((args.batch, 1), jnp.int32)
    lat = []
    try:
        for pos in range(args.steps):
            t0 = time.monotonic()
            logits, cache = serve(params, cache, tok,
                                  jnp.full((args.batch,), pos, jnp.int32))
            nxt = jnp.argmax(logits[:, 0, :cfg.vocab_size], axis=-1)
            nxt.block_until_ready()
            t1 = time.monotonic()
            agent.tracer.record_collective(group, "DecodeStep",
                                           entry=t0, exit=t1)
            lat.append(t1 - t0)
            if not (cfg.embeds_as_input and not cfg.is_enc_dec):
                tok = nxt[:, None].astype(jnp.int32)
    finally:
        agent.stop()

    ms = sorted(x * 1e3 for x in lat[2:])
    print(f"[serve] {cfg.name}: batch={args.batch}, {args.steps} steps, "
          f"p50={ms[len(ms)//2]:.2f}ms p95={ms[int(len(ms)*0.95)]:.2f}ms")
    print(f"[serve] traced {len(agent.tracer.drain())} step events; "
          f"sampler kept {agent.sampler.kept} stacks "
          f"(cpu {agent.sampler.cpu_fraction*100:.3f}%)")


if __name__ == "__main__":
    main()
