"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Two modes:

  * default       — actually train on the local device(s): the arch's
                    reduced (tiny) config unless --full, synthetic corpus,
                    checkpoints, observability agent + central service.
  * --lower-only  — build the FULL published config against the production
                    mesh and stop after lower+compile (what a real cluster
                    submission does before burning accelerator hours).

Every assigned architecture is selectable; the observability feature
(SysOM-AI) is on by default, exactly as deployed in production.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import List, Optional


def main(argv: Optional[List[str]] = None):
    """Run the launcher on ``argv`` (default: the command line) and return
    the ``LoopResult`` of the training run."""
    ap = argparse.ArgumentParser(description="repro training launcher")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine", choices=["cosine", "wsd"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (heavy!)")
    ap.add_argument("--lower-only", action="store_true",
                    help="lower+compile the full config on the production "
                         "mesh and exit (delegates to launch.dryrun)")
    ap.add_argument("--no-observability", action="store_true")
    ap.add_argument("--sampling-rate", type=float, default=0.10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.lower_only:
        # Re-exec through dryrun so the 512-device XLA flag is set before
        # jax initializes (it must be the process's first jax-touching act).
        import subprocess
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", "train_4k"]
        raise SystemExit(subprocess.call(cmd))

    from repro import configs
    from repro.core.service import CentralService
    from repro.launch.compile_cache import use_compile_cache
    from repro.data import DataPipeline, SyntheticCorpus
    from repro.models import build_model
    from repro.train.loop import LoopConfig, train_loop

    use_compile_cache()
    cfg = configs.get(args.arch) if args.full else configs.tiny(args.arch)
    if not args.full:
        cfg = dataclasses.replace(cfg, param_dtype="float32")
    if args.arch == "minicpm-2b":
        args.schedule = "wsd"   # the arch's published schedule
    model = build_model(cfg)
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params "
          f"({'full' if args.full else 'reduced'} config), "
          f"{args.steps} steps x (batch {args.batch} x seq {args.seq})")

    if cfg.embeds_as_input or cfg.is_enc_dec:
        print("[train] NOTE: modality-stub arch — synthetic embeddings")

    corpus = SyntheticCorpus(cfg.vocab_size, seq_len=args.seq, seed=args.seed)
    pipeline = DataPipeline(corpus, global_batch=args.batch)

    if cfg.embeds_as_input or cfg.is_enc_dec:
        # wrap the pipeline to emit stub embeddings alongside tokens
        import numpy as np

        class _StubPipeline(DataPipeline):
            def build_batch(self, cursor):
                b = super().build_batch(cursor)
                rng = np.random.default_rng(cursor)
                if cfg.is_enc_dec:
                    b["embeds"] = rng.normal(
                        0, 0.02, (self.local_batch, cfg.encoder_seq_len,
                                  cfg.d_model)).astype(np.float32)
                else:
                    b["embeds"] = rng.normal(
                        0, 0.02, (self.local_batch, b["tokens"].shape[1],
                                  cfg.d_model)).astype(np.float32)
                    del b["tokens"]
                return b

        pipeline = _StubPipeline(corpus, global_batch=args.batch)

    service = None if args.no_observability else CentralService()
    loop_cfg = LoopConfig(
        total_steps=args.steps, warmup_steps=max(args.steps // 20, 5),
        peak_lr=args.lr, schedule=args.schedule, log_every=10,
        checkpoint_every=max(args.steps // 4, 10),
        checkpoint_dir=args.ckpt_dir,
        observability=not args.no_observability,
        sampling_rate=args.sampling_rate, seed=args.seed)
    res = train_loop(model, pipeline, loop_cfg, service=service)
    print(f"[train] done: loss {res.losses[0]:.4f} -> {res.losses[-1]:.4f} "
          f"at {res.steps_per_s:.2f} steps/s")
    if service is not None:
        print(f"[train] observability: {service.ingested} profiles ingested, "
              f"{len(service.events)} diagnostic events "
              f"{json.dumps(service.event_counts())}")
    return res


if __name__ == "__main__":
    main()
