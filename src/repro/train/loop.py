"""Production training loop with first-class observability.

Wires together: data pipeline -> jit'd train step -> async checkpointing,
with the SysOM-AI node agent attached: per-step collective events (host
entry/exit timestamps around the blocking step, §3.2's library-boundary
analog), the real sampling profiler (§5.1), periodic uploads to the central
service, and a mitigation hook fed by the service's diagnoses.

Each iteration is a ``train`` step annotation holding the spans
``sysom.loop.next_batch``, ``dispatch``, ``step_wait``, ``loss_fetch`` and,
with the agent on, ``observe`` (``repro.core.spans``), so a profiler trace
of the job names what the host did between the device's steps.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, load_checkpoint
from repro.core.agent import AgentConfig, NodeAgent
from repro.core.events import CollectiveEvent, IterationProfile
from repro.core.service import CentralService
from repro.core.spans import span
from repro.data import DataPipeline
from repro.models import Model
from repro.optim import make_schedule
from repro.train.step import init_train_state, make_train_step


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    peak_lr: float = 3e-4
    warmup_steps: int = 20
    schedule: str = "cosine"
    observability: bool = True
    sampling_rate: float = 0.10
    group_hash: int = 0x51CAFE0051CAFE00
    comm_version: str = "nccl-2.18"
    seed: int = 0


@dataclasses.dataclass
class LoopResult:
    losses: List[float]
    steps_per_s: float
    final_step: int
    diagnostics: List[Any]
    # host-clock seconds per step, each ending when the loss reaches the
    # host (the first one includes the step's compile)
    step_times: List[float]
    service_cycles: int          # service.process() calls during the run
    profiles_ingested: int       # service.ingested after the final flush
    agent: Dict[str, int]        # NodeAgent.counters() after the final flush


def train_loop(model: Model, pipeline: DataPipeline, cfg: LoopConfig,
               service: Optional[CentralService] = None,
               rank: int = 0) -> LoopResult:
    key = jax.random.PRNGKey(cfg.seed)
    schedule = make_schedule(cfg.schedule, peak_lr=cfg.peak_lr,
                             warmup_steps=cfg.warmup_steps,
                             total_steps=cfg.total_steps)
    step_fn = jax.jit(make_train_step(model, schedule), donate_argnums=(0,))

    # -- restore or init -----------------------------------------------------
    start_step = 0
    state = None
    ckpt = None
    if cfg.checkpoint_dir:
        ckpt = AsyncCheckpointer(cfg.checkpoint_dir)
        last = latest_step(cfg.checkpoint_dir)
        if last is not None:
            template = init_train_state(model, key)
            state, manifest = load_checkpoint(cfg.checkpoint_dir, last, template)
            start_step = manifest["step"]
            pipeline.cursor = manifest["cursor"]
    if state is None:
        state = jax.jit(init_train_state, static_argnums=0)(model, key)

    # -- observability agent ---------------------------------------------------
    agent = None
    if cfg.observability:
        agent = NodeAgent(AgentConfig(rank=rank, sampling_rate=cfg.sampling_rate),
                          service=service)
        from repro.core.collective.introspect import CommStructCodec
        snap = CommStructCodec.pack(cfg.comm_version,
                                    comm_hash=cfg.group_hash, rank=rank,
                                    n_ranks=max(pipeline.num_shards, 1))
        agent.register_process(pid=0, rank=rank, job_id="train-loop",
                               comm_snapshots=[snap])
        agent.start()
    group_id = f"{cfg.group_hash:016x}"

    pipeline.start()
    losses: List[float] = []
    step_times: List[float] = []
    diagnostics: List[Any] = []
    service_cycles = 0
    t_start = time.monotonic()
    try:
        for step in range(start_step, cfg.total_steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with span("sysom.loop.next_batch", step=step):
                    batch_np = next(pipeline)
                with span("sysom.loop.dispatch", step=step):
                    batch = {k: jax.numpy.asarray(v)
                             for k, v in batch_np.items()}
                    t0 = time.monotonic()
                    state, metrics = step_fn(state, batch)
                # the step's outputs come from one execution: its counter
                # is ready once the device has done the whole step
                with span("sysom.loop.step_wait", step=step):
                    jax.block_until_ready(state["step"])
                with span("sysom.loop.loss_fetch", step=step):
                    loss = float(metrics["loss"])
                t1 = time.monotonic()
                losses.append(loss)
                step_times.append(t1 - t0)

                if agent is not None:
                    with span("sysom.loop.observe", step=step):
                        # step boundary = the collective boundary on this
                        # substrate
                        ev = agent.tracer.record_collective(
                            group_id, "AllReduce", entry=t0, exit=t1,
                            nbytes=sum(int(np.prod(l.shape)) * 2 for l in
                                       jax.tree.leaves(state["params"])))
                        prof = IterationProfile(
                            rank=rank, iteration=step, group_id=group_id,
                            iter_time=t1 - t0, cpu_samples=[],
                            kernel_events=[], collectives=[ev])
                        agent.submit(prof)
                        if (step + 1) % 10 == 0:
                            agent.flush()
                            if service is not None:
                                diagnostics.extend(service.process())
                                service_cycles += 1

                if ckpt and (step + 1) % cfg.checkpoint_every == 0:
                    ckpt.save(step + 1, state, cursor=pipeline.cursor)

                if (step + 1) % cfg.log_every == 0:
                    dt = time.monotonic() - t_start
                    line = (f"step {step+1}/{cfg.total_steps} "
                            f"loss={loss:.4f} "
                            f"({(step+1-start_step)/dt:.2f} steps/s)")
                    if "expert_tokens" in metrics:
                        # tokens routed to each expert of each MoE layer:
                        # the busiest expert's load over the mean, by layer
                        tokens = np.asarray(metrics["expert_tokens"])
                        load = tokens.max(-1) / np.maximum(
                            tokens.mean(-1), 1e-9)
                        line += " expert_load_max/mean=" + ",".join(
                            f"{x:.2f}" for x in load)
                    if "dispatch_full" in metrics:
                        # 1 where a dropless layer ran the full-size dispatch
                        line += " dispatch_full=" + ",".join(map(
                            str, np.asarray(metrics["dispatch_full"])))
                    print(line)
    finally:
        pipeline.stop()
        if agent is not None:
            agent.stop()
            agent.flush()
        if ckpt:
            ckpt.wait()

    elapsed = time.monotonic() - t_start
    n = max(cfg.total_steps - start_step, 1)
    return LoopResult(
        losses=losses, steps_per_s=n / elapsed, final_step=cfg.total_steps,
        diagnostics=diagnostics, step_times=step_times,
        service_cycles=service_cycles,
        profiles_ingested=service.ingested if service is not None else 0,
        agent=agent.counters() if agent is not None else {})
