"""End-to-end system behaviour: real training with the observability stack
attached, checkpoint/restart, the full agent->service->diagnosis loop
on real (not simulated) collective timings, and sharded-vs-unsharded
service equivalence on the paper's five §5.4 case studies."""
import dataclasses
import tempfile

import jax
import pytest

from repro import configs
from repro.core import simcluster as sc
from repro.core.service import CentralService
from repro.core.sharded import ShardedService
from repro.data import DataPipeline, SyntheticCorpus
from repro.models import build_model
from repro.train.loop import LoopConfig, train_loop


@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(configs.tiny("llama3.2-1b"),
                              param_dtype="float32")
    return build_model(cfg)


def test_train_loop_learns(tiny_model):
    corpus = SyntheticCorpus(tiny_model.cfg.vocab_size, 64, seed=0)
    pipe = DataPipeline(corpus, global_batch=8)
    res = train_loop(tiny_model, pipe,
                     LoopConfig(total_steps=60, warmup_steps=5,
                                peak_lr=1e-3, log_every=1000,
                                observability=False))
    first = sum(res.losses[:5]) / 5
    last = sum(res.losses[-5:]) / 5
    assert last < first - 0.1, (first, last)


def test_train_loop_with_observability_and_resume(tiny_model):
    corpus = SyntheticCorpus(tiny_model.cfg.vocab_size, 64, seed=0)
    svc = CentralService()
    with tempfile.TemporaryDirectory() as d:
        pipe = DataPipeline(corpus, global_batch=8)
        train_loop(tiny_model, pipe,
                   LoopConfig(total_steps=20, warmup_steps=5,
                              checkpoint_every=10, checkpoint_dir=d,
                              log_every=1000, sampling_rate=0.5),
                   service=svc)
        assert svc.ingested >= 1            # agent uploaded profiles
        # resume: picks up at step 20 from the step-20 checkpoint
        pipe2 = DataPipeline(corpus, global_batch=8)
        res2 = train_loop(tiny_model, pipe2,
                          LoopConfig(total_steps=25, warmup_steps=5,
                                     checkpoint_every=10, checkpoint_dir=d,
                                     log_every=1000),
                          service=svc)
        assert len(res2.losses) == 5        # only steps 20..25 ran


def test_real_profiler_collects_from_training(tiny_model):
    """The real SamplingProfiler attached to real JAX training produces
    aggregated python stacks (the §5.1 instrument)."""
    from repro.core.agent import AgentConfig, NodeAgent
    agent = NodeAgent(AgentConfig(sampling_rate=1.0, hz=200.0))
    corpus = SyntheticCorpus(tiny_model.cfg.vocab_size, 64, seed=0)
    pipe = DataPipeline(corpus, global_batch=8)
    agent.start()
    try:
        train_loop(tiny_model, pipe,
                   LoopConfig(total_steps=8, warmup_steps=2, log_every=1000,
                              observability=False))
    finally:
        agent.stop()
    stacks = agent.drain_stacks()
    assert stacks, "sampler collected nothing"
    assert agent.sampler.kept > 0
    assert agent.aggregator.stats.reduction >= 1.0


# ---------------------------------------------------------------------------
# cross-path equivalence over the whole scenario registry (dataclass
# ingest, wire-encoded columnar, sharded and pod paths, event for
# event) lives in tests/test_scenarios.py — one run of
# simcluster.run_scenario_matrix asserts both the expected verdicts and
# path-equality, replacing the hand-enumerated five-case tests that used
# to sit here.  Below: the multi-group concurrent-fault equivalence case
# the matrix does not cover.
# ---------------------------------------------------------------------------


def test_sharded_matches_unsharded_multi_group():
    """Concurrent faults in different groups, groups spread over shards:
    the merged sharded view reports exactly the unsharded diagnoses."""
    def drive(svc):
        fleet = sc.MultiGroupSimCluster(n_groups=6, ranks_per_group=8,
                                        seed=11, samples_per_iter=100)
        fleet.run(svc, 30)
        fleet.add_fault(0, sc.nic_softirq(2, start=30))
        fleet.add_fault(3, sc.thermal_throttle(5, start=30))
        fleet.run(svc, 60)
        return sorted((e.group_id, e.root_cause, e.straggler_rank)
                      for e in svc.events)

    plain = drive(CentralService(window=50))
    sharded = drive(ShardedService(n_shards=4, window=50))
    assert plain and sharded == plain
    causes = {c for _, c, _ in plain}
    assert {"nic_softirq_contention", "gpu_uniform_slowdown"} <= causes
