"""The program names its own work in a profiler trace: the training loop's
step annotation and ``sysom.*`` host spans, and the model's named scopes on
the train step's operations."""
import glob
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core.service import CentralService
from repro.data import DataPipeline, SyntheticCorpus
from repro.models import build_model
from repro.optim import make_schedule
from repro.train.loop import LoopConfig, train_loop
from repro.train.step import init_train_state, make_train_step

ROOT = Path(__file__).resolve().parents[1]
LOOP_SPANS = ("sysom.loop.next_batch", "sysom.loop.dispatch",
              "sysom.loop.step_wait", "sysom.loop.loss_fetch",
              "sysom.loop.observe")
STEPS = 20


@pytest.fixture(scope="module")
def model():
    return build_model(configs.tiny("qwen2-0.5b"))


def _traced_loop(model, tmp_path, observability):
    corpus = SyntheticCorpus(model.cfg.vocab_size, 32, seed=0)
    pipe = DataPipeline(corpus, global_batch=4)
    cfg = LoopConfig(total_steps=STEPS, warmup_steps=2, log_every=1000,
                     observability=observability)
    service = CentralService() if observability else None
    with jax.profiler.trace(str(tmp_path)):
        train_loop(model, pipe, cfg, service=service)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("sysom.") or e.name == "train")


@pytest.fixture(scope="module")
def agent_trace(model, tmp_path_factory):
    return _traced_loop(model, tmp_path_factory.mktemp("agent"), True)


def test_each_loop_span_comes_once_a_step_in_order(agent_trace):
    loop = [(name, args["step"]) for _, _, name, args in agent_trace
            if name.startswith("sysom.loop.")]
    assert loop == [(name, step) for step in range(STEPS)
                    for name in LOOP_SPANS]


def test_the_step_annotation_holds_its_steps_spans(agent_trace):
    steps = [(s, e, int(args["step_num"])) for s, e, name, args in
             agent_trace if name == "train"]
    assert [n for _, _, n in steps] == list(range(STEPS))
    for s, e, name, args in agent_trace:
        if name.startswith("sysom.loop."):
            (holder,) = [n for ss, ee, n in steps if ss <= s and e <= ee]
            assert holder == args["step"]


def test_flush_and_service_run_inside_every_tenth_steps_observe(
        agent_trace):
    observe = {args["step"]: (s, e) for s, e, name, args in agent_trace
               if name == "sysom.loop.observe"}
    for name in ("sysom.agent.flush", "sysom.service.process"):
        inside = sorted(step for s, e, n, _ in agent_trace if n == name
                        for step, (os_, oe) in observe.items()
                        if os_ <= s and e <= oe)
        assert inside == [9, 19], name
    flushed = [args["step"] for _, _, n, args in agent_trace
               if n == "sysom.agent.flush" and args["step"] >= 0]
    assert flushed == [9, 19]
    epochs = [args["epoch"] for _, _, n, args in agent_trace
              if n == "sysom.service.process"]
    assert epochs == [1, 2]


def test_without_observability_no_agent_spans(model, tmp_path):
    names = {name for _, _, name, _ in _traced_loop(model, tmp_path, False)}
    assert names == {"train"} | set(LOOP_SPANS) - {"sysom.loop.observe"}


def test_core_imports_and_spans_leave_jax_out():
    code = ("import sys\n"
            "import repro.core.agent, repro.core.service\n"
            "from repro.core.spans import span\n"
            "with span('sysom.x', step=1):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          env={"PYTHONPATH": str(ROOT / "src")},
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_the_train_steps_ops_carry_each_scope(model):
    state = jax.eval_shape(lambda k: init_train_state(model, k),
                           jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    step = make_train_step(model, make_schedule(
        "cosine", peak_lr=1e-3, warmup_steps=2, total_steps=10))
    text = jax.jit(step).lower(state, batch).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("embed", "attention", "mlp", "head_loss", "optimizer"):
        assert any(re.search(rf"(^|[/(]){scope}($|[/)])", p)
                   for p in paths), scope
