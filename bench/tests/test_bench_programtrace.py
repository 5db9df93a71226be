"""The reduction of a trace by the program's own names, on a small recorded
trace: device time by model scope, device idle by the loop's spans, the
wait on a step against its last device operation; the harness's own
readings unchanged where the program's spans are present; and a traced run
of the tiny cell read by ``bench/trace_report.py`` on the CPU."""
import pytest

import tinycell
from benchlib import harness, peaks, programtrace, spec, tracereduce

# A recorded stretch (ns) of two steps and the next dispatch.  Device: the
# layer loop ``while.1`` covers two fusions of its body; ``copy.5`` has no
# op name.  Host: the loop's spans with their step, the harness's spans.
DEVICE = [(10, 100, "while.1"), (15, 40, "fusion.1"), (40, 95, "fusion.2"),
          (105, 150, "fusion.3"), (150, 170, "fusion.4"),
          (212, 230, "copy.5")]
HLO = """
ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %while.1 = (f32[4]) while(%t), condition=%c, body=%b, metadata={op_name="jit(train_step)/jvp()/while"}
  %fusion.1 = f32[4]{0} fusion(%p), kind=kOutput, calls=%f1, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/checkpoint/rematted_computation/attention/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(train_step)/transpose(jvp(mlp))/mul;jit(train_step)/transpose(jvp(attention))/mul"}
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f3, metadata={op_name="jit(train_step)/transpose(jvp(head_loss))/dot_general" stack_frame_id=3}
  ROOT %fusion.4 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f4, metadata={op_name="jit(train_step)/optimizer/sqrt"}
  %copy.5 = f32[4]{0} copy(%p)
}
"""
LOOP = [(0, 5, "sysom.loop.dispatch", 0), (5, 175, "sysom.loop.step_wait", 0),
        (175, 180, "sysom.loop.loss_fetch", 0),
        (180, 200, "sysom.loop.observe", 0),
        (185, 195, "sysom.agent.flush", 0),
        (186, 194, "sysom.service.process", None),
        (200, 203, "sysom.loop.next_batch", 1),
        (203, 210, "sysom.loop.dispatch", 1),
        (210, 228, "sysom.loop.step_wait", 1),
        (228, 235, "sysom.loop.loss_fetch", 1),
        (235, 236, "sysom.loop.next_batch", 2),
        (236, 240, "sysom.loop.dispatch", 2)]
HARNESS = [(0, 4, "step_dispatch"), (175, 180, "loss_sync"),
           (184, 196, "agent_flush"), (200, 203, "next_batch"),
           (228, 235, "loss_sync"), (235, 236, "next_batch")]
LO, HI = 0, 240


def _xspace(device, host):
    """A serialized ``XSpace``: one TPU plane with its op line, and one host
    thread whose spans carry their ``step`` as an event stat."""
    from jax.profiler import ProfileData

    def plane(pid, name, line, events):
        names = sorted({e[2] for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} "
            + (f"stats {{ metadata_id: 1 int64_value: {rest[0]} }} "
               if rest and rest[0] is not None else "") + "} "
            for s, e, n, *rest in events)
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }} ' for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {evs}}} {meta}'
                f'stat_metadata {{ key: 1 value {{ id: 1 name: "step" }} }} '
                f'}} ')

    return ProfileData.text_proto_to_serialized_xspace(
        plane(1, "/device:TPU:0", tracereduce.DEVICE_OPS_LINE, device)
        + plane(2, "/host:CPU", "python", host))


def _trace_dir(tmp_path, host):
    path = tmp_path / "plugins" / "profile" / "t" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_xspace(DEVICE, host))
    return str(tmp_path)


@pytest.mark.parametrize("path,scope", [
    ("jit(train_step)/transpose(jvp(attention))/dot_general", "attention"),
    ("jit(train_step)/jvp()/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/mul", "mlp"),
    ("jit(train_step)/jvp(embed)/jit(_take)/gather", "embed"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/jvp(head_loss)/reduce_max;"
     "jit(train_step)/attention/mul", "head_loss"),
    ("jit(train_step)/jvp()/while/body/closed_call/add", "unscoped"),
    ("jit(train_step)/jvp(attentions)/mul", "unscoped"),
    ("", "unscoped"),
])
def test_a_scope_is_a_component_of_the_op_name(path, scope):
    assert programtrace.scope_of(path) == scope


def test_op_names_come_from_the_compiled_text():
    paths = programtrace.op_paths(HLO)
    assert set(paths) == {"while.1", "fusion.1", "fusion.2", "fusion.3",
                          "fusion.4"}
    assert paths["fusion.3"].endswith("(jvp(head_loss))/dot_general")


def test_a_loop_counts_only_what_its_body_leaves_uncovered():
    by_op = programtrace.innermost(DEVICE, LO, HI)
    assert by_op == {"while.1": 10, "fusion.1": 25, "fusion.2": 55,
                     "fusion.3": 45, "fusion.4": 20, "copy.5": 18}
    # clipped to the window, the loop's body still counted once
    assert programtrace.innermost(DEVICE, 30, 60) == {"fusion.1": 10,
                                                      "fusion.2": 20}


def test_scopes_and_the_unscoped_rest_add_up_to_busy():
    scopes = programtrace.device_by_scope(
        programtrace.innermost(DEVICE, LO, HI), programtrace.op_paths(HLO))
    assert scopes == {"unscoped": 28, "attention": 25, "mlp": 55,
                      "head_loss": 45, "optimizer": 20}
    assert sum(scopes.values()) == tracereduce.busy(
        ((s, e) for s, e, _ in DEVICE), LO, HI)


def test_idle_by_span_counts_each_idle_nanosecond_once():
    idle = tracereduce.gaps(((s, e) for s, e, _ in DEVICE), LO, HI)
    assert idle == [(0, 10), (100, 105), (170, 212), (230, 240)]
    by_span = programtrace.idle_by_span(idle, LOOP, LO, HI)
    assert by_span == {"dispatch": 20, "step_wait": 17, "loss_fetch": 10,
                       "observe": 20}
    assert sum(by_span.values()) == sum(e - s for s, e in idle)
    # the agent's spans nest inside ``observe``: still 20, not 38
    nested = programtrace.idle_by_span(idle, LOOP, LO, HI, {"agent": (
        "sysom.loop.observe", "sysom.agent.flush", "sysom.service.process")})
    assert nested == {"agent": 20}


def test_the_wait_is_read_against_the_steps_last_device_op():
    lags = programtrace.step_wait_lag(((s, e) for s, e, _ in DEVICE), LOOP)
    # step 0's wait ends 5 ns after its last op; step 1's 2 ns before
    assert lags == [(0, -5), (1, 2)]


def test_the_programs_spans_are_read_with_each_spans_step(tmp_path):
    spans = programtrace.read_spans(_trace_dir(tmp_path, LOOP + HARNESS))
    assert spans == sorted(LOOP, key=lambda sp: sp[:2])


def test_the_harness_reads_the_same_with_the_programs_spans(tmp_path):
    alone = harness_reading(tmp_path / "alone", HARNESS)
    beside = harness_reading(tmp_path / "beside", HARNESS + LOOP)
    assert alone == beside
    assert alone["spans"] == sorted(HARNESS)
    assert alone["gaps"] == [("agent_flush", 42), ("step_dispatch", 10),
                             ("loss_sync", 10), ("unspanned", 5)]


def harness_reading(tmp_path, host):
    devices, spans = tracereduce.read_xplane(_trace_dir(tmp_path, host),
                                             harness.SPANS)
    ops = devices["/device:TPU:0"]
    idle = tracereduce.gaps(((s, e) for s, e, _ in ops), LO, HI)
    return {"spans": sorted(spans), "ops": ops,
            "busy": tracereduce.busy(((s, e) for s, e, _ in ops), LO, HI),
            "top": tracereduce.top_ops(ops, LO, HI),
            "gaps": tracereduce.name_gaps(idle, spans)}


def test_a_traced_cpu_run_finds_the_programs_spans(monkeypatch, tmp_path):
    import jax
    import trace_report

    name = tinycell.make_root(tmp_path, "qwen2-0.5b")
    tinycell.use_cpu(monkeypatch, tmp_path, "qwen2-0.5b")
    cell = spec.find_cell(name, tmp_path)
    cell.traffic.update(trace_from_step=2, trace_steps=12)
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"flops_bf16": 1e12})
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        out = trace_report.report(cell, 11, 3.0, 0.0)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
    assert out["steps"] == 11
    # the CPU has no device plane: what reads it is left out, not 0
    assert "device_ms_by_scope" not in out
    assert "idle_ms_by_span" not in out
    assert out["per_layer"]["device_idle_share"] is None
    for metric in ("input_wait_ms_per_step", "agent_ms_per_step",
                   "step_mfu"):
        assert out["per_layer"][metric] >= 0, metric
    counts = out["span_counts"]
    # the first traced step's batch was asked for before the trace began
    assert counts["sysom.loop.next_batch"] == 11
    for span in ("dispatch", "step_wait", "loss_fetch", "observe"):
        assert counts[f"sysom.loop.{span}"] == 12, span
    assert counts["sysom.agent.flush"] == counts["sysom.service.process"]
    assert 1 <= counts["sysom.agent.flush"] <= 2
    assert 0 < out["host_cost_us_per_step"]


def test_a_program_that_opens_no_spans_has_no_span_cost(monkeypatch):
    import sys

    import trace_report
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert trace_report.host_cost_us_per_step(10) is None


def test_the_op_names_are_the_programs_own_past_the_compile_cache(
        tmp_path):
    """The persistent cache keys a module without its debug information:
    a build that differs only in its scopes gets the other's op names."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def build(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2
        return jax.jit(f)

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_enable_compilation_cache")
    was = {n: getattr(jax.config, n) for n in names}
    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        assert "before/" in build("before").lower(x).compile().as_text()
        cached = build("after").lower(x).compile().as_text()
        assert "before/" in cached and "after/" not in cached
        own = harness.compiled_text(build("after"), (x,))
        assert "after/" in own and "before/" not in own
        assert jax.config.jax_enable_compilation_cache
    finally:
        for n, v in was.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


# Pallas kernels as the compiled HLO prints them: the splash kernels' block
# sizes over several lines before the op name (a TPU v5e compile of the
# train step); a kernel without metadata prints ``{}`` on one line, and the
# computation closes after the fusion that follows it.
KERNEL_HLO = """
  %splash_mqa_fwd_residuals.9 = (bf16[2,7,1024,64]{3,2,1,0}, f32[2,7,1024,128]{3,2,1,0}) custom-call(%fusion.471, %fusion.474), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512, \\"block_kv\\": 512, \\"q_layout\\": 2}"
}}, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/cond/branch_0_fun/flash/vmap(jit(_splash_attention))/splash_mqa_fwd_residuals/pallas_call" stack_frame_id=75}, backend_config={"flag_configs":[]}
  %closed_call.31 = bf16[2,7,1024,64]{3,2,1,0} get-tuple-element(%splash_mqa_fwd_residuals.9), index=0, frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512}"
}}, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/cond/branch_0_fun/flash/vmap(jit(_splash_attention))/splash_mqa_fwd_residuals/pallas_call" stack_frame_id=75}
  %splash_mqa_dq_no_residuals.12 = bf16[2,7,1024,64]{3,2,1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q_dq\\": 512}"
}}, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/attention/cond/branch_0_fun/flash/vmap(jit(_splash_attention))/splash_mqa_dq_no_residuals/pallas_call" stack_frame_id=62}
  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f7, metadata={op_name="jit(train_step)/transpose(jvp(attention))/mul"}
  %fusion.11 = bf16[2,7,64,1024]{3,2,1,0} fusion(%p), kind=kLoop, calls=%f11, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attention/cond/branch_0_fun/flash/transpose"}
  %rms_norm.3 = bf16[4096,896]{1,0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/mlp/rms_norm/pallas_call"}
  ROOT %fusion.10 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f10, metadata={op_name="jit(train_step)/jvp(mlp)/mul"}
}
"""


def test_a_kernel_with_metadata_keeps_its_op_name():
    paths = programtrace.op_paths(KERNEL_HLO)
    assert set(paths) == {"splash_mqa_fwd_residuals.9", "closed_call.31",
                          "splash_mqa_dq_no_residuals.12", "fusion.7",
                          "fusion.11", "rms_norm.3", "fusion.10"}
    assert paths["splash_mqa_dq_no_residuals.12"].endswith(
        "/splash_mqa_dq_no_residuals/pallas_call")
    assert programtrace.scope_of(paths["splash_mqa_fwd_residuals.9"]) == \
        "attention"
    # an empty ``{}`` hides neither its own op name nor the next one's
    assert paths["rms_norm.3"].endswith("/mlp/rms_norm/pallas_call")
    assert programtrace.scope_of(paths["fusion.10"]) == "mlp"


def test_a_scope_counts_its_nested_scopes_and_any_name_is_read():
    by_op = {"splash_mqa_fwd_residuals.9": 6.0,
             "splash_mqa_dq_no_residuals.12": 7.5, "fusion.7": 2.0,
             "fusion.8": 3.0, "fusion.9": 1.0, "copy.1": 0.5}
    paths = dict(programtrace.op_paths(KERNEL_HLO),
                 **{"fusion.8": "jit(train_step)/jvp(mlp)/experts/"
                                "transpose(jvp(router))/dot_general",
                    "fusion.9": "jit(train_step)/mlp/mul;"
                                "jit(train_step)/experts/mul"})
    assert programtrace.in_scope(by_op, paths, "attention") == 15.5
    assert programtrace.in_scope(by_op, paths, "flash") == 13.5
    # names the scopes of SCOPES never had, inside ``mlp``
    assert programtrace.in_scope(by_op, paths, "experts") == 3.0
    assert programtrace.in_scope(by_op, paths, "router") == 3.0
    assert programtrace.in_scope(by_op, paths, "mlp") == 4.0
    assert programtrace.in_scope(by_op, paths, "optimizer") == 0
    # a component, not a substring: ``_splash_attention`` is no
    # ``attention``, and ``flash`` no ``fla``
    assert programtrace.in_scope(by_op, paths, "fla") == 0
    # the nested names do not change the split by ``SCOPES``
    scopes = programtrace.device_by_scope(by_op, paths)
    assert scopes == {"attention": 15.5, "mlp": 4.0, "unscoped": 0.5}


def _context(by_op, paths, idle, steps=2):
    return harness.TraceContext(
        cell=spec.find_cell("qwen2-0.5b.train.agent"), steps=steps,
        window_s=1.0, busy_s=sum(by_op.values()), step_flops=1.0,
        peak_flops=1.0, timings={}, sampler_cpu_s=None,
        sampler_window_s=1.0, agent=True, device_kind="TPU v5 lite",
        device_s_by_op=by_op, op_path=paths, idle_s_by_span=idle)


def test_the_scope_metrics_and_the_rest_add_up_to_device_time():
    by_op = programtrace.innermost(DEVICE, LO, HI)
    paths = programtrace.op_paths(HLO)
    ctx = _context(by_op, paths, {"dispatch": 20, "step_wait": 17})
    read = {m: spec.metric_reader(m)(ctx) for m in (
        "attention_ms_per_step", "mlp_ms_per_step", "head_loss_ms_per_step",
        "optimizer_ms_per_step", "device_ms_per_step",
        "flash_attention_roofline", "dispatch_idle_ms_per_step",
        "step_wait_idle_ms_per_step", "observe_idle_ms_per_step")}
    assert [read[f"{n}_ms_per_step"] for n in (
        "attention", "mlp", "head_loss", "optimizer")] == [
        1e3 * 25 / 2, 1e3 * 55 / 2, 1e3 * 45 / 2, 1e3 * 20 / 2]
    rest = programtrace.device_by_scope(by_op, paths)
    assert sum(read[f"{n}_ms_per_step"] for n in (
        "attention", "mlp", "head_loss", "optimizer")) + 1e3 * (
        rest.get("embed", 0) + rest["unscoped"]) / 2 == \
        read["device_ms_per_step"]
    # nothing ran under ``flash``, and no ``observe`` span was held
    assert read["flash_attention_roofline"] is None
    assert read["observe_idle_ms_per_step"] is None
    assert read["dispatch_idle_ms_per_step"] == 1e3 * 20 / 2
    assert read["step_wait_idle_ms_per_step"] == 1e3 * 17 / 2


def test_the_attention_kernels_roofline_is_their_least_time_over_theirs():
    # qwen2-0.5b's step: 541 GFLOP of attention at 197 TFLOP/s is
    # 2.747 ms, over the 0.99 ms its 811 MB take at 819 GB/s
    kernels = spec.metric_module("flash_attention_roofline")
    paths = programtrace.op_paths(KERNEL_HLO)
    # the kernels count; not the layout under ``flash`` (fusion.11), the
    # rest of ``attention`` (fusion.7) or a kernel elsewhere (rms_norm.3)
    by_op = {"splash_mqa_fwd_residuals.9": 30 * 6.25e-3,
             "splash_mqa_dq_no_residuals.12": 30 * 7.71e-3,
             "fusion.7": 30 * 10e-3, "fusion.11": 30 * 1.8e-3,
             "rms_norm.3": 30 * 1e-3}
    ctx = _context(by_op, paths, {}, steps=30)
    assert kernels.kernel_s(ctx) == pytest.approx(30 * (6.25e-3 + 7.71e-3))
    least = kernels.flops(ctx.cell.config["model"], 4, 1024) / 197e12
    assert kernels.read(ctx) == pytest.approx(
        100 * least / (6.25e-3 + 7.71e-3))
    assert 19 < kernels.read(ctx) < 20
