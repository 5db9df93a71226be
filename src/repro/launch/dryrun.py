import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# NOTE: the lines above MUST run before any other import (jax locks the
# device count and the backend on first init).  This module is the ONLY
# place the 512 placeholder devices exist; tests/benches see the real
# single CPU device.  It is a placeholder-device tool, pinned to the CPU so
# that it never takes an accelerator from the process that owns it.

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                      # noqa: E402
from repro.launch import mesh as mesh_lib      # noqa: E402
from repro.models import SHAPES, build_model   # noqa: E402
from repro.optim import make_schedule          # noqa: E402
from repro.parallel.sharding import tree_pspecs, batch_pspec  # noqa: E402
from repro.parallel.context import sharding_context  # noqa: E402
from repro.roofline import hlo as hlo_lib      # noqa: E402
from repro.train import (                      # noqa: E402
    make_prefill_step, make_serve_step, make_train_step,
)
from repro.train.step import abstract_train_state, abstract_init, train_state_pspecs  # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# --------------------------------------------------------------------------
# hillclimb variants: sharding-rule + config overrides (EXPERIMENTS.md §Perf)
# --------------------------------------------------------------------------
VARIANTS = {
    "baseline": dict(rules={}, cfg={}),
    "no_fsdp": dict(rules={"fsdp": False}, cfg={}),
    "remat_none": dict(rules={}, cfg={"remat": "none"}),
    "remat_full": dict(rules={}, cfg={"remat": "full"}),
    "no_kvshard": dict(rules={"shard_kv_seq": False}, cfg={}),
    "fp32_params": dict(rules={}, cfg={"param_dtype": "float32"}),
    "chunked_attn": dict(rules={}, cfg={"attention_impl": "chunked"}),
    "chunked_attn_nofsdp": dict(rules={"fsdp": False},
                                cfg={"attention_impl": "chunked"}),
    "chunked_attn_remat_full": dict(
        rules={}, cfg={"attention_impl": "chunked", "remat": "full"}),
    "chunked_attn_remat_none": dict(
        rules={}, cfg={"attention_impl": "chunked", "remat": "none"}),
    "opt_dense": dict(rules={"fsdp": False},
                      cfg={"attention_impl": "chunked", "ce_impl": "chunked"}),
    "opt_fsdp": dict(rules={},
                     cfg={"attention_impl": "chunked", "ce_impl": "chunked"}),
    "seq_parallel": dict(rules={"seq_parallel": True}, cfg={}),
    "chunked_attn_sp": dict(rules={"seq_parallel": True},
                            cfg={"attention_impl": "chunked"}),
    "no_ssm_tp": dict(rules={"ssm_tp": False}, cfg={}),
    "no_ssm_tp_nofsdp": dict(rules={"ssm_tp": False, "fsdp": False}, cfg={}),
    "opt_moe": dict(rules={}, cfg={"attention_impl": "chunked",
                                   "ce_impl": "chunked",
                                   "moe_dispatch_groups": 16}),
    "opt_moe_sp": dict(rules={"seq_parallel": True},
                       cfg={"attention_impl": "chunked",
                            "ce_impl": "chunked",
                            "moe_dispatch_groups": 16}),
    "opt_sp": dict(rules={"seq_parallel": True},
                   cfg={"attention_impl": "chunked", "ce_impl": "chunked"}),
    "opt_serve": dict(rules={"seq_parallel": True, "fsdp": False},
                      cfg={"attention_impl": "chunked"}),
}


def _abstract_cache(model, batch, seq_len):
    holder = {}

    def f():
        cache, specs = model.init_cache(batch, seq_len)
        holder["specs"] = specs
        return cache

    shapes = jax.eval_shape(f)
    return shapes, holder["specs"]


def _sharding(mesh, pspec_tree):
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               variant: str = "baseline") -> dict:
    vconf = VARIANTS[variant]
    cfg = configs.get(arch)
    cfg = dataclasses.replace(cfg, **vconf["cfg"])
    shape = SHAPES[shape_name]
    model = build_model(cfg)

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rules = mesh_lib.make_rules(multi_pod=multi_pod, **vconf["rules"])
    n_devices = mesh.devices.size

    t0 = time.time()
    ctx = sharding_context(mesh, rules)
    ctx.__enter__()
    if shape.kind == "train":
        state_shapes, state_specs = abstract_train_state(model)
        state_ps = train_state_pspecs(state_shapes, state_specs, mesh, rules)
        batch_shapes = model.batch_spec(shape)
        batch_ps = batch_pspec(batch_shapes, mesh, rules)
        step = make_train_step(model, make_schedule("cosine", peak_lr=3e-4))
        jitted = jax.jit(
            step,
            in_shardings=(_sharding(mesh, state_ps), _sharding(mesh, batch_ps)),
            out_shardings=(_sharding(mesh, state_ps), None),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_shapes, batch_shapes)
    elif shape.kind == "prefill":
        p_shapes, p_specs = abstract_init(model)
        p_ps = tree_pspecs(p_specs, p_shapes, mesh, rules)
        batch_shapes = model.batch_spec(shape)
        batch_ps = batch_pspec(batch_shapes, mesh, rules)
        step = make_prefill_step(model)
        jitted = jax.jit(
            step,
            in_shardings=(_sharding(mesh, p_ps), _sharding(mesh, batch_ps)),
        )
        lowered = jitted.lower(p_shapes, batch_shapes)
    else:  # decode
        p_shapes, p_specs = abstract_init(model)
        p_ps = tree_pspecs(p_specs, p_shapes, mesh, rules)
        b = shape.global_batch
        cache_shapes, cache_specs = _abstract_cache(model, b, shape.seq_len)
        cache_ps = tree_pspecs(cache_specs, cache_shapes, mesh, rules)
        if cfg.embeds_as_input and not cfg.is_enc_dec:
            tok = jax.ShapeDtypeStruct((b, 1, cfg.d_model), "float32")
        else:
            tok = jax.ShapeDtypeStruct((b, 1), "int32")
        pos = jax.ShapeDtypeStruct((b,), "int32")
        io_ps = batch_pspec({"tok": tok, "pos": pos}, mesh, rules)
        step = make_serve_step(model)
        jitted = jax.jit(
            step,
            in_shardings=(_sharding(mesh, p_ps), _sharding(mesh, cache_ps),
                          _sharding(mesh, io_ps["tok"]),
                          _sharding(mesh, io_ps["pos"])),
            out_shardings=(None, _sharding(mesh, cache_ps)),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(p_shapes, cache_shapes, tok, pos)
    ctx.__exit__(None, None, None)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # ---- artifacts --------------------------------------------------------
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost = {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and (
                "flops" in k or "bytes" in k or "utilization" not in k)}

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for field in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "temp_size_in_bytes",
                      "alias_size_in_bytes"):
            if hasattr(ma, field):
                mem[field] = int(getattr(ma, field))
    except Exception as e:  # noqa: BLE001
        mem["error"] = repr(e)

    hlo_text = compiled.as_text()
    coll_total, coll_by_op, coll_counts = hlo_lib.collective_bytes(hlo_text)

    cfg_n = configs.get(arch)
    record = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "multi_pod": multi_pod, "devices": int(n_devices),
        "device_kind": mesh_lib.PRODUCTION_DEVICE_KIND,
        "kind": shape.kind,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_analysis": cost,
        "memory_analysis": mem,
        "collective_bytes_total": int(coll_total),
        "collective_bytes_by_op": coll_by_op,
        "collective_counts": coll_counts,
        "hlo_chars": len(hlo_text),
        "params_total": cfg_n.param_count(),
        "params_active": cfg_n.param_count(active_only=True),
        "ok": True,
    }
    return record


# --------------------------------------------------------------------------
# cost-extrapolation pass
#
# XLA's cost_analysis() counts a while-loop (lax.scan) body ONCE, so the
# scanned full-depth program under-reports per-layer flops/bytes by ~L.
# The accurate-cost path lowers UNROLLED reduced-depth variants at two
# depths L1 < L2 and extrapolates linearly:  cost(L) = fixed + L * slope.
# Layer cost is exactly linear in depth (identical layers), so this is
# exact up to GSPMD schedule differences, and it also corrects
# "bytes accessed" and the collective schedule, which cannot be hand-fixed.
# --------------------------------------------------------------------------


def _cost_metrics(arch, shape_name, L, *, multi_pod, variant):
    vconf = VARIANTS[variant]
    cfg = configs.get(arch)
    overrides = dict(vconf["cfg"])
    overrides.update(num_layers=L, scan_layers=False)
    if cfg.is_enc_dec:
        overrides["encoder_layers"] = L
    cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rules = mesh_lib.make_rules(multi_pod=multi_pod, **vconf["rules"])

    ctx = sharding_context(mesh, rules)
    ctx.__enter__()
    if shape.kind == "train":
        state_shapes, state_specs = abstract_train_state(model)
        state_ps = train_state_pspecs(state_shapes, state_specs, mesh, rules)
        batch_shapes = model.batch_spec(shape)
        batch_ps = batch_pspec(batch_shapes, mesh, rules)
        step = make_train_step(model, make_schedule("cosine", peak_lr=3e-4))
        compiled = jax.jit(
            step,
            in_shardings=(_sharding(mesh, state_ps), _sharding(mesh, batch_ps)),
            out_shardings=(_sharding(mesh, state_ps), None),
            donate_argnums=(0,),
        ).lower(state_shapes, batch_shapes).compile()
    elif shape.kind == "prefill":
        p_shapes, p_specs = abstract_init(model)
        p_ps = tree_pspecs(p_specs, p_shapes, mesh, rules)
        batch_shapes = model.batch_spec(shape)
        batch_ps = batch_pspec(batch_shapes, mesh, rules)
        compiled = jax.jit(
            make_prefill_step(model),
            in_shardings=(_sharding(mesh, p_ps), _sharding(mesh, batch_ps)),
        ).lower(p_shapes, batch_shapes).compile()
    else:
        p_shapes, p_specs = abstract_init(model)
        p_ps = tree_pspecs(p_specs, p_shapes, mesh, rules)
        b = shape.global_batch
        cache_shapes, cache_specs = _abstract_cache(model, b, shape.seq_len)
        cache_ps = tree_pspecs(cache_specs, cache_shapes, mesh, rules)
        if cfg.embeds_as_input and not cfg.is_enc_dec:
            tok = jax.ShapeDtypeStruct((b, 1, cfg.d_model), "float32")
        else:
            tok = jax.ShapeDtypeStruct((b, 1), "int32")
        pos = jax.ShapeDtypeStruct((b,), "int32")
        io_ps = batch_pspec({"tok": tok, "pos": pos}, mesh, rules)
        compiled = jax.jit(
            make_serve_step(model),
            in_shardings=(_sharding(mesh, p_ps), _sharding(mesh, cache_ps),
                          _sharding(mesh, io_ps["tok"]),
                          _sharding(mesh, io_ps["pos"])),
            out_shardings=(None, _sharding(mesh, cache_ps)),
            donate_argnums=(1,),
        ).lower(p_shapes, cache_shapes, tok, pos).compile()
    ctx.__exit__(None, None, None)

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    total, by_op, _counts = hlo_lib.collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(total),
            "coll_by_op": {k: float(v) for k, v in by_op.items()}}


def _extrapolation_depths(cfg) -> tuple:
    if cfg.is_hybrid:
        return cfg.attn_every, 2 * cfg.attn_every
    return 2, 4


def cost_extrapolate(arch, shape_name, *, multi_pod=False,
                     variant="baseline") -> dict:
    cfg = configs.get(arch)
    L_full = cfg.num_layers
    L1, L2 = _extrapolation_depths(cfg)
    m1 = _cost_metrics(arch, shape_name, L1, multi_pod=multi_pod,
                       variant=variant)
    m2 = _cost_metrics(arch, shape_name, L2, multi_pod=multi_pod,
                       variant=variant)

    def extr(key):
        slope = (m2[key] - m1[key]) / (L2 - L1)
        return max(m1[key] + (L_full - L1) * slope, 0.0)

    by_op = {}
    for op in set(m1["coll_by_op"]) | set(m2["coll_by_op"]):
        a, b = m1["coll_by_op"].get(op, 0.0), m2["coll_by_op"].get(op, 0.0)
        slope = (b - a) / (L2 - L1)
        by_op[op] = max(a + (L_full - L1) * slope, 0.0)

    return {"arch": arch, "shape": shape_name, "variant": variant,
            "multi_pod": multi_pod, "L1": L1, "L2": L2, "L_full": L_full,
            "flops_per_device": extr("flops"),
            "bytes_per_device": extr("bytes"),
            "collective_bytes_total": extr("coll"),
            "collective_bytes_by_op": by_op,
            "probes": {"L1": m1, "L2": m2}, "ok": True}


def run_cost_and_save(arch, shape_name, multi_pod, variant="baseline",
                      out_dir: Path = RESULTS_DIR) -> dict:
    tag = (f"{arch}_{shape_name}_{'pod2' if multi_pod else 'pod1'}_"
           f"{variant}_cost")
    out_dir.mkdir(parents=True, exist_ok=True)
    try:
        rec = cost_extrapolate(arch, shape_name, multi_pod=multi_pod,
                               variant=variant)
        print(f"[cost] OK  {tag}: flops/dev={rec['flops_per_device']:.3e} "
              f"coll={rec['collective_bytes_total']:.3e}B")
    except Exception as e:  # noqa: BLE001
        rec = {"arch": arch, "shape": shape_name, "variant": variant,
               "multi_pod": multi_pod, "ok": False, "error": repr(e),
               "traceback": traceback.format_exc()[-4000:]}
        print(f"[cost] FAIL {tag}: {e!r}"[:400])
    (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    return rec


def run_and_save(arch, shape_name, multi_pod, variant="baseline",
                 out_dir: Path = RESULTS_DIR) -> dict:
    tag = f"{arch}_{shape_name}_{'pod2' if multi_pod else 'pod1'}_{variant}"
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{tag}.json"
    try:
        rec = lower_cell(arch, shape_name, multi_pod=multi_pod, variant=variant)
        print(f"[dryrun] OK  {tag}: compile={rec['compile_s']}s "
              f"flops={rec['cost_analysis'].get('flops', 0):.3e} "
              f"coll={rec['collective_bytes_total']:.3e}B")
    except Exception as e:  # noqa: BLE001
        rec = {"arch": arch, "shape": shape_name, "variant": variant,
               "multi_pod": multi_pod, "ok": False,
               "error": repr(e), "traceback": traceback.format_exc()[-4000:]}
        print(f"[dryrun] FAIL {tag}: {e!r}"[:400])
    path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=sorted(VARIANTS))
    ap.add_argument("--all", action="store_true",
                    help="all applicable cells on the selected mesh")
    ap.add_argument("--cost", action="store_true",
                    help="run the unrolled cost-extrapolation pass instead")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.all:
        n_ok = n_fail = n_skip = 0
        for arch in configs.ASSIGNED_ARCHS:
            for shape_name in SHAPES:
                if not configs.shape_applicable(arch, shape_name):
                    print(f"[dryrun] SKIP {arch}_{shape_name} (per DESIGN.md §4)")
                    n_skip += 1
                    continue
                tag = (f"{arch}_{shape_name}_"
                       f"{'pod2' if args.multi_pod else 'pod1'}_{args.variant}"
                       + ("_cost" if args.cost else ""))
                if args.skip_existing and (RESULTS_DIR / f"{tag}.json").exists():
                    existing = json.loads((RESULTS_DIR / f"{tag}.json").read_text())
                    if existing.get("ok"):
                        n_ok += 1
                        continue
                runner = run_cost_and_save if args.cost else run_and_save
                rec = runner(arch, shape_name, args.multi_pod, args.variant)
                n_ok += int(rec.get("ok", False))
                n_fail += int(not rec.get("ok", False))
        print(f"[dryrun] done: ok={n_ok} fail={n_fail} "
              f"skipped-inapplicable={n_skip}")
        raise SystemExit(1 if n_fail else 0)

    if not args.arch or not args.shape:
        ap.error("need --arch and --shape (or --all)")
    runner = run_cost_and_save if args.cost else run_and_save
    rec = runner(args.arch, args.shape, args.multi_pod, args.variant)
    raise SystemExit(0 if rec.get("ok") else 1)


if __name__ == "__main__":
    main()
