import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we build ShapeDtypeStruct stand-ins for params / optimizer
state / inputs / caches (NO device allocation), jit the step with explicit
in/out shardings, ``.lower().compile()`` against the production mesh of
512 forced host devices, and record memory_analysis / cost_analysis /
per-kind collective bytes and the roofline terms of
``repro.roofline.analysis`` into
artifacts/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
    python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    python -m repro.launch.dryrun --all [--mesh pod|multipod|both] [--skip-existing]
"""
import argparse
import functools
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, SHAPES, cell_is_runnable, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import (
    ShardCtx, cache_specs, init_cache, init_params, input_specs,
    make_prefill_step, make_serve_step, make_train_step, mesh_axes, param_specs,
)
from repro.optim.adamw import AdamW, opt_state_specs
from repro.roofline.analysis import (
    collective_bytes_from_hlo, model_flops, roofline_terms,
)

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")


def _named(tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               mla_absorb: bool = False, extra_tags=None, cfg_override=None,
               donate: bool = False, fsdp: bool = False):
    """Returns (lowered, compiled, report dict)."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp, tp = mesh_axes(mesh)
    ctx = ShardCtx(mesh=mesh, dp=dp, tp=tp)
    n_chips = mesh.size

    pspecs = param_specs(cfg, mesh, fsdp=fsdp)
    params_sds = jax.eval_shape(functools.partial(init_params, cfg),
                                jax.random.key(0))
    psh = _named(pspecs, mesh)
    batch_sds, batch_pspecs = input_specs(cfg, shape, mesh)
    bsh = _named(batch_pspecs, mesh)
    t0 = time.time()

    # unroll=True: every layer appears in the HLO, so cost_analysis FLOPs and
    # parsed collective bytes are whole-program (XLA counts a while body once)
    if shape.kind == "train":
        opt = AdamW(lr=1e-4)
        opt_sds = jax.eval_shape(opt.init, params_sds)
        osh = _named(opt_state_specs(pspecs), mesh)
        step_fn = make_train_step(cfg, opt, ctx, unroll=True)
        jitted = jax.jit(step_fn,
                         in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, NamedSharding(mesh, P())),
                         donate_argnums=(0, 1) if donate else ())
        lowered = jitted.lower(params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        step_fn = make_prefill_step(cfg, ctx, unroll=True)
        jitted = jax.jit(step_fn, in_shardings=(psh, bsh))
        lowered = jitted.lower(params_sds, batch_sds)
    else:  # decode
        cache_sds = init_cache(cfg, shape.global_batch, shape.seq_len,
                               as_shape=True)
        csh = _named(cache_specs(cfg, shape, mesh), mesh)
        step_fn = make_serve_step(cfg, ctx, absorb=mla_absorb, unroll=True)
        jitted = jax.jit(step_fn,
                         in_shardings=(psh, csh, bsh["tokens"], bsh["t"]),
                         out_shardings=(None, csh),
                         donate_argnums=(1,) if donate else ())
        lowered = jitted.lower(params_sds, cache_sds,
                               batch_sds["tokens"], batch_sds["t"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        mem_report = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_report = {"error": str(e)}

    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))

    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    terms = roofline_terms(flops, bytes_accessed, coll.total_bytes, n_chips)
    mf = model_flops(cfg, shape, shape.kind)

    report = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "kind": shape.kind,
        "tags": extra_tags or {},
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem_report,
        "hlo_flops": flops,
        "hlo_bytes_accessed": bytes_accessed,
        "collective_bytes": coll.total_bytes,
        "collective_bytes_by_kind": coll.bytes_by_kind,
        "collective_count_by_kind": coll.count_by_kind,
        "top_collectives": coll.top_ops,
        "model_flops": mf,
        # hlo flops are per-device; scale up for the whole-program ratio
        "useful_flops_ratio": (mf / (flops * n_chips)) if flops else None,
        "n_params": get_config(arch).n_params(),
        "n_active_params": get_config(arch).n_active_params(),
        **terms,
    }
    return lowered, compiled, report


def run_cell(arch, shape_name, multi_pod, out_dir, skip_existing=False,
             mla_absorb=False, suffix="", cfg_override=None, donate=False,
             fsdp=False):
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    name = f"{arch}__{shape_name}__{mesh_tag}{suffix}"
    path = os.path.join(out_dir, name + ".json")
    if skip_existing and os.path.exists(path):
        print(f"[skip] {name}")
        return True
    cfg = get_config(arch)
    ok, reason = cell_is_runnable(cfg, SHAPES[shape_name])
    if not ok:
        report = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                  "skipped": True, "reason": reason}
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"[SKIP-RULE] {name}: {reason}")
        return True
    try:
        _, compiled, report = lower_cell(arch, shape_name, multi_pod,
                                         mla_absorb=mla_absorb,
                                         cfg_override=cfg_override,
                                         donate=donate, fsdp=fsdp)
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"[ok] {name}: compile={report['compile_s']}s "
              f"flops={report['hlo_flops']:.3e} coll={report['collective_bytes']:.3e} "
              f"dom={report['dominant']} frac={report['roofline_fraction']:.3f}")
        del compiled
        return True
    except Exception:
        err = traceback.format_exc()
        with open(path + ".err", "w") as f:
            f.write(err)
        print(f"[FAIL] {name}:\n{err}")
        return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--mla-absorb", action="store_true",
                    help="use the absorbed MLA decode path (perf variant)")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=["global", "grouped"],
                    help="override MoE dispatch strategy (perf variant)")
    ap.add_argument("--remat-policy", default=None, choices=["full", "dots"],
                    help="override remat policy (perf variant)")
    ap.add_argument("--pin-proj", action="store_true",
                    help="force bf16 TP all-reduces (perf variant)")
    ap.add_argument("--moe-cf", type=float, default=None,
                    help="override MoE capacity factor (perf variant)")
    ap.add_argument("--donate", action="store_true",
                    help="donate params/opt (train) or cache (decode)")
    ap.add_argument("--fsdp", action="store_true",
                    help="FSDP/ZeRO-3 param+optimizer storage sharding")
    ap.add_argument("--quant-cache", action="store_true",
                    help="int8 KV/latent cache (perf variant)")
    ap.add_argument("--suffix", default="", help="artifact filename suffix")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    out_dir = args.out or os.path.abspath(ARTIFACTS)
    os.makedirs(out_dir, exist_ok=True)
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    archs = ARCH_NAMES if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                cfg_override = None
                if args.moe_dispatch or args.remat_policy or args.pin_proj \
                        or args.moe_cf or args.quant_cache:
                    import dataclasses as _dc
                    cfg_override = get_config(arch)
                    if args.moe_dispatch and cfg_override.moe is not None:
                        cfg_override = _dc.replace(
                            cfg_override,
                            moe=_dc.replace(cfg_override.moe,
                                            dispatch=args.moe_dispatch))
                    if args.remat_policy:
                        cfg_override = _dc.replace(
                            cfg_override, remat_policy=args.remat_policy)
                    if args.pin_proj:
                        cfg_override = _dc.replace(
                            cfg_override, pin_proj_outputs=True)
                    if args.moe_cf and cfg_override.moe is not None:
                        cfg_override = _dc.replace(
                            cfg_override,
                            moe=_dc.replace(cfg_override.moe,
                                            capacity_factor=args.moe_cf))
                    if args.quant_cache:
                        cfg_override = _dc.replace(
                            cfg_override, quantized_cache=True)
                ok = run_cell(arch, shape_name, mp, out_dir,
                              skip_existing=args.skip_existing,
                              mla_absorb=args.mla_absorb, suffix=args.suffix,
                              cfg_override=cfg_override, donate=args.donate,
                              fsdp=args.fsdp)
                failures += 0 if ok else 1
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
