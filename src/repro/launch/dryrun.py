import os, sys  # noqa: E401  (brief: set XLA_FLAGS before ANY other import)
# a forced-host-device dry-run by design: never reach for an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=" + os.environ.get("REPRO_DEVICES", "512" if "--multi-pod" in sys.argv else "256")).strip()  # noqa: E501

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, print memory/cost analysis, derive the 3-term roofline.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod]
  REPRO_DEVICES=16 python -m repro.launch.dryrun ... --mesh 4x4   (dev only)

Writes one JSON per cell under results/dryrun/.
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.common.config import SHAPES, TrainConfig
from repro.core import roofline
from repro.launch import specs as S
from repro.launch.mesh import ctx_for_mesh, make_production_mesh
from repro.profiler.hlo import analyze_hlo
from repro.runtime import serve as serve_rt
from repro.runtime import sharding as shd
from repro.runtime import train as train_rt
from repro.runtime.tiering import apply_tier_shardings  # noqa: E402


# Grad-accumulation factors tuned so every train_4k cell's per-device temp
# fits v5e HBM (16 GiB) — measured from the v1 baseline sweep temps.
TRAIN_MICROBATCHES = {
    "smollm_360m": 2,
    "granite_moe_1b_a400m": 1,
    "granite_3_2b": 8,
    "paligemma_3b": 4,
    "mamba2_780m": 8,
    "mistral_nemo_12b": 8,
    "qwen2_5_32b": 16,
    "kimi_k2_1t_a32b": 8,
    "jamba_1_5_large_398b": 16,
    "seamless_m4t_large_v2": 32,
}


def build_mesh(args):
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        return jax.make_mesh(
            (d, m), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )
    return make_production_mesh(multi_pod=args.multi_pod)


def mesh_name(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def lower_cell(arch: str, shape_name: str, mesh, args):
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    ctx = ctx_for_mesh(mesh, fsdp=not args.no_fsdp, remat=args.remat)
    rules = shd.ShardingRules.for_training(
        fsdp_axis=ctx.fsdp_axis, tp_axis=ctx.tp_axis
    )
    ins = S.input_specs(arch, shape_name)

    tier_info = None
    if shape.kind == "train":
        mb = args.microbatches or TRAIN_MICROBATCHES.get(
            configs.canonical(arch), 8
        )
        tcfg = TrainConfig(microbatches=mb)
        bundle = train_rt.make_bundle(
            cfg, ctx, tcfg, rules, mesh, ins["batch"]
        )
        astate = bundle.abstract_state
        if args.tier_policy != "none":
            astate, bundle, tier_info = apply_tier_shardings(
                cfg, ctx, tcfg, rules, mesh, ins["batch"], bundle, shape,
                policy=args.tier_policy, pool_fraction=args.pool_fraction,
            )
        lowered = bundle.step_fn.lower(astate, ins["batch"])
        tokens = shape.tokens
        mf = roofline.model_flops_train(cfg.active_param_count(), tokens)
    elif shape.kind == "prefill":
        rules = shd.ShardingRules.for_serving(
            data_axis=ctx.fsdp_axis, tp_axis=ctx.tp_axis
        )
        sb = serve_rt.make_bundle(
            cfg, ctx, rules, mesh,
            batch=shape.global_batch, max_seq=shape.seq_len,
            enc_len=shape.seq_len if cfg.frontend == "audio_stub" else 0,
        )
        lowered = sb.prefill_fn.lower(sb.abstract_params, ins["batch"])
        mf = roofline.model_flops_decode(
            cfg.active_param_count(), shape.tokens
        )
    else:  # decode
        rules = shd.ShardingRules.for_serving(
            data_axis=ctx.fsdp_axis, tp_axis=ctx.tp_axis
        )
        enc_len = shape.seq_len if cfg.frontend == "audio_stub" else 0
        sb = serve_rt.make_bundle(
            cfg, ctx, rules, mesh,
            batch=shape.global_batch, max_seq=shape.seq_len, enc_len=enc_len,
        )
        lowered = sb.decode_fn.lower(
            sb.abstract_params, ins["token"], sb.abstract_caches, ins["t"]
        )
        mf = roofline.model_flops_decode(
            cfg.active_param_count(), shape.global_batch
        )
    return lowered, mf, tier_info


def run_cell(arch: str, shape_name: str, mesh, args, outdir: str):
    t0 = time.time()
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name(mesh),
        "tier_policy": args.tier_policy, "status": "ok",
    }
    try:
        lowered, model_flops, tier_info = lower_cell(
            arch, shape_name, mesh, args
        )
        if tier_info is not None:
            record["tier"] = tier_info
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        print(ma)                               # proves it fits
        ca = compiled.cost_analysis()
        print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
        cost = analyze_hlo(compiled.as_text())
        rep = roofline.report(
            arch, shape_name, mesh_name(mesh), cost,
            n_devices=mesh.size, model_flops=model_flops,
        )
        record.update(
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            memory_analysis={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "host_argument_bytes": ma.host_argument_size_in_bytes,
                "generated_code_bytes": ma.generated_code_size_in_bytes,
            },
            xla_cost={"flops": ca.get("flops"),
                      "bytes_accessed": ca.get("bytes accessed")},
            hlo_cost={
                "flops_per_device": rep.flops,
                "hbm_bytes_per_device": rep.hbm_bytes,
                "wire_bytes_per_device": rep.wire_bytes,
                "collectives": rep.collective_by_kind,
                "warnings": rep.warnings[:10],
            },
            roofline={
                "t_compute_s": rep.t_compute,
                "t_memory_s": rep.t_memory,
                "t_collective_s": rep.t_collective,
                "dominant": rep.dominant,
                "model_flops": rep.model_flops,
                "useful_ratio": rep.useful_ratio,
                "bound_overlap_s": rep.bound_overlap,
                "bound_serial_s": rep.bound_serial,
                "roofline_fraction": rep.roofline_fraction,
            },
        )
        print(
            f"[{arch} x {shape_name} @ {record['mesh']}] "
            f"compute={rep.t_compute:.4f}s memory={rep.t_memory:.4f}s "
            f"collective={rep.t_collective:.4f}s -> {rep.dominant}-bound, "
            f"roofline_fraction={rep.roofline_fraction:.3f}"
        )
    except Exception as e:
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        traceback.print_exc()
    record["wall_s"] = round(time.time() - t0, 1)
    os.makedirs(outdir, exist_ok=True)
    suffix = "" if args.tier_policy == "none" else f"_{args.tier_policy}"
    fn = os.path.join(
        outdir,
        f"{arch}_{shape_name}_{record['mesh']}{suffix}.json",
    )
    with open(fn, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default=None, help="dev override, e.g. 4x4")
    ap.add_argument("--remat", default="block", choices=["none", "block"])
    ap.add_argument("--microbatches", type=int, default=0,
                    help="0 = per-arch auto (fits HBM)")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--tier-policy", default="none",
                    choices=["none", "first_touch", "hotness", "balanced_bw",
                             "capacity"])
    ap.add_argument("--pool-fraction", type=float, default=0.5)
    ap.add_argument("--outdir", default="results/dryrun")
    args = ap.parse_args(argv)

    mesh = build_mesh(args)
    cells = (
        configs.all_cells()
        if args.all
        else [(configs.canonical(args.arch), args.shape)]
    )
    results = []
    for arch, shape in cells:
        results.append(run_cell(arch, shape, mesh, args, args.outdir))
    n_ok = sum(1 for r in results if r["status"] == "ok")
    print(f"\n{n_ok}/{len(results)} cells OK on mesh {mesh_name(mesh)}")
    if n_ok < len(results):
        for r in results:
            if r["status"] != "ok":
                print(" FAIL", r["arch"], r["shape"], r["error"])
        sys.exit(1)


if __name__ == "__main__":
    main()
