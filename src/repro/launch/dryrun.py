import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

The lines above MUST run before any other import (jax locks the
platform and device count on first init); 512 placeholder host devices
back both the single-pod (16,16) and multi-pod (2,16,16) production
meshes, on the CPU even where a chip is attached.

For each combination this:
  1. builds the production mesh and the sharding spec trees,
  2. ``jax.jit(step, in_shardings, out_shardings, donate...)``
     ``.lower(**input_specs)`` — ShapeDtypeStructs only, no allocation,
  3. ``.compile()`` — any sharding mismatch / OOM-at-compile /
     unsupported collective fails HERE, which is the point,
  4. records ``memory_analysis()`` / ``cost_analysis()`` / parsed
     collective traffic to a JSON blob for EXPERIMENTS.md §Dry-run and
     the roofline table (§Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import (
    ARCH_IDS,
    SHAPES,
    canonical_id,
    get_config,
    input_specs,
    shape_applicable,
)
from repro.distributed import sharding as sh
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import (
    HBM_BW,
    ICI_LINK_BW,
    PEAK_FLOPS_BF16,
    make_production_mesh,
)
from repro.launch.steps import (
    adamw_config_for,
    eval_opt_shapes,
    eval_param_shapes,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)


def _metric_specs():
    return None  # metrics replicate; let jit infer


def lower_combo(cfg, shape, mesh, *, opt: bool = False, xla_options=None):
    """Lower + compile one (arch, shape, mesh). Returns (lowered, compiled).

    ``opt`` enables the beyond-baseline optimizations that won the §Perf
    hillclimb: activation/score/MoE-buffer sharding constraints + the
    split-softmax decode. The baseline table is recorded with opt=False;
    EXPERIMENTS.md §Perf records both.
    """
    from repro.models.model import set_decode_mode

    # The split decode + score constraint fix the W-sharded-cache gather;
    # when kv heads divide the model axis the cache is head-sharded and
    # the baseline concat path is already shard-local (the split variant
    # only adds work — measured regressions on phi3/codeqwen long_500k).
    mi0 = sh.mesh_info(mesh)
    w_sharded_cache = (
        cfg.uses_attention and cfg.num_kv_heads % mi0.model_size != 0
    )
    set_decode_mode("split" if (opt and w_sharded_cache) else "concat")
    mi = sh.mesh_info(mesh)
    specs = input_specs(cfg, shape)
    in_raw = sh.input_spec_tree(cfg, mesh, shape, specs)
    in_spec_tree = sh.named(mesh, in_raw)
    pshapes = eval_param_shapes(cfg)
    praw = sh.param_spec_tree(
        cfg, mesh, "train" if shape.kind == "train" else "serve", pshapes
    )
    pspecs = sh.named(mesh, praw)

    with mesh:
        if shape.kind == "train":
            opt_cfg = adamw_config_for(cfg)
            oshapes = eval_opt_shapes(cfg, pshapes, opt_cfg)
            ospecs = sh.named(mesh, sh.opt_state_specs(praw))
            step = make_train_step(cfg, opt_cfg, mesh=mesh if opt else None)
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, ospecs, in_spec_tree),
                out_shardings=(pspecs, ospecs, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(pshapes, oshapes, specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, shape, mesh=mesh if opt else None)
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, in_spec_tree),
                out_shardings=None,
            )
            lowered = jitted.lower(pshapes, specs)
        else:  # decode
            step = make_serve_step(cfg, mesh=mesh if opt else None)
            cache_sds = specs["cache"]
            cache_specs_tree = in_spec_tree["cache"]
            token_spec = in_spec_tree["token"]
            batch_axis = in_raw["token"][0] if in_raw["token"] else None
            logits_spec = sh.named(
                mesh,
                P(
                    batch_axis,
                    "model" if cfg.vocab_size % mi.model_size == 0 else None,
                ),
            )
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, cache_specs_tree, token_spec),
                out_shardings=(logits_spec, cache_specs_tree),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(pshapes, cache_sds, specs["token"])
        compiled = lowered.compile()
    return lowered, compiled


def analyze(cfg, shape, mesh, lowered, compiled, elapsed_s, cost_override=None):
    chips = mesh.devices.size
    mi = sh.mesh_info(mesh)
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": repr(e)}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
    except Exception as e:  # pragma: no cover
        cost = {"error": repr(e)}
    hlo = compiled.as_text()
    cstats = ha.collective_stats(hlo, default_group=chips)
    if cost_override is not None:
        flops = cost_override["flops"]
        bytes_accessed = cost_override["bytes"]
        coll_bytes = cost_override["coll"]
    else:
        flops = float(cost.get("flops", 0.0) or 0.0)
        bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
        coll_bytes = cstats.per_device_traffic_bytes
    model_flops = ha.model_flops_estimate(cfg, shape)
    rf = ha.roofline_terms(
        per_device_flops=flops,
        per_device_bytes=bytes_accessed,
        per_device_collective_bytes=coll_bytes,
        chips=chips,
        model_flops=model_flops,
        peak_flops=PEAK_FLOPS_BF16,
        hbm_bw=HBM_BW,
        link_bw=ICI_LINK_BW,
    )
    return {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": f"{'x'.join(str(s) for s in mesh.devices.shape)}",
        "axes": list(mesh.axis_names),
        "chips": int(chips),
        "compile_s": elapsed_s,
        "memory_analysis": mem_d,
        "cost_analysis_flops_per_device": flops,
        "cost_analysis_bytes_per_device": bytes_accessed,
        "collectives": {
            "per_device_traffic_bytes": coll_bytes,
            "scan_hlo_traffic_bytes": cstats.per_device_traffic_bytes,
            "op_counts": cstats.op_counts,
            "op_bytes": cstats.op_bytes,
        },
        "cost_extrapolation": cost_override,
        "roofline": rf.to_dict(),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }


def _reduced_depth_cfg(cfg, n_layers: int):
    """Same architecture at a shallower depth (for cost extrapolation)."""
    import dataclasses

    changes = {"num_layers": n_layers}
    if cfg.encoder_layers:
        changes["encoder_layers"] = min(cfg.encoder_layers, n_layers)
    return dataclasses.replace(cfg, **changes)


def extrapolate_costs(cfg, shape, mesh, *, opt: bool):
    """Exact per-layer cost extrapolation.

    XLA's cost analysis counts a while-loop (scan) body ONCE, so the
    full-depth scan compile under-reports FLOPs/bytes/collectives by ~L.
    We compile the SAME architecture at depths L1 and L2 (fully unrolled
    — they're tiny) and extrapolate linearly: total(L) = c(L1) +
    (L - L1)/(L2 - L1) * (c(L2) - c(L1)). The layer stack is homogeneous
    within a family, so this is exact up to compiler noise; for the
    hybrid (zamba2) L1/L2 are multiples of attn_every so the shared-attn
    block amortizes correctly. Validated against fully-unrolled compiles
    in EXPERIMENTS.md §Dry-run (calibration table).
    """
    from repro.models.model import set_scan_unroll

    chips = mesh.devices.size
    step_l = cfg.attn_every if cfg.family == "hybrid" else 1
    # Depths 2x/3x (not 1x): a single-layer scan lowers structurally
    # differently (no while loop, different remat elision) and sits off
    # the per-layer cost line — calibrated L=1..4 in EXPERIMENTS.md.
    L1, L2 = 2 * step_l, 3 * step_l
    L = cfg.num_layers
    vals = {}
    for n in (L1, L2):
        rcfg = _reduced_depth_cfg(cfg, n)
        set_scan_unroll(max(n, rcfg.encoder_layers))
        lowered, compiled = lower_combo(rcfg, shape, mesh, opt=opt)
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        cstats = ha.collective_stats(compiled.as_text(), default_group=chips)
        vals[n] = {
            "flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes": float(ca.get("bytes accessed", 0.0) or 0.0),
            "coll": cstats.per_device_traffic_bytes,
        }
    out = {}
    for k in ("flops", "bytes", "coll"):
        slope = (vals[L2][k] - vals[L1][k]) / (L2 - L1)
        out[k] = vals[L1][k] + slope * (L - L1)
    out["per_layer"] = {
        k: (vals[L2][k] - vals[L1][k]) / (L2 - L1) for k in ("flops", "bytes", "coll")
    }
    out["base"] = {k: vals[L1][k] - out["per_layer"][k] * L1
                   for k in ("flops", "bytes", "coll")}
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
            verbose=True, opt: bool = False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        rec = {
            "arch": cfg.name,
            "shape": shape.name,
            "skipped": True,
            "reason": "long_500k inapplicable (see DESIGN.md §4)",
        }
        _write(out_dir, cfg.name, shape.name, multi_pod, rec, opt)
        if verbose:
            print(f"SKIP  {cfg.name} x {shape.name}: {rec['reason']}")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.models.model import set_scan_unroll

    # 1) THE dry-run artifact: the full config, scan-over-layers (the
    #    production form). Compile success/memory_analysis come from here.
    set_scan_unroll(1)
    t0 = time.time()
    lowered, compiled = lower_combo(cfg, shape, mesh, opt=opt)
    dt = time.time() - t0
    # 2) exact cost extrapolation from shallow unrolled compiles
    extra = extrapolate_costs(cfg, shape, mesh, opt=opt)
    rec = analyze(cfg, shape, mesh, lowered, compiled, dt,
                  cost_override=extra)
    rec["opt"] = opt
    _write(out_dir, cfg.name, shape.name, multi_pod, rec, opt)
    if verbose:
        ma = rec["memory_analysis"]
        print(
            f"OK    {cfg.name} x {shape.name} mesh={rec['mesh']} "
            f"compile={dt:.1f}s flops/dev={rec['cost_analysis_flops_per_device']:.3e} "
            f"argbytes/dev={ma.get('argument_bytes')} "
            f"dominant={rec['roofline']['dominant']}"
        )
        print("  memory_analysis:", {k: v for k, v in ma.items()})
        print(
            "  roofline: compute=%.4fs memory=%.4fs collective=%.4fs useful=%.3f"
            % (
                rec["roofline"]["compute_s"],
                rec["roofline"]["memory_s"],
                rec["roofline"]["collective_s"],
                rec["roofline"]["useful_flops_ratio"],
            )
        )
    return rec


def _write(out_dir: Path, arch: str, shape: str, multi_pod: bool, rec,
           opt: bool = False):
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "pod2" if multi_pod else "pod1"
    if opt:
        suffix += "_opt"
    path = out_dir / f"{arch.replace('.', '_')}__{shape}__{suffix}.json"
    path.write_text(json.dumps(rec, indent=2, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="enable the beyond-baseline §Perf optimizations")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    out_dir = Path(args.out)

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        combos.append((canonical_id(args.arch), args.shape))

    failures = []
    for arch, shape_name in combos:
        try:
            run_one(arch, shape_name, args.multi_pod, out_dir, opt=args.opt)
        except Exception as e:
            failures.append((arch, shape_name, repr(e)))
            print(f"FAIL  {arch} x {shape_name}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} combos failed: {failures}")


if __name__ == "__main__":
    main()
