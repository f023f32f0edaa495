import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

"""§Perf hillclimb driver: compile one (arch x shape) under named
variants and report the roofline-term deltas.

Variants (cumulative unless noted):
  base            — paper-faithful baseline (what the sweep recorded)
  constraints     — activation sharding constraints (hidden/logits)
  remat_dots      — + save matmul outputs in the scan body (train only)
  decode_split    — split-softmax decode (decode only; replaces concat)

Usage: PYTHONPATH=src python scripts/hillclimb.py --arch mixtral-8x7b \
           --shape prefill_32k --variants base,constraints
Writes experiments/perf/<arch>__<shape>__<variant>.json
"""

import argparse
import json
import time
from pathlib import Path

from repro.configs import SHAPES, canonical_id, get_config
from repro.launch import dryrun as dr
from repro.launch.mesh import make_production_mesh
import repro.models.model as M


def run_variant(cfg, shape, mesh, variant: str):
    """variant = "base" or "+"-joined flags:
    constraints | remat_dots | decode_split | moe_chunk<N>."""
    import repro.models.moe as MOE

    import repro.models.layers as LYR
    import repro.models.kv_cache as KVC

    flags = set() if variant == "base" else set(variant.split("+"))
    opt = "constraints" in flags
    M.set_remat_policy("dots" if "remat_dots" in flags else "nothing")
    M.set_decode_mode("split" if "decode_split" in flags else "concat")
    LYR.set_gqa_mode("grouped" if "gqa_grouped" in flags else "repeat")
    KVC.set_ring_mode("scatter" if "ring_scatter" in flags else "onehot")
    LYR.set_attn_qtile(0)
    for f in flags:
        if f.startswith("moe_chunk"):
            MOE.set_moe_seq_chunks(int(f[len("moe_chunk"):]))
        if f.startswith("qtile"):
            LYR.set_attn_qtile(int(f[len("qtile"):]))
    try:
        M.set_scan_unroll(1)
        t0 = time.time()
        lowered, compiled = dr.lower_combo(cfg, shape, mesh, opt=opt)
        dt = time.time() - t0
        extra = dr.extrapolate_costs(cfg, shape, mesh, opt=opt)
        rec = dr.analyze(cfg, shape, mesh, lowered, compiled, dt,
                         cost_override=extra)
        rec["variant"] = variant
        return rec
    finally:
        M.set_remat_policy("nothing")
        M.set_decode_mode("concat")
        LYR.set_gqa_mode("repeat")
        KVC.set_ring_mode("onehot")
        MOE.set_moe_seq_chunks(1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variants", default="base,constraints")
    ap.add_argument("--out", default="experiments/perf")
    args = ap.parse_args()

    cfg = get_config(canonical_id(args.arch))
    shape = SHAPES[args.shape]
    mesh = make_production_mesh()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    base_terms = None
    for variant in args.variants.split(","):
        rec = run_variant(cfg, shape, mesh, variant)
        rf = rec["roofline"]
        path = out / f"{cfg.name.replace('.', '_')}__{shape.name}__{variant}.json"
        path.write_text(json.dumps(rec, indent=2, default=str))
        line = (f"{variant:14s} compute={rf['compute_s']:.4f}s "
                f"memory={rf['memory_s']:.4f}s collective={rf['collective_s']:.4f}s "
                f"dominant={rf['dominant']} useful={rf['useful_flops_ratio']:.3f} "
                f"temp={rec['memory_analysis'].get('temp_bytes', 0)/2**30:.1f}GiB")
        if base_terms:
            dd = rf[f"{base_terms['dominant']}_s"] / base_terms[f"{base_terms['dominant']}_s"]
            line += f"  [dominant-term x{dd:.3f} vs base]"
        else:
            base_terms = rf
        print(line)


if __name__ == "__main__":
    main()
