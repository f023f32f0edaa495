"""Readings that the limits of ``bench/reference.py`` are set from.

    python3 bench/limits.py --workload testbed.azure --seeds 11,12,13

For each seed, one whole pass of the cell's timed path at its own size,
then the float64 reference replay of what it served, with the controls
(the reference in float32 with its dot products at ``Precision.HIGH``
and in one bfloat16 pass) on the same stream. Prints one JSON line per
seed: the program's and each control's ``served_gap``,
``weight_dev_p50`` and ``weight_dev_max``, the breach count, and the
pass's wall seconds. Runs on the chip, in one process for all seeds.
"""

import argparse
import json
import os
import sys
import time

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT,
                                                           ".jax_cache")
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from bench import harness, invariants, reference

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(CHECKOUT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    device = harness.require_chips(int(cell["chips"]))

    from repro.compile_cache import enable_compile_cache
    from repro.core.agent_arena import ArenaEngine

    enable_compile_cache()
    warm = False
    for seed in (int(s) for s in args.seeds.split(",")):
        c = harness.Cell(bench, args.workload, seed)
        if not warm:
            c.warm_up()
            warm = True
        rec = reference.Recorder()
        with rec.recording(ArenaEngine):
            sim = c.new_sim()
            t0 = time.perf_counter()
            results = sim.run(c.trace)
            pass_s = time.perf_counter() - t0
        (engine, stream), = rec.take()
        got = reference.replay(stream, reference.engine_weights(
            engine, reference.updated_functions(stream)),
            controls=tuple(reference.DOTS))
        got["breaches"] += len(invariants.breaches(sim, c.trace, results))
        print(json.dumps(dict(got, workload=args.workload, seed=seed,
                              pass_s=pass_s, invocations=len(results),
                              device=device.device_kind)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
