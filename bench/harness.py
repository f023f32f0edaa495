"""One run of one cell: set-up, the measured window, the checks, and the
result line.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic mix in ``bench/traffic/<traffic>.json`` and each metric's
reader in ``bench/metrics/<metric>.py``.

The window is a closed-loop replay in passes: each pass builds a fresh
simulator and policy, as ``repro.serving.experiment.build_simulator``
builds them, and runs the cell's whole trace with ``Simulator.run``.
Passes run back to back until the window's seconds are spent; the pass
in flight then is stopped at its next policy or router call.

The result line's ``attempted`` and ``failed`` count one whole pass, the
first: the seed's trace and those of its invocations that were shed,
timed out or OOM-killed. Every later whole pass replays the same trace
and is checked equal to the first (``pass_disagreements``, limit 0), so
summing over passes would only weight the seed's failure share by how
many passes the window held, that is, by the program's speed.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from bench import invariants, peaks, quality, reference, traffic, xplane
from bench.spans import Deadline, Probe
from bench.world import build_world

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a dimension's warm-up function runs this many times, one at a time:
# past both confidence thresholds, so its predict and update kernels
# compile before the window
WARMUP_RUNS = 24
WARMUP_GAP_S = 30.0


class NoChip(RuntimeError):
    pass


def say(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)


def require_chips(n: int):
    """The first TPU device, if JAX finds at least ``n`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"this cell needs {n} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[0]


class CompileClock:
    """While open, counts backend compiles (persistent-cache reads
    included) and their seconds."""

    def __enter__(self):
        import jax

        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def take(self):
        out = (self.n, self.seconds)
        self.n, self.seconds = 0, 0.0
        return out


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Pass:
    sim: object
    results: list
    stream: list
    engine: object


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    setup_s: float
    window_s: float
    terminal: int  # terminal invocations in the window, partial pass included
    passes: List[Dict]  # quality of each completed pass
    probe: Probe
    peak: Dict
    trace: Optional[Dict]


def sim_seed(seed: int) -> int:
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def warmup_trace(functions, dim_of) -> list:
    """One function per feature dimension of the cell, each run
    ``WARMUP_RUNS`` times with gaps long enough that every run completes
    and feeds back before the next."""
    first = {}
    for fn in functions:
        first.setdefault(dim_of(fn), fn)
    rows = [(k * WARMUP_GAP_S + 0.01 * j, fn)
            for j, fn in enumerate(first[d] for d in sorted(first))
            for k in range(WARMUP_RUNS)]
    rows.sort()
    return [traffic.Arrival(i, t, fn, 0) for i, (t, fn) in enumerate(rows)]


class Cell:
    """One cell made ready to run: its world, its pass trace for a
    seed, and the simulator's configuration."""

    def __init__(self, bench: Dict, name: str, seed: int):
        from repro.core.featurizer import FEATURE_SCHEMAS
        from repro.serving.simulator import SimConfig

        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.cell = cells[name]
        self.config = load_json(BENCH_DIR, "configs",
                                f"{self.cell['config']}.json")
        self.mix = traffic.load_mix(self.cell["traffic"])
        self.profiles, self.pool, self.slo = build_world(
            self.config["world"], int(self.mix.get("clones", 1)))
        self.functions = sorted(self.profiles)
        ipf = {f: len(self.pool[f]) for f in self.functions}
        self.trace = traffic.pass_trace(self.mix, self.functions, ipf, seed)
        self.cfg = SimConfig(seed=sim_seed(seed), **self.config["sim"])
        self.dim_of = {fn: len(FEATURE_SCHEMAS[p.input_type])
                       for fn, p in self.profiles.items()}

    def new_sim(self):
        from repro.serving.experiment import build_simulator

        return build_simulator(self.config["policy"], self.profiles,
                               self.pool, self.slo, seed=self.cfg.seed,
                               sim_cfg=self.cfg)

    def warm_up(self) -> None:
        """Calibrate the arena for the cell's feature dimensions and run
        the warm-up trace, so that every program the window runs is
        compiled; prints the arena's backend table."""
        from repro.core import agent_arena

        dims = sorted(set(self.dim_of.values()))
        agent_arena.calibrate(dims)
        say("backends: dim | numpy_backend | vmap_backend | numpy_crossover_rows")
        for d in dims:
            say(f"backends: {d} | {agent_arena.numpy_backend(d)} | "
                f"{agent_arena.vmap_backend(d)} | "
                f"{agent_arena.numpy_crossover_rows(d)}")
        self.new_sim().run(warmup_trace(self.functions, self.dim_of.get))


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    bench = load_json(CHECKOUT, "BENCHMARK.json")
    names = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in names:
        say(f"no workload {args.workload!r}; known: {sorted(names)}")
        return 2
    try:
        device = require_chips(int(names[args.workload]["chips"]))
    except NoChip as e:
        say(f"refused: {e}")
        return 3
    peak = peaks.peaks(device.device_kind)

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core.agent_arena import ArenaEngine

    say(f"device {device.platform}:{device.device_kind} x{jax.device_count()}; "
        f"compile cache {enable_compile_cache()}")
    cell = Cell(bench, args.workload, args.seed)
    trace = cell.trace
    say(f"cell {args.workload}: {len(trace)} invocations a pass over "
        f"{cell.mix['duration_s']} simulated s, {len(cell.functions)} "
        f"functions, {cell.cfg.n_clusters} x {cell.cfg.n_workers} workers; "
        f"seed {args.seed}")
    with CompileClock() as clock:
        cell.warm_up()
        n, s = clock.take()
    setup_s = time.perf_counter() - t_start
    say(f"set-up {setup_s:.3f} s, {n} compiles ({s:.3f} s) in it")

    # ---------------------------------------------------------- window
    probe = Probe(annotate=bool(args.trace))
    recorder = reference.Recorder()
    passes: List[Pass] = []
    partial, partial_stream = 0, []
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    with CompileClock() as clock, probe.arena(ArenaEngine), \
            recorder.recording(ArenaEngine):
        if log_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            window_span = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            window_span.__enter__()
        t0 = time.perf_counter()
        probe.deadline = t0 + args.seconds
        while True:
            sim = cell.new_sim()
            probe.instrument(sim)
            try:
                results = sim.run(trace)
            except Deadline:
                partial = len(sim.results)
                partial_stream = [s for _, s in recorder.take()]
                break
            (engine, stream), = recorder.take()
            passes.append(Pass(sim, list(results), stream, engine))
            if time.perf_counter() >= probe.deadline:
                break
        window_s = time.perf_counter() - t0
        if log_dir:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        n_compiles, compile_s = clock.take()
    probe.deadline = float("inf")
    terminal = partial + sum(len(p.results) for p in passes)
    say(f"window {window_s:.3f} s: {len(passes)} whole passes, {partial} "
        f"terminal invocations of the pass in flight; {n_compiles} compiles "
        f"({compile_s:.3f} s) inside the window")
    if recorder.calls:
        say(f"recording for the reference: {recorder.calls} arena calls, "
            f"{recorder.seconds:.3f} s in the window "
            f"({recorder.seconds / recorder.calls * 1e6:.2f} us a call)")
    if probe.decisions_s:
        d = np.array(probe.decisions_s) * 1e6
        say(f"decisions: {d.size}, mean {d.mean():.1f} us, p50/p90/p95/p99 "
            + "/".join(f"{np.percentile(d, q):.1f}" for q in (50, 90, 95, 99))
            + " us")
    if not passes:
        say("failed: no pass completed inside the window")
        return 1
    stats = device.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    trace_red = None
    if log_dir:
        pd = xplane.load(xplane.find_trace(log_dir))
        say(f"trace planes: {xplane.layout(pd)}")
        trace_red = xplane.reduce_trace(pd)
        shutil.rmtree(log_dir, ignore_errors=True)

    # ---------------------------------------------------------- checks
    t_checks = time.perf_counter()
    checks = {"served_gap": 0.0, "weight_dev_p50": 0.0, "breaches": 0,
              "pass_disagreements": 0}
    qualities = []
    first = None
    for p in passes:
        got = reference.replay(p.stream, reference.engine_weights(
            p.engine, reference.updated_functions(p.stream)))
        checks["served_gap"] = max(checks["served_gap"], got["served_gap"])
        checks["weight_dev_p50"] = max(checks["weight_dev_p50"],
                                       got["weight_dev_p50"])
        checks["breaches"] += got["breaches"]
        bad = invariants.breaches(p.sim, trace, p.results)
        ran = sum(not (r.shed or r.timed_out) for r in p.results)
        if got["updates"] != ran:
            bad.append(f"{got['updates']} updates reached the arena for "
                       f"{ran} invocations that ran")
        if not got["served"]:
            bad.append("the pass served no prediction")
        for b in bad[:5]:
            say(f"invariant breach: {b}")
        checks["breaches"] += len(bad)
        q = quality.pass_quality(p.results)
        served = [ev[5:] for ev in p.stream if ev[0] == "predict"]
        if first is None:
            first = (q, served)
        elif (q, served) != first:
            checks["pass_disagreements"] += 1
        qualities.append(q)
    # the stopped pass served what the first pass served, up to its stop
    for stream in partial_stream:
        served = [ev[5:] for ev in stream if ev[0] == "predict"]
        if served != first[1][:len(served)]:
            checks["pass_disagreements"] += 1
    limits = dict(reference.LIMITS, breaches=0, pass_disagreements=0)
    correct = all(checks[k] <= limits[k] for k in checks)
    say(f"reference and checks {time.perf_counter() - t_checks:.3f} s")

    run = Run(setup_s=setup_s, window_s=window_s, terminal=terminal,
              passes=qualities, probe=probe, peak=peak, trace=trace_red)
    key = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench[key]:
        if "workloads" in m and args.workload not in m["workloads"]:
            continue
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": jax.device_count(), "memory_peak_bytes": peak_bytes}
    out = {"correct": correct,
           "attempted": qualities[0]["n"],
           "failed": qualities[0]["failed"],
           "metrics": metrics, "device": dev}
    if trace_red is not None:
        dev["busy_s"] = trace_red.get("busy_s", 0.0)
        dev["window_s"] = trace_red["window_s"]
        out["breakdown"] = {"device_ops": trace_red.get("top_ops", []),
                            "idle_gaps": trace_red.get("idle_by_span", [])}
    say(f"quality of the first pass: {qualities[0]}")
    say(f"failures: {qualities[0]['failed']} of {qualities[0]['n']} in one "
        f"whole pass, the first of {len(passes)}")
    for k in checks:
        say(f"check {k}: {checks[k]!r} (limit {limits[k]!r})")
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]} for k in checks}
    print(json.dumps(out), flush=True)
    return 0

