"""The one traffic generator: reads a mix's data file, builds its trace.

A mix is a JSON file under ``bench/traffic/`` that names one of the
shapes below and gives its parameters. The shapes are copies of the
program's scenario generators (``repro.serving.workload``), kept here so
that no later change to the program can move the yardstick;
``bench/tests/test_bench_yardstick.py`` checks that each copy still
reproduces the program's output for a fixed seed.

A run's ``--seed`` draws the whole trace: arrival times, the function of
each arrival and its input. The mix fixes the shape, the rate and the
pass length, so every seed offers the same number of invocations (the
``azure`` shape) or the same expected number (the Poisson shapes).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, Dict, List, Mapping, Sequence

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


@dataclasses.dataclass(slots=True)
class Arrival:
    """Field for field the program's ``repro.serving.workload.Arrival``;
    the simulator reads only these four attributes."""
    invocation_id: int
    t: float
    function: str
    input_idx: int


# ------------------------------------------------ copies of the program's
def azure_minute_weights(n_minutes: int, rng: np.random.Generator) -> np.ndarray:
    base = 1.0 + 0.3 * np.sin(np.linspace(0, 2 * np.pi, n_minutes))
    burst = rng.lognormal(mean=0.0, sigma=0.45, size=n_minutes)
    w = base * burst
    return w / w.sum()


def function_popularity(functions: Sequence[str],
                        rng: np.random.Generator) -> np.ndarray:
    ranks = np.arange(1, len(functions) + 1, dtype=np.float64)
    rng.shuffle(ranks)
    w = 1.0 / ranks ** 0.9
    return w / w.sum()


def _azure(p, functions, inputs_per_function, rng):
    """Azure Functions trace shape (Shahrad et al., ATC '20): lognormal
    bursty minutes on a sinusoid, Zipf 0.9 popularity, start times
    uniform within each minute. params: uniform_popularity."""
    n_minutes = int(np.ceil(p["duration_s"] / 60.0))
    weights = azure_minute_weights(n_minutes, rng)
    total = int(round(p["rps"] * p["duration_s"]))
    per_minute = rng.multinomial(total, weights)
    if p.get("uniform_popularity", 0):
        pop = np.full(len(functions), 1.0 / len(functions))
    else:
        pop = function_popularity(functions, rng)
    out = []
    for minute, count in enumerate(per_minute):
        starts = rng.uniform(minute * 60.0, (minute + 1) * 60.0, size=count)
        starts.sort()
        fns = rng.choice(len(functions), size=count, p=pop)
        for t, fi in zip(starts, fns):
            fn = functions[fi]
            idx = int(rng.integers(inputs_per_function[fn]))
            out.append((float(t), fn, idx))
    return out


def _poisson_times(rate: float, duration_s: float,
                   rng: np.random.Generator) -> np.ndarray:
    if rate <= 0.0 or duration_s <= 0.0:
        return np.empty(0)
    n = int(rng.poisson(rate * duration_s))
    return np.sort(rng.uniform(0.0, duration_s, size=n))


def _thinned_times(rate_fn: Callable[[np.ndarray], np.ndarray],
                   peak_rate: float, duration_s: float,
                   rng: np.random.Generator) -> np.ndarray:
    cand = _poisson_times(peak_rate, duration_s, rng)
    if cand.size == 0:
        return cand
    accept = rate_fn(cand) / peak_rate
    if float(accept.max()) > 1.0 + 1e-9:
        raise ValueError("rate exceeds the thinning bound")
    keep = rng.uniform(0.0, 1.0, size=cand.size) < accept
    return cand[keep]


def _assemble(times, functions, pop, inputs_per_function, rng):
    out = []
    if times.size == 0:
        return out
    fis = rng.choice(len(functions), size=times.size, p=pop)
    for t, fi in zip(times, fis):
        fn = functions[fi]
        out.append((float(t), fn, int(rng.integers(inputs_per_function[fn]))))
    return out


def _uniform_poisson(p, functions, inputs_per_function, rng):
    """Poisson arrivals, uniform popularity over the (cloned) function
    set: the trace's keep-alive-defeating long tail. params: none."""
    pop = np.full(len(functions), 1.0 / len(functions))
    times = _poisson_times(p["rps"], p["duration_s"], rng)
    return _assemble(times, functions, pop, inputs_per_function, rng)


def _hot_surge(p, functions, inputs_per_function, rng):
    """``hot_frac`` of the traffic on ``hot_fns`` functions drawn from
    the seed, plus a window at ``spike_mult`` x the base rate. params:
    hot_fns, hot_frac, spike_mult, spike_start_frac, spike_duration_s."""
    n_hot = max(1, min(int(p["hot_fns"]), len(functions)))
    hot_frac = min(max(float(p["hot_frac"]), 0.0), 1.0)
    hot = rng.choice(len(functions), size=n_hot, replace=False)
    pop = np.full(len(functions),
                  (1.0 - hot_frac) / max(len(functions) - n_hot, 1))
    pop[hot] = hot_frac / n_hot
    pop = pop / pop.sum()
    rps, dur, mult = p["rps"], p["duration_s"], float(p["spike_mult"])
    t0 = float(p["spike_start_frac"]) * dur
    t1 = min(t0 + float(p["spike_duration_s"]), dur)

    def rate(t):
        return np.where((t >= t0) & (t < t1), rps * mult, rps)

    times = _thinned_times(rate, rps * max(mult, 1.0), dur, rng)
    return _assemble(times, functions, pop, inputs_per_function, rng)


# name in a mix file -> (shape, the program's scenario it copies)
SHAPES: Dict[str, Callable] = {
    "azure": _azure,
    "uniform-poisson": _uniform_poisson,
    "hot-surge": _hot_surge,
}
PROGRAM_SCENARIO = {"azure": "azure", "uniform-poisson": "cold-storm",
                    "hot-surge": "multi-cluster"}


def load_mix(name: str) -> Dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("shape") not in SHAPES:
        raise ValueError(f"traffic {name!r}: unknown shape {mix.get('shape')!r}")
    return mix


def pass_trace(mix: Mapping, functions: Sequence[str],
               inputs_per_function: Mapping[str, int],
               seed: int) -> List[Arrival]:
    """The mix's trace drawn from ``seed``, time-sorted and numbered
    0..n-1 as ``generate_scenario`` numbers it."""
    params = dict(mix.get("params", {}), rps=float(mix["rps"]),
                  duration_s=float(mix["duration_s"]))
    rng = np.random.default_rng(seed)
    rows = SHAPES[mix["shape"]](params, list(functions), inputs_per_function,
                                rng)
    rows.sort(key=lambda r: r[0])
    return [Arrival(i, t, fn, idx) for i, (t, fn, idx) in enumerate(rows)]
