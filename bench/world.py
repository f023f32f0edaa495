"""The simulated world a configuration runs in: functions, input pool,
SLO table, and the digests that pin them.

The function profiles and the input pool are the program's
(``repro.serving.profiles``): they stand for the paper's testbed
hardware and inputs. The SLO table and the clone expansion are copies of
``repro.serving.baselines.build_slo_table`` and
``repro.serving.experiment.expand_function_clones``, so the yardstick
stays put when the program changes. A configuration file records the
sha256 of the input pool and of this SLO table; :func:`build_world`
refuses a world whose digests differ.
"""

from __future__ import annotations

import hashlib
import json
from typing import Dict, List, Tuple

import numpy as np


def build_slo_table(profiles, pool, *, multiplier: float = 1.4,
                    max_vcpus: int = 32, runs: int = 5,
                    seed: int = 1234) -> Dict[Tuple[str, int], float]:
    """Paper §7.1: an input's SLO is ``multiplier`` x the median of its
    isolated runs at its best vCPU allocation."""
    rng = np.random.default_rng(seed)
    table: Dict[Tuple[str, int], float] = {}
    for fn, prof in profiles.items():
        for idx, meta in enumerate(pool[fn]):
            best = np.inf
            for v in (1, 2, 4, 8, 12, 16, 20, 24, 28, 32):
                if v > max_vcpus:
                    break
                times = [prof.exec_time(meta, v, rng) for _ in range(runs)]
                best = min(best, float(np.median(times)))
            table[(fn, idx)] = multiplier * best
    return table


def expand_clones(profiles, pool, slo_table, clones: int):
    """Each function as ``clones`` aliases (``fn``, ``fn::1``, ...) that
    share its profile, inputs and SLOs but are distinct functions to the
    system: own warm pools, own home worker, own allocator agents."""
    if clones <= 1:
        return profiles, pool, slo_table
    P, L, S = {}, {}, {}
    for fn in profiles:
        for k in range(clones):
            alias = fn if k == 0 else f"{fn}::{k}"
            P[alias] = profiles[fn]
            L[alias] = pool[fn]
            for idx in range(len(pool[fn])):
                S[(alias, idx)] = slo_table[(fn, idx)]
    return P, L, S


# The pool's and the table's last bits differ from one CPU to another
# (the chip host's differed from the build machine's), so values are
# compared at 9 significant digits: far above that noise, far below any
# change to a profile or an input.
DIGITS = 9


def _sig(v):
    return f"{v:.{DIGITS}g}" if isinstance(v, float) else v


def digest_pool(pool: Dict[str, List[Dict]]) -> str:
    rows = {fn: [{k: _sig(v) for k, v in meta.items()} for meta in metas]
            for fn, metas in pool.items()}
    blob = json.dumps(rows, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


def digest_slo_table(table: Dict[Tuple[str, int], float]) -> str:
    rows = sorted((fn, idx, _sig(float(v))) for (fn, idx), v in table.items())
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()


def build_world(world_cfg: Dict, clones: int):
    """(profiles, pool, slo_table) of a configuration's ``world`` block,
    with the mix's clone count applied; checks the recorded digests."""
    from repro.serving.profiles import build_input_pool, build_profiles

    profiles = build_profiles()
    pool = build_input_pool(seed=int(world_cfg["input_pool_seed"]))
    slo = build_slo_table(profiles, pool,
                          multiplier=float(world_cfg["slo_multiplier"]))
    got = {"input_pool": digest_pool(pool), "slo_table": digest_slo_table(slo)}
    if got != world_cfg["digests"]:
        raise RuntimeError(f"the simulated world changed: digests {got}, "
                           f"configuration records {world_cfg['digests']}")
    return expand_clones(profiles, pool, slo, clones)
