"""Readings of the program's own spans and counters (``repro.spans``).
The program records them, annotated into the trace, in every
``Simulator.run`` made while a profiler trace is being collected: in a
traced run, the passes of the measured window. :func:`window` hands the
record to the readers, and None to a program without spans of its own.

Span names are the program's; ``arena.*`` are the agent arena's:
``ARENA_CALLS`` its public calls, and inside them one span per step of a
device dispatch: ``arena.h2d`` (argument copies to the device),
``arena.launch`` (the kernel call, until it returns) and ``arena.d2h``
(result reads, which wait for the device).
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional

import numpy as np

ARENA_CALLS = ("arena.predict", "arena.predict_batch", "arena.flush",
               "arena.enqueue_update")
TRANSFERS = ("arena.h2d", "arena.d2h")
LAUNCH = "arena.launch"
DISPATCH = "arena.dispatch/"

_reported = None  # the first record of the window whose tables are printed


def window() -> Optional[Dict]:
    """``repro.spans.snapshot()`` of the last profiled stretch, or None
    where the program has no spans or recorded none. The first reading of
    a stretch prints its decisions and tables on stderr."""
    global _reported
    try:
        from repro import spans
    except ImportError:  # a program without spans of its own
        return None
    snap = spans.snapshot()
    if not snap["records"]:
        return None
    if snap["records"][0] != _reported:
        _reported = snap["records"][0]
        for line in report(snap):
            print("[bench]", line, file=sys.stderr, flush=True)
    return snap


def seconds(snap: Dict, names) -> float:
    """Outermost seconds of the spans ``names``, summed."""
    return sum(snap["spans"].get(n, {}).get("seconds", 0.0) for n in names)


def calls(snap: Dict, names) -> int:
    return sum(snap["spans"].get(n, {}).get("calls", 0) for n in names)


def dispatches(snap: Dict) -> int:
    return sum(v for k, v in snap["counters"].items() if k.startswith(DISPATCH))


def arena_host_s(snap: Dict) -> float:
    """Self seconds of the arena's public calls: their host work."""
    return sum(snap["spans"].get(n, {}).get("self_seconds", 0.0)
               for n in ARENA_CALLS)


def arena_outer_s(snap: Dict) -> float:
    """Seconds inside an arena public call that no other one encloses."""
    recs = snap["records"]
    ns = sum(t1 - t0 for name, t0, t1, parent, _ in recs
             if name in ARENA_CALLS and t1 is not None
             and (parent is None or recs[parent][0] not in ARENA_CALLS))
    return ns * 1e-9


def decisions_s(snap: Dict) -> List[float]:
    """Per decision: from the ``policy.begin_batch`` or else the
    ``policy.allocate`` that carries an invocation to the end of its next
    ``router.route``; retries, which route again without allocating,
    start none. Records are in start order. Invocation ids repeat from
    pass to pass, so each allocation starts the decision afresh."""
    start: Dict[int, int] = {}
    batched = set()  # allocated by a batch, not yet by their own call
    out = []
    for name, t0, t1, _, rid in snap["records"]:
        if name == "policy.begin_batch":
            for r in rid or ():
                start[r] = t0
                batched.add(r)
        elif name == "policy.allocate":
            if rid in batched:
                batched.discard(rid)
            else:
                start[rid] = t0
        elif name == "router.route" and t1 is not None and rid in start:
            out.append((t1 - start.pop(rid)) * 1e-9)
    return out


def report(snap: Dict) -> List[str]:
    """The stderr lines of a traced run: the decisions measured by
    request id, the spans by outermost seconds, then the counters."""
    lines = []
    d = np.array(decisions_s(snap)) * 1e6
    if d.size:
        lines.append(f"program decisions: {d.size}, p50/p95/p99 " + "/".join(
            f"{np.percentile(d, q):.1f}" for q in (50, 95, 99)) + " us")
    lines.append("program spans: name | calls | seconds | self seconds")
    for name, s in sorted(snap["spans"].items(),
                          key=lambda kv: -kv[1]["seconds"]):
        lines.append(f"program spans: {name} | {s['calls']} | "
                     f"{s['seconds']:.6f} | {s['self_seconds']:.6f}")
    lines.append("program counters: name | count")
    for name, n in sorted(snap["counters"].items()):
        lines.append(f"program counters: {name} | {n}")
    return lines
