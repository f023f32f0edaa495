"""Reduction of a profiler trace (``.xplane.pb``) to device busy and idle
time, operation counts, the heaviest operations, and idle time by the
host span open during it.

Device operations are the events of the lines that ``select(plane,
line)`` accepts; on a TPU those are the ``XLA Ops`` lines of the
``/device:TPU:<n>`` planes. Host spans are the ``bench/<layer>``
annotations that :class:`bench.spans.Probe` opens in traced runs; the
``bench/window`` span bounds the measured window.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Callable, Dict, List, Tuple

WINDOW_SPAN = "bench/window"
LOOP = "loop"  # label of idle time while no benchmark span is open


def tpu_ops(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


def find_trace(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def layout(pd) -> Dict[str, List[str]]:
    """Plane name -> its line names (printed so that a new chip's naming
    shows before the reduction silently finds nothing)."""
    return {p.name: [ln.name for ln in p.lines] for p in pd.planes}


def _events(pd, keep: Callable[[str, str], bool]):
    out = collections.defaultdict(list)
    for p in pd.planes:
        for ln in p.lines:
            if keep(p.name, ln.name):
                for e in ln.events:
                    out[p.name].append((e.start_ns, e.start_ns + e.duration_ns,
                                        e.name))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _labels(spans: List[Tuple[float, float, str]],
            times: List[float]) -> List[str]:
    """For each of the sorted ``times``, the innermost benchmark span open
    then. One thread's spans nest, so the open ones form a stack."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            sp = spans[i]
            i += 1
            while stack and stack[-1][1] < sp[0]:
                stack.pop()
            stack.append(sp)
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else LOOP)
    return out


def reduce_trace(pd, select: Callable[[str, str], bool] = tpu_ops,
                 top: int = 10) -> Dict:
    """Busy and idle seconds inside the ``bench/window`` span, averaged
    over the devices found; operation count and the ``top`` operations
    by device seconds; idle seconds by the host span open at each gap's
    middle."""
    host = _events(pd, lambda p, ln: p.startswith("/host:"))
    spans = sorted((s, e, n) for evs in host.values() for s, e, n in evs
                   if n.startswith("bench/"))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench/window span")
    lo, hi = windows[0]
    spans = [sp for sp in spans if sp[2] != WINDOW_SPAN]
    devices = _events(pd, select)
    if not devices:
        return {"devices": 0, "window_s": (hi - lo) * 1e-9}
    busy, n_ops = 0.0, 0
    op_s: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    for evs in devices.values():
        inside = [(s, e, n) for s, e, n in evs if e > lo and s < hi]
        n_ops += len(inside)
        for s, e, n in inside:
            op_s[n] += (min(e, hi) - max(s, lo)) * 1e-9
        merged = _union(_clip([(s, e) for s, e, _ in inside], lo, hi))
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2]) if ge > gs]
        for (gs, ge), name in zip(gaps, _labels(spans, [(gs + ge) / 2
                                                        for gs, ge in gaps])):
            idle[name] += (ge - gs) * 1e-9
    n_dev = len(devices)
    return {
        "devices": n_dev,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / n_dev,
        "ops": n_ops / n_dev,
        "top_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_by_span": sorted(((k, v / n_dev) for k, v in idle.items()),
                               key=lambda kv: -kv[1])[:top],
    }


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)
