"""What decides ``correct``: the allocator's served decisions and final
weights against a plain float64 CSOAA (paper §4), and its control.

The reference is written from the paper's cost definition and update
rule and imports nothing of the program. It replays the ordered stream
of predictions and updates that the timed path made (recorded by
:class:`Recorder`), with the features and observations the program
handed its arena, and computes two numbers per pass:

* ``served_gap``: the widest gap, relative to the reference's best cost,
  by which a class the program served costs more than the reference's
  arg-min;
* ``weight_dev_p50``: for each agent, the median over the weights that
  either side moved from 0 of the program's deviation from the
  reference, relative to the agent's largest weight; the largest
  agent's. Every agent counts, so a fault
  on one function's or one feature dimension's path shows. The median
  over an agent's weights and not their largest: in a few seeds one
  weight of one agent amplifies float32 rounding at a change of input
  regime (PERF.md, "How correct is decided"), which no fault explains,
  while a fault moves most of an agent's weights;

and counts ``breaches``: predictions served where the reference's
confidence count did not allow one or withheld where it did, and agents
the reference has and the program lacks. Its limit is 0. It also
returns the counts of served predictions and of updates, which the
harness holds against the pass's invocations.

The control is the same replay in float32 with a lower precision of
every dot product: ``HIGH`` (three bfloat16 passes, the next below the
arena's ``Precision.HIGHEST``) or ``bf16`` (one pass, the TPU's default
precision). :func:`replay` computes a control's numbers beside the
program's, from the same stream.
"""

from __future__ import annotations

import collections
import contextlib
import math
import time
from typing import Dict, List, Tuple

import numpy as np

# The agents as the configurations' ``shabari`` policy sets them (paper
# §4.3, §6): classes are 1..32 vCPUs and 1..40 x 128 MB, AdaGrad rate
# 0.5, predictions served once an agent has seen 10 (vCPU) or 20
# (memory) completions.
N_CLASSES = {"vcpu": 32, "mem": 40}
MEM_CLASS_MB = 128
LR = 0.5
CONFIDENCE = {"vcpu": 10, "mem": 20}
# Absolute vCPU costs (§4.3.1): every 0.5 s of SLO violation moves the
# target one class above the vCPUs used, every 1.5 s of slack one class
# below; a violation at under 90% utilization is not the allocation's
# fault. Costs are 1 at the target and grow linearly away from it,
# underprediction more steeply.
VIOLATION_S_PER_CLASS = 0.5
SLACK_S_PER_CLASS = 1.5
HIGH_UTIL = 0.9
SLOPES = {"vcpu": (3.0, 1.0), "mem": (6.0, 1.0)}  # (under, over) per class

# Limits on the two numbers, set from the readings in PERF.md ("How
# correct is decided"): above the largest reading of sound runs, below
# the smallest reading of the control.
LIMITS = {"served_gap": 1e-03, "weight_dev_p50": 3e-06}


def reference_costs(obs) -> Dict[str, np.ndarray]:
    """{"vcpu": costs, "mem": costs} for one completed invocation."""
    def clamp(i, res):
        return max(0, min(N_CLASSES[res] - 1, i))

    used = clamp(math.ceil(obs.max_vcpus_used) - 1, "vcpu")
    if obs.exec_time_s <= obs.slo_s:
        slack = obs.slo_s - obs.exec_time_s
        v = (min(clamp(obs.alloc_vcpus - 1, "vcpu"), used)
             - int(slack / SLACK_S_PER_CLASS))
    elif obs.max_vcpus_used / max(obs.alloc_vcpus, 1) < HIGH_UTIL:
        v = used
    else:
        violation = obs.exec_time_s - obs.slo_s
        v = used + 1 + int(violation / VIOLATION_S_PER_CLASS)
    if obs.oom_killed:  # the need exceeds the allocation
        m = math.ceil(obs.alloc_mem_mb / MEM_CLASS_MB)
    else:
        m = math.ceil(obs.max_mem_used_mb / MEM_CLASS_MB) - 1
    out = {}
    for res, target in (("vcpu", v), ("mem", m)):
        under, over = SLOPES[res]
        k = np.arange(N_CLASSES[res], dtype=np.float64) - clamp(target, res)
        out[res] = 1.0 + np.where(k < 0, -under * k, over * k)
    return out


# ------------------------------------------------------------- recording
class Recorder:
    """Records every arena engine's ordered stream while open. Events are
    ``("predict", fn, x, want_v, want_m, v_cls, m_cls)`` and
    ``("update", fn, x, obs)``; a predict that the engine serves through
    its own ``predict_batch`` is recorded once."""

    def __init__(self):
        self.streams: Dict[object, list] = collections.defaultdict(list)
        self._taken: set = set()
        self.calls = 0
        self.seconds = 0.0  # spent recording, inside the arena's calls

    def take(self) -> List[Tuple[object, list]]:
        """(engine, stream) of every engine first seen since the last
        call."""
        new = [(e, s) for e, s in self.streams.items() if e not in self._taken]
        self._taken.update(e for e, _ in new)
        return new

    @contextlib.contextmanager
    def recording(self, engine_cls):
        streams, depth = self.streams, collections.Counter()
        orig = {n: getattr(engine_cls, n)
                for n in ("predict", "predict_batch", "enqueue_update")}

        def record(eng, events):
            t0 = time.perf_counter()
            streams[eng].extend(ev[:2] + (np.array(ev[2], np.float32),) + ev[3:]
                                for ev in events)
            self.calls += 1
            self.seconds += time.perf_counter() - t0

        def predict(eng, fn, x, want_v, want_m):
            depth[eng] += 1
            try:
                out = orig["predict"](eng, fn, x, want_v, want_m)
            finally:
                depth[eng] -= 1
            if not depth[eng]:
                record(eng, [("predict", fn, x, want_v, want_m) + tuple(out)])
            return out

        def predict_batch(eng, items):
            depth[eng] += 1
            try:
                out = orig["predict_batch"](eng, items)
            finally:
                depth[eng] -= 1
            if not depth[eng]:
                record(eng, [("predict", fn, x, want_v, want_m) + tuple(cls)
                             for (fn, x, want_v, want_m), cls in zip(items, out)])
            return out

        def enqueue_update(eng, fn, x, obs):
            record(eng, [("update", fn, x, obs)])
            return orig["enqueue_update"](eng, fn, x, obs)

        for name, fn in (("predict", predict), ("predict_batch", predict_batch),
                         ("enqueue_update", enqueue_update)):
            setattr(engine_cls, name, fn)
        try:
            yield self
        finally:
            for name, fn in orig.items():
                setattr(engine_cls, name, fn)


# ------------------------------------------------------ the two agents
def _bf16(a: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), kept as float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    u = (u + (((u >> 16) & 1) + np.uint32(0x7FFF))) & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def _dot_high(w: np.ndarray, xb: np.ndarray) -> np.ndarray:
    """``w @ xb`` in float32 at ``Precision.HIGH``: each operand split
    into a bfloat16 head and tail, three products, float32 sums."""
    wh, xh = _bf16(w), _bf16(xb)
    wl, xl = _bf16(w - wh), _bf16(xb - xh)
    terms = (wh * xl + wl * xh) + wh * xh
    return terms.sum(axis=-1, dtype=np.float32)


def _dot_bf16(w: np.ndarray, xb: np.ndarray) -> np.ndarray:
    """``w @ xb`` at ``Precision.DEFAULT`` on a TPU: one bfloat16 pass,
    float32 sums."""
    return (_bf16(w) * _bf16(xb)).sum(axis=-1, dtype=np.float32)


DOTS = {"high": _dot_high, "bf16": _dot_bf16}


class _Agents:
    """One (w, g2) pair per (function, resource): in float64, or with a
    control's ``dot`` in float32."""

    def __init__(self, dot=None):
        self.dot = dot or (lambda w, xb: w @ xb)
        self.dtype = np.float64 if dot is None else np.float32
        self.state: Dict[Tuple[str, str], List[np.ndarray]] = {}

    def get(self, fn, res, d1):
        st = self.state.get((fn, res))
        if st is None:
            z = np.zeros((N_CLASSES[res], d1), self.dtype)
            st = self.state[(fn, res)] = [z, z.copy()]
        return st

    def costs(self, fn, res, xb):
        return self.dot(self.get(fn, res, len(xb))[0], xb)

    def update(self, fn, xb, costs):
        for res in ("vcpu", "mem"):
            st = self.get(fn, res, len(xb))
            w, g2 = st
            c = costs[res].astype(self.dtype)
            pred = self.dot(w, xb)
            grad = np.outer(pred - c, xb)
            g2 = g2 + grad * grad
            st[0] = w - self.dtype(LR) * grad / (np.sqrt(g2) + self.dtype(1e-6))
            st[1] = g2


def _gap(c_ref: np.ndarray, served: int) -> float:
    lo, lo_next = np.sort(c_ref)[:2]
    scale = max(abs(float(lo)), abs(float(lo_next)))
    diff = float(c_ref[served]) - float(lo)
    return diff / max(scale, 1e-30) if diff > 0.0 else 0.0


def _weight_devs(got: Dict[Tuple[str, str], np.ndarray],
                 ref: _Agents) -> Tuple[Dict[str, float], int]:
    """Each agent's deviations relative to its largest weight, as
    ``weight_dev_p50`` (the largest agent's median over its moved weights) and
    ``weight_dev_max`` (the largest single weight's); and the count of
    agents missing from ``got``."""
    p50 = dmax = 0.0
    missing = 0
    for key, (w, _) in ref.state.items():
        g = got.get(key)
        if g is None:
            missing += 1
            continue
        g = g.astype(np.float64)
        d = np.abs(g - w) / max(float(np.abs(w).max()), 1e-30)
        # a feature that is always 0 leaves its weights at exactly 0 on
        # both sides; only weights that either side moved count
        moved = (w != 0.0) | (g != 0.0)
        if moved.any():
            p50 = max(p50, float(np.median(d[moved])))
        dmax = max(dmax, float(d.max()))
    return {"weight_dev_p50": p50, "weight_dev_max": dmax}, missing


def engine_weights(engine, functions) -> Dict[Tuple[str, str], np.ndarray]:
    """The program's final weights per (function, resource), through the
    engine's public ``weights`` accessor."""
    out = {}
    for fn in functions:
        vw, _, mw, _ = engine.weights(fn)
        out[(fn, "vcpu")], out[(fn, "mem")] = vw, mw
    return out


def replay(stream, program_weights, controls=()) -> Dict:
    """The program's ``served_gap``, ``weight_dev_p50`` and ``breaches``
    against the float64 reference over one recorded stream, with the
    largest agent's deviation (``weight_dev_max``) and the counts of
    served predictions and updates; for each name in ``controls`` (keys
    of :data:`DOTS`), that control's numbers on the same stream."""
    ref = _Agents()
    ctls = {name: _Agents(DOTS[name]) for name in controls}
    gap_c = dict.fromkeys(ctls, 0.0)
    seen = collections.Counter()
    gap_p = 0.0
    served = breaches = 0
    for ev in stream:
        fn, x = ev[1], ev[2]
        xb64 = np.append(x.astype(np.float64), 1.0)
        xb32 = np.append(x.astype(np.float32), np.float32(1.0))
        if ev[0] == "update":
            costs = reference_costs(ev[3])
            ref.update(fn, xb64, costs)
            for ctl in ctls.values():
                ctl.update(fn, xb32, costs)
            seen[fn] += 1
            continue
        for res, want, got in (("vcpu", ev[3], ev[5]), ("mem", ev[4], ev[6])):
            if want != (seen[fn] >= CONFIDENCE[res]) or want != (got is not None):
                breaches += 1
                continue
            if not want:
                continue
            served += 1
            c_ref = ref.costs(fn, res, xb64)
            gap_p = max(gap_p, _gap(c_ref, got))
            for name, ctl in ctls.items():
                gap_c[name] = max(gap_c[name], _gap(c_ref, int(np.argmin(
                    ctl.costs(fn, res, xb32)))))
    devs, missing = _weight_devs(program_weights, ref)
    out = dict(devs, served_gap=gap_p, breaches=breaches + missing,
               served=served, updates=sum(seen.values()))
    for name, ctl in ctls.items():
        out[f"control_{name}"] = dict(_weight_devs(
            {k: st[0] for k, st in ctl.state.items()}, ref)[0],
            served_gap=gap_c[name])
    return out


def updated_functions(stream) -> List[str]:
    return sorted({ev[1] for ev in stream if ev[0] == "update"})
