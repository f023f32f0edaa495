"""Batched CSOAA agent arena: all functions' regressors in stacked tensors.

``repro.core.allocator``'s legacy engine keeps one ``OnlineCSC`` per
(function, resource) pair and pays a jit'd dispatch per agent per event
(~107 µs a predict, ~130 µs an update on the bench machine), the
overhead wall the paper measures in Fig. 14. The arena fuses them:

* **Stacked state** — every agent with the same ``(n_classes, dim)``
  shape lives as one row of a weight / AdaGrad tensor pair
  (:class:`AgentArena`); a function-name→row map assigns slots, and
  released slots are zeroed and reused. Where the NumPy backend holds
  (below) the pair is a host ``(capacity, n_classes, dim+1)`` array
  that grows by doubling, one arena per resource. Elsewhere (every
  dimension on a TPU, and the one-hot formulation's uncalibrated
  features anywhere) the state is *resident on the device* in blocks of
  ``_MAX_BUCKET`` rows: slot ``s`` is row ``s % 16`` of block ``s //
  16``, growth appends a zeroed block, and the learning rate is a device
  scalar made once. A resident dim keeps ONE arena: a function's row
  stacks its vCPU regressors (classes ``[:n_vcpu]``) and its memory
  regressors (the rest) on the class axis. CSOAA updates and predicts
  each class row on its own, so the stacked row computes exactly what
  the two agents compute apart, with one slot, launch and cost block.
* **Deferred microbatched updates** — completed-invocation feedbacks are
  queued (:class:`ArenaEngine`) and flushed lazily. The ordering rule —
  *pending updates for function F flush before any predict for F* —
  makes served allocations bit-identical to the sequential path: updates
  touching distinct rows commute exactly (disjoint state), and same-row
  updates are applied in arrival order via conflict-free passes.
* **Masked block dispatches** — on resident state a flush pass copies
  in, per touched block, a ``(16, dim+1)`` input and a ``(16,
  n_vcpu + n_mem)`` cost matrix with the pass's rows at their slot
  offsets and zeros elsewhere, launches :data:`_batched_update` (buffers
  donated) on the whole block and reads nothing back: a zero input row
  is an exact no-op (zero gradient, so ``w`` and ``g2`` keep their
  bits). A predict launches :data:`_batched_predict` once per touched
  block, reads all its cost blocks in one transfer and takes the
  arg-min of each wanted resource's classes on the host. The kernels
  are looked up at call time and never wrapped in a further ``jit`` (a
  fused gather/update/scatter program is not bit-identical to the
  reference on the CPU). The engine does not consult
  :func:`vmap_backend`: where the batched kernel differs from the
  per-row one in its last bits (some dims on a v5e) it runs all the
  same, held to the chip benchmark's float64 reference.
* **Calibrated NumPy backend** — for the small batches that dominate a
  discrete-event loop (most events carry one predict or one update), a
  dispatch-free NumPy path beats the JAX call by a wide margin. XLA's
  CPU codegen contracts the per-class dot product and the AdaGrad
  accumulator into FMA chains, so naive NumPy is NOT bit-identical;
  :func:`_matvec_exact` / :func:`_update_exact` reproduce the FMA chain
  via double-precision emulation with a double-rounding hazard check
  (rare hazards fall back to ``libm.fmaf``). The backend is enabled per
  feature dimension only after :func:`numpy_backend` proves it
  bit-identical to the jitted reference on random samples; such
  dimensions keep host state, and :func:`numpy_crossover_rows`
  benchmarks both backends once per shape to size a flush's chunks.

Bit-identity with the legacy per-object path is the load-bearing
guarantee — the golden-metrics harness and the ``sim_bench`` engine A/B
both assert it — which is why the reference kernels (``_csc_predict`` /
``_csc_update``) are *defined here* and shared with the legacy
``OnlineCSC`` rather than duplicated.
"""

from __future__ import annotations

import collections
import ctypes
import ctypes.util
import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans

F32 = np.float32
F64 = np.float64

# ---------------------------------------------------------------------------
# Reference jit kernels (shared with the legacy OnlineCSC path)
# ---------------------------------------------------------------------------

# every dot is full f32: a backend may otherwise run an f32 matmul as a
# single bf16 pass (the TPU's default precision on its matrix unit)
_F32_DOT = jax.lax.Precision.HIGHEST


# Each kernel's operations carry a stable name (``arena/<kernel>``) in
# the device trace, whatever XLA calls the fusions.
@functools.partial(jax.jit, static_argnums=(2,))
def _csc_predict(w: jax.Array, x: jax.Array, n_classes: int) -> jax.Array:
    with jax.named_scope("arena/csc_predict"):
        xb = jnp.concatenate([x, jnp.ones((1,), x.dtype)])
        return jnp.dot(w, xb, precision=_F32_DOT)  # (n_classes,) costs


@jax.jit
def _csc_update(
    w: jax.Array, g2: jax.Array, x: jax.Array, costs: jax.Array, lr: jax.Array
):
    """One-against-all least-squares step on every class's regressor."""
    with jax.named_scope("arena/csc_update"):
        xb = jnp.concatenate([x, jnp.ones((1,), x.dtype)])
        pred = jnp.dot(w, xb, precision=_F32_DOT)
        err = pred - costs  # (n_classes,)
        grad = err[:, None] * xb[None, :]  # (n_classes, dim+1)
        g2 = g2 + jnp.square(grad)
        step = lr * grad / (jnp.sqrt(g2) + 1e-6)
        return w - step, g2


# Batched variants: vmap over stacked rows, xb precomputed by the caller.
# The math is the inner body of the reference kernels — vmap'ing it keeps
# the per-row XLA codegen identical (asserted by vmap_backend()).


def _update_core(w, g2, xb, costs, lr):
    pred = jnp.dot(w, xb, precision=_F32_DOT)
    err = pred - costs
    grad = err[:, None] * xb[None, :]
    g2 = g2 + jnp.square(grad)
    step = lr * grad / (jnp.sqrt(g2) + 1e-6)
    return w - step, g2


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _batched_update(w, g2, xb, costs, lr):
    with jax.named_scope("arena/batched_update"):
        return jax.vmap(_update_core, in_axes=(0, 0, 0, 0, None))(
            w, g2, xb, costs, lr)


@jax.jit
def _batched_predict(w, xb):
    with jax.named_scope("arena/batched_predict"):
        return jax.vmap(lambda wr, xr: jnp.dot(wr, xr, precision=_F32_DOT))(
            w, xb)


# rows of a resident block: every device dispatch has this many rows
_MAX_BUCKET = 16


# ---------------------------------------------------------------------------
# Exact float32 FMA emulation (the NumPy fast path)
# ---------------------------------------------------------------------------

try:  # pragma: no cover - import-time environment probe
    _LIBM = ctypes.CDLL(ctypes.util.find_library("m") or "libm.so.6")
    _LIBM.fmaf.restype = ctypes.c_float
    _LIBM.fmaf.argtypes = [ctypes.c_float] * 3
except (OSError, AttributeError):  # no libm → calibration simply fails
    _LIBM = None


def _fmaf_scalar(a: float, b: float, c: float) -> np.float32:
    return np.float32(
        _LIBM.fmaf(ctypes.c_float(a), ctypes.c_float(b), ctypes.c_float(c))
    )


# hazard probes: a relative nudge of ~90 float64 ulps, orders of
# magnitude wider than the true hazard zone (~1 ulp) yet narrow enough
# that false positives are vanishingly rare
_P_HI = np.float64(1.0 + 2e-14)
_P_LO = np.float64(1.0 - 2e-14)


def _fma32(a: np.ndarray, b, c: np.ndarray) -> np.ndarray:
    """Vectorized float32 fused multiply-add: round(a*b + c) with a
    SINGLE rounding, matching hardware fmaf.

    a*b is exact in float64 (24-bit mantissas), so ``float32(float64(a*b
    + c))`` is correct except when the float64 sum lands within a float64
    ulp of a float32 rounding midpoint (the double-rounding hazard).
    Hazard lanes are detected by nudging the sum ±~90 ulps — if the two
    nudges round to different float32s, the value straddles a midpoint —
    and recomputed with libm's fmaf."""
    t64 = np.multiply(a, b, dtype=F64)
    t64 += c
    r32 = t64.astype(F32)
    hi = (t64 * _P_HI).astype(F32)
    lo = (t64 * _P_LO).astype(F32)
    if not np.array_equal(hi, lo):
        ab = np.broadcast_to(a, t64.shape).reshape(-1)
        bb = np.broadcast_to(b, t64.shape).reshape(-1)
        cb = np.broadcast_to(c, t64.shape).reshape(-1)
        flat = r32.reshape(-1)
        for i in np.nonzero((hi != lo).reshape(-1))[0]:
            flat[i] = _fmaf_scalar(float(ab[i]), float(bb[i]), float(cb[i]))
    return r32


def _matvec_exact(w: np.ndarray, xb: np.ndarray) -> np.ndarray:
    """Row-stacked ``w @ xb`` reproducing XLA's FMA-chain codegen.

    ``w`` is (rows, dim+1); ``xb`` is (dim+1,) or per-row (rows, dim+1)
    — per-row results are independent, so agents with different feature
    vectors (and even different class counts) can be stacked into one
    call. The chain is: exact first product, emulated-FMA middle steps,
    and a plain add for the bias column (xb[..., -1] == 1.0 makes the
    product exact, so float64 addition is double-rounding-safe, see
    Figueroa's 2p+2 theorem). Bit-identity holds for xb lengths 2..7 —
    every Table-2 feature schema — and is asserted per dim by
    numpy_backend() before use.

    The double-rounding hazard probes are DEFERRED: the chain runs with
    plain float64 emulation while stashing each step's unrounded sum,
    then every step is verified in one batched probe at the end; any
    flagged step (vanishingly rare) reruns the whole chain with
    per-step repair (_matvec_checked)."""
    cols = (lambda i: xb[i]) if xb.ndim == 1 else (lambda i: xb[:, i])
    d1 = w.shape[-1]
    acc = np.multiply(w[:, 0], cols(0), dtype=F64).astype(F32)
    if d1 > 2:
        mids = np.empty((d1 - 2,) + acc.shape, F64)
        for i in range(1, d1 - 1):
            t64 = np.multiply(w[:, i], cols(i), dtype=F64)
            t64 += acc
            mids[i - 1] = t64
            acc = t64.astype(F32)
        hi = (mids * _P_HI).astype(F32)
        lo = (mids * _P_LO).astype(F32)
        if not np.array_equal(hi, lo):
            return _matvec_checked(w, xb)
    # bias column: product by 1.0 is exact, add in float64 is safe
    t64 = np.multiply(w[:, d1 - 1], cols(d1 - 1), dtype=F64)
    t64 += acc
    return t64.astype(F32)


def _matvec_checked(w: np.ndarray, xb: np.ndarray) -> np.ndarray:
    """Slow sibling of _matvec_exact: per-step hazard repair."""
    cols = (lambda i: xb[i]) if xb.ndim == 1 else (lambda i: xb[:, i])
    d1 = w.shape[-1]
    acc = np.multiply(w[:, 0], cols(0), dtype=F64).astype(F32)
    for i in range(1, d1 - 1):
        acc = _fma32(w[:, i], cols(i), acc)
    t64 = np.multiply(w[:, d1 - 1], cols(d1 - 1), dtype=F64)
    t64 += acc
    return t64.astype(F32)


# Certified arg-min screen: the exact FMA chain differs from a plain
# float64 dot by at most d1 float32 roundings of intermediates, each
# bounded by 0.5 ulp of the largest partial sum — which Σ|w·x| bounds.
# The worst-case RELATIVE half-ulp is 2^-24 ≈ 5.96e-8 (value just above
# a power of two, where ulp32(v)/v ≈ 2^-23), slightly inflated by the
# (1+2^-24)^d1 growth of rounded partial sums and the float64 dot's own
# error; 1.25e-7 gives a genuine ~2x margin over all of it. When the
# screened margin separates the two smallest costs, the float64 argmin
# IS the exact chain's argmin (strict, so tie order is moot); otherwise
# the caller falls back to the exact chain. Widening the constant only
# costs fallbacks — NEVER tighten it below 2^-24 plus slack.
_SCREEN_EPS = 1.25e-07


def _argmin_screened(w: np.ndarray, xb64: np.ndarray) -> Optional[int]:
    c = w @ xb64  # float64 gemv (screen only — never served directly)
    bound = np.abs(w) @ np.abs(xb64)
    delta = bound * (w.shape[-1] * _SCREEN_EPS)
    m = int(np.argmin(c))
    lo = c - delta
    hi_m = c[m] + delta[m]
    lo[m] = np.inf
    return m if hi_m < lo.min() else None


def _update_exact(
    w: np.ndarray,
    g2: np.ndarray,
    xb: np.ndarray,
    costs: np.ndarray,
    lr: np.float32,
) -> Tuple[np.ndarray, np.ndarray]:
    """Row-stacked NumPy mirror of ``_csc_update``; XLA contracts the
    AdaGrad accumulation ``g2 + grad**2`` into an FMA, hence _fma32 —
    except the bias lane of a 3-wide row (dim 2), which XLA's CPU
    codegen leaves as a rounded square plus an add."""
    pred = _matvec_exact(w, xb)
    pred -= costs
    err = pred  # in place: (rows,)
    if xb.ndim == 1:
        grad = err[:, None] * xb[None, :]
    else:
        grad = err[:, None] * xb
    g2n = _fma32(grad, grad, g2)
    if w.shape[-1] == 3:
        g2n[:, 2] = grad[:, 2] * grad[:, 2] + g2[:, 2]
    denom = np.sqrt(g2n)
    denom += F32(1e-6)
    step = lr * grad
    step /= denom
    return w - step, g2n


# ---------------------------------------------------------------------------
# Backend calibration: trust NumPy / vmap only where provably identical
# ---------------------------------------------------------------------------

_CAL_TRIALS = 24
_CAL_ROWS = (8, 16, 32, 40, 48)


def _reference_pair(rng, n: int, dim: int):
    w = (rng.standard_normal((n, dim + 1)) * 10.0 ** rng.uniform(-2, 2)).astype(F32)
    g2 = (rng.random((n, dim + 1)) * 10.0 ** rng.uniform(-2, 2)).astype(F32)
    x = (rng.standard_normal(dim) * 10.0 ** rng.uniform(-1, 1)).astype(F32)
    costs = (1.0 + rng.random(n) * 30).astype(F32)
    return w, g2, x, costs


@functools.lru_cache(maxsize=None)
def numpy_backend(dim: int) -> bool:
    """True iff the exact-FMA NumPy path is bit-identical to the jitted
    reference kernels for this feature dimension (checked empirically:
    XLA's chain shape is a codegen detail, not a contract)."""
    if _LIBM is None:
        return False
    rng = np.random.default_rng(0xC5C)
    lr = F32(0.5)
    for _ in range(_CAL_TRIALS):
        for n in _CAL_ROWS:
            w, g2, x, costs = _reference_pair(rng, n, dim)
            xb = np.concatenate([x, np.ones(1, F32)])
            ref_c = np.asarray(_csc_predict(jnp.asarray(w), jnp.asarray(x), n))
            if not np.array_equal(ref_c, _matvec_exact(w, xb)):
                return False
            ref_w, ref_g = _csc_update(
                jnp.asarray(w), jnp.asarray(g2), jnp.asarray(x),
                jnp.asarray(costs), jnp.asarray(lr),
            )
            got_w, got_g = _update_exact(w, g2, xb, costs, lr)
            if not (np.array_equal(np.asarray(ref_w), got_w)
                    and np.array_equal(np.asarray(ref_g), got_g)):
                return False
    return True


@functools.lru_cache(maxsize=None)
def vmap_backend(dim: int) -> bool:
    """True iff the vmapped batched kernels, on a resident block whose
    rows stack 32 vCPU and 40 memory classes, match per-agent reference
    calls on each half bitwise (they do on CPU XLA; a diagnostic the
    benchmark prints, not a switch)."""
    rng = np.random.default_rng(0xBA7C)
    lr = F32(0.5)
    k = _MAX_BUCKET
    W, G2, X, C = (np.stack(a) for a in zip(
        *[_reference_pair(rng, 32 + 40, dim) for _ in range(k)]))
    XB = np.concatenate([X, np.ones((k, 1), F32)], axis=1)
    # copies: _batched_update donates its first two buffers
    bw, bg = (np.asarray(a) for a in _batched_update(
        jnp.asarray(W), jnp.asarray(G2), jnp.asarray(XB),
        jnp.asarray(C), jnp.asarray(lr),
    ))
    bc = np.asarray(_batched_predict(jnp.asarray(W), jnp.asarray(XB)))
    for i in range(k):
        for h in (slice(0, 32), slice(32, 72)):
            rw, rg = _csc_update(
                jnp.asarray(W[i, h]), jnp.asarray(G2[i, h]),
                jnp.asarray(X[i]), jnp.asarray(C[i, h]), jnp.asarray(lr),
            )
            rc = _csc_predict(jnp.asarray(W[i, h]), jnp.asarray(X[i]),
                              h.stop - h.start)
            if not (np.array_equal(bw[i, h], np.asarray(rw))
                    and np.array_equal(bg[i, h], np.asarray(rg))
                    and np.array_equal(bc[i, h], np.asarray(rc))):
                return False
    return True


# a microbatch never routes to JAX below this many stacked rows: one
# dispatch costs ~100 µs on CPU, several times the whole NumPy update
# for a handful of agents (72 rows = one function's vCPU+mem pair)
_NUMPY_MIN_ROWS = 512


@functools.lru_cache(maxsize=None)
def numpy_crossover_rows(dim: int, n_classes: int = 32) -> int:
    """Benchmark the NumPy path against one batched JAX dispatch and
    return the stacked-row count above which JAX wins (a flush's chunk
    size on the NumPy backend). On CPU the dispatch overhead (~60-130
    µs) dwarfs the NumPy arithmetic until the stack is thousands of rows
    tall; timing is min-of-reps so a noisy sample can't misroute the
    steady-state singleton batches."""
    if not numpy_backend(dim):
        return 0
    rng = np.random.default_rng(3)
    lr = F32(0.5)
    best = _NUMPY_MIN_ROWS
    # beyond 4096 rows the NumPy path chunks anyway (see _flush_pass),
    # so probing larger stacks would only buy XLA compile time
    for k in (32, 128):
        rows = k * n_classes
        w = (rng.standard_normal((rows, dim + 1))).astype(F32)
        g2 = (rng.random((rows, dim + 1))).astype(F32)
        xb = np.concatenate(
            [rng.standard_normal((rows, dim)).astype(F32), np.ones((rows, 1), F32)],
            axis=1,
        )
        costs = (1.0 + rng.random(rows) * 30).astype(F32)
        W = w.reshape(k, n_classes, dim + 1)
        G2 = g2.reshape(k, n_classes, dim + 1)
        XB = xb.reshape(k, n_classes, dim + 1)[:, 0, :]
        C = costs.reshape(k, n_classes)
        _batched_update(jnp.asarray(W), jnp.asarray(G2), jnp.asarray(XB),
                        jnp.asarray(C), jnp.asarray(lr))  # trace
        t_np = min(
            _timed(lambda: _update_exact(w, g2, xb, costs, lr))
            for _ in range(3)
        )
        t_jax = min(
            _timed(lambda: jax.block_until_ready(_batched_update(
                jnp.asarray(W), jnp.asarray(G2), jnp.asarray(XB),
                jnp.asarray(C), jnp.asarray(lr))))
            for _ in range(3)
        )
        if t_np <= t_jax:
            best = max(best, rows)
        else:
            break
    return best


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _dispatched(kernel: str, dim: int, rows: int) -> None:
    """Count one device dispatch of ``kernel`` for feature dim ``dim``
    carrying ``rows`` real agent rows."""
    if spans.on:
        spans.count(f"arena.dispatch/{kernel}/{dim}")
        spans.count("arena.dispatch_rows", rows)


def calibrate(dims) -> None:
    """Force the one-time per-dim backend calibration + crossover
    benchmark now (results are process-cached). Benchmarks call this
    during warm-up so no timed leg pays a calibration or an XLA
    compile mid-run."""
    for d in dims:
        numpy_backend(d)
        numpy_crossover_rows(d)


# ---------------------------------------------------------------------------
# The arena proper
# ---------------------------------------------------------------------------


class AgentArena:
    """Stacked homogeneous agents: one ``(n_classes, dim+1)`` row pair
    per agent, in doubling-growth host tensors where ``numpy_backend``
    holds for ``dim`` and in device-resident 16-row blocks elsewhere."""

    def __init__(self, n_classes: int, dim: int, lr: float = 0.5,
                 capacity: int = 4):
        self.n_classes = n_classes
        self.dim = dim
        self.lr = F32(lr)
        self.resident = not numpy_backend(dim)
        if self.resident:
            # (w, g2) per block of _MAX_BUCKET slots, on the device
            self.blocks: List[Tuple[jax.Array, jax.Array]] = []
            self.lr_dev = jax.device_put(self.lr)
        else:
            self.w = np.zeros((capacity, n_classes, dim + 1), F32)
            self.g2 = np.zeros((capacity, n_classes, dim + 1), F32)
        self._slots: Dict[str, int] = {}
        self._free: List[int] = []

    @property
    def capacity(self) -> int:
        if self.resident:
            return len(self.blocks) * _MAX_BUCKET
        return self.w.shape[0]

    def slot(self, name: str) -> int:
        """Row index for ``name``, assigning (and growing) on first use."""
        s = self._slots.get(name)
        if s is not None:
            return s
        if self._free:
            s = self._free.pop()
        else:
            s = len(self._slots)
            if s >= self.capacity and self.resident:  # a zeroed block
                shape = (_MAX_BUCKET, self.n_classes, self.dim + 1)
                self.blocks.append((jnp.zeros(shape, F32),
                                    jnp.zeros(shape, F32)))
                spans.count("arena.block_grow")
            elif s >= self.capacity:  # grow by doubling
                pad = np.zeros_like(self.w)
                self.w = np.concatenate([self.w, pad])
                self.g2 = np.concatenate([self.g2, np.zeros_like(pad)])
        self._slots[name] = s
        return s

    def has(self, name: str) -> bool:
        return name in self._slots

    def row(self, name: str) -> Tuple[np.ndarray, np.ndarray]:
        """Host copies of ``name``'s (w, g2) rows."""
        s = self.slot(name)
        if not self.resident:
            return self.w[s].copy(), self.g2[s].copy()
        b, r = divmod(s, _MAX_BUCKET)
        w, g2 = jax.device_get(self.blocks[b])
        return w[r].copy(), g2[r].copy()

    def release(self, name: str) -> None:
        """Free ``name``'s row for reuse; the row is zeroed so a future
        tenant starts as a fresh agent (per-function isolation)."""
        s = self._slots.pop(name, None)
        if s is None:
            return
        if self.resident:
            b, r = divmod(s, _MAX_BUCKET)
            self.blocks[b] = tuple(a.at[r].set(0.0) for a in self.blocks[b])
        else:
            self.w[s] = 0.0
            self.g2[s] = 0.0
        self._free.append(s)


def _stage(groups):
    """Copy in the inputs of resident dispatches, all in one
    ``device_put``. Per ``(arena, functions, xbs, costs or None)`` group
    (functions distinct) and touched block: ``XB (16, dim+1)`` and ``C
    (16, n_classes)`` with the group's rows at their slot offsets, zeros
    elsewhere. Returns ``(group, arena, block, js, rs, XB, C)`` per
    block: ``js`` index the group's rows, ``rs`` their offsets in the
    block."""
    host: List[np.ndarray] = []
    todo = []
    for g, (ar, fns, xbs, costs) in enumerate(groups):
        by_block: Dict[int, Tuple[List[int], List[int]]] = {}
        for j, fn in enumerate(fns):
            b, r = divmod(ar.slot(fn), _MAX_BUCKET)
            js, rs = by_block.setdefault(b, ([], []))
            js.append(j)
            rs.append(r)
        for b, (js, rs) in by_block.items():
            XB = np.zeros((_MAX_BUCKET, ar.dim + 1), F32)
            XB[rs] = xbs[js]
            kx = len(host)
            host.append(XB)
            kc = None
            if costs is not None:
                C = np.zeros((_MAX_BUCKET, ar.n_classes), F32)
                C[rs] = costs[js]
                kc = len(host)
                host.append(C)
            todo.append((g, ar, b, js, rs, kx, kc))
    with spans.span("arena.h2d"):
        dev = jax.device_put(host)
    return [(g, ar, b, js, rs, dev[kx], None if kc is None else dev[kc])
            for g, ar, b, js, rs, kx, kc in todo]


def _update_resident(groups) -> None:
    """Apply each ``(arena, functions, xbs, costs)`` group: one masked
    ``_batched_update`` per touched block, state rebound to its donated
    outputs, nothing read back."""
    for _, ar, b, js, _, XB, C in _stage(groups):
        with spans.span("arena.launch"):
            ar.blocks[b] = _batched_update(*ar.blocks[b], XB, C, ar.lr_dev)
        _dispatched("batched_update", ar.dim, len(js))


def _predict_resident(groups) -> List[np.ndarray]:
    """``(len(functions), n_classes)`` cost rows of each ``(arena,
    functions, xbs, None)`` group: one masked ``_batched_predict`` per
    touched block, then one read of every block's costs."""
    staged = _stage(groups)
    costs = []
    for _, ar, b, js, _, XB, _ in staged:
        with spans.span("arena.launch"):
            costs.append(_batched_predict(ar.blocks[b][0], XB))
        _dispatched("batched_predict", ar.dim, len(js))
    with spans.span("arena.d2h"):
        costs = jax.device_get(costs)
    out = [np.empty((len(fns), ar.n_classes), F32)
           for ar, fns, _, _ in groups]
    for (g, _, _, js, rs, _, _), c in zip(staged, costs):
        out[g][js] = c[rs]
    return out


@dataclasses.dataclass
class _PendingUpdate:
    function: str
    xb: np.ndarray  # (dim+1,) featurized input with bias, float32
    obs: object  # cost_functions.Observation; costs derived at flush


class ArenaEngine:
    """The vCPU and memory agents behind ``ResourceAllocator``.

    Feedbacks enqueue; predicts flush. A flush drains the queue in
    conflict-free passes (each agent row at most once per pass — rows
    are disjoint state, so inter-row reordering is exact) and runs each
    pass as one fused computation: the calibrated NumPy path stacks
    every agent of equal dim (vCPU and memory regressors included) into
    a single row-stacked update; on resident state, where each dim's
    vCPU and memory regressors share one stacked arena, the pass copies
    its inputs in once and launches one masked block update per dim and
    touched block, reading nothing back."""

    def __init__(
        self,
        *,
        n_vcpu_classes: int,
        n_mem_classes: int,
        vcpu_cost_fn: Callable,
        mem_class_mb: int,
        lr: float = 0.5,
    ):
        from repro.core import cost_functions as CF

        self.n_vcpu_classes = n_vcpu_classes
        self.n_mem_classes = n_mem_classes
        self.vcpu_cost_fn = vcpu_cost_fn
        self.mem_class_mb = mem_class_mb
        self.lr = F32(lr)
        self._vcpu_batch_fn = CF.BATCHED_COST_FNS.get(vcpu_cost_fn)
        self._mem_batch_fn = CF.memory_costs_batch
        self._arenas: Dict[Tuple[int, str], AgentArena] = {}  # (dim, agents)
        self._dim_arenas: Dict[int, Tuple[AgentArena, ...]] = {}
        self._dims: Dict[str, int] = {}  # function → feature dim
        self._counts: Dict[str, List[int]] = {}  # eager, incl. pending
        self._pending: List[_PendingUpdate] = []
        # functions with queued updates: a predict only forces a flush
        # when ITS function is in here (updates for other functions
        # touch disjoint rows, so deferring them past this predict is
        # exact) — which lets the queue grow into bigger fused batches
        self._pending_fns: set = set()

    # ------------------------------------------------------------ slots
    def _arenas_of(self, dim: int) -> Tuple[AgentArena, ...]:
        """The arenas of ``dim``'s agents. Where their state is resident,
        one arena of ``n_vcpu_classes + n_mem_classes`` classes whose row
        holds a function's vCPU regressors in classes ``[:n_vcpu_classes]``
        and its memory regressors after them, so that one slot, one
        launch and one cost block serve both; on the NumPy backend, the
        vCPU and the memory host arenas."""
        ars = self._dim_arenas.get(dim)
        if ars is None:
            nv, nm = self.n_vcpu_classes, self.n_mem_classes
            parts = ({"vcpu": nv, "mem": nm} if numpy_backend(dim)
                     else {"vcpu+mem": nv + nm})
            for part, n in parts.items():
                self._arenas[(dim, part)] = AgentArena(n, dim,
                                                       lr=float(self.lr))
            ars = tuple(self._arenas[(dim, part)] for part in parts)
            self._dim_arenas[dim] = ars
        return ars

    def _dim_of(self, function: str, x: np.ndarray) -> int:
        dim = self._dims.setdefault(function, len(x))
        if dim != len(x):
            raise ValueError(
                f"feature dim changed for {function!r}: {dim} -> {len(x)}"
            )
        return dim

    def updates(self, function: str) -> Tuple[int, int]:
        c = self._counts.get(function)
        return (c[0], c[1]) if c else (0, 0)

    def release(self, function: str) -> None:
        dim = self._dims.pop(function, None)
        self._counts.pop(function, None)
        self._pending = [p for p in self._pending if p.function != function]
        self._pending_fns.discard(function)
        if dim is not None:
            for ar in self._arenas_of(dim):
                ar.release(function)

    # ---------------------------------------------------------- feedback
    def enqueue_update(self, function: str, x: np.ndarray, obs) -> None:
        """Defer one completed-invocation feedback (CSOAA update for
        both agents). Nothing is applied yet — the update is queued and
        applied by the next :meth:`flush`, which every predict for
        ``function`` forces first (the flush-before-predict contract:
        a prediction never reads stale rows of its OWN function;
        updates for other functions touch disjoint rows and may stay
        queued, which is what lets batches grow). ``updates()`` counts
        the queued feedback immediately, so confidence thresholds see
        it without a flush."""
        with spans.span("arena.enqueue_update"):
            dim = self._dim_of(function, x)
            xb = np.concatenate([np.asarray(x, F32), np.ones(1, F32)])
            self._pending.append(_PendingUpdate(function, xb, obs))
            self._pending_fns.add(function)
            c = self._counts.setdefault(function, [0, 0])
            c[0] += 1
            c[1] += 1
            # make sure slots exist so growth happens off the predict path
            for ar in self._arenas_of(dim):
                ar.slot(function)

    # ------------------------------------------------------------- flush
    def flush(self, cause: str = "call") -> None:
        """Apply every pending update. Passes preserve per-function
        order; each pass touches each agent row at most once. ``cause``
        says why the flush runs: ``own`` (a predict of a function with
        pending updates), ``cap`` (the 256-entry queue cap) or ``call``
        (an explicit call)."""
        with spans.span("arena.flush"):
            spans.count("arena.flush_cause/" + cause)
            pending = self._pending
            self._pending = []
            self._pending_fns.clear()
            while pending:
                seen = set()
                batch: List[_PendingUpdate] = []
                rest: List[_PendingUpdate] = []
                for p in pending:
                    if p.function in seen:
                        rest.append(p)
                    else:
                        seen.add(p.function)
                        batch.append(p)
                spans.count("arena.flush_pass")
                spans.count("arena.flush_rows", len(batch))
                self._flush_pass(batch)
                pending = rest

    def _cost_matrices(self, batch: Sequence[_PendingUpdate]):
        from repro.core.cost_functions import memory_costs

        obs = [p.obs for p in batch]
        # the vectorized variants win only once the batch amortizes
        # their array-building preamble; tiny batches (the steady-state
        # case) use the scalar functions — both produce bit-identical
        # rows (tests/test_agent_arena.py)
        if len(obs) < 4 or self._vcpu_batch_fn is None:
            vc = np.stack([self.vcpu_cost_fn(o, self.n_vcpu_classes)
                           for o in obs])
            mc = np.stack([memory_costs(o, self.n_mem_classes,
                                        self.mem_class_mb) for o in obs])
        else:
            vc = self._vcpu_batch_fn(obs, self.n_vcpu_classes)
            mc = self._mem_batch_fn(obs, self.n_mem_classes, self.mem_class_mb)
        return vc, mc

    def _flush_pass(self, batch: List[_PendingUpdate]) -> None:
        by_dim: Dict[int, List[int]] = {}
        for i, p in enumerate(batch):
            by_dim.setdefault(len(p.xb) - 1, []).append(i)
        vc, mc = self._cost_matrices(batch)
        resident = []
        for dim, idxs in by_dim.items():
            ars = self._arenas_of(dim)
            fns = [batch[i].function for i in idxs]
            xbs = np.stack([batch[i].xb for i in idxs])
            if ars[0].resident:  # vCPU then memory costs, as rows stack
                costs = np.concatenate([vc[idxs], mc[idxs]], axis=1)
                resident.append((ars[0], fns, xbs, costs.astype(F32)))
                continue
            va, ma = ars
            vcosts = np.ascontiguousarray(vc[idxs]).astype(F32)
            mcosts = np.ascontiguousarray(mc[idxs]).astype(F32)
            vslots = [va.slot(f) for f in fns]
            mslots = [ma.slot(f) for f in fns]
            # row-disjoint chunks are exact, so oversized passes (e.g.
            # the pending-cap flush during the learning phase) split
            # instead of falling back to a fresh XLA compile
            per_item = self.n_vcpu_classes + self.n_mem_classes
            step = max(numpy_crossover_rows(dim) // per_item, 1)
            for lo in range(0, len(idxs), step):
                sl = slice(lo, lo + step)
                self._update_numpy(va, vslots[sl], ma, mslots[sl],
                                   xbs[sl], vcosts[sl], mcosts[sl])
        if resident:
            _update_resident(resident)

    def _update_numpy(self, va, vslots, ma, mslots, xbs, vcosts, mcosts):
        """One row-stacked exact update covering both resources of the
        whole pass: per-row results are independent, so vCPU (32-class)
        and memory (40-class) blocks concatenate freely."""
        nv, nm = va.n_classes, ma.n_classes
        k, d1 = xbs.shape
        if k == 1:  # steady-state fast path: one completion, both agents
            sv, sm = vslots[0], mslots[0]
            w = np.concatenate([va.w[sv], ma.w[sm]])
            g2 = np.concatenate([va.g2[sv], ma.g2[sm]])
            costs = np.concatenate([vcosts[0], mcosts[0]])
            nw, ng = _update_exact(w, g2, xbs[0], costs, self.lr)
            va.w[sv] = nw[:nv]
            va.g2[sv] = ng[:nv]
            ma.w[sm] = nw[nv:]
            ma.g2[sm] = ng[nv:]
            return
        wv = va.w[vslots].reshape(k * nv, d1)
        wm = ma.w[mslots].reshape(k * nm, d1)
        g2v = va.g2[vslots].reshape(k * nv, d1)
        g2m = ma.g2[mslots].reshape(k * nm, d1)
        w = np.concatenate([wv, wm])
        g2 = np.concatenate([g2v, g2m])
        xb = np.concatenate(
            [np.repeat(xbs, nv, axis=0), np.repeat(xbs, nm, axis=0)]
        )
        costs = np.concatenate([vcosts.reshape(-1), mcosts.reshape(-1)])
        nw, ng = _update_exact(w, g2, xb, costs, self.lr)
        split = k * nv
        va.w[vslots] = nw[:split].reshape(k, nv, d1)
        va.g2[vslots] = ng[:split].reshape(k, nv, d1)
        ma.w[mslots] = nw[split:].reshape(k, nm, d1)
        ma.g2[mslots] = ng[split:].reshape(k, nm, d1)

    # ------------------------------------------------------------ predict
    def predict_batch(
        self, items: Sequence[Tuple[str, np.ndarray, bool, bool]]
    ) -> List[Tuple[Optional[int], Optional[int]]]:
        """Arg-min classes for a microbatch of (function, features,
        want_vcpu, want_mem). Flushes pending updates first (the
        ordering rule), then runs all wanted predictions as one fused
        computation per backend group."""
        with spans.span("arena.predict_batch"):
            out: List[Tuple[Optional[int], Optional[int]]] = [
                (None, None) for _ in items]
            by_dim: Dict[int, List[int]] = {}
            for i, (fn, x, want_v, want_m) in enumerate(items):
                if want_v or want_m:
                    by_dim.setdefault(self._dim_of(fn, x), []).append(i)
            if not by_dim:
                # nothing will read agent state, so nothing needs to flush;
                # a cap keeps the queue bounded through long learning phases
                if len(self._pending) >= 256:
                    self.flush("cap")
                return out
            if self._pending_fns and any(
                    items[i][0] in self._pending_fns
                    for idxs in by_dim.values() for i in idxs):
                self.flush("own")
            elif len(self._pending) >= 256:
                self.flush("cap")
            picks: Dict[int, List[Optional[int]]] = {
                i: [None, None] for idxs in by_dim.values() for i in idxs}
            nv, nm = self.n_vcpu_classes, self.n_mem_classes
            groups, owners = [], []  # resident predicts, their items
            for dim, idxs in by_dim.items():
                ars = self._arenas_of(dim)
                if not ars[0].resident and len(items) == 1:
                    fn, x, want_v, want_m = items[0]
                    out[0] = self._predict_one_numpy(fn, x, dim, want_v, want_m)
                    return out
                xb_of = {i: np.append(np.asarray(items[i][1], F32), F32(1))
                         for i in idxs}
                if ars[0].resident:
                    # a function twice in the cohort shares a row, so
                    # its second item goes in the next round's dispatch
                    rounds = collections.defaultdict(list)
                    seen = collections.Counter()
                    for i in idxs:
                        rounds[seen[items[i][0]]].append(i)
                        seen[items[i][0]] += 1
                    for rnd in rounds.values():
                        groups.append((ars[0], [items[i][0] for i in rnd],
                                       np.stack([xb_of[i] for i in rnd]),
                                       None))
                        owners.append(rnd)
                    continue
                va, ma = ars
                v_items = [i for i in idxs if items[i][2]]
                m_items = [i for i in idxs if items[i][3]]
                w = np.concatenate(
                    [va.w[va.slot(items[i][0])] for i in v_items]
                    + [ma.w[ma.slot(items[i][0])] for i in m_items])
                xb = np.concatenate(
                    [np.repeat(xb_of[i][None, :], nv, axis=0) for i in v_items]
                    + [np.repeat(xb_of[i][None, :], nm, axis=0) for i in m_items])
                costs = _matvec_exact(w, xb)
                off = 0
                for sel, n, pos in ((v_items, nv, 0), (m_items, nm, 1)):
                    for i in sel:
                        picks[i][pos] = int(np.argmin(costs[off:off + n]))
                        off += n
            # a stacked row's costs: the vCPU classes, then the memory
            # classes; a side not wanted was computed and is ignored
            for rnd, costs in zip(
                    owners, _predict_resident(groups) if groups else []):
                for i, c in zip(rnd, costs):
                    want_v, want_m = items[i][2], items[i][3]
                    if want_v:
                        picks[i][0] = int(np.argmin(c[:nv]))
                    if want_m:
                        picks[i][1] = int(np.argmin(c[nv:]))
                    if spans.on:
                        spans.count("arena.predict_want/" + (
                            "both" if want_v and want_m
                            else "vcpu" if want_v else "mem"))
            for i, (v, m) in picks.items():
                out[i] = (v, m)
            return out

    def _predict_one_numpy(self, fn: str, x: np.ndarray, dim: int,
                           want_v: bool, want_m: bool):
        """Dispatch-free singleton prediction: both agents' regressors
        stacked into one computation, xb broadcast across rows. The
        certified float64 screen picks the arg-min without running the
        exact FMA chain; near-ties (and all-zero agents) fall back to
        the bit-exact matvec."""
        va, ma = self._arenas_of(dim)
        nv = self.n_vcpu_classes
        if want_v and want_m:
            w = np.concatenate([va.w[va.slot(fn)], ma.w[ma.slot(fn)]])
        elif want_v:
            w = va.w[va.slot(fn)]
        else:
            w = ma.w[ma.slot(fn)]
        xb64 = np.empty(dim + 1, F64)
        xb64[:dim] = x
        xb64[dim] = 1.0
        if want_v and want_m:
            mv = _argmin_screened(w[:nv], xb64)
            mm = _argmin_screened(w[nv:], xb64) if mv is not None else None
            if mm is not None:
                return (mv, mm)
        else:
            m = _argmin_screened(w, xb64)
            if m is not None:
                return (m, None) if want_v else (None, m)
        costs = _matvec_exact(w, xb64.astype(F32))
        if want_v and want_m:
            return (int(np.argmin(costs[:nv])), int(np.argmin(costs[nv:])))
        m = int(np.argmin(costs))
        return (m, None) if want_v else (None, m)

    def predict(self, function: str, x: np.ndarray, want_vcpu: bool,
                want_mem: bool) -> Tuple[Optional[int], Optional[int]]:
        """Singleton prediction — the event loop's steady state, so it
        skips the batch machinery entirely on the NumPy backend.
        Honors the flush-before-predict contract: pending updates for
        ``function`` are applied first (see :meth:`enqueue_update`);
        pending updates for OTHER functions are left queued unless the
        256-entry cap forces a drain."""
        with spans.span("arena.predict"):
            if not (want_vcpu or want_mem):
                if len(self._pending) >= 256:
                    self.flush("cap")
                return (None, None)
            dim = self._dim_of(function, x)
            if not self._arenas_of(dim)[0].resident:
                if function in self._pending_fns:
                    self.flush("own")
                elif len(self._pending) >= 256:
                    self.flush("cap")
                return self._predict_one_numpy(function, x, dim,
                                               want_vcpu, want_mem)
            return self.predict_batch([(function, x, want_vcpu, want_mem)])[0]

    def predicted_costs(self, function: str, x: np.ndarray):
        """Full cost vectors (vcpu, mem) — diagnostics path."""
        self.flush()
        ars = self._arenas_of(self._dim_of(function, x))
        xb = np.concatenate([np.asarray(x, F32), np.ones(1, F32)])
        if ars[0].resident:
            (c,) = _predict_resident([(ars[0], [function], xb[None], None)])
            return c[0, :self.n_vcpu_classes], c[0, self.n_vcpu_classes:]
        va, ma = ars
        return (_matvec_exact(va.w[va.slot(function)], xb),
                _matvec_exact(ma.w[ma.slot(function)], xb))

    # ------------------------------------------------------------- debug
    def weights(self, function: str):
        """(vcpu_w, vcpu_g2, mem_w, mem_g2) copies for tests; flushes."""
        self.flush()
        ars = self._arenas_of(self._dims[function])
        if not ars[0].resident:
            return ars[0].row(function) + ars[1].row(function)
        w, g2 = ars[0].row(function)
        nv = self.n_vcpu_classes
        return w[:nv], g2[:nv], w[nv:], g2[nv:]
