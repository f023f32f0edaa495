"""Layer timing from the benchmark's side, by wrapping the public entry
points of the objects a pass builds.

Each wrapped call costs two host clock reads and a few additions. With
``annotate`` on (traced runs) each call also opens a
``jax.profiler.TraceAnnotation`` named ``bench/<layer>``, so that the
trace reduction can say what the host was doing in each device gap.

Layers, outermost first:

* ``allocate``, ``begin_batch``, ``feedback``: the policy
  (``ShabariPolicy``): featurize, predict, learn;
* ``route``: the front door and placement (``Router.route``, the
  scheduler inside it);
* ``arena.*``: the agent arena's public calls (``ArenaEngine``), nested
  inside the policy's; ``arena.flush`` is the deferred-update flush.

Time that no span covers is the event loop's own.

The probe also ends a pass at the window's deadline: the first policy or
router call past it raises :class:`Deadline`.
"""

from __future__ import annotations

import collections
import contextlib
import time

from bench import peaks as P

ARENA_METHODS = ("predict", "predict_batch", "flush", "enqueue_update")


class Deadline(Exception):
    """Raised into the simulator to stop the pass in flight."""


class Probe:
    def __init__(self, *, annotate: bool = False):
        self.annotate = annotate
        self.deadline = float("inf")
        self.seconds = collections.Counter()  # layer -> outermost seconds
        self.calls = collections.Counter()
        self.covered = 0.0  # seconds inside any outermost span
        self.decisions_s: list = []  # per fresh arrival, cohort start -> route
        self.arena_s = 0.0
        self.arena_bytes = 0
        self.arena_ops = 0
        self._depth = 0
        self._arena_depth = 0
        self._open = collections.Counter()
        self._cohort_t0: dict = {}
        self._current = None  # start time of the arrival being decided
        if annotate:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        else:
            self._annotation = None

    # ------------------------------------------------------------ spans
    def _enter(self, layer: str) -> float:
        t0 = time.perf_counter()
        if self._depth == 0 and t0 >= self.deadline:
            raise Deadline
        self._depth += 1
        self._open[layer] += 1
        return t0

    def _exit(self, layer: str, t0: float) -> float:
        t1 = time.perf_counter()
        dt = t1 - t0
        self._depth -= 1
        self._open[layer] -= 1
        if not self._open[layer]:
            self.seconds[layer] += dt
        self.calls[layer] += 1
        if not self._depth:
            self.covered += dt
        return t1

    def _call(self, layer, fn, args, kwargs):
        t0 = self._enter(layer)
        try:
            if self._annotation is None:
                return fn(*args, **kwargs)
            with self._annotation("bench/" + layer):
                return fn(*args, **kwargs)
        finally:
            self._exit(layer, t0)

    def span(self, layer: str, fn):
        def wrapped(*args, **kwargs):
            return self._call(layer, fn, args, kwargs)
        return wrapped

    # ---------------------------------------------------- one pass's objects
    def instrument(self, sim) -> None:
        """Wrap the policy's and the router's entry points of ``sim``."""
        policy, router = sim.policy, sim.router
        allocate = policy.allocate_with_aux
        begin = policy.begin_arrival_batch
        route = router.route

        def allocate_with_aux(arrival, *args, **kwargs):
            t0 = time.perf_counter()
            self._current = self._cohort_t0.pop(arrival.invocation_id, t0)
            return self._call("allocate", allocate, (arrival,) + args, kwargs)

        def begin_arrival_batch(items, *args, **kwargs):
            t0 = time.perf_counter()
            out = self._call("begin_batch", begin, (items,) + args, kwargs)
            for arrival, _ in items:
                self._cohort_t0[arrival.invocation_id] = t0
            return out

        def route_one(*args, **kwargs):
            t0 = self._enter("route")
            try:
                if self._annotation is None:
                    return route(*args, **kwargs)
                with self._annotation("bench/route"):
                    return route(*args, **kwargs)
            finally:
                t1 = self._exit("route", t0)
                if self._current is not None:
                    # the first route of a fresh arrival: its decision is made
                    self.decisions_s.append(t1 - self._current)
                    self._current = None

        policy.allocate_with_aux = allocate_with_aux
        policy.begin_arrival_batch = begin_arrival_batch
        policy.feedback = self.span("feedback", policy.feedback)
        router.route = route_one

    # ----------------------------------------------------------- the arena
    def _arena_work(self, engine, name, args):
        if name == "predict":
            items = [(args[0], args[1], args[2], args[3])]
        elif name == "predict_batch":
            items = args[0]
        elif name == "enqueue_update":
            x = args[1]
            for n in (engine.n_vcpu_classes, engine.n_mem_classes):
                b, o = P.update_work(n, len(x))
                self.arena_bytes += b
                self.arena_ops += o
            return
        else:
            return
        for _, x, want_v, want_m in items:
            for want, n in ((want_v, engine.n_vcpu_classes),
                            (want_m, engine.n_mem_classes)):
                if want:
                    b, o = P.predict_work(n, len(x))
                    self.arena_bytes += b
                    self.arena_ops += o

    @contextlib.contextmanager
    def arena(self, engine_cls):
        """Wrap the arena's public calls at class level while open."""
        orig = {n: getattr(engine_cls, n) for n in ARENA_METHODS}

        def wrap(name, fn):
            layer = "arena." + name

            def wrapped(engine, *args, **kwargs):
                outer = not self._arena_depth
                if outer:
                    self._arena_work(engine, name, args)
                self._arena_depth += 1
                t0 = time.perf_counter()
                try:
                    return self._call(layer, fn, (engine,) + args, kwargs)
                finally:
                    self._arena_depth -= 1
                    if outer:
                        self.arena_s += time.perf_counter() - t0
            return wrapped

        for name, fn in orig.items():
            setattr(engine_cls, name, wrap(name, fn))
        try:
            yield self
        finally:
            for name, fn in orig.items():
                setattr(engine_cls, name, fn)
