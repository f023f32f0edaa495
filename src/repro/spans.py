"""Program spans and counters, off by default.

A span times one call at a layer boundary; a counter counts work done
there. Both are single-threaded, like the simulator they instrument.

Off (the default), :func:`span` returns one shared no-op context and
:func:`count` returns at once: one test of the module flag :data:`on`,
no allocation, no clock read. Hot sites test :data:`on` themselves
before building a counter's name.

On, every span keeps a record in memory: its name, start and end
(``time.perf_counter_ns``), the index of the span open around it, and
the request id its caller gave. Per name the module sums the calls, the
outermost seconds (a span inside another of the same name adds
nothing) and the self seconds (each span's duration less what its child
spans cover). With ``annotate``, every span also opens a
``jax.profiler.TraceAnnotation`` named ``shabari/<name>``, which puts it
into a profiler trace on the clock of the device's operations.

    from repro import spans

    spans.reset()
    spans.enable(annotate=False)
    ...  # run the program
    spans.disable()
    snap = spans.snapshot()

A program run inside :func:`profiled` records on its own while a JAX
profiler trace is being collected, so that a profiled run carries the
program's spans into its trace with no other switch;
``Simulator.run`` runs inside it.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, Hashable, List, Optional

on = False  # the one flag every site tests

_annotation = None  # TraceAnnotation while annotating
_generation = 0  # bumped by reset(): spans opened before it are dropped
_records: List[list] = []  # [name, start_ns, end_ns, parent, rid], start order
_stack: List[int] = []  # indices of the open records, innermost last
_counters: collections.Counter = collections.Counter()
_profiling = False  # the last profiled() block found a trace being collected


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    """Records its start and end only; :func:`snapshot` sums the rest."""

    __slots__ = ("name", "rid", "index", "generation", "annotation")

    def __init__(self, name: str, rid):
        self.name = name
        self.rid = rid

    def __enter__(self):
        self.annotation = None
        if _annotation is not None:
            self.annotation = _annotation("shabari/" + self.name)
            self.annotation.__enter__()
        self.generation = _generation
        self.index = len(_records)
        _records.append([self.name, time.perf_counter_ns(), None,
                         _stack[-1] if _stack else None, self.rid])
        _stack.append(self.index)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if (self.generation == _generation and _stack
                and _stack[-1] == self.index):
            _stack.pop()
            _records[self.index][2] = t1
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False


def span(name: str, rid: Optional[Hashable] = None):
    """Context manager timing one call named ``name``; ``rid`` is the
    request (or tuple of requests) it serves, where the caller has one."""
    if not on:
        return _OFF
    return _Span(name, rid)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if on:
        _counters[name] += n


def enable(annotate: bool = False) -> None:
    """Start recording; with ``annotate``, also into the profiler trace."""
    global on, _annotation
    if annotate:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    else:
        _annotation = None
    on = True


def disable() -> None:
    """Stop recording. Spans open now still close and count."""
    global on, _annotation
    on = False
    _annotation = None


@contextlib.contextmanager
def profiled():
    """Record annotated spans inside the block if a JAX profiler trace is
    being collected as it opens, and stop at its end; the records stay
    for :func:`snapshot`. The first block to find a trace after one that
    found none starts afresh (:func:`reset`), so the record covers one
    profiled stretch. While :func:`enable` has turned recording on, the
    block changes nothing."""
    global _profiling
    from jax.profiler import TraceAnnotation

    tracing = TraceAnnotation.is_enabled()
    started = tracing and not on
    if started:
        if not _profiling:
            reset()
        enable(annotate=True)
    _profiling = tracing
    try:
        yield
    finally:
        if started:
            disable()


def reset() -> None:
    """Forget every record and counter; spans open now are dropped."""
    global _generation
    _generation += 1
    _records.clear()
    _stack.clear()
    _counters.clear()


def snapshot() -> Dict:
    """What was recorded since the last :func:`reset`: per span name its
    ``calls``, outermost ``seconds`` and ``self_seconds`` (closed spans
    only); the counters; the records as ``(name, start_ns, end_ns,
    parent, rid)`` tuples in start order, ``end_ns`` None while open; and
    the number of spans still open."""
    records = [tuple(r) for r in _records]
    child_ns = [0] * len(records)
    for name, t0, t1, parent, _ in records:
        if t1 is not None and parent is not None:
            child_ns[parent] += t1 - t0
    calls = collections.Counter()
    outer_ns = collections.Counter()
    self_ns = collections.Counter()
    for i, (name, t0, t1, parent, _) in enumerate(records):
        if t1 is None:
            continue
        calls[name] += 1
        self_ns[name] += t1 - t0 - child_ns[i]
        while parent is not None and records[parent][0] != name:
            parent = records[parent][3]
        if parent is None:  # no enclosing span of the same name
            outer_ns[name] += t1 - t0
    return {
        "spans": {n: {"calls": calls[n], "seconds": outer_ns[n] * 1e-9,
                      "self_seconds": self_ns[n] * 1e-9} for n in calls},
        "counters": dict(_counters),
        "records": records,
        "open": len(_stack),
    }
