"""The agent arena's share of its roofline: the least time the chip
needs for the bytes and operations of the agent rows actually predicted
and updated (bench/peaks.py), over the wall seconds spent in the arena's
public calls. The denominator is wall time, so the share reads the same
work whether the arena runs on the device or on the host."""

from bench.peaks import least_seconds


def read(run):
    p = run.probe
    if p.arena_s <= 0 or not p.arena_bytes:
        return None
    return 100.0 * least_seconds(p.arena_bytes, p.arena_ops, run.peak) / p.arena_s
