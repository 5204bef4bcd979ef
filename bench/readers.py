"""Helpers the per-layer metric readers share: records inside the window,
program time from the trace reduction, and the cell's required work."""

from __future__ import annotations


def in_window(rec: dict):
    """The requests due in the window."""
    w0, w1 = rec["window"]
    return [r for r in rec["requests"] if w0 <= r["due"] < w1]


def window_steps(rec: dict):
    w0, w1 = rec["window"]
    return [s for s in rec["steps"] if w0 <= s["t0"] and s["t1"] <= w1]


def per_call(red: dict, names) -> float | None:
    """Mean device seconds of one run of the named programs."""
    from bench import trace
    n = trace.program_count(red, names)
    return trace.program_seconds(red, names) / n if n else None


def share(num: float, den: float) -> float | None:
    """A share in percent; nothing to read where the base is empty."""
    return 100.0 * num / den if den > 0 else None
