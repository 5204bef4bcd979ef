"""From a JAX profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

- The device busy union: the intervals in which an operation ran on each
  device ("XLA Ops" lines of the ``/device:...`` planes), clipped to the
  measured window, which the harness marks with a ``bench.window`` host
  span.
- Per-program device time: the "XLA Modules" lines, by program name with
  the run id stripped (``jit_decode_impl(1234)`` -> ``jit_decode_impl``).
- The longest idle gaps of the first device, each labelled with the
  innermost host span that covers its middle (the harness's ``bench.*``
  spans, and the program's own ``serving.step`` when ``REPRO_PROFILE=1``).

Host and device events share the profiler's clock in nanoseconds.
"""

from __future__ import annotations

import glob
import gzip
import os
import re

WINDOW_SPAN = "bench.window"
_RUN_ID = re.compile(r"\(\d+\)$")


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    """A profile from a trace directory or an ``.xplane.pb`` file (gzipped
    where the name ends in ``.gz``)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find(path)
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def program_name(name: str) -> str:
    return _RUN_ID.sub("", name)


def union(intervals):
    """Merge [(start, end), ...] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def read(profile) -> dict:
    """Raw events: per device plane its ops and programs, and host spans."""
    devices, host = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "programs": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = _events(line)
                elif line.name == "XLA Modules":
                    dev["programs"] = [(program_name(n), s, e)
                                       for n, s, e in _events(line)]
            if dev["ops"] or dev["programs"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line) if ev[2] > ev[1])
    return {"devices": devices, "host": host}


def _label(host, t: float) -> str:
    """Innermost (shortest) host span covering time t."""
    best = None
    for name, s, e in host:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no host span"


def reduce(raw: dict, top: int = 10) -> dict:
    """Busy union, per-program time and labelled idle gaps, in seconds,
    inside the ``bench.window`` span; a trace without it is an error."""
    devs = raw["devices"]
    if not devs:
        raise ValueError("the trace has no device plane with events")
    marks = [(s, e) for n, s, e in raw["host"] if n == WINDOW_SPAN]
    if not marks:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
    w0, w1 = marks[0]
    busy = []
    for d in devs:
        ivs = [(s, e) for _, s, e in (d["ops"] or d["programs"])]
        busy.append(union(clip(ivs, w0, w1)))
    programs: dict[str, dict] = {}
    for name, s, e in devs[0]["programs"]:
        if s >= w0 and e <= w1:
            p = programs.setdefault(name, {"count": 0, "seconds": 0.0,
                                           "intervals": []})
            p["count"] += 1
            p["seconds"] += (e - s) * 1e-9
            p["intervals"].append((s, e))
    ops: dict[str, float] = {}
    for name, s, e in devs[0]["ops"]:
        if s >= w0 and e <= w1:
            # the op's own name, without the HLO text of its operands
            name = name.split(" = ", 1)[0]
            ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
    gaps, prev = [], w0
    for s, e in busy[0] + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [h for h in raw["host"] if h[0] != WINDOW_SPAN]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(total(b) for b in busy) / len(busy) * 1e-9,
        "busy_intervals": busy[0],
        "window": (w0, w1),
        "programs": programs,
        "device_ops": sorted(([n, t] for n, t in ops.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[_label(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }


def program_seconds(red: dict, names) -> float:
    return sum(p["seconds"] for n, p in red["programs"].items() if n in names)


def program_count(red: dict, names) -> int:
    return sum(p["count"] for n, p in red["programs"].items() if n in names)


def launch_gaps(red: dict, names) -> list[float]:
    """Seconds between the end of one run of the named programs and the
    start of the next, over the window."""
    ivs = sorted(iv for n, p in red["programs"].items() if n in names
                 for iv in p["intervals"])
    return [(b[0] - a[1]) * 1e-9 for a, b in zip(ivs, ivs[1:])]
