"""The program's own spans, read from its tracer's ring after a run.

``repro.obs.trace.TRACER`` records a begin and an end record for every span
the program opens while its ring is on (``REPRO_PROFILE=1``, which
``--trace 1`` sets). Its clock is ``CLOCK_MONOTONIC``, the clock of the
drivers' ``time.perf_counter`` stamps, so the ring and ``rec["window"]``
compare directly.

The ring is bounded: where a window holds more records than it keeps, the
oldest are gone, and the readers read the part of the window the ring
still holds (``held``), never extrapolating past it.
"""

from __future__ import annotations


def records() -> list[dict]:
    """The process tracer's ring (empty where its ring was off)."""
    from repro.obs import trace
    return trace.TRACER.events()


def pair(recs) -> list[dict]:
    """Complete spans ``{"name", "id", "parent", "t0", "t1", "attrs"}`` in
    order of their begin; a begin with no end, or an end whose begin the
    ring dropped, makes no span."""
    open_, out = {}, []
    for r in recs:
        if r["kind"] == "begin":
            open_[r["span"]] = r
        elif r["kind"] == "end" and r["span"] in open_:
            b = open_.pop(r["span"])
            out.append({"name": b["name"], "id": b["span"],
                        "parent": b.get("parent"), "t0": b["t"],
                        "t1": r["t"], "attrs": b["attrs"]})
    return sorted(out, key=lambda s: s["t0"])


def held(recs, window) -> tuple[float, float] | None:
    """The part of the window the ring holds: from the window's start, or
    from the ring's first record where the ring starts later, to its end;
    None where the ring holds nothing of the window."""
    w0, w1 = window
    if not recs or recs[-1]["t"] < w0:
        return None
    lo = max(w0, recs[0]["t"])
    return (lo, w1) if lo < w1 else None


def window_spans(ctx) -> tuple[list[dict], tuple[float, float]] | None:
    """The complete spans that lie inside the held part of the run's
    window, and that part; None where the ring holds none of it."""
    recs = records()
    part = held(recs, ctx["records"]["window"])
    if part is None:
        return None
    lo, hi = part
    return [s for s in pair(recs) if lo <= s["t0"] and s["t1"] <= hi], part


def named(spans, name: str) -> list[dict]:
    return [s for s in spans if s["name"] == name]


def duration(s: dict) -> float:
    return s["t1"] - s["t0"]


def under(spans, ancestor: str, name: str) -> dict[int, float]:
    """Seconds of the ``name`` spans nested (at any depth) in each
    ``ancestor`` span, by the ancestor's id."""
    by_id = {s["id"]: s for s in spans}
    out = {s["id"]: 0.0 for s in spans if s["name"] == ancestor}
    for s in spans:
        if s["name"] != name:
            continue
        p = by_id.get(s["parent"])
        while p is not None and p["name"] != ancestor:
            p = by_id.get(p["parent"])
        if p is not None:
            out[p["id"]] += duration(s)
    return out
