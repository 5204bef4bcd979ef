"""Arithmetic of the end-to-end metrics, from the harness's own records.

Open-loop requests are timed from when they were *due*, not when they were
sent. A tail is taken over every request due in the window; one that has
not produced its first token by the close counts at its wait so far, and a
request still decoding at the close adds its open gap, so a stall cannot
hide behind requests that never finish.
"""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def ttft_samples(requests, w0: float, w1: float) -> list[float]:
    """Seconds from due to first token of every request due in [w0, w1);
    one without a first token by w1 counts at w1 - due."""
    out = []
    for r in requests:
        if not w0 <= r["due"] < w1:
            continue
        first = r["tokens"][0] if r["tokens"] else None
        out.append((first if first is not None and first <= w1 else w1)
                   - r["due"])
    return out


def itl_samples(requests, w0: float, w1: float) -> list[float]:
    """Every gap between consecutive tokens of requests due in [w0, w1),
    up to w1; a request still decoding at w1 adds its open gap."""
    out = []
    for r in requests:
        if not w0 <= r["due"] < w1:
            continue
        ts = [t for t in r["tokens"] if t <= w1]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
        done = r["finished"] is not None and r["finished"] <= w1
        if ts and not done:
            out.append(w1 - ts[-1])
    return out


def tokens_in(requests, w0: float, w1: float) -> int:
    """Tokens emitted in [w0, w1), whenever their request was due."""
    return sum(sum(1 for t in r["tokens"] if w0 <= t < w1)
               for r in requests)


def rate(count: float, w0: float, w1: float) -> float:
    return count / (w1 - w0)
