"""The one request generator: a traffic mix's parameters in, requests out.

Every seed gets the same multiset of lengths, inter-arrival gaps and burst
sizes -- stratified quantiles of the stated distributions. They are laid
out over time in a fixed low-discrepancy order, so that every run of
``BLOCK`` consecutive requests holds an even spread of the quantiles, and
the seed shuffles them only within such blocks. So neither the total work
of a run nor the work that falls in its measured window moves with the
seed; only which request comes when, within a few seconds, does. Prompts'
token ids are drawn from the seed too.

A mix's ``arrivals`` is ``{"kind": "poisson", "rate": r}`` (requests/s) or
``{"kind": "bursts", "rate": r, "burst_mean": m, "burst_cap": c}``: burst
starts form a Poisson stream at ``r / m`` per second, a burst holds a
geometric number of requests (mean ``m``, capped at ``c``), all due at its
start. Lengths are ``{"median", "sigma", "min", "max"}`` of a lognormal,
clipped.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_STD_NORMAL = statistics.NormalDist()


BLOCK = 8
# one irrational step per quantity, so that their orders are unrelated
_STEP = {"gap": (5 ** 0.5 - 1) / 2, "prompt": 2 ** 0.5 - 1,
         "output": 3 ** 0.5 - 1, "size": math.pi - 3}


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def spread(values: np.ndarray, what: str,
           rng: np.random.Generator) -> np.ndarray:
    """``values`` laid out so that position i takes the rank of the i-th
    point of a low-discrepancy sequence (even spread over every stretch),
    then shuffled by ``rng`` within consecutive blocks of ``BLOCK``."""
    n = len(values)
    rank = np.argsort(np.argsort(np.mod(np.arange(n) * _STEP[what], 1.0),
                                 kind="stable"), kind="stable")
    out = np.sort(values)[rank]
    for lo in range(0, n, BLOCK):
        out[lo:lo + BLOCK] = rng.permutation(out[lo:lo + BLOCK])
    return out


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """n integer lengths at the stratified quantiles of the clipped
    lognormal ``spec`` (sorted)."""
    z = np.asarray([_STD_NORMAL.inv_cdf(q) for q in _strata(n)])
    vals = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, mean: float) -> np.ndarray:
    """n gaps at stratified exponential quantiles, scaled to sum n * mean."""
    g = -np.log1p(-_strata(n))
    return g * (n * mean / g.sum())


def geometric_sizes(n: int, mean: float, cap: int) -> np.ndarray:
    """n burst sizes at stratified quantiles of a geometric law on 1, 2, ...
    with the given mean, capped."""
    if mean <= 1:
        return np.ones(n, np.int64)
    p = 1.0 / mean
    k = np.ceil(np.log1p(-_strata(n)) / math.log1p(-p))
    return np.clip(k, 1, cap).astype(np.int64)


def arrival_times(arrivals: dict, horizon: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from the start of the load) over ``horizon``."""
    rate = float(arrivals["rate"])
    if arrivals["kind"] == "poisson":
        n = max(1, math.ceil(rate * horizon))
        return np.cumsum(spread(exponential_gaps(n, 1.0 / rate), "gap", rng))
    if arrivals["kind"] == "bursts":
        mean = float(arrivals["burst_mean"])
        nb = max(1, math.ceil(rate / mean * horizon))
        starts = np.cumsum(spread(exponential_gaps(nb, mean / rate), "gap",
                                  rng))
        sizes = spread(geometric_sizes(nb, mean, int(arrivals["burst_cap"])),
                       "size", rng)
        return np.repeat(starts, sizes)
    raise ValueError(f"unknown arrival kind {arrivals['kind']!r}")


def requests(mix: dict, seed: int, horizon: float, vocab: int) -> list[dict]:
    """The requests of one run: ``due`` (s from the start of the load),
    ``prompt`` (token ids) and ``max_new_tokens``, in due order."""
    rng = np.random.default_rng(seed)
    due = arrival_times(mix["arrivals"], horizon, rng)
    n = len(due)
    prompts = spread(lognormal_lengths(n, mix["prompt"]), "prompt", rng)
    outputs = spread(lognormal_lengths(n, mix["output"]), "output", rng)
    return [{"due": float(d), "prompt": rng.integers(0, vocab, int(p)),
             "max_new_tokens": int(o)}
            for d, p, o in zip(due, prompts, outputs)]
