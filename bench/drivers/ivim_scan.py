"""Whole IVIM scans through ``engine.predict_volume``, one in flight.

A scanner console hands over whole ``X x Y x Z x n_b`` float32 volumes as
host arrays; a request ends when the mean and std maps are both back on the
host. The loop is closed: the next scan starts when the last one is back,
cycling through ``n_volumes`` synthetic volumes made from the seed. The
window opens at the first scan and closes at the end of the last scan that
started within ``seconds``, so the rate covers all the work and all the
time of the window.

Weights (random, batchnorm statistics included) and volumes are made on
the device from the seed, each in one jitted call; the masks come from
``bench/reference/masks.py``. ``correct`` compares every voxel of a seeded
sample of the window's scans with ``bench/reference/ivim.py``.
"""

from __future__ import annotations

import contextlib
import functools
import time

import numpy as np

from bench import harness
from bench.reference import ivim as ivim_ref
from bench.reference import masks as masks_ref

PARAM_RANGES = {"D": (0.0005, 0.003), "Dstar": (0.01, 0.1), "f": (0.0, 0.4),
                "S0": (0.8, 1.2)}


@functools.lru_cache(maxsize=None)
def _weight_fn(g: int, w: int):
    import jax
    import jax.numpy as jnp

    def fn(key):
        k = iter(jax.random.split(key, 16))
        he = (2.0 / w) ** 0.5

        def nrm(shape, s):
            return jax.random.normal(next(k), shape, jnp.float32) * s

        def bn():
            return {"gamma": 1.0 + nrm((g, w), 0.1), "beta": nrm((g, w), 0.1),
                    "mean": nrm((g, w), 0.3),
                    "var": jax.random.uniform(next(k), (g, w), jnp.float32,
                                              0.5, 2.0)}

        return {"fc1": {"w": nrm((g, w, w), he), "b": nrm((g, w), 0.1)},
                "fc2": {"w": nrm((g, w, w), he), "b": nrm((g, w), 0.1)},
                "enc": {"w": nrm((g, w, 1), he), "b": nrm((g, 1), 0.1)},
                "bn1": bn(), "bn2": bn()}

    return jax.jit(fn)


def make_weights(config: dict, seed: int) -> dict:
    """The network's weights from the seed, on the device, plus the masks
    the configuration states."""
    import jax
    import jax.numpy as jnp
    w = _weight_fn(config["sub_networks"], config["width"])(
        jax.random.PRNGKey(harness.sub_seed(seed, 1)))
    for slot, salt in (("mask1", 0), ("mask2", 1)):
        m = masks_ref.masks(config["width"], config["n_masks"],
                            config["mask_scale"], config["mask_seed"] + salt)
        w[slot] = jnp.asarray(m, jnp.float32)
    return w


@functools.lru_cache(maxsize=None)
def _volume_fn(nv: int, shape: tuple, b_values: tuple, snr: float):
    import jax
    import jax.numpy as jnp
    n = int(np.prod(shape))

    def fn(key):
        kp, kn = jax.random.split(key)
        ks = jax.random.split(kp, 4)
        p = [jax.random.uniform(k, (nv, n, 1), jnp.float32, lo, hi)
             for k, (lo, hi) in zip(ks, PARAM_RANGES.values())]
        d, dstar, f, s0 = p
        b = jnp.asarray(b_values, jnp.float32)
        s = s0 * (f * jnp.exp(-b * dstar) + (1.0 - f) * jnp.exp(-b * d))
        noisy = s + (s0 / snr) * jax.random.normal(kn, s.shape, jnp.float32)
        b0 = int(np.argmin(b_values))
        return (noisy / jnp.maximum(noisy[..., b0:b0 + 1], 1e-6)).reshape(
            (nv,) + shape + (len(b_values),))

    return jax.jit(fn)


def make_volumes(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """[n_volumes, X, Y, Z, n_b] float32 host arrays: IVIM signals (Le Bihan
    biexponential) at uniform clinical parameters, Gaussian noise of std
    S0/SNR, normalised by the measured b = 0 signal."""
    import jax
    fn = _volume_fn(traffic["n_volumes"], tuple(traffic["volume"]),
                    tuple(config["b_values"]), float(traffic["snr"]))
    return np.asarray(fn(jax.random.PRNGKey(harness.sub_seed(seed, 2))))


def to_program(config: dict, weights: dict):
    """The program's (IvimConfig, params, batchnorm state) for the weights."""
    from repro.ivim import model as ivim_model
    cfg = ivim_model.IvimConfig(
        b_values=tuple(config["b_values"]), n_masks=config["n_masks"],
        scale=config["mask_scale"], mask_seed=config["mask_seed"],
        out_ranges=tuple(tuple(r) for r in config["out_ranges"]))
    params = {k: weights[k] for k in ("fc1", "fc2", "enc", "mask1", "mask2")}
    state = {}
    for bn in ("bn1", "bn2"):
        params[bn] = {k: weights[bn][k] for k in ("gamma", "beta")}
        state[bn] = {k: weights[bn][k] for k in ("mean", "var")}
    return cfg, params, state


def serve(plan, volume):
    """One request: the scan through the engine, both maps to the host."""
    from repro.serving import engine
    mean, std = engine.predict_volume(plan, volume)
    return np.asarray(mean), np.asarray(std)


def setup(config: dict, traffic: dict, seed: int) -> dict:
    from repro.ivim import model as ivim_model
    weights = make_weights(config, seed)
    cfg, params, state = to_program(config, weights)
    plan = ivim_model.pack_for_serving(cfg, params, state)
    volumes = make_volumes(config, traffic, seed)
    for i in range(traffic["warmup_scans"]):
        serve(plan, volumes[i % len(volumes)])
    return {"config": config, "traffic": traffic, "seed": seed,
            "weights": weights, "plan": plan, "volumes": volumes}


def run(cell: dict, seconds: float, span=None, window=None) -> dict:
    """The measured window: closed-loop scans for ``seconds``. ``span``
    makes a named host span, ``window`` the context of the window."""
    span = span or (lambda name: contextlib.nullcontext())
    window = window or contextlib.nullcontext
    plan, volumes = cell["plan"], cell["volumes"]
    keep = cell["traffic"]["checked_scans"]
    rng = np.random.default_rng(harness.sub_seed(cell["seed"], 3))
    scans, kept = [], []
    n_vox = int(np.prod(volumes.shape[1:-1]))
    with window():
        w0 = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            if scans and t0 - w0 >= seconds:
                break
            i, v = len(scans), len(scans) % len(volumes)
            with span("bench.scan"):
                mean, std = serve(plan, volumes[v])
            scans.append({"start": t0, "end": time.perf_counter(),
                          "volume": v, "voxels": n_vox})
            # a seeded reservoir sample of the window's scans to compare
            j = i if i < keep else int(rng.integers(0, i + 1))
            if j < len(kept):
                kept[j] = (v, mean, std)
            elif j < keep:
                kept.append((v, mean, std))
    return {"window": (w0, scans[-1]["end"]), "scans": scans, "kept": kept,
            "attempted": len(scans), "failed": 0}


def end_to_end(cell: dict, rec: dict) -> dict:
    w0, w1 = rec["window"]
    vox = sum(s["voxels"] for s in rec["scans"])
    return {"voxels_per_s": vox / (w1 - w0)}


def release(cell: dict) -> None:
    cell.pop("plan", None)


def _range_err(got, ref, span) -> tuple[float, float]:
    """(largest, root-mean-square) |got - ref| as a share of the range."""
    e = np.abs(np.asarray(got, np.float64) - ref) / span
    return float(e.max()), float(np.sqrt(np.mean(e * e)))


def readings(cell: dict, rec: dict, control: str | None = None) -> dict:
    """Largest gap of the served mean and std maps from the reference's,
    over every voxel of the kept scans, as a share of each parameter's
    range. ``control`` (a dtype, e.g. "bfloat16") reads the reference
    computed in that precision in the program's place instead."""
    config = cell["config"]
    ranges = np.asarray(config["out_ranges"], np.float64)
    span = ranges[:, 1] - ranges[:, 0]
    nb = len(config["b_values"])
    out = {"mean_range_err": 0.0, "std_range_err": 0.0,
           "mean_range_rms": 0.0, "std_range_rms": 0.0, "std_rel_p50": 0.0}
    for v in sorted({v for v, _, _ in rec["kept"]}):
        x = cell["volumes"][v].reshape(-1, nb)
        ref = ivim_ref.moments(cell["weights"], x, ranges,
                               eps=config["bn_eps"])
        if control:
            served = [ivim_ref.moments(cell["weights"], x, ranges,
                                       eps=config["bn_eps"], dtype=control)]
        else:
            served = [(m.reshape(-1, 4), s.reshape(-1, 4))
                      for u, m, s in rec["kept"] if u == v]
        for mean, std in served:
            for name, got, want in (("mean", mean, ref[0]),
                                    ("std", std, ref[1])):
                mx, rms = _range_err(got, want, span)
                out[f"{name}_range_err"] = max(out[f"{name}_range_err"], mx)
                out[f"{name}_range_rms"] = max(out[f"{name}_range_rms"], rms)
            # the median relative gap of the served std (the uncertainty)
            rel = np.abs(np.asarray(std, np.float64) - ref[1]) \
                / np.maximum(ref[1], 1e-3 * span)
            out["std_rel_p50"] = max(out["std_rel_p50"], float(np.median(rel)))
    return out
