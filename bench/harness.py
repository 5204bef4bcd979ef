"""What every cell shares: finding a cell's files by name, the device table,
and loading the per-cell modules (drivers, metric readers) from their paths.

Nothing here knows a cell, a mix or a metric by name: ``BENCHMARK.json``
names them, and the files are found under ``bench/`` by those names.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoDevice(RuntimeError):
    """The devices found are not ones the benchmark has peaks for."""


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str, name: str | None = None):
    """Import a file by path (metric files carry dots in their names)."""
    name = name or "bench_mod_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str, root: str = ROOT) -> dict:
    """A cell's entry with everything it names: its configuration (the
    file's contents), its traffic mix, its driver module path, the path of
    its limits for ``correct`` (``limits/<cell>.json``), its metrics
    (end-to-end and per-layer entries that apply to it)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[workload]
    bench_dir = os.path.join(root, os.path.relpath(BENCH, ROOT))
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[cell["config"]]
    config = read_json(os.path.join(root, centry["file"]))
    traffic = read_json(os.path.join(bench_dir, "traffic",
                                     cell["traffic"] + ".json"))
    driver = os.path.join(bench_dir, "drivers", traffic["driver"] + ".py")
    if not os.path.isfile(driver):
        raise FileNotFoundError(driver)

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in e2e_names]
    return {"cell": cell, "config_entry": centry, "config": config,
            "traffic": traffic, "driver": driver,
            "limits": os.path.join(bench_dir, "limits", workload + ".json"),
            "end_to_end": e2e,
            "per_layer": layer,
            "metric_files": {m["name"]: os.path.join(bench_dir, "metrics",
                                                     m["name"] + ".py")
                             for m in layer}}


def sub_seed(seed: int, salt: int) -> int:
    """A 31-bit seed for one use of a run's seed (any whole number)."""
    import numpy as np
    return int(np.random.default_rng([abs(int(seed)), int(seed < 0),
                                      salt]).integers(0, 2 ** 31 - 1))


def peaks(kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = read_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise NoDevice(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


def devices(chips: int):
    """The accelerator this run measures on, or NoDevice."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform not in ("tpu",):
        raise NoDevice(f"JAX runs on {d0.platform}, not an accelerator")
    peaks(d0.device_kind)
    if len(devs) < chips:
        raise NoDevice(f"{len(devs)} device(s), the cell needs {chips}")
    return devs
