"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cell; its configuration, traffic mix, driver
and per-layer metric readers are files under ``bench/`` found by name. The
run makes weights and inputs from ``--seed``, warms up every shape the
cell uses (set-up), measures for ``--seconds``, then checks what the timed
path produced against a plain reference (``correct``). ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` traces the same window
with the JAX profiler and reports the per-layer metrics; a mix's
``trace_s`` caps how long a traced window is.

The last line of stdout is one JSON object; the numbers compared for
``correct`` are the last lines of stderr and the result's last key. A run
on a device that ``bench/peaks.json`` does not list -- the CPU included --
exits nonzero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402

CACHE_DIR = os.path.join(harness.ROOT, ".jax_cache")


def _configure_jax(trace: bool):
    """The compile cache at its fixed place in the checkout, every program
    cached; the program's own host spans on when tracing."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    if trace:
        os.environ["REPRO_PROFILE"] = "1"
    src = os.path.join(harness.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise harness.NoDevice(f"no program under {src}")
    sys.path.insert(0, src)
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction: its access-time files race between compile threads, and
    # the writes that lose are dropped (a size limit may come from the
    # environment)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


class _CompileCount:
    """When each compilation or trace ended (jax.monitoring)."""

    def __init__(self):
        import jax
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_a, **_k):
        if "backend_compile" in name or "trace_duration" in name:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


def _annotate(name):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


@contextlib.contextmanager
def _traced_window(tdir: str):
    """The measured window under the JAX profiler, marked by a
    ``bench.window`` host span; the trace goes to ``tdir``."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with _annotate("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()


def _peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class NothingToRead(RuntimeError):
    """A per-layer metric listed for the cell found nothing in the trace."""


def compare(limits: dict, read: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether all keep to
    theirs. Nothing may compile inside the window (limit 0): set-up warms
    every shape the cell uses."""
    limits = dict(limits, window_compiles=0)
    checks = {k: {"value": read[k], "limit": lim} for k, lim in limits.items()}
    return checks, all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values())


def per_layer(info: dict, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its reader. The cell is listed
    for every one of them, so a reader that finds nothing is an error."""
    metrics = {}
    for m in info["per_layer"]:
        reader = harness.load_module(info["metric_files"][m["name"]])
        value = reader.read(ctx)
        if value is None:
            raise NothingToRead(
                f"{m['name']} found nothing to read in this cell's trace "
                f"(programs: {sorted(ctx['trace']['programs'])})")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def measure(info: dict, seed: int, seconds: float, trace: bool, devs,
            peaks: dict, t_start: float = T_START,
            keep_trace: str | None = None) -> dict:
    """Set up, measure, check. Returns the result object (without
    printing); ``checks`` maps each compared number to value and limit,
    the compiles inside the window among them (limit 0). ``keep_trace``
    is a path to copy the traced window's ``.xplane.pb`` to."""
    if trace:
        # a mix may trace a shorter window than it measures (``trace_s``), so
        # that its trace can be read within the run's time
        seconds = min(seconds, info["traffic"].get("trace_s", seconds))
    driver = harness.load_module(info["driver"])
    limits = harness.read_json(info["limits"])
    cell = driver.setup(info["config"], info["traffic"], seed)
    if hasattr(driver, "prepare"):
        driver.prepare(cell, seconds)
    counter = _CompileCount()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    rec = driver.run(
        cell, seconds, span=_annotate if trace else None,
        window=(lambda: _traced_window(tdir)) if trace else None)
    # set-up ends where the window opens: a driver's pre-window load counts
    setup_s = rec["window"][0] - t_start
    memory_peak = _peak_bytes(devs[:info["cell"]["chips"]])
    e2e = driver.end_to_end(cell, rec)
    driver.release(cell)
    read = driver.readings(cell, rec)
    checks, correct = compare(limits, dict(
        read, window_compiles=counter.between(*rec["window"])))

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"]}
    if trace:
        from bench import trace as trace_lib
        try:
            if keep_trace:
                shutil.copyfile(trace_lib.find(tdir), keep_trace)
            red = trace_lib.reduce(trace_lib.read(trace_lib.load(tdir)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        metrics = per_layer(info, {
            "records": rec, "config": info["config"],
            "traffic": info["traffic"], "peaks": peaks, "trace": red})
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        programs = {n: [p["count"], p["seconds"]]
                    for n, p in red["programs"].items()}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in info["end_to_end"]}
    info_out = {"readings": {k: v for k, v in read.items()
                             if k not in checks}}
    if trace:
        info_out["programs"] = programs
    result.update(metrics=metrics, device=device, info=info_out,
                  checks=checks)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        info = harness.resolve(harness.benchmark(), args.workload)
        _configure_jax(bool(args.trace))
        devs = harness.devices(info["cell"]["chips"])
        peaks = harness.peaks(devs[0].device_kind)
    except (harness.NoDevice, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result = measure(info, args.seed, args.seconds, bool(args.trace), devs,
                     peaks)
    line = json.dumps(result)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
