"""Readings that set a cell's limits and rate, on the chip; the benchmark's
own runs never run this.

    # what the program and the control read, seed by seed
    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20
    # a knee sweep of an open-loop mix: attainment at each fixed rate
    python3 bench/calibrate.py --workload <cell> --rates 1,2,4 --seconds 20
    # one traced run whose .xplane.pb is kept (the trace tests' data)
    python3 bench/calibrate.py --workload <cell> --seeds 5 --seconds 1 \
        --record-trace bench/tests/data/<cell>.xplane.pb

For every seed: the cell's set-up, a short window at its own load, the
readings of what the program served against the reference, and the same
readings of the control -- the reference at the precision the
configuration's ``control`` names (the nearest below its own) in the
program's place. A limit lies between the program's highest reading and
the control's lowest.

The sweep runs one set-up, then for each rate a window of the mix at that
rate, and prints the share of requests due in it that met the mix's
``slo`` (first token within ``ttft_ms`` of being due, every gap between
tokens within ``gap_ms``; one unfinished at the close is judged on what it
has shown), with the tails, the token rate and the queue left at the
close. The sweep stops at the first rate whose backlog grows through the
window. Each line is JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness, stats  # noqa: E402


def attainment(rec: dict, slo: dict) -> dict:
    w0, w1 = rec["window"]
    due = [r for r in rec["requests"] if w0 <= r["due"] < w1]
    mid = (w0 + w1) / 2
    met = 0
    for r in due:
        if r["rejected"]:
            continue
        ts = [t for t in r["tokens"] if t <= w1]
        first = ts[0] - r["due"] if ts else w1 - r["due"]
        gaps = [b - a for a, b in zip(ts, ts[1:])]
        if ts and r["finished"] is None:
            gaps.append(w1 - ts[-1])
        if first * 1e3 <= slo["ttft_ms"] and all(
                g * 1e3 <= slo["gap_ms"] for g in gaps):
            met += 1
    e2e_reqs = [r for r in rec["requests"] if not r["rejected"]]
    ttft = stats.ttft_samples(e2e_reqs, w0, w1)
    itl = stats.itl_samples(e2e_reqs, w0, w1)
    return {"due": len(due), "met": met,
            "attainment": met / len(due) if due else None,
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "itl_p50_ms": 1e3 * stats.percentile(itl, 50),
            "itl_p95_ms": 1e3 * stats.percentile(itl, 95),
            "tokens_per_s": stats.rate(stats.tokens_in(e2e_reqs, w0, w1),
                                       w0, w1),
            "backlog_mid": sum(1 for r in due if r["due"] <= mid
                               and not r["rejected"]
                               and (r["admit"] is None or r["admit"] > mid)),
            "unadmitted_at_close": sum(1 for r in due if r["admit"] is None
                                       and not r["rejected"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record-trace", default="")
    args = ap.parse_args(argv)
    from bench import run as bench_run
    info = harness.resolve(harness.benchmark(), args.workload)
    bench_run._configure_jax(bool(args.record_trace))
    devs = harness.devices(info["cell"]["chips"])
    driver = harness.load_module(info["driver"])
    config, mix = info["config"], info["traffic"]

    if args.record_trace:
        seed = int(args.seeds.split(",")[0])
        path = args.record_trace
        result = bench_run.measure(
            info, seed, args.seconds, True, devs,
            harness.peaks(devs[0].device_kind), time.perf_counter(),
            keep_trace=path[:-3] if path.endswith(".gz") else path)
        if path.endswith(".gz"):
            import gzip
            import shutil
            with open(path[:-3], "rb") as src, gzip.open(path, "wb") as dst:
                shutil.copyfileobj(src, dst)
            os.remove(path[:-3])
        print(json.dumps(result), flush=True)
        return 0

    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.rates:
        cell = driver.setup(config, mix, seeds[0] if seeds else 0)
        for r in [float(x) for x in args.rates.split(",")]:
            cell["traffic"] = dict(mix, arrivals=dict(mix["arrivals"], rate=r))
            driver.prepare(cell, args.seconds)
            counter = bench_run._CompileCount()
            rec = driver.run(cell, args.seconds)
            out = {"rate": r, **attainment(rec, mix["slo"]),
                   "window_compiles": counter.between(*rec["window"])}
            # a backlog that grows through the window: past the knee
            out["growing"] = out["unadmitted_at_close"] >= max(
                5, 1.5 * out["backlog_mid"])
            print(json.dumps(out), flush=True)
            if out["growing"]:
                break
            driver.drain(cell)
        return 0

    for seed in seeds:
        t0 = time.perf_counter()
        cell = driver.setup(config, mix, seed)
        if hasattr(driver, "prepare"):
            driver.prepare(cell, args.seconds)
        t1 = time.perf_counter()
        counter = bench_run._CompileCount()
        rec = driver.run(cell, args.seconds)
        compiles = counter.between(*rec["window"])
        peak = bench_run._peak_bytes(devs[:1])
        e2e = driver.end_to_end(cell, rec)
        driver.release(cell)
        prog = driver.readings(cell, rec)
        ctrl = driver.readings(cell, rec, control=config["control"])
        print(json.dumps({"seed": seed, "setup_s": t1 - t0, "e2e": e2e,
                          "window_compiles": compiles,
                          "memory_peak_bytes": peak, "program": prog,
                          "control": ctrl,
                          "check_s": time.perf_counter() - t0}), flush=True)
        del cell

    return 0


if __name__ == "__main__":
    sys.exit(main())
