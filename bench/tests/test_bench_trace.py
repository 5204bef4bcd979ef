"""The reduction from a profiler trace to busy time, per-program time and
labelled idle gaps, on synthetic events laid out as the profiler's planes
and lines are."""

from types import SimpleNamespace as NS

import pytest

from bench import harness, run as bench_run, trace


def test_union_and_clip():
    ivs = [(5, 8), (0, 2), (1, 3), (7, 9), (12, 13)]
    assert trace.union(ivs) == [(0, 3), (5, 9), (12, 13)]
    assert trace.clip(trace.union(ivs), 2, 12.5) == [(2, 3), (5, 9),
                                                    (12, 12.5)]
    assert trace.total([(0, 3), (5, 9)]) == 7
    assert trace.program_name("jit_decode_impl(123)") == "jit_decode_impl"


RAW = {
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [("fusion.1", 100, 200), ("fusion.2", 150, 300),
                ("custom", 500, 600), ("fusion.1", 900, 1000),
                ("fusion.1", 2000, 2100)],
        "programs": [("jit_step", 100, 300), ("jit_run", 500, 600),
                     ("jit_step", 900, 1000), ("jit_step", 2000, 2100)]}],
    "host": [("bench.window", 0, 1200), ("serving.step", 50, 350),
             ("bench.idle", 300, 900), ("PjitFunction(step)", 880, 905)],
}


def test_reduce_busy_programs_and_gaps():
    red = trace.reduce(RAW)
    assert red["window_s"] == pytest.approx(1200e-9)
    # busy: [100, 300] + [500, 600] + [900, 1000]; the op at 2000 is outside
    assert red["busy_s"] == pytest.approx(400e-9)
    assert red["programs"]["jit_step"]["count"] == 2
    assert trace.program_seconds(red, ("jit_step",)) == pytest.approx(300e-9)
    assert trace.program_count(red, ("jit_step", "jit_run")) == 3
    assert trace.launch_gaps(red, ("jit_step",)) == pytest.approx([600e-9])
    # idle: 0..100, 300..500, 600..900, 1000..1200, longest first, each
    # labelled by the innermost host span over its middle
    gaps = [(n, round(s * 1e9)) for n, s in red["idle_gaps"]]
    assert gaps[0] == ("bench.idle", 300)
    assert sorted(gaps[1:]) == [("bench.idle", 200), ("no host span", 200),
                                ("serving.step", 100)]
    assert red["device_ops"][0][0] == "fusion.1"


def test_a_trace_without_a_device_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"devices": [], "host": []})


def test_a_trace_without_the_window_span_is_an_error():
    no_window = dict(RAW, host=[h for h in RAW["host"]
                                if h[0] != trace.WINDOW_SPAN])
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(no_window)


def test_a_listed_metric_that_finds_nothing_fails_the_run():
    info = harness.resolve(harness.benchmark(), "qwen2-1.5b.chat")
    red = trace.reduce(RAW)        # no decode or prefill program in it
    ctx = {"records": {"window": (0, 1), "requests": [], "steps": [],
                       "max_slots": 1}, "config": info["config"],
           "traffic": info["traffic"], "peaks": {}, "trace": red}
    with pytest.raises(bench_run.NothingToRead, match="jit_step"):
        bench_run.per_layer(info, ctx)


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs]) for ln, evs in lines])


def test_read_takes_device_ops_programs_and_host_spans():
    profile = NS(planes=[
        _plane("/device:TPU:0", [
            ("XLA Ops", [("fusion.1", 100, 100)]),
            ("XLA Modules", [("jit_decode_impl(77)", 90, 120)]),
            ("Steps", [("0", 90, 120)])]),
        _plane("/device:TPU:0 SparseCore", [("Other", [("x", 0, 5)])]),
        _plane("/host:CPU", [("python", [("bench.window", 0, 500),
                                         ("instant", 50, 0)])])])
    raw = trace.read(profile)
    assert len(raw["devices"]) == 1
    dev = raw["devices"][0]
    assert dev["ops"] == [("fusion.1", 100.0, 200.0)]
    assert dev["programs"] == [("jit_decode_impl", 90.0, 210.0)]
    assert raw["host"] == [("bench.window", 0.0, 500.0)]



CHIP_TRACE = harness.os.path.join(harness.BENCH, "tests", "data",
                                  "ivim-clinical.scan.xplane.pb.gz")


def test_a_recorded_chip_trace_reduces_to_the_cells_metrics():
    """A short traced window of ``ivim-clinical.scan`` recorded on a TPU v5e
    (``bench/calibrate.py --record-trace``): the reduction finds the
    window, the moments executor and the scans, and every per-layer metric
    of the cell reads a number in its range."""
    raw = trace.read(trace.load(CHIP_TRACE))
    red = trace.reduce(raw)
    assert raw["devices"][0]["name"].startswith("/device:TPU:0")
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["programs"]["jit_run"]["count"] > 1
    w0, w1 = red["window"]
    scans = [(s, e) for n, s, e in raw["host"]
             if n == "bench.scan" and w0 <= s and e <= w1]
    assert scans
    info = harness.resolve(harness.benchmark(), "ivim-clinical.scan")
    voxels = 1
    for n in info["traffic"]["volume"]:
        voxels *= n
    ctx = {"records": {"scans": [{"voxels": voxels}] * len(scans)},
           "config": info["config"], "traffic": info["traffic"],
           "peaks": harness.peaks("TPU v5 lite"), "trace": red}
    got = bench_run.per_layer(info, ctx)
    assert set(got) == {m["name"] for m in info["per_layer"]}
    for name, m in got.items():
        assert m["value"] > 0, name
        if m["unit"] == "%":
            assert m["value"] <= 100, name
