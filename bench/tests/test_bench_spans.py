"""The readers of the program's own spans (``bench/spans.py`` and the
metrics that read it) on synthetic rings worked out by hand, a ring that
starts inside the window among them; and the same spans on the profiler's
clock: under a CPU profiler here, and in a chip trace."""

import os
import tempfile

import jax
import numpy as np
import pytest

from bench import harness, spans, trace
from repro.obs import trace as obs_trace

METRICS = os.path.join(harness.BENCH, "metrics")


def _reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py")).read


def _tracer(capacity=65536):
    clock = obs_trace.ManualClock(100.0)
    return obs_trace.Tracer(capacity=capacity, clock=clock,
                            enabled=True), clock


def test_pair_and_held():
    tr, clock = _tracer()
    with tr.span("a", k=1):
        clock.advance(1.0)
        with tr.span("b"):
            clock.advance(2.0)
    tr.event("e")
    recs = tr.events()
    got = spans.pair(recs)
    assert [(s["name"], s["t0"], s["t1"]) for s in got] == [
        ("a", 100.0, 103.0), ("b", 101.0, 103.0)]
    assert got[1]["parent"] == got[0]["id"] and got[0]["attrs"] == {"k": 1}
    # an end whose begin is gone, and a begin never ended, make no span
    assert [s["name"] for s in spans.pair(recs[2:])] == []
    assert spans.held(recs, (99.0, 110.0)) == (100.0, 110.0)
    assert spans.held(recs, (101.5, 110.0)) == (101.5, 110.0)
    assert spans.held(recs, (104.0, 110.0)) is None     # all before it
    assert spans.held([], (0.0, 1.0)) is None


# -- IVIM: engine.predict_volume > engine.chunk > plan.dispatch/epilogue ------

CHUNKS = (0.002, 0.003, 0.004)       # each chunk's span; their mean: 3000 us


def _scan(tr, clock):
    """One scan as the engine records it: 0.001 s before the chunk loop,
    three chunks 0.0005 s apart, a 0.002 s assembly, then a 0.010 s fetch
    outside the engine -- 0.0235 s, of which 0.009 s is in chunks."""
    with tr.span("engine.predict_volume"):
        clock.advance(0.001)
        for d in CHUNKS:
            with tr.span("engine.chunk", valid=4096, padded=0,
                         host_input=True):
                with tr.span("plan.dispatch"):
                    clock.advance(d / 2)
                with tr.span("plan.epilogue"):
                    clock.advance(d / 2)
            clock.advance(0.0005)
        with tr.span("engine.assemble"):
            clock.advance(0.002)
    clock.advance(0.010)


def _ivim_ring(capacity):
    """A warm-up scan, then a window that opens 0.0005 s before four scans
    and closes at the end of the last: 22 records a scan."""
    tr, clock = _tracer(capacity)
    _scan(tr, clock)
    clock.advance(1.0)
    w0 = clock()
    clock.advance(0.0005)
    for _ in range(4):
        _scan(tr, clock)
    return tr, {"records": {"window": (w0, clock())}}


@pytest.mark.parametrize("capacity,chunk_us,overhead_ms", [
    # the whole window: (0.0005 + 4 x (0.0235 - 0.009)) / 4 scans
    (65536, 3000.0, 14.625),
    # the ring keeps the last 50 of 110 records: the tail of the window's
    # second scan (no whole chunk), then scans 3 and 4, read from the
    # third's start: (2 x 0.0235 - 2 x 0.009) / 2
    (50, 3000.0, 14.5),
], ids=["whole-window", "ring-starts-in-window"])
def test_ivim_readers_by_hand(monkeypatch, capacity, chunk_us, overhead_ms):
    tr, ctx = _ivim_ring(capacity)
    monkeypatch.setattr(obs_trace, "TRACER", tr)
    assert (spans.held(tr.events(), ctx["records"]["window"])[0]
            > ctx["records"]["window"][0]) == (capacity == 50)
    assert _reader("ivim.chunk_host_us")(ctx) == pytest.approx(chunk_us)
    assert _reader("ivim.scan_overhead_ms")(ctx) == pytest.approx(
        overhead_ms)


# -- LM: serving.step > serving.admit > serving.prefill/sync, decode, ... ----

def _step(tr, clock, admit=None):
    """One engine step: an optional admission (("lm", sync seconds): a
    0.002 s prefill then the first token's fetch; ("voxel", seconds): no
    sync), a 0.003 s decode, a 0.070 s fetch, a 0.001 s absorb."""
    with tr.span("serving.step") as step:
        if admit is not None:
            kind, secs = admit
            with tr.span("serving.admit", req_id=0, slot=0, kind=kind):
                if kind == "lm":
                    with tr.span("serving.prefill"):
                        clock.advance(0.002)
                    with tr.span("serving.sync"):
                        clock.advance(secs)
                else:
                    clock.advance(secs)
        step.set(lm=1, voxel=0, queue_depth=0)
        with tr.span("serving.decode"):
            clock.advance(0.003)
        with tr.span("serving.sync"):
            clock.advance(0.070)
        with tr.span("serving.absorb"):
            clock.advance(0.001)


def _lm_ring(capacity):
    """A warm-up step with a long admission, then a window of five steps:
    LM admissions whose fetches take 0.040, 0.050, 0.060 and 0.100 s, and
    a voxel admission of 0.010 s (14 records a step, 10 for the last)."""
    tr, clock = _tracer(capacity)
    _step(tr, clock, ("lm", 5.0))
    clock.advance(1.0)
    w0 = clock()
    for secs in (0.040, 0.050, 0.060, 0.100):
        _step(tr, clock, ("lm", secs))
    _step(tr, clock, ("voxel", 0.010))
    clock.advance(0.001)
    return tr, {"records": {"window": (w0, clock())}}


@pytest.mark.parametrize("capacity,admit_ms,host_ms", [
    # admissions 42, 52, 62, 102 ms: p95 = 62 + 0.85 x 40; host time per
    # step 6 ms (decode, absorb, prefill) x 4 and 14 ms (voxel admission)
    (65536, 96.0, 7.6),
    # the ring drops the warm-up step and the start of the first: the
    # admissions 52, 62, 102 ms (p95 = 62 + 0.9 x 40) and four steps
    (61, 98.0, 8.0),
], ids=["whole-window", "ring-starts-in-window"])
def test_lm_readers_by_hand(monkeypatch, capacity, admit_ms, host_ms):
    tr, ctx = _lm_ring(capacity)
    monkeypatch.setattr(obs_trace, "TRACER", tr)
    assert (spans.held(tr.events(), ctx["records"]["window"])[0]
            > ctx["records"]["window"][0]) == (capacity == 61)
    assert _reader("lm.admit_p95_ms")(ctx) == pytest.approx(admit_ms)
    assert _reader("lm.step_host_ms")(ctx) == pytest.approx(host_ms)


@pytest.mark.parametrize("name", ["ivim.chunk_host_us",
                                  "ivim.scan_overhead_ms",
                                  "lm.admit_p95_ms", "lm.step_host_ms"])
def test_a_ring_that_was_off_reads_nothing(monkeypatch, name):
    """A program whose ring was off in the run (or that has none of these
    spans) gives the reader nothing to read: None, no error."""
    tr, clock = _tracer()
    tr.disable()
    _scan(tr, clock)
    _step(tr, clock, ("lm", 0.05))
    monkeypatch.setattr(obs_trace, "TRACER", tr)
    assert _reader(name)({"records": {"window": (0.0, 1e9)}}) is None


# -- the same spans on the profiler's clock -----------------------------------

PROGRAM = ("engine.", "plan.", "serving.")


def _nesting(spans_):
    """(name, innermost enclosing name) in order of start, from (name,
    start, end) in that order."""
    out = []
    for i, (n, s, e) in enumerate(spans_):
        outer = [m for m, s2, e2 in spans_[:i] if s2 <= s and e <= e2]
        out.append((n, outer[-1] if outer else None))
    return out


def test_program_spans_land_on_the_profilers_host_plane():
    """Under a CPU profiler, a small scan and two server steps put the
    engine's, the plan's and the server's spans on the host plane, with the
    names, counts and nesting the ring records."""
    from repro.configs import registry as cfg_registry
    from repro.ivim import model as ivim_model
    from repro.models import build_model
    from repro.serving import BayesianLMServer, ServerConfig, engine

    icfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state = ivim_model.init(icfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(icfg, params, state)
    vol = np.asarray(jax.random.uniform(jax.random.PRNGKey(3),
                                        (3, 2, 2, icfg.width)))
    lcfg = cfg_registry.smoke_config("qwen2-1.5b", n_layers=2)
    model = build_model(lcfg)
    server = BayesianLMServer(model, model.init(jax.random.PRNGKey(0)),
                              ServerConfig(max_slots=2, max_prompt_len=8,
                                           max_new_tokens=4))
    engine.predict_volume(plan, vol, chunk=5, backend="xla")   # compile
    server.submit([1, 2, 3])
    server.run()

    tr = obs_trace.TRACER
    tr.configure(capacity=65536)
    tr.enable()
    tr.enable_profiling()
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        engine.predict_volume(plan, vol, chunk=5, backend="xla")
        server.submit([4, 5, 6, 7])
        server.step()
        server.step()
    finally:
        jax.profiler.stop_trace()
        tr.disable_profiling()
        tr.disable()
    paired = spans.pair(tr.events())
    by_id = {s["id"]: s["name"] for s in paired}
    ring = [(s["name"], by_id.get(s["parent"])) for s in paired]
    names = {n for n, _ in ring}
    host = sorted(((n, s, e) for n, s, e in trace.read(trace.load(tdir))
                   ["host"] if n in names), key=lambda h: (h[1], -h[2]))
    got = [n for n, _, _ in host]
    assert got == [n for n, _ in ring]
    assert all(n.startswith(PROGRAM) for n in got)
    for name, count in (("engine.chunk", 3), ("plan.dispatch", 3),
                        ("plan.epilogue", 3), ("serving.step", 2),
                        ("serving.admit", 1), ("serving.sync", 3)):
        assert got.count(name) == count, name
    assert _nesting(host) == ring
    assert ("plan.dispatch", "engine.chunk") in _nesting(host)
    assert ("serving.sync", "serving.admit") in _nesting(host)


SPANS_TRACE = os.path.join(harness.BENCH, "tests", "data",
                           "ivim-clinical.scan.spans.xplane.pb.gz")


def test_a_chip_trace_has_the_programs_spans_on_the_device_clock():
    """A short traced window of ``ivim-clinical.scan`` recorded on a TPU v5e
    (``bench/calibrate.py --record-trace``): the engine's and the plan's
    spans are on a host plane, one of each per chunk of 4096 voxels, and
    the n-th run of the moments executor on the device starts after the
    n-th ``plan.dispatch`` span begins -- one clock for both."""
    raw = trace.read(trace.load(SPANS_TRACE))
    red = trace.reduce(raw)
    w0, w1 = red["window"]
    host = [(n, s, e) for n, s, e in raw["host"] if w0 <= s and e <= w1]

    def count(name):
        return sum(1 for n, _, _ in host if n == name)

    info = harness.resolve(harness.benchmark(), "ivim-clinical.scan")
    chunks = int(np.prod(info["traffic"]["volume"])) // 4096
    scans = count("engine.predict_volume")
    assert scans >= 1 and count("engine.assemble") == scans
    for name in ("engine.chunk", "plan.dispatch", "plan.epilogue"):
        assert count(name) == chunks * scans, name
    dispatch = sorted(s for n, s, _ in host if n == "plan.dispatch")
    runs = sorted(s for s, _ in red["programs"]["jit_run"]["intervals"])
    assert len(runs) == len(dispatch)
    assert all(r >= d for d, r in zip(dispatch, runs))
