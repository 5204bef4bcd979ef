"""Observability subsystem: registry, the one span API and its two sinks,
exposition, and the serving integration (trace-on == trace-off bitwise,
verifier-clean lifecycle logs, summary/exposition agreement)."""

import importlib.util
import math
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import registry as cfg_registry
from repro.core import plan as plan_lib
from repro.ivim import model as ivim_model
from repro.models import build_model
from repro.obs import export as export_lib
from repro.obs import registry as reg_lib
from repro.obs import trace as trace_lib
from repro.serving import BayesianLMServer, QueueFullError, ServerConfig
from repro.serving import engine
from repro.serving.metrics import MetricsCollector

DATA = pathlib.Path(__file__).parent / "data"


def _load_verify_obs():
    path = pathlib.Path(__file__).parent.parent / "benchmarks" / \
        "verify_obs.py"
    spec = importlib.util.spec_from_file_location("verify_obs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def small():
    cfg = cfg_registry.smoke_config("qwen2-1.5b", n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, n, length=6, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (n, length), 0, cfg.vocab_size))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    r = reg_lib.Registry()
    c = r.counter("c", "a counter", labels=("m",))
    c.inc(m="lm")
    c.inc(2.5, m="voxel")
    assert c.value(m="lm") == 1.0 and c.value(m="voxel") == 2.5
    assert c.total() == 3.5
    b = c.labels(m="lm")
    b.inc()
    assert c.value(m="lm") == 2.0
    g = r.gauge("g", "a gauge")
    assert math.isnan(g.value())              # honest "no data", not 0.0
    g.set(7)
    assert g.value() == 7.0
    h = r.histogram("h", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    st = h.values[()]
    assert st["buckets"] == [1, 2]            # cumulative per upper bound
    assert st["count"] == 3 and st["sum"] == pytest.approx(5.55)
    # get-or-create is idempotent; mismatches are loud
    assert r.counter("c", labels=("m",)) is c
    with pytest.raises(ValueError):
        r.gauge("c")                          # kind mismatch
    with pytest.raises(ValueError):
        r.counter("c", labels=("other",))     # label-set mismatch
    with pytest.raises(ValueError):
        c.inc(wrong="x")                      # undeclared label


def test_registry_value_snapshot_reset():
    r = reg_lib.Registry()
    c = r.counter("total", labels=("k",))
    c.inc(k="a")
    c.inc(k="b")
    assert r.value("total") == 2.0
    assert r.value("absent") == 0.0
    snap = r.snapshot()
    assert snap["total"]["kind"] == "counter"
    assert snap["total"]["values"] == {"k=a": 1.0, "k=b": 1.0}
    r.reset()
    assert r.value("total") == 0.0            # values zeroed ...
    assert r.counter("total", labels=("k",)) is c   # ... registration kept


def test_dump_restore_isolation():
    r = reg_lib.Registry()
    c = r.counter("n")
    c.inc()
    state = r.dump_state()
    c.inc(5)
    late = r.counter("late")
    late.inc()
    r.restore_state(state)
    assert c.total() == 1.0                   # rolled back
    assert late.total() == 0.0                # post-dump metric zeroed


def test_keyed_counter_is_the_plan_trace_counter():
    # The bare collections.Counter that used to live at
    # core.plan.fused_trace_counts is now the registered KeyedCounter —
    # mapping surface intact, exposition/reset/snapshot included.
    kc = plan_lib.fused_trace_counts
    assert isinstance(kc, reg_lib.KeyedCounter)
    assert reg_lib.REGISTRY.keyed_counter("fused_trace_total") is kc
    key = ("test-obs-unique-spec", None, "decode")
    assert kc[key] == 0                       # Counter-style default
    kc[key] += 1
    kc[key] += 1
    assert kc[key] == 2 and key in kc
    assert dict(kc.items())[key] == 2
    assert reg_lib.key_str(key) == "('test-obs-unique-spec', None, 'decode')"
    snap = reg_lib.REGISTRY.snapshot()["fused_trace_total"]
    assert snap["values"][reg_lib.key_str(key)] == 2
    del kc[key]
    assert kc[key] == 0


def test_key_str_opaque_objects():
    class Spec:
        __hash__ = lambda self: 0xDEADBEEF          # noqa: E731
    s = reg_lib.key_str(Spec())
    assert s == "Spec#deadbeef"
    assert reg_lib.key_str((1, "a", None)) == "(1, 'a', None)"


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------


def _golden_registry() -> reg_lib.Registry:
    """Deterministic registry content for the golden-file test (primitive
    keyed keys only — opaque keys hash per-process)."""
    r = reg_lib.Registry()
    c = r.counter("requests_total", "work items enqueued",
                  labels=("modality",))
    c.inc(modality="lm")
    c.inc(3, modality="voxel")
    g = r.gauge("queue_depth", "queued items at last step")
    g.set(float("nan"))
    g2 = r.gauge("occupancy", "slot occupancy fraction", labels=("pool",))
    g2.set(0.5, pool="a")
    h = r.histogram("latency_seconds", "request latency",
                    buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    k = r.keyed_counter("traces_total", "jit traces by key")
    k[("spec", None, "decode")] += 2
    k["warm\nup"] += 1                        # exercises label escaping
    return r


def test_exposition_golden_file():
    text = export_lib.prometheus_text(_golden_registry())
    golden = (DATA / "exposition_golden.txt").read_text()
    assert text == golden


def test_exposition_parses_back():
    text = export_lib.prometheus_text(_golden_registry())
    samples = export_lib.parse_exposition(text)
    assert samples[("requests_total", (("modality", "lm"),))] == 1.0
    assert samples[("requests_total", (("modality", "voxel"),))] == 3.0
    assert math.isnan(samples[("queue_depth", ())])
    assert samples[("occupancy", (("pool", "a"),))] == 0.5
    assert samples[("latency_seconds_bucket", (("le", "0.1"),))] == 1.0
    assert samples[("latency_seconds_bucket", (("le", "1"),))] == 2.0
    assert samples[("latency_seconds_bucket", (("le", "+Inf"),))] == 3.0
    assert samples[("latency_seconds_count", ())] == 3.0
    # key_str of a str key is its repr, so the newline is a literal
    # backslash-n; exposition escapes that backslash and the parser's
    # single-pass unescape must give it back (not a newline).
    assert samples[("traces_total",
                    (("key", "'warm\\nup'"),))] == 1.0


def test_parse_exposition_rejects_malformed():
    with pytest.raises(ValueError):
        export_lib.parse_exposition("no value here\n")
    with pytest.raises(ValueError):
        export_lib.parse_exposition('m{bad labels} 1\n')
    with pytest.raises(ValueError):
        export_lib.parse_exposition("m not_a_number\n")


def test_host_provenance():
    prov = export_lib.host_provenance()
    assert isinstance(prov["hostname"], str) and prov["hostname"]
    # this repo is a git work tree, so the SHA must resolve
    assert isinstance(prov["git_sha"], str) and len(prov["git_sha"]) == 40


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_spans_nest_and_export():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = trace_lib.Tracer(capacity=64, clock=clock)
    tr.event("dropped")                       # disabled: no record, no tick
    assert tr.events() == [] and t[0] == 0.0
    tr.enable()
    with tr.span("outer", a=1):
        tr.event("inside")
        with tr.span("inner"):
            pass
    evs = tr.events()
    assert [e["name"] for e in evs] == ["outer", "inside", "inner",
                                       "inner", "outer"]
    outer_id = evs[0]["span"]
    assert evs[0]["kind"] == "begin" and evs[0]["parent"] is None
    assert evs[1]["span"] == outer_id         # event inside outer
    assert evs[2]["parent"] == outer_id       # inner nests under outer
    assert evs[4] == {"t": 5.0, "name": "outer", "kind": "end",
                      "span": outer_id, "attrs": {}}
    jsonl = tr.to_jsonl()
    assert len(jsonl.splitlines()) == 5


def test_tracer_ring_bounded():
    tr = trace_lib.Tracer(capacity=4)
    tr.enable()
    for i in range(10):
        tr.event("e", i=i)
    evs = tr.events()
    assert len(evs) == 4
    assert [e["attrs"]["i"] for e in evs] == [6, 7, 8, 9]


def test_span_with_both_sinks_off_is_one_shared_no_op():
    """Both sinks off: every ``span()`` is the one shared no-op context (no
    per-call object), and nothing is recorded, attrs set late included."""
    tr = trace_lib.Tracer()
    assert not tr.enabled and not tr.profiling
    assert tr.span("a") is trace_lib.NO_SPAN
    assert tr.span("b", x=1) is trace_lib.NO_SPAN
    with tr.span("a", x=1) as s:
        s.set(y=2)
        tr.event("e")
    assert tr.events() == []
    tr.enable()
    assert tr.span("a") is not trace_lib.NO_SPAN
    tr.disable()
    assert tr.span("a") is trace_lib.NO_SPAN


def test_span_set_adds_attrs_to_its_begin_record():
    tr = trace_lib.Tracer(clock=trace_lib.ManualClock(), enabled=True)
    with tr.span("serving.step", a=1) as s:
        s.set(lm=3, a=2)
    begin, end = tr.events()
    assert begin["attrs"] == {"a": 2, "lm": 3}
    assert end["attrs"] == {} and end["span"] == begin["span"]


def test_repro_profile_switches_on_both_sinks():
    """``REPRO_PROFILE=1`` at import turns on the ring and the profiler of
    the process tracer; unset, both are off."""
    code = ("from repro.obs import trace; t = trace.TRACER; "
            "print(t.enabled, t.profiling)")
    src = str(pathlib.Path(__file__).parent.parent / "src")
    for value, want in (("1", "True True"), ("", "False False")):
        env = dict(os.environ, PYTHONPATH=src, REPRO_PROFILE=value)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, check=True)
        assert out.stdout.split() == want.split()


# ---------------------------------------------------------------------------
# metrics collector on the registry + injectable clock
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_request_timeline_fake_clock():
    r = reg_lib.Registry()
    clk = FakeClock()
    mc = MetricsCollector(2, clock=clk, registry=r)
    mc.on_enqueue(0)
    clk.t = 1.0
    mc.on_admit(0)
    clk.t = 2.5
    mc.on_token(0)
    clk.t = 5.0
    mc.on_finish(0)
    tl = mc.timelines[0]
    assert tl.queue_wait == 1.0
    assert tl.ttft == 2.5
    assert tl.latency == 5.0
    # None edges: never admitted / never emitted / never finished
    mc.on_enqueue(1)
    tl1 = mc.timelines[1]
    assert tl1.queue_wait is None and tl1.ttft is None \
        and tl1.latency is None
    s = mc.summary()
    assert s.completed == 1 and s.requests == 2
    assert s.latency_p50_s == 5.0
    assert r.histogram("serving_request_latency_seconds",
                       labels=("modality",)).values[("lm",)]["count"] == 1


def test_summary_and_exposition_report_identical_totals():
    """Scripted mixed LM+voxel run: the human summary and the Prometheus
    exposition are two views of one double-entry collector — every total
    must agree."""
    r = reg_lib.Registry()
    clk = FakeClock()
    mc = MetricsCollector(2, clock=clk, registry=r)
    for rid in (0, 1, 2):
        mc.on_enqueue(rid)
    mc.on_enqueue(3, modality="voxel")
    for rid in (0, 1):
        clk.t += 1
        mc.on_admit(rid)
        mc.on_token(rid)
        mc.on_token(rid)
        mc.on_finish(rid, escalated=(rid == 1))
    mc.on_admit(3)
    mc.on_token(3, units=96)
    mc.on_finish(3)
    for _ in range(5):
        mc.on_step(2, 1, voxel_occupied=1)

    s = mc.summary()
    samples = export_lib.parse_exposition(export_lib.prometheus_text(r))

    def total(name):
        return sum(v for (n, _), v in samples.items() if n == name)

    assert total("serving_requests_total") == s.requests == 4
    assert samples[("serving_emissions_total",
                    (("modality", "lm"),))] == s.total_tokens == 4
    assert samples[("serving_emissions_total",
                    (("modality", "voxel"),))] == s.total_voxels == 96
    assert total("serving_finished_total") == s.completed == 3
    assert total("serving_escalated_total") == s.escalated == 1
    assert total("serving_decode_steps_total") == s.decode_steps == 5
    assert samples[("serving_queue_depth", ())] == 1.0
    assert samples[("serving_occupied_slots", ())] == 2.0
    # and the formatted summary carries the same numbers
    txt = s.format()
    assert "3/4 completed (1 escalated)" in txt
    assert "4 tokens" in txt and "5 decode steps" in txt
    assert "96 voxels" in txt


# ---------------------------------------------------------------------------
# serving integration: bitwise invariance, verifier-clean lifecycle logs
# ---------------------------------------------------------------------------


def _run_lm(model, params, prompts, trace):
    srv = BayesianLMServer(model, params, ServerConfig(
        max_slots=2, max_prompt_len=8, max_new_tokens=4, trace=trace))
    rids = [srv.submit(p) for p in prompts]
    srv.run()
    return [(list(srv.result(r).generated),
             list(srv.result(r).uncertainty)) for r in rids]


def test_tracing_is_bitwise_invisible(small):
    """Tokens and uncertainties are bit-identical with tracing on vs off,
    and the traced run adds zero jit retraces (the step graphs key on
    shapes/config, never on the trace knob)."""
    cfg, model, params = small
    prompts = _prompts(cfg, 4)
    off = _run_lm(model, params, prompts, trace=False)
    rt0 = reg_lib.REGISTRY.value("retrace_total")
    trace_lib.TRACER.configure(capacity=65536)
    on = _run_lm(model, params, prompts, trace=True)
    trace_lib.TRACER.disable()
    assert reg_lib.REGISTRY.value("retrace_total") == rt0
    assert off == on                          # exact float equality


def test_server_trace_replays_through_verifier(small):
    cfg, model, params = small
    trace_lib.TRACER.configure(capacity=65536)
    _run_lm(model, params, _prompts(cfg, 4), trace=True)
    trace_lib.TRACER.disable()
    events = trace_lib.TRACER.events()
    assert len(events) > 0
    names = {e["name"] for e in events}
    assert {"enqueue", "serving.admit", "serving.prefill", "serving.step",
            "serving.decode", "serving.sync", "serving.absorb", "decode",
            "token", "finish"} <= names
    prefills = [e for e in events
                if e["name"] == "serving.prefill" and e["kind"] == "begin"]
    assert prefills and all(
        {"path", "bucket", "length"} <= set(e["attrs"]) for e in prefills)
    admits = [e for e in events
              if e["name"] == "serving.admit" and e["kind"] == "begin"]
    assert all(e["attrs"]["prompt_len"] == 6 and "bucket" in e["attrs"]
               for e in admits)
    verify_obs = _load_verify_obs()
    assert verify_obs.verify_trace_events(events) == []
    # and the exposition side of the verifier
    assert verify_obs.verify_metrics_text(
        export_lib.prometheus_text(reg_lib.REGISTRY)) == []


def test_verifier_catches_violations():
    verify_obs = _load_verify_obs()

    def ev(name, rid=None, kind="event", t=1.0, **extra):
        rec = {"t": t, "name": name, "kind": kind, "span": None,
               "attrs": {} if rid is None else {"req_id": rid}}
        rec.update(extra)
        return rec

    # token before admit
    errs = verify_obs.verify_trace_events(
        [ev("enqueue", 0), ev("token", 0)])
    assert any("no emission before admission" in e for e in errs)
    # event after finish
    good = [ev("enqueue", 0),
            ev("serving.admit", 0, kind="begin", span=1, parent=None),
            ev("serving.admit", kind="end", span=1),
            ev("token", 0), ev("finish", 0)]
    assert verify_obs.verify_trace_events(good) == []
    errs = verify_obs.verify_trace_events(good + [ev("token", 0)])
    assert any("after finish" in e for e in errs)
    # unfinished request
    errs = verify_obs.verify_trace_events([ev("enqueue", 0)])
    assert any("not finished" in e for e in errs)
    # clock going backwards
    errs = verify_obs.verify_trace_events(
        [ev("enqueue", 0, t=2.0)] + good[1:])
    assert any("backwards" in e for e in errs)
    # unbalanced spans
    errs = verify_obs.verify_trace_events(
        [ev("serving.step", kind="begin", span=7, parent=None)])
    assert any("never ended" in e for e in errs)


def test_queue_rejection_counted_and_traced(small):
    cfg, model, params = small
    before = reg_lib.REGISTRY.value("serving_queue_rejections_total")
    trace_lib.TRACER.configure(capacity=256)
    srv = BayesianLMServer(model, params, ServerConfig(
        max_slots=2, max_queue=2, max_prompt_len=8, max_new_tokens=4,
        trace=True))
    prompts = _prompts(cfg, 3)
    srv.submit(prompts[0])
    srv.submit(prompts[1])
    with pytest.raises(QueueFullError):
        srv.submit(prompts[2])
    trace_lib.TRACER.disable()
    after = reg_lib.REGISTRY.value("serving_queue_rejections_total")
    assert after == before + 1
    rejects = [e for e in trace_lib.TRACER.events()
               if e["name"] == "reject"]
    assert len(rejects) == 1 and rejects[0]["attrs"]["kind"] == "lm"
    srv.run()                                 # drain for cleanliness


# ---------------------------------------------------------------------------
# the profiler sink
# ---------------------------------------------------------------------------


def test_profile_annotate_guarded():
    """The profiler sink alone: spans are real (they annotate the profiler)
    while the ring records nothing; switched off, the no-op is back."""
    tr = trace_lib.Tracer()
    tr.enable_profiling()
    assert tr.profiling and not tr.enabled
    sp = tr.span("x", a=1)
    assert sp is not trace_lib.NO_SPAN
    with sp:
        with tr.span("y"):
            pass
    assert tr.events() == []
    tr.disable_profiling()
    assert tr.span("x") is trace_lib.NO_SPAN


def _tiny_scan():
    cfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(cfg, params, state)
    vol = np.asarray(jax.random.uniform(jax.random.PRNGKey(3),
                                        (4, 3, 2, cfg.width)))
    return plan, vol


def test_profile_adds_no_retraces(small):
    """With both sinks on, LM tokens and uncertainties and IVIM moments are
    bitwise identical to the untraced run, with no added retraces."""
    cfg, model, params = small
    prompts = _prompts(cfg, 2)
    plan, vol = _tiny_scan()
    off = _run_lm(model, params, prompts, trace=False)   # warm every graph
    m_off = engine.predict_volume(plan, vol, chunk=5, backend="xla")
    rt0 = reg_lib.REGISTRY.value("retrace_total")
    tr = trace_lib.TRACER
    tr.configure(capacity=65536)
    tr.enable_profiling()
    try:
        on = _run_lm(model, params, prompts, trace=True)
        m_on = engine.predict_volume(plan, vol, chunk=5, backend="xla")
    finally:
        tr.disable_profiling()
        tr.disable()
    assert reg_lib.REGISTRY.value("retrace_total") == rt0
    assert off == on
    for a, b in zip(m_off, m_on):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    names = [e["name"] for e in tr.events() if e["kind"] == "begin"]
    assert names.count("engine.chunk") == 5           # 24 voxels / 5
    assert names.count("plan.dispatch") == names.count("plan.epilogue") == 5
    assert names.count("engine.predict_volume") == 1
    assert names.count("engine.assemble") == 1


# ---------------------------------------------------------------------------
# decode traffic by stage
# ---------------------------------------------------------------------------


def test_decode_stage_traffic_sums_to_decode_traffic(small):
    cfg, _, _ = small
    spec = plan_lib.decode_fused_spec(cfg)
    rows, max_seq = cfg.mask_samples * 4, 24
    for fused in (True, False):
        tm = plan_lib.decode_traffic(spec, rows, max_seq, fused=fused)
        stages = plan_lib.decode_stage_traffic(spec, rows, max_seq,
                                               fused=fused)
        assert {"attn", "ffn", "interstage"} <= set(stages)
        assert sum(t.weight_bytes for t in stages.values()) \
            == tm.weight_bytes
        assert sum(t.act_bytes for t in stages.values()) == tm.act_bytes
        assert sum(t.flops for t in stages.values()) == tm.flops
        assert sum(t.weight_loads for t in stages.values()) \
            == tm.weight_loads
    # the fused/per-op difference is inter-stage activations + launches only
    st_f = plan_lib.decode_stage_traffic(spec, rows, max_seq, fused=True)
    st_p = plan_lib.decode_stage_traffic(spec, rows, max_seq, fused=False)
    for name in st_f:
        if name != "interstage":
            assert st_f[name] == st_p[name]
    assert st_f["interstage"].act_bytes < st_p["interstage"].act_bytes
    assert st_f["interstage"].weight_loads == 1
