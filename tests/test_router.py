"""Fault-tolerant multi-host router: failover determinism, spill/shed
degradation, straggler-driven remesh, and the fault-injection harness.

The load-bearing property: slot-pool rows are batch-independent (see
serving/server.py), so a request's results do not depend on which host
served it — a host killed mid-run must therefore yield tokens and scan
moments bitwise-identical to an unfaulted run."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import build_model
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.obs.trace import ManualClock
from repro.serving import (BayesianLMServer, FaultEvent, FaultPlan,
                           QueueFullError, RouterConfig, ServerConfig,
                           ServingRouter, engine)


@pytest.fixture(scope="module")
def small():
    cfg = registry.smoke_config("qwen2-1.5b", n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, n, length=6, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (n, length), 0, cfg.vocab_size))


def _scfg(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_new_tokens", 4)
    return ServerConfig(**kw)


def _router(model, params, scfg=None, faults=None, **rkw):
    clock = ManualClock()
    rkw.setdefault("n_hosts", 3)
    rkw.setdefault("heartbeat_timeout_s", 2.5)
    router = ServingRouter(model, params, scfg or _scfg(),
                           RouterConfig(**rkw), faults=faults, clock=clock)
    return router, clock


def _single_host_reference(model, params, prompts, scfg=None):
    srv = BayesianLMServer(model, params, scfg or _scfg())
    rids = [srv.submit(p) for p in prompts]
    srv.run()
    return [(list(srv.result(r).generated), list(srv.result(r).uncertainty))
            for r in rids]


# ---------------------------------------------------------------------------
# the fault-injection harness
# ---------------------------------------------------------------------------


def test_fault_plan_validation_and_queries():
    with pytest.raises(ValueError, match="unknown fault action"):
        FaultEvent(step=0, host=0, action="melt")
    with pytest.raises(ValueError, match="delay_s > 0"):
        FaultEvent(step=0, host=0, action="delay")
    with pytest.raises(ValueError, match="span"):
        FaultEvent(step=0, host=0, action="drop", span=0)
    plan = FaultPlan(events=(
        FaultEvent(step=5, host=1, action="kill"),
        FaultEvent(step=2, host=0, action="drop", span=2),
        FaultEvent(step=3, host=2, action="delay", delay_s=1.5, span=2)))
    # kill is permanent from its step; drop/delay cover [step, step+span)
    assert not plan.killed(1, 4) and plan.killed(1, 5) and plan.killed(1, 99)
    assert plan.kill_step(1) == 5 and plan.kill_step(0) is None
    assert not plan.drops(0, 1) and plan.drops(0, 2) and plan.drops(0, 3) \
        and not plan.drops(0, 4)
    assert plan.delay(2, 2) == 0.0 and plan.delay(2, 4) == 1.5
    # events are normalized into (step, host) order
    assert [e.step for e in plan.events] == [2, 3, 5]


def test_fault_plan_seeded_deterministic_and_bounded():
    a = FaultPlan.seeded(7, n_hosts=3, horizon=40)
    b = FaultPlan.seeded(7, n_hosts=3, horizon=40)
    assert a == b                       # same seed -> same scenario
    assert a != FaultPlan.seeded(8, n_hosts=3, horizon=40)
    kills = [e for e in a.events if e.action == "kill"]
    assert len(kills) == 1
    assert 10 <= kills[0].step < 30     # middle half of the horizon
    with pytest.raises(ValueError, match="kill all hosts"):
        FaultPlan.seeded(0, n_hosts=2, horizon=40, n_kills=2)


# ---------------------------------------------------------------------------
# routing basics
# ---------------------------------------------------------------------------


def test_router_no_faults_matches_single_host(small):
    """Multi-host routing is invisible to results: every request's tokens
    and uncertainties are bitwise those of a single-host pool (rows are
    batch-independent, and every host runs the same pool shape)."""
    cfg, model, params = small
    prompts = _prompts(cfg, 5)
    ref = _single_host_reference(model, params, prompts)
    router, clock = _router(model, params, n_hosts=2)
    rids = [router.submit(p) for p in prompts]
    s = router.run(tick=lambda: clock.advance(1.0))
    assert s.completed == 5 and s.lost == 0 and s.shed == 0
    assert s.host_deaths == 0 and s.retries == 0
    # sticky round-robin homes over both hosts
    assert {router.result(r).home for r in rids} == {0, 1}
    for r, (toks, unc) in zip(rids, ref):
        rec = router.result(r)
        assert rec.status == "done"
        assert rec.generated == toks
        assert rec.uncertainty == unc
    assert router.queue_depth == 0 and router.occupied_slots == 0
    assert len(router.host_summaries()) == 2


def test_router_config_validation(small):
    with pytest.raises(ValueError, match="n_hosts"):
        RouterConfig(n_hosts=0)
    with pytest.raises(ValueError, match="pod"):
        RouterConfig(n_hosts=3, mesh_shape={"pod": 2, "data": 1})
    with pytest.raises(ValueError, match="heartbeat"):
        RouterConfig(heartbeat_timeout_s=0.0)


def test_router_spill_on_home_backpressure(small):
    """A full sticky home overflows onto another host instead of
    rejecting (counted per home in router_spills_total)."""
    cfg, model, params = small
    scfg = _scfg(max_slots=1, max_queue=1)
    router, clock = _router(model, params, scfg, n_hosts=2)
    p = _prompts(cfg, 2)
    a = router.submit(p[0])              # home 0, placed on host 0
    router._rr = 0                       # pin the next home back to host 0
    before = obs_registry.REGISTRY.value("router_spills_total")
    b = router.submit(p[1])              # home 0 is full -> spills to 1
    assert router.result(a).host == 0
    assert router.result(b).home == 0 and router.result(b).host == 1
    assert router.n_spills == 1
    assert obs_registry.REGISTRY.value("router_spills_total") == before + 1
    s = router.run(tick=lambda: clock.advance(1.0))
    assert s.completed == 2 and s.spills == 1


def test_router_shed_under_pressure_terminate_policy(small):
    """Graceful degradation: with every host saturated, the terminate
    escalation policy sheds overflow work (counted, traced, terminal)
    instead of erroring — and the shed request stays queryable."""
    cfg, model, params = small
    scfg = _scfg(max_slots=1, max_queue=1, escalation_policy="terminate")
    router, clock = _router(model, params, scfg, n_hosts=2, max_retries=0,
                            max_pending=16)
    p = _prompts(cfg, 5)
    # one queue seat per host: the first two submissions fill them, the
    # remaining three find every host backpressured and shed immediately
    rids = [router.submit(q) for q in p]
    shed = [r for r in rids if router.result(r).status == "shed"]
    assert len(shed) == 3 and router.n_shed == 3
    s = router.run(tick=lambda: clock.advance(1.0))
    assert s.shed == 3 and s.completed == 2 and s.lost == 0


def test_router_deprioritize_policy_degrades_not_sheds(small):
    """The deprioritize policy keeps overflow work alive at worsening
    priority: it waits out the backpressure and completes."""
    cfg, model, params = small
    scfg = _scfg(max_slots=1, max_queue=1,
                 escalation_policy="deprioritize")
    router, clock = _router(model, params, scfg, n_hosts=2, max_retries=3,
                            max_pending=16)
    p = _prompts(cfg, 5)
    rids = [router.submit(q) for q in p]
    overflow = [r for r in rids if router.result(r).status == "pending"]
    assert overflow and all(
        router.result(r).effective_priority > 0 for r in overflow)
    s = router.run(tick=lambda: clock.advance(1.0))
    assert s.completed == 5 and s.shed == 0 and s.lost == 0


def test_router_admission_guards(small):
    cfg, model, params = small
    router, clock = _router(model, params, n_hosts=2, max_pending=2)
    p = _prompts(cfg, 3)
    router.submit(p[0])
    router.submit(p[1])
    with pytest.raises(QueueFullError, match="max_pending"):
        router.submit(p[2])
    router.run(tick=lambda: clock.advance(1.0))
    router.submit(p[2])                  # capacity freed -> admits again


# ---------------------------------------------------------------------------
# failover
# ---------------------------------------------------------------------------


def test_kill_host_mid_decode_bitwise_identical(small):
    """The acceptance scenario: a host killed mid-decode is declared dead
    by heartbeat, its resident requests are resubmitted, and every
    recovered request's tokens AND uncertainties are bitwise-identical to
    an unfaulted run. Counters reflect exactly one death."""
    cfg, model, params = small
    prompts = _prompts(cfg, 6)
    ref = _single_host_reference(model, params, prompts)
    deaths0 = obs_registry.REGISTRY.value("router_host_deaths_total")
    retries0 = obs_registry.REGISTRY.value("router_retries_total")
    # host 1 goes silent at step 2 — mid-decode for its residents
    faults = FaultPlan(events=(FaultEvent(step=2, host=1, action="kill"),))
    router, clock = _router(model, params, faults=faults, max_retries=3)
    rids = [router.submit(p) for p in prompts]
    assert any(router.result(r).home == 1 for r in rids)
    s = router.run(max_steps=300, tick=lambda: clock.advance(1.0))
    assert s.host_deaths == 1 and s.lost == 0 and s.shed == 0
    assert s.retries >= 1                # the dead host held work
    assert s.remeshes >= 1
    assert s.completed == len(prompts)
    assert s.hosts_alive == 2
    assert s.recovery_steps and all(r >= 0 for r in s.recovery_steps)
    assert obs_registry.REGISTRY.value("router_host_deaths_total") == \
        deaths0 + 1
    assert obs_registry.REGISTRY.value("router_retries_total") == \
        retries0 + s.retries
    for r, (toks, unc) in zip(rids, ref):
        rec = router.result(r)
        assert rec.status == "done"
        assert rec.generated == toks     # bitwise: failover is invisible
        assert rec.uncertainty == unc


def test_kill_host_mid_scan_resumes_at_chunk_cursor(small):
    """Voxel failover is a cross-host ``_preempt``: the resubmitted scan
    resumes at its synced chunk cursor (chunks computed before the death
    are carried over BY IDENTITY, not recomputed) and the reassembled
    moments are bitwise-identical to the direct predict_packed path."""
    from repro.ivim import model as ivim_model

    cfg, model, params = small
    icfg = ivim_model.IvimConfig(n_masks=cfg.mask_samples, scale=2.0)
    iparams, istate = ivim_model.init(icfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(icfg, iparams, istate)
    rng = np.random.default_rng(3)
    x = rng.uniform(size=(96, icfg.width)).astype(np.float32)
    direct = engine.predict_packed(plan, x, chunk=16)

    faults = FaultPlan(events=(FaultEvent(step=3, host=0, action="kill"),))
    router, clock = _router(model, params, faults=faults, max_retries=3)
    router._rr = 0                       # scan's sticky home = host 0
    rid = router.submit_scan(plan, x, chunk=16)   # 6 chunks
    rec = router.result(rid)
    assert rec.home == 0
    # drive manually so we can capture a pre-death chunk object
    first_chunk = None
    for _ in range(300):
        busy = router.step()
        clock.advance(1.0)
        if first_chunk is None and rec.chunk_results:
            first_chunk = rec.chunk_results[0]
        if not busy and rec.done:
            break
    s = router.summary()
    assert s.host_deaths == 1 and s.retries >= 1 and s.lost == 0
    assert rec.status == "done"
    assert rec.final.chunk_results[0] is first_chunk   # resumed, not redone
    mean, std = rec.scan_moments()
    assert np.array_equal(np.asarray(mean), np.asarray(direct[0]))
    assert np.array_equal(np.asarray(std), np.asarray(direct[1]))
    assert s.total_voxels == 96


def test_all_hosts_dead_loses_work_without_hanging(small):
    """When the last host dies, pending work is terminally lost (counted,
    traced) and run() returns instead of spinning; new admissions are
    refused loudly."""
    cfg, model, params = small
    faults = FaultPlan(events=(FaultEvent(step=1, host=0, action="kill"),
                               FaultEvent(step=1, host=1, action="kill")))
    router, clock = _router(model, params, n_hosts=2, faults=faults,
                            max_retries=3)
    p = _prompts(cfg, 4)
    rids = [router.submit(q) for q in p]
    s = router.run(max_steps=300, tick=lambda: clock.advance(1.0))
    assert s.host_deaths == 2 and s.hosts_alive == 0
    assert s.completed + s.lost == 4 and s.lost >= 1
    assert all(router.result(r).done for r in rids)
    with pytest.raises(RuntimeError, match="no accepting hosts"):
        router.submit(p[0])


def test_straggler_drain_escalates_to_remesh(small):
    """A scripted persistent delay on one host drives the monitor's
    straggle -> drain -> plan_remesh escalation: the host stops taking
    work, membership is recomputed (pod axis shrinks), and results are
    unchanged."""
    cfg, model, params = small
    prompts = _prompts(cfg, 6)
    ref = _single_host_reference(model, params, prompts,
                                 _scfg(max_slots=1))
    # healthy steps take 0 virtual seconds on the ManualClock, so a
    # scripted 2s delay is an unambiguous outlier once the monitor warms;
    # one slot per host keeps the run long enough for the delay window
    faults = FaultPlan(events=(
        FaultEvent(step=2, host=0, action="delay", delay_s=2.0, span=4),))
    remesh0 = obs_registry.REGISTRY.value("router_remesh_total")
    router, clock = _router(model, params, _scfg(max_slots=1),
                            faults=faults, straggler_min_samples=2,
                            straggler_patience=2, straggler_window=8)
    rids = [router.submit(p) for p in prompts]
    s = router.run(max_steps=300, tick=lambda: clock.advance(1.0))
    assert s.remeshes >= 1
    assert obs_registry.REGISTRY.value("router_remesh_total") == \
        remesh0 + s.remeshes
    assert router.remeshes[0].new_shape["pod"] == 2    # 3 hosts -> 2
    assert not router.hosts[0].accepting               # drained out
    assert s.host_deaths == 0                          # slow, not dead
    assert s.completed == len(prompts) and s.lost == 0 and s.shed == 0
    for r, (toks, _) in zip(rids, ref):
        assert router.result(r).generated == toks


def test_drop_faults_are_transient_and_lossless(small):
    """Dropped step reports (a network partition shorter than the
    heartbeat timeout) delay harvesting but lose nothing: no deaths, no
    retries, bitwise-identical results."""
    cfg, model, params = small
    prompts = _prompts(cfg, 4)
    ref = _single_host_reference(model, params, prompts)
    faults = FaultPlan(events=(
        FaultEvent(step=1, host=0, action="drop", span=2),
        FaultEvent(step=2, host=1, action="drop", span=1)))
    router, clock = _router(model, params, faults=faults)
    rids = [router.submit(p) for p in prompts]
    s = router.run(max_steps=300, tick=lambda: clock.advance(1.0))
    assert s.host_deaths == 0 and s.retries == 0 and s.lost == 0
    assert s.completed == 4
    for r, (toks, unc) in zip(rids, ref):
        assert router.result(r).generated == toks
        assert router.result(r).uncertainty == unc


# ---------------------------------------------------------------------------
# surfaces: engine client, tracing, server hooks
# ---------------------------------------------------------------------------


def test_predict_volume_accepts_router_as_server(small):
    """The router duck-types the pool-client surface, so
    engine.predict_volume(server=router) serves a scan through the
    multi-host pool bitwise-identically to the direct path."""
    from repro.ivim import model as ivim_model

    cfg, model, params = small
    icfg = ivim_model.IvimConfig(n_masks=cfg.mask_samples, scale=2.0)
    iparams, istate = ivim_model.init(icfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(icfg, iparams, istate)
    rng = np.random.default_rng(5)
    vol = rng.uniform(size=(4, 8, icfg.width)).astype(np.float32)
    direct = engine.predict_volume(plan, jnp.asarray(vol), chunk=16)
    router, _ = _router(model, params, n_hosts=2)
    pooled = engine.predict_volume(plan, jnp.asarray(vol), chunk=16,
                                   server=router)
    assert np.array_equal(np.asarray(pooled[0]), np.asarray(direct[0]))
    assert np.array_equal(np.asarray(pooled[1]), np.asarray(direct[1]))


def test_traced_chaos_run_is_bitwise_and_verifier_clean(small):
    """Tracing a faulted run changes nothing (bitwise tokens, zero added
    retraces) and the emitted span log satisfies verify_obs's failover
    lifecycle state machine (host-death -> retry -> re-admit)."""
    path = pathlib.Path(__file__).parent.parent / "benchmarks" / \
        "verify_obs.py"
    spec = importlib.util.spec_from_file_location("verify_obs", path)
    verify_obs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(verify_obs)

    cfg, model, params = small
    prompts = _prompts(cfg, 5)
    faults = FaultPlan(events=(FaultEvent(step=2, host=2, action="kill"),))

    def scenario():
        router, clock = _router(model, params, faults=faults,
                                max_retries=3)
        rids = [router.submit(p) for p in prompts]
        router.run(max_steps=300, tick=lambda: clock.advance(1.0))
        return [router.result(r).generated for r in rids], \
            router.summary()

    plain_toks, plain_s = scenario()
    tracer = obs_trace.TRACER
    tracer.clear()
    retr0 = obs_registry.REGISTRY.value("retrace_total")
    tracer.enable()
    try:
        traced_toks, traced_s = scenario()
        events = tracer.events()
    finally:
        tracer.disable()
    assert traced_toks == plain_toks          # tracing is invisible
    assert obs_registry.REGISTRY.value("retrace_total") == retr0
    assert traced_s.host_deaths == plain_s.host_deaths
    assert verify_obs.verify_trace_events(events) == []
    names = {e["name"] for e in events}
    assert {"router.host_death", "retry", "enqueue", "remesh"} <= names


def test_server_req_id_pinning_and_cancel(small):
    """The per-host hooks the router builds on: caller-pinned request ids
    (one global id space across hosts), duplicate-id rejection, queued-
    only cancel with tombstone-corrected queue depth, and scan
    resume_results validation."""
    cfg, model, params = small
    srv = BayesianLMServer(model, params, _scfg())
    p = _prompts(cfg, 3)
    assert srv.submit(p[0], req_id=7) == 7
    with pytest.raises(ValueError, match="already tracked"):
        srv.submit(p[1], req_id=7)
    rid = srv.submit(p[1], req_id=9)
    assert srv.queue_depth == 2
    srv.cancel(rid)
    assert srv.queue_depth == 1 and rid not in srv.states
    with pytest.raises(ValueError, match="unknown"):
        srv.cancel(rid)
    srv.run()
    st = srv.result(7)
    assert st.status == "done" and len(st.generated) == 4
    with pytest.raises(ValueError, match="not queued"):
        srv.cancel(7)

    from repro.ivim import model as ivim_model
    icfg = ivim_model.IvimConfig(n_masks=cfg.mask_samples, scale=2.0)
    iparams, istate = ivim_model.init(icfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(icfg, iparams, istate)
    x = np.random.default_rng(0).uniform(size=(32, icfg.width)) \
        .astype(np.float32)
    with pytest.raises(ValueError, match="nothing left to run"):
        srv.submit_scan(plan, x, chunk=16,
                        resume_results=[object(), object()])


# ---------------------------------------------------------------------------
# one device per host
# ---------------------------------------------------------------------------

_PLACEMENT_SNIPPET = """
import jax, numpy as np
from repro.configs import registry
from repro.models import build_model
from repro.obs.trace import ManualClock
from repro.serving import (BayesianLMServer, RouterConfig, ServerConfig,
                           ServingRouter)
devs = jax.devices()
assert len(devs) == 4, devs
cfg = registry.smoke_config("qwen2-1.5b", n_layers=2)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
scfg = ServerConfig(max_slots=2, max_prompt_len=8, max_new_tokens=3)
clock = ManualClock()
router = ServingRouter(model, params, scfg, RouterConfig(n_hosts=4),
                       clock=clock)
for i, hs in enumerate(router.hosts):
    for tree in (hs.server.params, hs.server._caches):
        placed = {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}
        assert placed == {devs[i]}, (i, placed)
prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0,
                                        cfg.vocab_size))
rids = [router.submit(p) for p in prompts]
router.run(max_steps=200, tick=lambda: clock.advance(1.0))
assert sorted(router.result(r).home for r in rids) == [0, 1, 2, 3]
single = BayesianLMServer(model, params, scfg, device=devs[0])
sids = [single.submit(p) for p in prompts]
single.run()
for r, s in zip(rids, sids):
    assert router.result(r).generated == single.result(s).generated
    np.testing.assert_allclose(router.result(r).uncertainty,
                               single.result(s).uncertainty, atol=1e-6)
from repro.ivim import model as ivim_model
from repro.serving import engine
icfg = ivim_model.IvimConfig(n_masks=cfg.mask_samples, scale=2.0)
plan = ivim_model.pack_for_serving(icfg, *ivim_model.init(
    icfg, jax.random.PRNGKey(2)))
x = np.random.default_rng(0).uniform(size=(48, icfg.width)).astype(np.float32)
sids = [router.submit_scan(plan, x, chunk=16) for _ in range(4)]
router.run(max_steps=200, tick=lambda: clock.advance(1.0))
ref = engine.predict_packed(plan, x, chunk=16)
assert sorted(router.result(r).home for r in sids) == [0, 1, 2, 3]
assert router.summary().spills == 0
for r in sids:
    rec = router.result(r)
    placed = {d for m, s in rec.final.chunk_results for d in m.devices()}
    assert placed == {devs[rec.home]}, (rec.home, placed)
    for got, want in zip(rec.scan_moments(), ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
print("PLACEMENT_OK")
"""


def test_router_hosts_on_distinct_devices():
    """On a 4-device host, router host i keeps its params and KV pool on
    device i and answers like a single-host server on device 0; a voxel
    scan routed to host i runs its chunks on device i."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _PLACEMENT_SNIPPET], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "PLACEMENT_OK" in out.stdout
