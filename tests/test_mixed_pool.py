"""Mixed-modality pool: voxel-chunk work items riding the LM slot pool,
bucketed fused prefill, and the shared admission/escalation surface."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import plan as plan_lib
from repro.core import scheduler as scheduler_lib
from repro.ivim import model as ivim_model
from repro.models import build_model
from repro.serving import (BayesianLMServer, QueueFullError, ServerConfig,
                           VoxelScanRequest, engine, step_fns)


@pytest.fixture(scope="module")
def small():
    cfg = registry.smoke_config("qwen2-1.5b", n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def ivim():
    cfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(cfg, params, state)
    return cfg, plan


def _prompts(cfg, n, length=6, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (n, length), 0, cfg.vocab_size))


def _server(model, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_new_tokens", 4)
    return BayesianLMServer(model, params, ServerConfig(**kw))


# ---------------------------------------------------------------------------
# voxel-chunk admission: pooled == direct, bitwise
# ---------------------------------------------------------------------------


def test_pooled_volume_bitwise_matches_direct(small, ivim):
    """The tentpole equivalence: predict_volume through the pool (one
    voxel-chunk work item per scan, one chunk per engine step) returns
    moments BITWISE-identical to the direct streamed path — both run the
    one plan_chunk_runner over the same chunk_bounds partition."""
    _, model, params = small
    icfg, plan = ivim
    vol = jax.random.uniform(jax.random.PRNGKey(3), (5, 3, 2, icfg.width))
    dm, ds = engine.predict_volume(plan, vol, chunk=7, backend="xla")
    srv = _server(model, params)
    pm, ps = engine.predict_volume(plan, vol, chunk=7, backend="xla",
                                   server=srv)
    np.testing.assert_array_equal(np.asarray(dm), np.asarray(pm))
    np.testing.assert_array_equal(np.asarray(ds), np.asarray(ps))
    assert srv.occupied_slots == 0 and srv.queue_depth == 0
    # the scan never touched the KV pool: every slot group is still empty
    assert (np.asarray(srv._caches[0]["b0"]["kpos"]) == -1).all()


def test_mixed_traffic_one_pool(small, ivim):
    """LM requests and a scan share the queue, the slots and the metrics
    stream — and neither modality perturbs the other's results."""
    cfg, model, params = small
    icfg, plan = ivim
    x = jax.random.uniform(jax.random.PRNGKey(5), (11, icfg.width))
    want_m, want_s = engine.predict_packed(plan, x, chunk=4, backend="xla")
    prompts = _prompts(cfg, 2)
    solo = _server(model, params)
    want_gen = []
    for p in prompts:
        r = solo.submit(p)
        solo.run()
        want_gen.append(solo.result(r).generated)

    srv = _server(model, params, max_slots=2)
    r0 = srv.submit(prompts[0])
    rs = srv.submit_scan(plan, x, chunk=4, backend="xla")
    r1 = srv.submit(prompts[1])
    summary = srv.run()
    st = srv.result(rs)
    assert st.kind == "voxel" and st.status == "done"
    assert isinstance(st.request, VoxelScanRequest)
    mean, std = st.scan_moments()
    np.testing.assert_array_equal(np.asarray(mean), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(std), np.asarray(want_s))
    assert srv.result(r0).generated == want_gen[0]
    assert srv.result(r1).generated == want_gen[1]
    # per-modality metrics rollup
    assert summary.lm_requests == 2 and summary.voxel_requests == 1
    assert summary.total_voxels == 11 and summary.total_tokens == 8
    assert summary.voxels_per_s > 0
    assert max(srv.metrics.voxel_occupancy_samples) == 1
    tl = srv.metrics.timelines
    assert tl[rs].modality == "voxel" and tl[r0].modality == "lm"


def test_scan_admission_requires_matching_schedule(small):
    """A plan whose mask count does not map onto the pool layout is
    rejected at submit time, not at chunk time."""
    _, model, params = small
    icfg = ivim_model.IvimConfig(n_masks=8, scale=2.0)   # pool has 4
    ip, ist = ivim_model.init(icfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(icfg, ip, ist)
    srv = _server(model, params)
    with pytest.raises(ValueError):
        srv.submit_scan(plan, jnp.zeros((4, icfg.width)))


def test_scan_backpressure_shared_queue(small, ivim):
    """Scans count against the same max_queue as LM requests."""
    cfg, model, params = small
    _, plan = ivim
    srv = _server(model, params, max_queue=2)
    srv.submit(_prompts(cfg, 1)[0])
    srv.submit_scan(plan, jnp.zeros((4, 3)), chunk=2, backend="xla")
    with pytest.raises(QueueFullError):
        srv.submit_scan(plan, jnp.zeros((4, 3)), chunk=2, backend="xla")
    with pytest.raises(ValueError):
        srv.submit_scan(plan, jnp.zeros((4, 3, 2)))      # not [n_voxels, D]


# ---------------------------------------------------------------------------
# preemption: chunks never complete out of order
# ---------------------------------------------------------------------------


def test_voxel_preempt_requeue_in_order(small, ivim):
    """Deprioritize must preempt a flagged scan *between* chunks and resume
    it at the next unprocessed chunk — chunk results stay strictly in scan
    order, and the reassembled moments still equal the direct path."""
    cfg, model, params = small
    icfg, plan = ivim
    x = jax.random.uniform(jax.random.PRNGKey(7), (10, icfg.width))
    want_m, want_s = engine.predict_packed(plan, x, chunk=3, backend="xla")
    srv = BayesianLMServer(model, params, ServerConfig(
        max_slots=1, max_queue=8, max_prompt_len=8, max_new_tokens=4,
        uncertainty_threshold=0.0, escalation_patience=1,
        escalation_policy="deprioritize", deprioritize_penalty=5))
    rs = srv.submit_scan(plan, x, chunk=3, backend="xla")
    r1 = srv.submit(_prompts(cfg, 1)[0])
    summary = srv.run()
    st = srv.result(rs)
    # threshold 0 flags the first chunk; with queued LM traffic behind it
    # the scan must actually have bounced through the queue
    assert st.preempts >= 1 and st.escalated
    assert st.status == "done"
    assert len(st.chunk_results) == len(st.request.bounds) == 4
    mean, std = st.scan_moments()
    np.testing.assert_array_equal(np.asarray(mean), np.asarray(want_m))
    np.testing.assert_array_equal(np.asarray(std), np.asarray(want_s))
    assert srv.result(r1).status == "done"
    assert summary.completed == 2 and summary.escalated >= 1


def test_voxel_terminate_policy(small, ivim):
    """terminate stops a flagged scan early with partial chunk_results, and
    scan_moments refuses to reassemble the partial scan."""
    _, model, params = small
    icfg, plan = ivim
    x = jax.random.uniform(jax.random.PRNGKey(9), (9, icfg.width))
    srv = BayesianLMServer(model, params, ServerConfig(
        max_slots=1, max_prompt_len=8, max_new_tokens=4,
        uncertainty_threshold=0.0, escalation_patience=2,
        escalation_policy="terminate"))
    rs = srv.submit_scan(plan, x, chunk=2, backend="xla")
    srv.run()
    st = srv.result(rs)
    assert st.status == "escalated" and st.escalated
    assert len(st.chunk_results) == 2 < len(st.request.bounds)
    with pytest.raises(ValueError):
        st.scan_moments()


def test_chunk_bounds():
    assert scheduler_lib.chunk_bounds(10, 4) == ((0, 4), (4, 8), (8, 10))
    assert scheduler_lib.chunk_bounds(4, 8) == ((0, 4),)
    with pytest.raises(ValueError):
        scheduler_lib.chunk_bounds(0, 4)
    with pytest.raises(ValueError):
        scheduler_lib.chunk_bounds(4, 0)


# ---------------------------------------------------------------------------
# bucketed fused prefill
# ---------------------------------------------------------------------------


def test_prefill_retrace_bound(small):
    """≥8 distinct prompt lengths prefill through at most |buckets|
    distinct traces (counted in core.plan.fused_trace_counts) — the
    per-length exact path would trace 8 times."""
    cfg, model, params = small
    fns = step_fns(model)
    assert fns.prefill_spec is not None
    max_seq = 13
    before = {k: v for k, v in plan_lib.fused_trace_counts.items()
              if k[2] == "prefill"}
    exact_before = fns.trace_counts["prefill"]
    lengths = list(range(1, 9))
    rng = np.random.default_rng(0)
    for ln in lengths:
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, ln)),
                           jnp.int32)
        fns.prefill(params, toks, max_seq=max_seq)
    new = {k: v - before.get(k, 0)
           for k, v in plan_lib.fused_trace_counts.items()
           if k[2] == "prefill" and v > before.get(k, 0)}
    n_buckets = len(plan_lib.prefill_buckets(max_seq))
    assert len(lengths) >= 8
    assert sum(new.values()) <= n_buckets
    assert len(new) <= n_buckets
    # every new trace is a (bucket, max_seq) key, and none on the exact path
    assert all(k[3] in plan_lib.prefill_buckets(max_seq) and k[4] == max_seq
               for k in new)
    assert fns.trace_counts["prefill"] == exact_before


def test_bucketed_prefill_bitwise_matches_exact(small):
    """Padded bucket prefill matches the exact per-length prefill (the
    reference) — posterior, uncertainty AND the trimmed KV caches (so decode
    continuations agree too). The two are different XLA programs: attention
    runs over the bucket's key length instead of the prompt's, so XLA may
    sum the same f32 terms in another order. They agree to f32 rounding
    (a few ulps: rtol 1e-6 ~ 8 ulps), not bitwise; the cache positions
    (kpos, integers) stay exact."""
    cfg, model, params = small
    fb = step_fns(model)                       # auto power-of-two buckets
    fe = step_fns(model, prefill_buckets=())   # exact per-length path
    assert fb.prefill_spec is not None and fe.prefill_spec is None
    for ln in (3, 5, 8):
        toks = jnp.asarray(_prompts(cfg, 1, length=ln, seed=ln)[0][None]
                           .repeat(4, 0))
        mb, rb, cb = fb.prefill(params, toks, max_seq=12)
        me, re_, ce = fe.prefill(params, toks, max_seq=12)
        np.testing.assert_allclose(np.asarray(mb), np.asarray(me),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(rb), np.asarray(re_),
                                   rtol=1e-5, atol=1e-7)
        for a, b in zip(jax.tree.leaves(cb), jax.tree.leaves(ce)):
            if a.dtype == jnp.int32:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            else:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-6)


def test_prefill_bucket_selection():
    assert plan_lib.prefill_buckets(12) == (1, 2, 4, 8, 12)
    assert plan_lib.prefill_buckets(16, (4, 8)) == (4, 8)
    assert plan_lib.prefill_bucket(5, 12) == 8
    assert plan_lib.prefill_bucket(12, 12) == 12
    assert plan_lib.prefill_bucket(9, 16, (4, 8)) is None   # uncovered
    with pytest.raises(ValueError):
        plan_lib.prefill_buckets(16, ())
    with pytest.raises(ValueError):
        plan_lib.prefill_buckets(16, (0, 4))


def test_custom_bucket_fallback_to_exact(small):
    """Lengths no custom bucket covers fall back to the exact path (and
    only those lengths trace it)."""
    cfg, model, params = small
    fns = step_fns(model, prefill_buckets=(4,))
    before = fns.trace_counts["prefill"]
    toks = jnp.asarray(_prompts(cfg, 1, length=6, seed=2)[0][None]
                       .repeat(4, 0))
    fns.prefill(params, toks, max_seq=12)      # 6 > 4: exact path
    assert fns.trace_counts["prefill"] == before + 1
    toks = jnp.asarray(_prompts(cfg, 1, length=3, seed=2)[0][None]
                       .repeat(4, 0))
    fns.prefill(params, toks, max_seq=12)      # 3 <= 4: bucketed
    assert fns.trace_counts["prefill"] == before + 1


# ---------------------------------------------------------------------------
# loud config validation
# ---------------------------------------------------------------------------


def test_server_config_validation():
    with pytest.raises(ValueError):
        ServerConfig(max_slots=4, max_queue=3)        # queue < pool
    with pytest.raises(ValueError):
        ServerConfig(max_slots=0)
    with pytest.raises(ValueError):
        ServerConfig(max_prompt_len=0)
    with pytest.raises(ValueError):
        ServerConfig(prefill_buckets=(0, 4))          # non-positive bucket
    with pytest.raises(ValueError):
        step_fns(registry.smoke_config("qwen2-1.5b", n_layers=2),
                 prefill_buckets=(-1,))
    # () = bucketing disabled, valid; list normalizes to tuple
    assert ServerConfig(prefill_buckets=()).prefill_buckets == ()
    assert ServerConfig(prefill_buckets=[4, 8]).prefill_buckets == (4, 8)
