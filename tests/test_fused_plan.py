"""Fused whole-plan megakernel — equivalence, caching, chunk streaming.

The acceptance bar of the fused executor: ``plan.execute_fused`` must match
the per-op ``plan.execute`` (and, with ``moments=True``, ``uncertainty.
predictive_moments`` of it) to fp32 tolerance for every compiled family —
IVIM (groups + C(.) ranges), MaskedMlp (SharedDense prefix, pair-absorbed
head), and the transformer packed FFN shape — across N ∈ {1, 4, 8} on both
the pure-XLA reference tier and the Pallas interpreter tier; its traffic
model must price ≥2× fewer HBM bytes than the per-op path on the IVIM plan;
and the serving engine must stream chunks through ONE cached executor
(trace counter) with exactly one fused launch per chunk (dispatch spy).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masks as masks_lib
from repro.core import plan as plan_lib
from repro.core import transform
from repro.core import uncertainty as unc_lib
from repro.ivim import model as ivim_model
from repro.serving import engine

BACKENDS = ("xla", "pallas-interpret")
NS = (1, 4, 8)


def _close(got, want, tol=2e-4):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _ivim_plan(n_masks, seed=0):
    cfg = ivim_model.IvimConfig(n_masks=n_masks, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(seed))
    x = jax.random.uniform(jax.random.PRNGKey(1), (6, cfg.width))
    return plan_lib.compile_ivim(cfg, params, state), x


def _mlp_plan(n_masks, widths=(7, 16, 16, 2), dropout=(1, 2), seed=0):
    spec = transform.MlpSpec(widths=widths, dropout_after=dropout,
                             final_activation="sigmoid")
    model = transform.convert(spec, n_masks=n_masks, scale=2.0,
                              key=jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(2), (9, widths[0]))
    return plan_lib.compile_mlp(model), x


def _ffn_plan(n_masks, seed=0):
    d, f, d2 = 8, 24, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    plan = plan_lib.compile_masked_ffn(
        jax.random.normal(ks[0], (d, f)) * 0.3,
        jax.random.normal(ks[1], (f,)) * 0.1,
        jax.random.normal(ks[2], (f, d2)) * 0.3,
        jax.random.normal(ks[3], (d2,)) * 0.1,
        masks_lib.generate_masks(
            masks_lib.MaskSpec(width=f, n_masks=n_masks, scale=2.0)))
    return plan, jax.random.normal(ks[4], (10, d))


FAMILIES = {"ivim": _ivim_plan, "mlp": _mlp_plan, "ffn": _ffn_plan}


# ---------------------------------------------------------------------------
# equivalence: fused == per-op, samples and in-kernel moments
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n_masks", NS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fused_matches_per_op(family, n_masks, backend):
    plan, x = FAMILIES[family](n_masks)
    want = plan_lib.execute(plan, x, backend="xla")
    _close(plan_lib.execute_fused(plan, x, backend=backend), want)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n_masks", NS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fused_moments_match(family, n_masks, backend):
    plan, x = FAMILIES[family](n_masks)
    want_m, want_s = unc_lib.predictive_moments(
        plan_lib.execute(plan, x, backend="xla"))
    mean, std = plan_lib.execute_fused(plan, x, moments=True, backend=backend)
    _close(mean, want_m)
    _close(std, want_s)


def test_fused_mlp_shared_prefix_and_absorbed_head():
    """The two MaskedMlp grammar corners: a SharedDense prefix before the
    masked run, and a pair that absorbed the output layer (trailing bare
    Activation op)."""
    for widths, dropout in (((9, 12, 16, 16, 3), (2, 3)), ((6, 14, 2), (1,))):
        plan, x = _mlp_plan(4, widths=widths, dropout=dropout)
        want = plan_lib.execute(plan, x, backend="xla")
        _close(plan_lib.execute_fused(plan, x, backend="pallas-interpret"),
               want)


# ---------------------------------------------------------------------------
# executor cache: repeated same-shape calls must not retrace
# ---------------------------------------------------------------------------


def test_fused_executor_cached_no_retrace():
    plan, _ = _mlp_plan(3, widths=(5, 24, 24, 2), dropout=(1, 2), seed=7)
    spec = plan.fused_spec()
    key = (spec, "xla", True)
    assert plan_lib.fused_trace_counts[key] == 0, "unique spec expected"
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 5))
    engine.predict_packed(plan, x, backend="xla", fused=True)
    assert plan_lib.fused_trace_counts[key] == 1
    engine.predict_packed(plan, x + 1.0, backend="xla", fused=True)
    assert plan_lib.fused_trace_counts[key] == 1      # cache hit, no retrace
    engine.predict_packed(plan, x[:8], backend="xla", fused=True)
    assert plan_lib.fused_trace_counts[key] == 2      # new shape traces once
    # chunked streaming reuses the one fixed-shape executor across chunks
    engine.predict_packed(plan, x, chunk=4, backend="xla", fused=True)
    assert plan_lib.fused_trace_counts[(spec, "xla", True)] == 3


@pytest.mark.parametrize("moments", [True, False])
@pytest.mark.parametrize("family", ["ivim", "mlp"])
def test_fused_executor_one_program_no_uploads(family, moments):
    """The executor's whole result, C(.) and the group un-flattening
    included, comes from one jitted program: once warm, a call on a
    device-resident x moves nothing from host to device (the IVIM plan has
    out_ranges and 4 groups; the MLP plan has neither)."""
    plan, x = FAMILIES[family](4)
    assert (plan.out_ranges is not None) == (family == "ivim")
    assert plan.groups == (4 if family == "ivim" else 1)
    run = plan_lib.fused_executor(plan, moments=moments, backend="xla")
    run(x)
    x = jax.device_put(x)
    with jax.transfer_guard_host_to_device("disallow"):
        got = jax.block_until_ready(run(x))
    samples = plan_lib.execute(plan, x, backend="xla")
    if not moments:
        assert got.shape == samples.shape
        _close(got, samples)
        return
    for g, w in zip(got, unc_lib.predictive_moments(samples)):
        assert g.shape == w.shape
        _close(g, w)


# ---------------------------------------------------------------------------
# serving engine: chunk streaming + volumes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fused", [True, False, None])
@pytest.mark.parametrize("chunk", [4, 1, 32])
def test_predict_packed_chunk_edges(chunk, fused):
    """B=10 with chunk ∈ {4 (pad 2), 1 (degenerate), 32 (> B)} — pad rows
    must never leak into the returned moments."""
    cfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (10, cfg.width))
    want_m, want_s = ivim_model.predict(cfg, params, state, x)
    plan = ivim_model.pack_for_serving(cfg, params, state)
    mean, std = engine.predict_packed(plan, x, chunk=chunk, backend="xla",
                                      fused=fused)
    assert mean.shape == want_m.shape and std.shape == want_s.shape
    _close(mean, want_m)
    _close(std, want_s)


def test_predict_volume_streams_scan():
    cfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(cfg, params, state)
    vol = jax.random.uniform(jax.random.PRNGKey(3), (4, 3, 2, cfg.width))
    vm, vs = engine.predict_volume(plan, vol, chunk=5, backend="xla")
    assert vm.shape == (4, 3, 2, 4) and vs.shape == (4, 3, 2, 4)
    fm, fs = engine.predict_packed(plan, vol.reshape(-1, cfg.width),
                                   backend="xla")
    _close(vm.reshape(-1, 4), fm)
    _close(vs.reshape(-1, 4), fs)
    with pytest.raises(ValueError):
        engine.predict_volume(plan, vol[0, 0, 0])     # 1-D: no voxel axis


def test_fused_dispatch_once_per_chunk(monkeypatch):
    """Satellite acceptance: the fused path runs exactly once per streamed
    chunk (⌈10/4⌉ = 3), always in moments mode — and the plan is lowered
    exactly once per call, not once per chunk."""
    calls, factories = [], []
    real = plan_lib.fused_executor

    def spy_factory(plan, **kw):
        factories.append(kw.get("moments", False))
        run = real(plan, **kw)

        def apply(x):
            calls.append((x.shape[0], kw.get("moments", False)))
            return run(x)

        return apply

    monkeypatch.setattr(plan_lib, "fused_executor", spy_factory)
    cfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(cfg, params, state)
    x = jax.random.uniform(jax.random.PRNGKey(1), (10, cfg.width))
    engine.predict_packed(plan, x, chunk=4, backend="xla", fused=True)
    assert calls == [(4, True)] * 3
    assert factories == [True]          # one lowering per call


def test_predict_packed_falls_back_when_unsupported(monkeypatch):
    """fused=None degrades to the per-op executor when the plan has no
    fused lowering; fused=True surfaces the error."""
    cfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(cfg, params, state)
    x = jax.random.uniform(jax.random.PRNGKey(1), (6, cfg.width))
    want_m, want_s = engine.predict_packed(plan, x, backend="xla",
                                           fused=False)

    def boom(_plan):
        raise plan_lib.FusedPlanUnsupported("test")

    monkeypatch.setattr(plan_lib, "lower_fused", boom)
    mean, std = engine.predict_packed(plan, x, backend="xla")
    _close(mean, want_m)
    _close(std, want_s)
    with pytest.raises(plan_lib.FusedPlanUnsupported):
        engine.predict_packed(plan, x, backend="xla", fused=True)


def test_predict_packed_falls_back_on_vmem_guard(monkeypatch):
    """The moments-mode VMEM-residency guard fires at trace time, from
    inside the first fused launch — fused=None must still degrade to the
    per-op executor."""
    from repro import compat
    from repro.kernels.fused_plan import ops as fp_ops
    if compat.kernel_backend() == "xla":
        pytest.skip("guard lives in the Pallas tier; a forced xla probe "
                    "(REPRO_KERNEL_BACKEND=xla) routes even explicit "
                    "backend= requests to the reference path")
    cfg = ivim_model.IvimConfig(n_masks=5, scale=2.0)   # unique shape-key
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(cfg, params, state)
    x = jax.random.uniform(jax.random.PRNGKey(1), (7, cfg.width))
    want_m, want_s = engine.predict_packed(plan, x, backend="xla",
                                           fused=False)
    monkeypatch.setattr(fp_ops, "VMEM_MOMENTS_LIMIT", 1)
    mean, std = engine.predict_packed(plan, x, backend="pallas-interpret")
    _close(mean, want_m)
    _close(std, want_s)
    with pytest.raises(plan_lib.FusedPlanUnsupported):
        engine.predict_packed(plan, x, backend="pallas-interpret",
                              fused=True)


# ---------------------------------------------------------------------------
# pricing: the fused path must model strictly less HBM traffic
# ---------------------------------------------------------------------------


def test_fused_traffic_and_latency_pricing():
    plan, _ = _ivim_plan(8)
    per_op = plan.traffic(512)
    fused = plan.traffic(512, fused=True, moments=True)
    assert fused.total_bytes * 2 <= per_op.total_bytes   # acceptance: ≥2×
    assert fused.weight_loads == plan.sample_axis        # whole chain, once
    samples = plan.traffic(512, fused=True)
    assert fused.total_bytes < samples.total_bytes       # moments saves more
    assert plan.modeled_latency(20000, fused=True) < \
        plan.modeled_latency(20000)


def test_ivim_packed_apply_fused():
    cfg = ivim_model.IvimConfig(n_masks=4, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(0))
    plan = ivim_model.pack_for_serving(cfg, params, state)
    x = jax.random.uniform(jax.random.PRNGKey(1), (5, cfg.width))
    _close(ivim_model.packed_apply(plan, x, fused=True, backend="xla"),
           ivim_model.packed_apply(plan, x, backend="xla"))
