"""Quantized serving — int8 packed weights + low-precision KV cache.

Acceptance bar (PR 8): an int8-precision plan must execute equivalently
across every tier (per-op xla / per-op interpret / fused xla / fused
interpret agree to fp32 tolerance, because they share ONE quantizer), stay
within a documented tolerance of the fp32 plan per model family; the fp32
default must remain bitwise-identical (no 'ws' slots, master params served
as-is); the modeled HBM weight bytes of the int8 fused IVIM plan must be
<= 0.35x the fp32 fused path at f32 master-param pricing; bf16-KV fused
decode must produce bitwise-identical tokens vs the per-op path; int8 KV
must have NO fused lowering (per-op fallback) while staying token-identical
to the fp32-cache server; and ``compressed_allreduce`` must reduce over
integer lanes (i32 psum in the lowering text — the wire-compression fix).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import masks as masks_lib
from repro.core import plan as plan_lib
from repro.core import transform
from repro.core.plan import Precision
from repro.ivim import model as ivim_model
from repro.models import build_model, transformer
from repro.serving import BayesianLMServer, ServerConfig, server as server_lib

BACKENDS = ("xla", "pallas-interpret")
NS = (1, 4, 8)
INT8 = Precision(weights="int8")

# int8-vs-fp32 output drift bound per family: bounded-output families
# (IVIM / sigmoid MLP) sit near the int8 step of their small dynamic range;
# the raw randn-weight FFN toy has unbounded logits so its absolute drift
# is proportionally larger.
FP32_TOL = {"ivim": 2e-2, "mlp": 2e-2, "ffn": 0.8}


def _ivim_plan(n_masks, seed=0):
    cfg = ivim_model.IvimConfig(n_masks=n_masks, scale=2.0)
    params, state = ivim_model.init(cfg, jax.random.PRNGKey(seed))
    x = jax.random.uniform(jax.random.PRNGKey(1), (6, cfg.width))
    return plan_lib.compile_ivim(cfg, params, state), x


def _mlp_plan(n_masks, seed=0):
    spec = transform.MlpSpec(widths=(7, 16, 16, 2), dropout_after=(1, 2),
                             final_activation="sigmoid")
    model = transform.convert(spec, n_masks=n_masks, scale=2.0,
                              key=jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(2), (9, 7))
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


def _close(got, want, tol=2e-4):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# int8 weights: every tier agrees (shared quantizer)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n_masks", NS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_int8_fused_matches_per_op(family, n_masks, backend):
    plan, x = FAMILIES[family](n_masks)
    pq = plan.with_precision(INT8)
    want = plan_lib.execute(pq, x, backend="xla")
    _close(plan_lib.execute(pq, x, backend="pallas-interpret"), want)
    _close(plan_lib.execute_fused(pq, x, backend=backend), want)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_int8_moments_match(family, backend):
    from repro.core import uncertainty as unc_lib
    plan, x = FAMILIES[family](4)
    pq = plan.with_precision(INT8)
    want_m, want_s = unc_lib.predictive_moments(
        plan_lib.execute(pq, x, backend="xla"))
    mean, std = plan_lib.execute_fused(pq, x, moments=True, backend=backend)
    _close(mean, want_m)
    _close(std, want_s)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_int8_close_to_fp32(family):
    plan, x = FAMILIES[family](4)
    y_f = np.asarray(plan_lib.execute(plan, x, backend="xla"))
    y_q = np.asarray(plan_lib.execute(plan.with_precision(INT8), x,
                                      backend="xla"))
    assert np.abs(y_q - y_f).max() <= FP32_TOL[family], \
        f"{family}: int8 drift {np.abs(y_q - y_f).max():.4f}"


def test_int8_lowering_carries_scale_slots():
    from repro.kernels.fused_plan import ref as fused_ref
    plan, _ = _ffn_plan(4)
    spec, params = plan_lib.lower_fused(plan.with_precision(INT8))
    slots = fused_ref.param_slots(spec)
    kinds = [s for _, s in slots]
    assert "ws" in kinds
    table = dict(zip(slots, params))
    for (i, kind), arr in table.items():
        if kind == "w":
            assert arr.dtype == jnp.int8
            ws = table[(i, "ws")]
            assert ws.dtype == jnp.bfloat16
            assert ws.shape == arr.shape[:-2] + (1, arr.shape[-1])
        elif kind in ("b", "bp"):
            assert arr.dtype == jnp.bfloat16


def test_fp32_default_stays_bitwise():
    """The guard of the whole PR: default-precision plans must not pass
    through the quantizer at all — no 'ws' slots, master param arrays
    served untouched (the exact master arrays, bitwise), and the fused
    executor agrees with the per-op xla reference to f32 rounding. The two
    are different XLA programs (one fused chain vs per-op einsums), so
    their contractions may sum in another order: rtol 1e-5 allows ~80 f32
    ulps, while an int8 quantizer slipping in would be off by ~1e-2."""
    from repro.kernels.fused_plan import ref as fused_ref
    plan, x = _ffn_plan(4)
    spec, params = plan_lib.lower_fused(plan)
    assert all(kind != "ws" for _, kind in fused_ref.param_slots(spec))
    assert all(a.dtype == jnp.float32 for a in params)
    # the lowering of the DEFAULT precision is the identity on weights:
    # the exact master arrays flow into the kernel, not copies
    masters = {id(a) for a in jax.tree.leaves(plan.params)}
    assert all(id(a) in masters for a in params)
    y_po = np.asarray(plan_lib.execute(plan, x, backend="xla"))
    y_f = np.asarray(plan_lib.execute_fused(plan, x, backend="xla"))
    np.testing.assert_allclose(y_f, y_po, rtol=1e-5, atol=1e-6)


def test_int8_spec_distinct_from_fp32_spec():
    """Distinct precisions lower to distinct (separately cached) fused
    specs — a warm fp32 executor can never serve int8 bytes."""
    plan, _ = _ffn_plan(4)
    assert plan.with_precision(INT8).fused_spec() != plan.fused_spec()
    # re-stating the default precision is a spec-level identity
    assert plan.with_precision(Precision()).fused_spec() == plan.fused_spec()


# ---------------------------------------------------------------------------
# pricing: the ISSUE acceptance gate
# ---------------------------------------------------------------------------


def test_int8_weight_bytes_gate():
    """int8-weight fused IVIM plan models <= 0.35x the fp32 fused weight
    bytes at f32 master-param pricing (the PR acceptance gate), and the
    per-op schedule path shrinks too."""
    plan, _ = _ivim_plan(4)
    pq = plan.with_precision(INT8)
    for fused in (True, False):
        t_f = plan.traffic(512, 4, fused=fused, moments=fused)
        t_q = pq.traffic(512, 4, fused=fused, moments=fused)
        ratio = t_q.weight_bytes / t_f.weight_bytes
        assert ratio <= 0.35, f"fused={fused}: ratio {ratio:.4f}"
        # activations and flops are precision-independent
        assert t_q.act_bytes == t_f.act_bytes
        assert t_q.flops == t_f.flops


def test_fp32_traffic_pricing_unchanged():
    """Default-precision pricing must reduce to the pre-quantization
    formula exactly — hand-check one SharedDense + PackedPair chain."""
    plan, _ = _ffn_plan(4)
    tm = plan.traffic(64, 2, fused=True, moments=True)
    n = plan.sample_axis
    want_w = 0
    for op in plan.pairs:
        want_w += n * (op.d_in * op.keep + op.keep * op.d_out
                       + op.keep + op.d_out) * 2
    assert tm.weight_bytes == want_w


def test_dispatch_counter_carries_precision_label():
    from repro.obs import registry as obs_registry
    from repro import compat
    c = obs_registry.REGISTRY.counter("kernel_dispatch_total",
                                      labels=("tier", "precision"))
    tier = compat.kernel_backend()
    plan, x = _ffn_plan(3, seed=11)       # unique spec: forces fresh traces
    pq = plan.with_precision(INT8)
    base_q = c.value(tier="xla", precision="int8")
    base_f = c.value(tier="xla", precision="fp32")
    plan_lib.execute_fused(pq, x, backend="xla")
    plan_lib.execute_fused(plan, x, backend="xla")
    assert c.value(tier="xla", precision="int8") == base_q + 1
    assert c.value(tier="xla", precision="fp32") == base_f + 1


# ---------------------------------------------------------------------------
# low-precision KV cache
# ---------------------------------------------------------------------------


def _smoke_cfg(**overrides):
    return registry.smoke_config("qwen2-1.5b", n_layers=2, **overrides)


def _prefill_pool(cfg, params, b, plen=6, max_seq=12, seed=1):
    fns = server_lib.step_fns(cfg, fused=False)
    prompts = jax.random.randint(jax.random.PRNGKey(seed), (b, plen), 0,
                                 cfg.vocab_size)
    n = fns.n_samples
    mean, _, caches = fns.prefill(params, jnp.tile(prompts, (n, 1)),
                                  max_seq=max_seq)
    return jnp.argmax(mean, -1).astype(jnp.int32), caches, plen


def _greedy(decode, params, caches, tok0, n, start, steps):
    caches = jax.tree.map(lambda x: x, caches)
    cur = tok0
    toks, rels = [], []
    for i in range(steps):
        rows_tok = jnp.tile(cur, (n,))[:, None]
        mean, rel, caches = decode(params, caches, rows_tok,
                                   jnp.int32(start + i))
        cur = jnp.argmax(mean, -1).astype(jnp.int32)
        toks.append(np.asarray(cur))
        rels.append(np.asarray(rel))
    return np.stack(toks), np.stack(rels), caches


@pytest.fixture(scope="module")
def qsmoke():
    cfg = _smoke_cfg()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def test_kv_cache_leaf_dtypes(qsmoke):
    for kvd, want in (("", jnp.float32), ("bfloat16", jnp.bfloat16),
                      ("int8", jnp.int8)):
        cfg = _smoke_cfg(kv_dtype=kvd)
        caches = transformer.init_cache(cfg, 4, 8)
        leaves = jax.tree_util.tree_leaves_with_path(caches)
        kinds = {str(p[-1]): leaf for p, leaf in leaves}
        assert kinds["['k']"].dtype == want and kinds["['v']"].dtype == want
        if kvd == "int8":
            assert kinds["['kscale']"].dtype == jnp.float32
            assert kinds["['kscale']"].shape == kinds["['k']"].shape[:-1]
        else:
            assert "['kscale']" not in kinds
        # specs must describe init exactly (the server allocates from specs)
        for (_, a), (_, b) in zip(
                leaves, jax.tree_util.tree_leaves_with_path(
                    transformer.cache_specs(cfg, 4, 8))):
            assert a.dtype == b.dtype and a.shape == b.shape


@pytest.mark.parametrize("kv_dtype", ("bfloat16", "int8"))
def test_per_op_decode_low_precision_kv(kv_dtype, qsmoke):
    """Per-op decode with a compressed cache stays token-identical to the
    fp32-cache path on the smoke model, with small rel-uncertainty drift."""
    _, _, params = qsmoke
    cfg0 = _smoke_cfg()
    tok_f, caches, start = _prefill_pool(cfg0, params, b=3)
    perop = server_lib.step_fns(cfg0, fused=False).decode
    t_ref, r_ref, _ = _greedy(perop, params, caches, tok_f, cfg0.mask_samples,
                              start, 4)
    cfg = _smoke_cfg(kv_dtype=kv_dtype)
    tok_q, caches_q, start = _prefill_pool(cfg, params, b=3)
    perop_q = server_lib.step_fns(cfg, fused=False).decode
    t_q, r_q, _ = _greedy(perop_q, params, caches_q, tok_q, cfg.mask_samples,
                          start, 4)
    np.testing.assert_array_equal(t_q, t_ref)
    tol = 5e-4 if kv_dtype == "int8" else 2e-4
    np.testing.assert_allclose(r_q, r_ref, atol=tol)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_decode_bf16_kv_matches_per_op(backend, qsmoke):
    """bf16 KV rides the FUSED decode step: tokens bitwise vs per-op (both
    read the same bf16 cache); committed caches agree to 1 bf16 ulp (the
    two paths' fresh k/v differ by f32 rounding before the bf16 cast)."""
    _, _, params = qsmoke
    cfg = _smoke_cfg(kv_dtype="bfloat16")
    tok0, caches, start = _prefill_pool(cfg, params, b=3)
    perop = server_lib.step_fns(cfg, fused=False).decode
    fused = plan_lib.compile_decode_step(cfg, backend=backend)
    n = cfg.mask_samples
    t_ref, r_ref, c_ref = _greedy(perop, params, caches, tok0, n, start, 4)
    t_fus, r_fus, c_fus = _greedy(fused, params, caches, tok0, n, start, 4)
    np.testing.assert_array_equal(t_fus, t_ref)
    # rel-uncertainty drift widens a decade vs the fp32-cache grid: both
    # paths round the cache to bf16, but reduce the scores in different
    # orders from those coarser values
    np.testing.assert_allclose(r_fus, r_ref, atol=1e-4)
    assert plan_lib.decode_fused_spec(cfg).kv_dtype == "bfloat16"
    for a, b in zip(jax.tree.leaves(c_fus), jax.tree.leaves(c_ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-2, atol=1e-2)


def test_int8_kv_has_no_fused_lowering(qsmoke):
    cfg = _smoke_cfg(kv_dtype="int8")
    with pytest.raises(plan_lib.FusedPlanUnsupported, match="int8 KV"):
        plan_lib.decode_fused_spec(cfg)
    fns = server_lib.step_fns(cfg)          # fused=None degrades per-op
    assert fns.fused_spec is None


def test_server_kv_dtype_knob(qsmoke):
    """ServerConfig.kv_dtype compresses the pool cache without changing
    greedy tokens on the smoke model; '' inherits the model config."""
    cfg, model, params = qsmoke
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (3, 6),
                                            0, cfg.vocab_size))

    def run(kvd):
        srv = BayesianLMServer(model, params, ServerConfig(
            max_slots=2, max_prompt_len=8, max_new_tokens=4, fused=False,
            kv_dtype=kvd))
        rids = [srv.submit(p) for p in prompts]
        srv.run()
        return [srv.result(r) for r in rids], srv

    want, _ = run("")
    for kvd in ("bfloat16", "int8"):
        got, srv = run(kvd)
        assert srv.model_cfg.kv_dtype == kvd
        k = jax.tree_util.tree_leaves_with_path(srv._caches)
        assert any(str(p[-1]) == "['k']" and leaf.dtype ==
                   (jnp.int8 if kvd == "int8" else jnp.bfloat16)
                   for p, leaf in k)
        for g, w in zip(got, want):
            assert g.generated == w.generated
            np.testing.assert_allclose(g.uncertainty, w.uncertainty,
                                       atol=5e-4)
    # inheritance: a model-level kv_dtype survives the server default ""
    bf_model = build_model(_smoke_cfg(kv_dtype="bfloat16"))
    srv = BayesianLMServer(bf_model, params, ServerConfig(
        max_slots=2, max_prompt_len=8, max_new_tokens=2, fused=False))
    assert srv.model_cfg.kv_dtype == "bfloat16"


def test_cache_trim_clears_scale_leaves(qsmoke):
    _, _, params = qsmoke
    cfg = _smoke_cfg(kv_dtype="int8")
    _, caches, _ = _prefill_pool(cfg, params, b=2, plen=5, max_seq=10)
    trimmed = transformer.cache_trim_positions(caches, jnp.int32(3))
    for path, leaf in jax.tree_util.tree_leaves_with_path(trimmed):
        nm = str(path)
        if "kscale" in nm or "vscale" in nm:       # [reps, B, smax, hkv]
            assert np.all(np.asarray(leaf)[:, :, 3:] == 0), nm
            assert np.any(np.asarray(leaf)[:, :, :3] != 0), nm


def test_decode_stage_traffic_kv_dtype_pricing(qsmoke):
    """Per-dtype stage pricing: the stage split still sums field-for-field
    to decode_traffic (the test_obs invariant) at every kv_dtype, and a
    bf16 cache halves only the attn stage's KV term at f32 pricing."""
    def stages_of(kvd):
        spec = plan_lib.decode_fused_spec(_smoke_cfg(
            kv_dtype=kvd, packed_ffn_serving=False))
        return spec, plan_lib.decode_stage_traffic(spec, 16, 24, 4)

    spec_f, st_f = stages_of("")
    spec_b, st_b = stages_of("bfloat16")
    for spec, st in ((spec_f, st_f), (spec_b, st_b)):
        total = plan_lib.decode_traffic(spec, 16, 24, 4)
        for field in ("weight_bytes", "act_bytes", "flops", "weight_loads"):
            assert sum(getattr(t, field) for t in st.values()) \
                == getattr(total, field), field
    assert st_b["attn"].weight_bytes < st_f["attn"].weight_bytes
    for kind in ("norm", "ffn", "dense", "interstage"):
        assert st_b[kind] == st_f[kind]


def test_model_config_rejects_unknown_kv_dtype():
    with pytest.raises(ValueError, match="kv_dtype"):
        _smoke_cfg(kv_dtype="fp8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServerConfig(kv_dtype="fp8")


# ---------------------------------------------------------------------------
# compressed_allreduce: integer lanes on the wire (satellite fix)
# ---------------------------------------------------------------------------


def test_compressed_allreduce_reduces_int32():
    """The psum must run over int32 lanes (the compression exists on the
    wire), members must agree on one shared scale, and the result must
    approximate the exact f32 psum."""
    from test_distributed import run_subprocess
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.distributed import compression

mesh = compat.make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 32), jnp.float32)

fn = jax.jit(compat.shard_map(
    lambda v: compression.compressed_allreduce(v[0], "data"),
    mesh=mesh, in_specs=P("data"), out_specs=P()))
got = np.asarray(fn(x))
want = np.asarray(x.sum(0))
# shared-grid rounding: <= half an int8 step per member, 8 members
step = np.abs(np.asarray(x)).max() / 127.0
assert np.abs(got - want).max() <= 8 * 0.5 * step + 1e-6, \\
    (np.abs(got - want).max(), step)

import re
hlo = fn.lower(x).compile().as_text()
# result dtypes of the actual all-reduce instructions
red = re.findall(r"=\\s*(\\S+?)\\{[^ ]*\\s+all-reduce", hlo)
assert any(t.startswith("s32[4,32]") for t in red), red
# the payload-shaped reduction must be integer-only: an f32 all-reduce of
# the [4,32] gradient shape would mean the wire still moves full precision
assert not any(t.startswith("f32[4,32]") for t in red), red
print("I32_PSUM_OK")
"""
    assert "I32_PSUM_OK" in run_subprocess(code)
