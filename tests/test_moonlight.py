"""Moonlight-16B-A3B (the DeepSeek-V3 block) in the program: its published
sizes, latent attention's two paths, the dropless grouped dispatch, the
bucketed prefill of an MoE+MLA config, and its serving telemetry. The
comparison with the plain reference is in bench/tests/test_bench_moonlight.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import plan as plan_lib
from repro.models import build_model, layers, moe as moe_lib, transformer
from repro.obs import registry as obs_registry
from repro.obs import trace as obs_trace
from repro.serving import BayesianLMServer, ServerConfig
from repro.serving.server import step_fns

ARCH = "moonlight-16b-a3b"


@pytest.fixture(scope="module")
def smoke():
    cfg = registry.smoke_config(ARCH)
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


def test_published_sizes():
    """27 layers: 15.96 G parameters, 2.91 G active per token (top-6 of 64
    routed experts + 2 shared), 2.24 G of them outside the embedding and
    the untied head; the 5-layer first stage is 3.09 G (6.19 GB bf16)."""
    cfg = registry.get_config(ARCH)
    embed = 2 * cfg.vocab_size * cfg.d_model
    assert cfg.param_count() == pytest.approx(15.96e9, abs=0.005e9)
    assert cfg.active_param_count() == pytest.approx(2.915e9, abs=0.005e9)
    assert cfg.active_param_count() - embed == pytest.approx(2.24e9,
                                                            abs=0.005e9)
    stage = dataclasses.replace(cfg, n_layers=5)
    assert 2 * stage.param_count() == pytest.approx(6.19e9, abs=0.005e9)
    # the per-layer pieces of that count, by hand
    assert cfg.attn_param_count() == (2048 * 16 * 192 + 2048 * 576 + 512
                                      + 512 * 16 * 256 + 16 * 128 * 2048)
    assert [(s.pattern, s.reps) for s in cfg.segments()] == [
        (("attn",), 1), (("moe",), 26)]


def test_smoke_config_keeps_every_kind_of_layer(smoke):
    cfg, params = smoke
    assert cfg.mla and cfg.first_dense_layers == 1
    assert [s.pattern for s in cfg.segments()] == [("attn",), ("moe",)]
    assert cfg.n_experts >= 8 and cfg.top_k >= 2 and cfg.n_shared_experts
    assert cfg.router == "sigmoid_bias" and cfg.moe_dropless
    moe = params["segments"][1]["b0"]["moe"]
    assert {"router", "router_bias", "weg", "shared", "masks"} <= set(moe)
    assert "ffn" in params["segments"][0]["b0"]
    assert set(params["segments"][0]["b0"]["attn"]) == {
        "wq", "wkv_a", "kv_norm", "wkv_b", "wo"}


def test_absorbed_decode_equals_expanded_attention(smoke):
    """Decode scores q_nope.W_uk^T against the cached c_kv and never forms
    K/V; expanding the same latent cache to per-head K/V and attending
    gives the same output (f32: only the summation order differs)."""
    cfg, params = smoke
    a = jax.tree.map(lambda x: x[0], params["segments"][0]["b0"]["attn"])
    b, smax, h = 3, 12, cfg.n_heads
    width = cfg.kv_lora_rank + cfg.qk_rope_dim
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    lat = jax.random.normal(ks[0], (b, smax, width))
    q_nope = jax.random.normal(ks[1], (b, h, 1, cfg.qk_nope_dim))
    q_rope = jax.random.normal(ks[2], (b, h, 1, cfg.qk_rope_dim))
    pos = jnp.asarray([4, 11, 0], jnp.int32)
    kpos = jnp.where(jnp.arange(smax)[None] <= pos[:, None],
                     jnp.arange(smax)[None], -1)
    got = layers.mla_decode(a, q_nope, q_rope, lat, kpos, pos, cfg)
    k, v = layers.mla_expand(a, lat, cfg)           # [B,H,S,*]
    want = layers.attention_decode(jnp.concatenate([q_nope, q_rope], -1),
                                   k.swapaxes(1, 2), v.swapaxes(1, 2),
                                   kpos, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _per_token_moe(p, x, cfg, mask_ids):
    """Each token's top-k experts computed one token at a time."""
    act = jax.nn.silu
    out = np.zeros(x.shape, np.float64)
    topi, topv, _ = moe_lib.route(p, x, cfg)
    for bi in range(x.shape[0]):
        m = p["masks"][mask_ids[bi]]
        for si in range(x.shape[1]):
            xt = x[bi, si]
            for e, g in zip(np.asarray(topi[bi, si]),
                            np.asarray(topv[bi, si])):
                h = act(xt @ p["weg"][e]) * (xt @ p["weu"][e]) * m
                out[bi, si] += g * np.asarray(h @ p["wed"][e], np.float64)
    return out


@pytest.mark.parametrize("one_expert_set", [False, True],
                         ids=["routed", "all_to_the_same_experts"])
def test_dropless_dispatch_is_per_token_top_k(smoke, one_expert_set):
    """Sorted grouped dispatch = each token's own top-k sum; no pair is
    dropped, even where every token picks the same k experts (the bias,
    which only selects, forces it) and the other groups are empty."""
    cfg, params = smoke
    p = jax.tree.map(lambda x: x[0], params["segments"][1]["b0"]["moe"])
    p = {k: v for k, v in p.items() if k != "shared"}
    if one_expert_set:
        p["router_bias"] = jnp.zeros(cfg.n_experts).at[:cfg.top_k].set(1e3)
    x = jax.random.normal(jax.random.PRNGKey(7), (4, 9, cfg.d_model))
    mask_ids = jnp.arange(4)
    y, _, counts = moe_lib.moe_apply(p, x, cfg, mask_ids=mask_ids)
    want = _per_token_moe(p, x, cfg, mask_ids)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
    counts = np.asarray(counts)
    assert counts.sum() == 4 * 9 * cfg.top_k
    if one_expert_set:
        assert (counts[:cfg.top_k] == 36).all()
        assert (counts[cfg.top_k:] == 0).all()


def test_dropless_rows_do_not_see_each_other(smoke):
    """A row's output is the same whatever rows share the batch, and rows
    outside ``valid`` route (and count) nothing."""
    cfg, params = smoke
    p = jax.tree.map(lambda x: x[0], params["segments"][1]["b0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 5, cfg.d_model))
    ids = jnp.arange(4)
    y, _, _ = moe_lib.moe_apply(p, x, cfg, mask_ids=ids)
    y1, _, c1 = moe_lib.moe_apply(p, x[:1], cfg, mask_ids=ids[:1],
                                  valid=jnp.arange(5)[None] < 3)
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(y[0]),
                               rtol=1e-6, atol=1e-6)
    assert int(np.asarray(c1).sum()) == 3 * cfg.top_k


def test_dropless_dispatch_pads_bitwise(smoke):
    """Pad positions route to no real row: a batch padded from 5 to 8
    positions (``valid`` marking the 5) gives the unpadded batch's outputs
    and counts bitwise, within one jitted program each."""
    cfg, params = smoke
    p = jax.tree.map(lambda x: x[0], params["segments"][1]["b0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 8, cfg.d_model))
    ids = jnp.arange(4)
    fn = jax.jit(lambda p, x, v: moe_lib.moe_apply(p, x, cfg, mask_ids=ids,
                                                   valid=v))
    yp, _, cp = fn(p, x, jnp.arange(8)[None] < 5)
    ye, _, ce = fn(p, x[:, :5], None)
    np.testing.assert_array_equal(np.asarray(yp[:, :5]), np.asarray(ye))
    np.testing.assert_array_equal(np.asarray(cp), np.asarray(ce))


def test_prefill_spec_admits_exact_padding_only():
    """The bucketed prefill's gate reads the cache layout: MLA latent and
    dropless MoE pad exactly; capacity MoE and rolling caches do not."""
    assert plan_lib.prefill_spec(registry.smoke_config(ARCH)).n_samples == 4
    assert plan_lib.prefill_spec(registry.smoke_config("qwen2-1.5b"))
    for arch in ("phi3.5-moe-42b-a6.6b", "recurrentgemma-2b", "xlstm-350m",
                 "hubert-xlarge", "qwen2-vl-72b"):
        with pytest.raises(plan_lib.FusedPlanUnsupported):
            plan_lib.prefill_spec(registry.smoke_config(arch))
    with pytest.raises(plan_lib.FusedPlanUnsupported):
        plan_lib.lower_fused_decode(registry.smoke_config(ARCH))


def test_bucketed_prefill_matches_exact_prefill(smoke):
    """A prompt padded to its bucket gives the exact-length prefill's
    logits and caches: where the bucket is the prompt's length the two are
    one program and agree bitwise; elsewhere attention reduces over the
    bucket's keys, so XLA may sum the same f32 terms in another order (as
    for GQA, tests/test_mixed_pool.py) -- a few ulps of values of order 1
    (read 2.3e-6; 1e-5), with positions (kpos) exact and the pad tail
    bitwise the empty cache. The MoE layers pad bitwise (above)."""
    cfg, params = smoke
    fb, fe = step_fns(cfg), step_fns(cfg, prefill_buckets=())
    assert fb.prefill_spec is not None and fe.prefill_spec is None
    for ln in (5, 8, 13):
        toks = jnp.asarray(np.random.default_rng(ln).integers(
            0, cfg.vocab_size, (1, ln))).repeat(4, 0)
        ob = fb.prefill(params, toks, max_seq=32)
        oe = fe.prefill(params, toks, max_seq=32)
        assert fb.prefill_bucket(ln, 32) >= ln
        assert len(ob) == len(oe) == 4      # with the per-expert counts
        for a, b in zip(jax.tree_util.tree_leaves(ob),
                        jax.tree_util.tree_leaves(oe)):
            a, b = np.asarray(a), np.asarray(b)
            if ln == fb.prefill_bucket(ln, 32) or a.dtype.kind == "i":
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        latent = np.asarray(ob[2][1]["b0"]["latent"])
        assert (latent[:, :, ln:] == 0).all()
        assert (np.asarray(ob[2][1]["b0"]["kpos"])[:, :, ln:] == -1).all()


def test_server_serves_moonlight_and_records_routing(smoke):
    """Through submit/step, by bucket: every MoE step span and prefill span
    carries experts_hit and expert_load_max, and the routed-pairs counter
    counts top_k pairs per live token and MoE layer."""
    cfg, params = smoke
    counter = obs_registry.REGISTRY.counter(
        "serving_moe_routed_pairs_total", labels=("layer",))
    before = counter.total()
    obs_trace.TRACER.enable()
    srv = BayesianLMServer(build_model(cfg), params, ServerConfig(
        max_slots=3, max_prompt_len=16, max_new_tokens=6))
    lens = (3, 7, 11, 4)
    for i, n in enumerate(lens):
        srv.submit(np.arange(n) % cfg.vocab_size, max_new_tokens=5)
    srv.run()
    assert all(len(srv.result(i).generated) == 5 for i in range(4))
    recs = [e for e in obs_trace.TRACER.events() if e["kind"] == "begin"]
    steps = [e["attrs"] for e in recs if e["name"] == "serving.step"
             and e["attrs"].get("lm")]
    prefills = [e["attrs"] for e in recs if e["name"] == "serving.prefill"]
    assert steps and len(prefills) == 4
    assert all(p["path"] == "bucketed" for p in prefills)
    n_moe = sum(s.reps for s in cfg.segments() if s.pattern == ("moe",))
    for attrs in steps + prefills:
        assert 1 <= attrs["experts_hit"] <= n_moe * cfg.n_experts
        assert attrs["expert_load_max"] >= 1.0
    # each request runs as N rows: prefills route the prompt's tokens,
    # each decode one token per live slot
    live_tokens = sum(lens) + sum(a["lm"] for a in steps)
    assert counter.total() - before == \
        cfg.mask_samples * live_tokens * cfg.top_k * n_moe
    assert set(counter.values) >= {("1",), ("2",)}


def test_chunked_prefill_attention_matches_full(smoke):
    """Past ``attn_chunk`` queries attend in chunks (what bounds the score
    matrix of an 8k prompt); the values' width (v_head_dim) differs from
    the queries' (nope + rope), and the chunked path keeps it."""
    cfg, params = smoke
    toks = jax.random.randint(jax.random.PRNGKey(4), (4, 24), 0,
                              cfg.vocab_size)
    full, _ = transformer.prefill(cfg, params, {"tokens": toks}, max_seq=32)
    chunked, caches = transformer.prefill(
        dataclasses.replace(cfg, attn_chunk=8), params, {"tokens": toks},
        max_seq=32)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               rtol=1e-5, atol=1e-5)
    assert caches[1]["b0"]["latent"].shape == (
        2, 4, 32, cfg.kv_lora_rank + cfg.qk_rope_dim)
