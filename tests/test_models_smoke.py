"""Per-architecture smoke tests (deliverable f): every assigned arch at a
reduced same-family config runs one forward/train step on CPU with correct
shapes and no NaNs, plus prefill/decode parity with the training graph.
Masksembles (the paper's technique) is ON in every smoke config."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import SHAPES
from repro.configs.cells import enumerate_cells, skip_reason
from repro.models import build_model
from repro.optim import OptimizerConfig, build_optimizer
from repro.train import TrainConfig, make_train_step, train_state_init

ARCHS = registry.ARCH_IDS


def _batch(cfg, b=4, s=16, seed=0):
    key = jax.random.PRNGKey(seed)
    if cfg.embeds_input and cfg.family == "audio":
        return {"embeds": jax.random.normal(key, (b, s, cfg.d_model),
                                            cfg.dtype),
                "labels": jax.random.randint(key, (b, s), 0,
                                             cfg.vocab_size)}
    return {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
            "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = registry.smoke_config(arch)
    assert cfg.bayesian, "smoke configs must exercise the paper's technique"
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = model.forward(params, batch)
    assert logits.shape == (4, 16, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch):
    cfg = registry.smoke_config(arch)
    model = build_model(cfg)
    opt = build_optimizer(OptimizerConfig(lr=1e-3, warmup_steps=2,
                                          decay_steps=10))
    step = jax.jit(make_train_step(model, opt, TrainConfig()))
    state = train_state_init(model, opt, jax.random.PRNGKey(0))
    state, metrics = step(state, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    for leaf in jax.tree.leaves(state["params"]):
        assert bool(jnp.isfinite(leaf).all()), arch


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if registry.get_config(a).has_decode])
def test_prefill_decode_matches_forward(arch):
    cfg = registry.smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 4, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s + 1), 0,
                              cfg.vocab_size)
    logits_all, _ = model.forward(params, {"tokens": toks})
    lp, cache = model.prefill(params, {"tokens": toks[:, :s]}, max_seq=s + 2)
    np.testing.assert_allclose(np.asarray(lp),
                               np.asarray(logits_all[:, s - 1]),
                               rtol=5e-3, atol=5e-3)
    ld, _ = model.decode_step(params, cache, toks[:, s:s + 1], jnp.int32(s))
    np.testing.assert_allclose(np.asarray(ld), np.asarray(logits_all[:, s]),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("arch", ARCHS)
def test_masks_change_predictions_per_group(arch):
    """The paper's technique: different mask samples -> different outputs
    (otherwise uncertainty would be identically zero)."""
    cfg = registry.smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = cfg.mask_samples, 8
    batch = _batch(cfg, b=b, s=s, seed=2)
    batch.pop("labels")
    # identical rows, different mask groups
    same = jax.tree.map(lambda x: jnp.broadcast_to(x[:1], x.shape), batch)
    logits, _ = model.forward(params, same)
    spread = float(jnp.std(logits[:, -1], axis=0).mean())
    assert spread > 1e-6, "masks had no effect"


def test_cells_enumeration_counts():
    cells = enumerate_cells()
    assert len(cells) == 44
    skips = [c for c in cells if c.skip]
    # hubert decode+long, plus long_500k for 8 full-attention archs
    assert {(c.arch_id, c.shape.name) for c in skips} == {
        ("moonlight-16b-a3b", "long_500k"),
        ("hubert-xlarge", "decode_32k"), ("hubert-xlarge", "long_500k"),
        ("stablelm-12b", "long_500k"), ("qwen2-1.5b", "long_500k"),
        ("granite-20b", "long_500k"), ("deepseek-coder-33b", "long_500k"),
        ("phi3.5-moe-42b-a6.6b", "long_500k"), ("arctic-480b", "long_500k"),
        ("qwen2-vl-72b", "long_500k"),
    }
    # sub-quadratic archs DO run long_500k
    assert not skip_reason("recurrentgemma-2b", SHAPES["long_500k"])
    assert not skip_reason("xlstm-350m", SHAPES["long_500k"])


def test_full_configs_match_assignment():
    """Spot-check the exact public numbers from the assignment table."""
    c = registry.get_config("deepseek-coder-33b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads, c.d_ff,
            c.vocab_size) == (62, 7168, 56, 8, 19200, 32256)
    c = registry.get_config("arctic-480b")
    assert (c.n_experts, c.top_k, c.moe_dense_residual) == (128, 2, True)
    c = registry.get_config("qwen2-vl-72b")
    assert c.m_rope_sections == (16, 24, 24) and c.n_layers == 80
    c = registry.get_config("recurrentgemma-2b")
    assert c.local_window == 2048 and c.family == "hybrid"
    c = registry.get_config("hubert-xlarge")
    assert not c.causal and c.embeds_input
    c = registry.get_config("xlstm-350m")
    assert c.d_ff == 0 and c.family == "ssm"


def test_param_counts_sane():
    """param_count() should land within ~35% of the nameplate size."""
    expected = {"qwen2-1.5b": 1.5e9, "deepseek-coder-33b": 33e9,
                "granite-20b": 20e9, "arctic-480b": 480e9,
                "qwen2-vl-72b": 72e9, "stablelm-12b": 12e9}
    for arch, want in expected.items():
        got = registry.get_config(arch).param_count()
        assert 0.65 * want < got < 1.45 * want, (arch, got, want)


def test_packed_ffn_serving_exact():
    """The paper's mask-zero skipping at transformer scale: converting a
    trained masked-FFN checkpoint to per-sample packed weights must be
    numerically exact (zero-preserving activations)."""
    import dataclasses

    from repro.models import transformer

    cfg = registry.smoke_config("qwen2-1.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n, b0, s = cfg.mask_samples, 3, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (n * b0, s), 0,
                              cfg.vocab_size)
    mask_ids = jnp.repeat(jnp.arange(n), b0)
    want, _ = transformer.forward(cfg, params, {"tokens": toks},
                                  mask_ids=mask_ids)
    cfg_p = dataclasses.replace(cfg, packed_ffn_serving=True)
    params_p = transformer.pack_ffn_params(cfg, params)
    got, _ = transformer.forward(cfg_p, params_p, {"tokens": toks},
                                 mask_ids=mask_ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # packed hidden width strictly smaller (FLOPs shrink)
    ffn = params_p["segments"][0]["b0"]["ffn"]
    assert ffn["wgp"].shape[-1] < cfg.d_ff


def test_seq_shard_configs_are_identity_on_cpu():
    """seq_shard / bf16-scores / packed flags must not change single-device
    numerics (constraints are identity without a mesh)."""
    import dataclasses

    cfg = registry.smoke_config("qwen2-1.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    base, _ = model.forward(params, batch)
    cfg2 = dataclasses.replace(cfg, seq_shard=True)
    got, _ = build_model(cfg2).forward(params, batch)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


def test_vlm_positions_input():
    """qwen2-vl prefill accepts M-RoPE positions [3, B, S]."""
    cfg = registry.smoke_config("qwen2-vl-72b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 8
    batch = {"embeds": jnp.ones((b, s, cfg.d_model), cfg.dtype),
             "positions": jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                           (3, b, s))}
    logits, cache = model.prefill(params, batch)
    assert logits.shape == (b, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("rel", [-2, -1, 0, 1, 16, 19])
def test_local_attention_window_boundary_prefill_decode(rel):
    """Pin the local-attention boundaries: prompts at s ∈ {w-2, w-1, w, w+1,
    2w, 2w+3} prefill to a cache that decodes exactly like the full
    (window-masked) attention graph — the s < window, s == window and
    s > window cases share one slot = pos % smax cache layout, and the
    banded-vs-full attention split at s > window is value-equivalent."""
    cfg = registry.smoke_config("recurrentgemma-2b")
    w = cfg.local_window
    s = w + rel
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, s), 0,
                              cfg.vocab_size)
    lp, caches = model.prefill(params, {"tokens": toks}, max_seq=s + 4)
    full, _ = model.forward(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(lp), np.asarray(full[:, -1]),
                               rtol=5e-3, atol=5e-3)
    # decode across the window boundary: every step must match the
    # teacher-forced full-attention forward at the same length
    cur = jnp.argmax(lp, -1).astype(jnp.int32)
    seq = jnp.concatenate([toks, cur[:, None]], 1)
    for i in range(3):
        ld, caches = model.decode_step(params, caches, cur[:, None],
                                       jnp.int32(s + i))
        ref, _ = model.forward(params, {"tokens": seq})
        np.testing.assert_allclose(np.asarray(ld), np.asarray(ref[:, -1]),
                                   rtol=5e-3, atol=5e-3)
        cur = jnp.argmax(ld, -1).astype(jnp.int32)
        seq = jnp.concatenate([seq, cur[:, None]], 1)


def test_local_attention_rolling_cache_slot_invariant():
    """The prefill cache layout IS kv_cache_update's invariant: every kept
    position p sits at slot p % smax, for prompts shorter, equal to, and
    longer than the window."""
    cfg = registry.smoke_config("recurrentgemma-2b")
    w = cfg.local_window
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    for s in (w - 3, w, w + 5):
        toks = jax.random.randint(jax.random.PRNGKey(2), (1, s), 0,
                                  cfg.vocab_size)
        _, caches = model.prefill(params, {"tokens": toks}, max_seq=s + 2)
        # hybrid smoke: segment 0 block b2 is the local_attn layer
        kpos = np.asarray(caches[0]["b2"]["kpos"][0, 0])     # [smax]
        smax = kpos.shape[0]
        assert smax == min(w, s + 2)
        for slot, p in enumerate(kpos):
            if p >= 0:
                assert slot == p % smax, (s, slot, p)
        kept = sorted(p for p in kpos if p >= 0)
        assert kept == list(range(max(0, s - smax), s))


def test_mrope_sections_must_partition_rot_dim():
    """Bad M-RoPE sections raise a loud ValueError (was a bare assert)."""
    from repro.models import layers
    pos = jnp.zeros((3, 4))
    with pytest.raises(ValueError, match="must sum to rot_dim/2"):
        layers.mrope_cos_sin(pos, rot_dim=8, theta=1e4, sections=(1, 1))
