"""The plain references agree with the program at smoke size on the CPU,
and a dropped or rolled mask takes them far off it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import ivim as ivim_ref
from bench.reference import masks as masks_ref
from bench.reference import qwen2 as qwen2_ref

IVIM_DRIVER = harness.load_module(
    harness.os.path.join(harness.BENCH, "drivers", "ivim_scan.py"))
LM_DRIVER = harness.load_module(
    harness.os.path.join(harness.BENCH, "drivers", "lm_serve.py"))


@pytest.mark.parametrize("width,n,scale,seed", [
    (11, 4, 2.0, 0), (11, 4, 2.0, 1), (104, 4, 2.0, 0), (128, 8, 3.0, 7),
    (8960, 4, 2.0, 0), (16, 4, 1.0, 0)])
def test_masks_are_the_programs(width, n, scale, seed):
    from repro.core import masks as program_masks
    spec = program_masks.MaskSpec(width, n, scale, seed)
    np.testing.assert_array_equal(masks_ref.masks(width, n, scale, seed),
                                  program_masks.generate_masks(spec))
    assert masks_ref.keep_count(width, n, scale) == spec.keep


def _ivim_setup():
    config = harness.read_json(harness.os.path.join(
        harness.BENCH, "configs", "ivim-clinical.json"))
    mix = dict(harness.read_json(harness.os.path.join(
        harness.BENCH, "traffic", "scan.json")), volume=[8, 8, 4],
        n_volumes=1)
    weights = IVIM_DRIVER.make_weights(config, 3)
    x = IVIM_DRIVER.make_volumes(config, mix, 3)[0].reshape(
        -1, len(config["b_values"]))
    return config, weights, x


def _ivim_program(config, weights, x):
    from repro.ivim import model as ivim_model
    cfg, params, state = IVIM_DRIVER.to_program(config, weights)
    plan = ivim_model.pack_for_serving(cfg, params, state)
    return ivim_model.predict(cfg, params, state, jnp.asarray(x)), plan


def test_ivim_reference_agrees_with_the_program():
    config, weights, x = _ivim_setup()
    (mean, std), plan = _ivim_program(config, weights, x)
    from repro.serving import engine
    pm, ps = engine.predict_packed(plan, jnp.asarray(x))
    rm, rs = ivim_ref.moments(weights, x, config["out_ranges"])
    span = np.ptp(np.asarray(config["out_ranges"]), axis=1)
    for got in ((mean, std), (pm, ps)):
        assert np.max(np.abs(np.asarray(got[0]) - rm) / span) < 1e-5
        assert np.max(np.abs(np.asarray(got[1]) - rs) / span) < 1e-5


@pytest.mark.parametrize("fault", ["rolled", "dropped"])
def test_ivim_reference_sees_a_wrong_mask(fault):
    config, weights, x = _ivim_setup()
    rm, rs = ivim_ref.moments(weights, x, config["out_ranges"])
    bad = dict(weights)
    if fault == "rolled":
        bad["mask2"] = jnp.roll(weights["mask2"], 1, axis=0)
    else:
        bad["mask1"] = weights["mask1"].at[0].set(1.0)
    fm, fs = ivim_ref.moments(bad, x, config["out_ranges"])
    span = np.ptp(np.asarray(config["out_ranges"]), axis=1)
    err = max(np.max(np.abs(fm - rm) / span), np.max(np.abs(fs - rs) / span))
    assert err > 1e-2


def _lm_setup(n_layers=2):
    config = dict(harness.read_json(harness.os.path.join(
        harness.BENCH, "configs", "qwen2-1.5b.json")),
        hidden_size=64, intermediate_size=128, num_hidden_layers=n_layers,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        vocab_size=256)
    cfg = dataclasses.replace(LM_DRIVER.model_config(config),
                              dtype=jnp.float32)
    weights = LM_DRIVER.make_weights(config, cfg, 5)
    return config, cfg, weights


def test_qwen2_reference_agrees_with_the_program():
    from repro.models import transformer
    config, cfg, weights = _lm_setup()
    tokens = np.random.default_rng(0).integers(0, 256, 37)
    n = config["mask_samples"]
    logits, _ = transformer.forward(
        cfg, weights, {"tokens": jnp.tile(jnp.asarray(tokens)[None], (n, 1))},
        mask_ids=jnp.arange(n))
    want = jax.nn.log_softmax(logits.astype(jnp.float32), -1)[:, 10:30]
    got = qwen2_ref.log_probs(weights, config, tokens, 10, 20)[:, :20]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("fault", ["rolled", "dropped"])
def test_qwen2_reference_sees_a_wrong_mask(fault):
    config, cfg, weights = _lm_setup()
    tokens = np.random.default_rng(1).integers(0, 256, 24)
    good = qwen2_ref.log_probs(weights, config, tokens, 0, 24)
    ffn = weights["segments"][0]["b0"]["ffn"]
    m = ffn["masks"]
    m = jnp.roll(m, 1, axis=1) if fault == "rolled" else m.at[:, 0].set(1.0)
    bad = jax.tree.map(lambda a: a, weights)
    bad["segments"][0]["b0"]["ffn"] = dict(ffn, masks=m)
    lp = qwen2_ref.log_probs(bad, config, tokens, 0, 24)
    assert float(jnp.max(jnp.abs(lp - good))) > 1e-2
