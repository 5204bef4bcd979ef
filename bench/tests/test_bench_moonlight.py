"""Moonlight's plain reference against the program at smoke size on the
CPU: a prompt prefilled by bucket and decoded through the slot pool gives
the reference's full-forward log-probs, and the int8 control does not;
the required-work counts against counts worked by hand."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import moonlight as moonlight_ref
from bench.work import moonlight as work

DRIVER = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                          "moonlight_serve.py"))
SMOKE = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
             n_routed_experts=8, num_hidden_layers=3, num_attention_heads=4,
             num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, vocab_size=256)
# f32 program against the f32 reference: the two paths differ by design
# (absorbed decode over the latent cache, expanded prefill; grouped
# matmuls over sorted pairs against each expert over every token), so
# they agree to f32 rounding: ~1e-6 read on log-probs of magnitude ~5.
# 1e-4 leaves that 70x room and is 1000x below what int8 reads (~0.1).
TOL = 1e-4


def smoke_config() -> dict:
    return dict(harness.read_json(os.path.join(
        harness.BENCH, "configs", "moonlight-16b-a3b.json")), **SMOKE)


@pytest.fixture(scope="module")
def served():
    """One request of 30 tokens, teacher-forced: its first 21 prefilled
    (bucket 32) into slot 1 of a 2-slot pool, the rest decoded one step at
    a time beside an empty slot. Returns the config, the weights, the
    tokens, the first position read and the served mean log-probs."""
    from repro.core import scheduler
    from repro.models import transformer
    from repro.serving.server import step_fns
    config = smoke_config()
    cfg = dataclasses.replace(DRIVER.model_config(config), dtype=jnp.float32)
    weights = DRIVER.make_weights(config, cfg, 5)
    fns = step_fns(cfg)
    n, slots, max_seq, prompt = cfg.mask_samples, 2, 64, 21
    sched = scheduler.SlotSchedule(n, slots)
    toks = np.random.default_rng(0).integers(0, config["vocab_size"], 30)
    assert fns.prefill_bucket(prompt, max_seq) == 32
    out = fns.prefill(weights, jnp.tile(jnp.asarray(toks[:prompt])[None],
                                        (n, 1)), max_seq=max_seq)
    pool = transformer.cache_scatter_rows(
        transformer.init_cache(cfg, sched.rows, max_seq), out[2],
        sched.rows_for_slot(1))
    means = [np.asarray(out[0][0])]
    for t in range(prompt, len(toks)):
        tok = np.zeros(slots, np.int32)
        pos = np.full(slots, -1, np.int32)
        tok[1], pos[1] = toks[t], t
        mean, _, pool, _ = fns.decode(
            weights, pool, sched.row_values(jnp.asarray(tok))[:, None],
            sched.row_values(jnp.asarray(pos)))
        means.append(np.asarray(mean[1]))
    return config, weights, toks, prompt - 1, np.stack(means)


def test_served_log_probs_match_the_reference(served):
    config, weights, toks, start, got = served
    lp = moonlight_ref.log_probs(weights, config, toks, start, len(got))
    want = np.asarray(lp.mean(0))[:len(got)]
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


def test_int8_control_fails_the_same_tolerance(served):
    config, weights, toks, start, got = served
    lp = moonlight_ref.log_probs(weights, config, toks, start, len(got),
                                 quant="int8")
    ctl = np.asarray(lp.mean(0))[:len(got)]
    assert np.abs(ctl - got).max() > 100 * TOL


def test_reference_sees_each_part_of_the_block(served):
    """Dropping the selection bias or the shared experts' masks, or
    rotating at another theta, moves the reference far past the
    tolerance."""
    config, weights, toks, start, got = served
    good = np.asarray(moonlight_ref.log_probs(weights, config, toks, start,
                                              len(got)))
    moe = weights["segments"][1]["b0"]["moe"]
    for bad_moe in (dict(moe, router_bias=jnp.zeros_like(moe["router_bias"])),
                    dict(moe, shared=dict(moe["shared"], masks=jnp.ones_like(
                        moe["shared"]["masks"])))):
        bad = jax.tree.map(lambda a: a, weights)
        bad["segments"][1]["b0"] = dict(bad["segments"][1]["b0"], moe=bad_moe)
        lp = np.asarray(moonlight_ref.log_probs(bad, config, toks, start,
                                                len(got)))
        assert np.abs(lp - good).max() > 100 * TOL
    shifted = dict(config, rope_theta=10.0 * config["rope_theta"])
    lp = np.asarray(moonlight_ref.log_probs(weights, shifted, toks, start,
                                            len(got)))
    assert np.abs(lp - good).max() > 100 * TOL


def test_the_driver_maps_every_published_key():
    config = harness.read_json(os.path.join(harness.BENCH, "configs",
                                            "moonlight-16b-a3b.json"))
    cfg = DRIVER.model_config(config)
    assert (cfg.n_layers, cfg.d_model, cfg.n_experts, cfg.top_k,
            cfg.moe_d_ff, cfg.n_shared_experts, cfg.kv_lora_rank,
            cfg.vocab_size) == (5, 2048, 64, 6, 1408, 2, 512, 163840)
    assert cfg.router == "sigmoid_bias" and cfg.moe_dropless
    assert cfg.routed_scaling == 2.446 and cfg.norm_eps == 1e-5
    with pytest.raises(ValueError):
        DRIVER.model_config(dict(config, q_lora_rank=1536))


C = {"hidden_size": 8, "num_attention_heads": 2, "qk_nope_head_dim": 4,
     "qk_rope_head_dim": 2, "v_head_dim": 4, "kv_lora_rank": 6,
     "num_hidden_layers": 2, "first_k_dense_replace": 1,
     "intermediate_size": 16, "moe_intermediate_size": 4,
     "n_routed_experts": 4, "num_experts_per_tok": 2, "n_shared_experts": 2,
     "vocab_size": 10, "mask_samples": 4, "mask_scale": 2.0}


def test_moonlight_work_by_hand():
    # keeps: round(16 / 1.875) = 9, round(4 / 1.875) = 2, round(8 / 1.875) = 4
    # attention: q 8*2*6 = 96, kv_a 8*8 = 64, kv_norm 6, kv_b 6*2*8 = 96,
    # o 2*4*8 = 64, norms 16 -> 342
    assert work.attn_params(C) == 342
    assert work.expert_params(C) == 3 * 8 * 4
    # dense layer 342 + 3*8*16 = 726; MoE layer 342 + shared 3*8*8 = 192
    # + router 8*4 + bias 4 -> 570; head 8*10 + final norm 8 = 88
    assert work.non_expert_bytes(C) == 2 * (726 + 570 + 88)
    # per row-token: proj 2*8*12 + 2*8*8 + 2*8*8 = 448 a layer; dense FFN
    # 6*8*9 = 432; routed 2 x 6*8*2 = 192, shared 6*8*4 = 192, router 64
    tok = (448 + 432) + (448 + 192 + 192 + 64)
    flops, nbytes = work.decode_step(C, rows=4, attended=40, experts_hit=3)
    # absorb 2*2*4*6 + 2*2*6*4 = 192 a layer; per position 2*2*8 + 2*2*6
    # = 56 a layer; head 2*8*10
    assert flops == 4 * (tok + 2 * 192 + 160) + 2 * 56 * 40
    lat = 2 * 8 * 2
    assert nbytes == 2 * 1384 + 2 * 4 * 8 + 3 * 96 * 2 + lat * 44
    pf, pb = work.prefill(C, 5)
    # expand 2*6*2*8 = 192 a token and layer; attention 2*2*6*15 + 2*2*4*15
    assert pf == 4 * (5 * (tok + 2 * 192) + 2 * (360 + 240) + 160)
    assert pb == 2 * 1384 + 4 * 96 * 2 + 2 * 4 * 5 * (8 + 2 * 8)


def test_every_per_layer_reader_reads_a_run():
    """A smoke run with the program's ring on, and a device trace reduced to
    its programs: each metric listed for the cell reads a value, and the
    shares stay within 100 %."""
    from bench import run as bench_run
    from bench.tests import test_bench_faults_moonlight as faults
    from repro.obs import trace as obs_trace
    info = faults._info()
    driver = harness.load_module(info["driver"])
    was_on = obs_trace.TRACER.enabled
    obs_trace.TRACER.enable()
    try:
        cell = driver.setup(info["config"], info["traffic"], 11)
        driver.prepare(cell, 2.0)
        rec = driver.run(cell, 2.0)
        steps = [s for s in rec["steps"] if s["live"]]
        # the least time the chip could take, taken as each program's time
        red = {"programs": {
            "jit_decode_impl": {"count": len(steps), "seconds": len(steps)
                                * work.seconds(work.decode_step(
                                    info["config"], 4 * 4, 4 * 4 * 40, 3 * 8),
                                    faults.PEAKS)},
            "jit_run": {"count": 3, "seconds": 3 * work.seconds(
                work.prefill(info["config"], 40), faults.PEAKS)}},
            "busy_s": 0.75, "window_s": 1.0}
        ctx = {"records": rec, "config": info["config"],
               "traffic": info["traffic"], "peaks": faults.PEAKS,
               "trace": red}
        got = bench_run.per_layer(info, ctx)
    finally:
        if not was_on:
            obs_trace.TRACER.disable()
            obs_trace.TRACER.clear()
    assert set(got) == {m["name"] for m in info["per_layer"]}
    assert set(got) >= {"lm.decode_step_ms", "lm.prefill_share",
                        "moonlight.device_idle", "moonlight.decode_roofline",
                        "moonlight.prefill_roofline",
                        "moonlight.expert_load_max"}
    assert got["moonlight.device_idle"]["value"] == pytest.approx(25.0)
    assert got["moonlight.expert_load_max"]["value"] >= 1.0
    for name in ("moonlight.decode_roofline", "moonlight.prefill_roofline"):
        assert 0 < got[name]["value"] <= 100.0, got
