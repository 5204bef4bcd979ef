"""A whole run at smoke size on the CPU, past the harness's look for a
chip: sound, it comes out correct; with the timed path broken underneath,
``correct`` comes out false. The faults are those a serving cell can have:
a step that returns its state unchanged, and a token or an answer altered
where it is produced, and half of the mask samples left out with the
posterior taken over the rest (the serving form of half a batch left out).
(A lost exchange between chips is a multi-chip fault; these cells run on
one chip.)"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, run as bench_run

BENCH = harness.benchmark()
PEAKS = harness.peaks("TPU v5 lite")


def _lm_info(cell="qwen2-1.5b.chat"):
    info = harness.resolve(BENCH, cell)
    info["config"] = dict(info["config"], hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          head_dim=16, vocab_size=256)
    info["traffic"] = dict(
        info["traffic"], warmup_s=0.5, arrivals={"kind": "poisson",
                                                 "rate": 6.0},
        prompt={"median": 12, "sigma": 0.8, "min": 4, "max": 40},
        output={"median": 8, "sigma": 0.5, "min": 2, "max": 16},
        pool={"max_slots": 4, "max_prompt_len": 40, "max_new_tokens": 16,
              "max_queue": 64}, check={"requests": 6})
    return info


def _ivim_info():
    info = harness.resolve(BENCH, "ivim-clinical.scan")
    info["traffic"] = dict(info["traffic"], volume=[16, 16, 4],
                           warmup_scans=1)
    return info


def _measure(info, seed=2 ** 31 + 9):
    return bench_run.measure(info, seed, 1.5, False, jax.devices(), PEAKS)


def _with_fault(monkeypatch, info, fault):
    driver = harness.load_module(info["driver"])
    setup = driver.setup

    def broken_setup(*a, **k):
        cell = setup(*a, **k)
        fault(cell)
        return cell

    monkeypatch.setattr(driver, "setup", broken_setup)
    return _measure(info)


def test_sound_runs_are_correct():
    for info in (_lm_info(), _ivim_info()):
        r = _measure(info)
        assert r["correct"], r["checks"]
        assert r["attempted"] > 0 and r["failed"] == 0
        assert list(r)[-1] == "checks"


def _state_unchanged(cell):
    server = cell["server"]
    decode = server.steps.decode

    def stale(params, caches, tokens, pos):
        mean, rel, _ = decode(params, caches, tokens, pos)
        return mean, rel, caches

    server.steps = dataclasses.replace(server.steps, decode=stale)


def _token_altered(cell):
    server = cell["server"]
    absorb = server._absorb

    def altered(st, next_tok, rel):
        return absorb(st, (next_tok + 1) % 256, rel)

    server._absorb = altered


def _lm_half_masks(cell):
    """Rows of the second half of the masks run the first half's masks: the
    posterior is the mean over half the samples, counted twice."""
    server = cell["server"]
    seg = server.params["segments"][0]
    ffn = seg["b0"]["ffn"]
    n = ffn["masks"].shape[1]
    m = ffn["masks"].at[:, n // 2:].set(ffn["masks"][:, :n // 2])
    b0 = dict(seg["b0"], ffn=dict(ffn, masks=m))
    server.params = dict(server.params, segments=[dict(seg, b0=b0)]
                         + list(server.params["segments"][1:]))


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _lm_half_masks],
                         ids=["state_unchanged", "token_altered",
                              "half_masks"])
def test_lm_faults_are_not_correct(monkeypatch, fault):
    r = _with_fault(monkeypatch, _lm_info(), fault)
    assert not r["correct"], r["checks"]


def _answer_altered(cell):
    driver = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                              "ivim_scan.py"))
    serve = driver.serve

    def altered(plan, volume):
        mean, std = serve(plan, volume)
        mean, std = mean.copy(), std.copy()
        # one voxel's answer for S0 off by an eighth of its range
        mean[0, 0, 0, 3] += 0.05
        std[0, 0, 0, 3] += 0.05
        return mean, std

    driver.serve = altered          # the test's monkeypatch restores it


def _masks_rolled(cell):
    plan = cell["plan"]
    body = dict(plan.params["body"])
    body["w1p"] = np.roll(np.asarray(body["w1p"]), 1, axis=0)
    cell["plan"] = dataclasses.replace(plan, params=dict(plan.params,
                                                         body=body))


def _ivim_half_masks(cell):
    """Each sub-network's second half of mask samples runs the first
    half's weights: the moments are over half the samples."""
    plan = cell["plan"]
    n = plan.n_masks

    def dup(a):
        a = np.asarray(a)
        s = a.reshape((-1, n) + a.shape[1:]).copy()
        s[:, n // 2:] = s[:, :n // 2]
        return jnp.asarray(s.reshape(a.shape))

    params = {"body": {k: dup(v) for k, v in plan.params["body"].items()},
              "head": dict(plan.params["head"],
                           wp=dup(plan.params["head"]["wp"]))}
    cell["plan"] = dataclasses.replace(plan, params=params)


def _compiles_in_window(cell):
    """A program of a new shape compiled with each scan of the window."""
    driver = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                              "ivim_scan.py"))
    serve = driver.serve
    n = [0]

    def compiling(plan, volume):
        n[0] += 1
        jax.jit(lambda x: x * 2.0)(jnp.zeros(n[0] + 7)).block_until_ready()
        return serve(plan, volume)

    driver.serve = compiling          # the test's monkeypatch restores it


@pytest.mark.parametrize("fault", [_answer_altered, _masks_rolled,
                                   _ivim_half_masks, _compiles_in_window],
                         ids=["answer_altered", "masks_rolled",
                              "half_masks", "compiles_in_window"])
def test_ivim_faults_are_not_correct(monkeypatch, fault):
    info = _ivim_info()
    driver = harness.load_module(info["driver"])
    monkeypatch.setattr(driver, "serve", driver.serve)
    r = _with_fault(monkeypatch, info, fault)
    assert not r["correct"], r["checks"]
    if fault is _compiles_in_window:
        assert r["checks"]["window_compiles"]["value"] > 0, r["checks"]
