"""A whole Moonlight run at smoke size on the CPU, past the harness's look
for a chip: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false. First the faults only this block
can have: each token routed to one expert fewer than the configuration's
top-k (the gate renormalised over the rest, so no weight goes missing),
the rope part of the latent cache never stored (decode then scores cached
keys on content alone), and half of the mask samples left out (the second
half of the rows runs the first half's masks, on the dense FFN, the routed
and the shared experts). Then the faults every serving cell can have: a
decode step that returns its cache unchanged, and a token altered where
it is produced."""

import dataclasses

import jax
import pytest

from bench import harness, run as bench_run
from bench.tests import test_bench_moonlight as smoke

BENCH = harness.benchmark()
PEAKS = harness.peaks("TPU v5 lite")
CELL = "moonlight-16b-a3b.longqa"


def _info():
    info = harness.resolve(BENCH, CELL)
    info["config"] = smoke.smoke_config()
    info["traffic"] = dict(
        info["traffic"], warmup_s=0.5, arrivals={"kind": "poisson",
                                                 "rate": 6.0},
        prompt={"median": 12, "sigma": 0.8, "min": 4, "max": 40},
        output={"median": 8, "sigma": 0.5, "min": 2, "max": 16},
        pool={"max_slots": 4, "max_prompt_len": 40, "max_new_tokens": 24,
              "max_queue": 64}, check={"requests": 6})
    return info


def _measure(info, seed=2 ** 31 + 15):
    return bench_run.measure(info, seed, 1.5, False, jax.devices(), PEAKS)


@pytest.fixture
def driver(monkeypatch):
    """The cell's driver, its attributes restored after the test; the
    serving steps traced under a patched program are dropped with it."""
    from repro.core import plan as plan_lib
    from repro.serving import server as server_lib
    drv = harness.load_module(_info()["driver"])
    for name in ("setup", "model_config"):
        monkeypatch.setattr(drv, name, getattr(drv, name))
    yield drv
    server_lib._step_fns.cache_clear()
    plan_lib._prefill_runner.cache_clear()


def test_sound_run_is_correct():
    r = _measure(_info())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def _one_expert_fewer(drv, monkeypatch):
    model_config = drv.model_config

    def fewer(config):
        cfg = model_config(config)
        return dataclasses.replace(cfg, top_k=cfg.top_k - 1)

    drv.model_config = fewer


def _rope_not_stored(drv, monkeypatch):
    from repro.models import layers
    update = layers.latent_cache_update

    def no_rope(cache, new, pos):
        r = cache["latent"].shape[-1] - smoke.SMOKE["qk_rope_head_dim"]
        cache = dict(cache, latent=cache["latent"].at[..., r:].set(0))
        return update(cache, new.at[..., r:].set(0), pos)

    monkeypatch.setattr(layers, "latent_cache_update", no_rope)


def _half_masks(drv, monkeypatch):
    setup = drv.setup

    def halved(*a, **k):
        cell = setup(*a, **k)
        server = cell["server"]

        def dup(m):             # [reps, N, F]: rows N/2.. take the first half
            n = m.shape[1]
            return m.at[:, n // 2:].set(m[:, :n // 2])

        segs = []
        for seg in server.params["segments"]:
            b0 = dict(seg["b0"])
            if "ffn" in b0:
                b0["ffn"] = dict(b0["ffn"], masks=dup(b0["ffn"]["masks"]))
            if "moe" in b0:
                moe = dict(b0["moe"], masks=dup(b0["moe"]["masks"]))
                moe["shared"] = dict(moe["shared"],
                                     masks=dup(moe["shared"]["masks"]))
                b0["moe"] = moe
            segs.append(dict(seg, b0=b0))
        server.params = dict(server.params, segments=segs)
        return cell

    drv.setup = halved


def _state_unchanged(drv, monkeypatch):
    """The decode step hands back the cache it was given: no token's
    latent is ever stored past the prompt."""
    setup = drv.setup

    def stale_setup(*a, **k):
        cell = setup(*a, **k)
        server = cell["server"]
        decode = server.steps.decode

        def stale(params, caches, tokens, pos):
            mean, rel, _, routes = decode(params, caches, tokens, pos)
            return mean, rel, caches, routes

        server.steps = dataclasses.replace(server.steps, decode=stale)
        return cell

    drv.setup = stale_setup


def _token_altered(drv, monkeypatch):
    """Every token is altered where the step produces it."""
    setup = drv.setup

    def altered_setup(*a, **k):
        cell = setup(*a, **k)
        server = cell["server"]
        absorb, vocab = server._absorb, cell["cfg"].vocab_size

        def altered(st, next_tok, rel):
            return absorb(st, (next_tok + 1) % vocab, rel)

        server._absorb = altered
        return cell

    drv.setup = altered_setup


@pytest.mark.parametrize("fault", [_one_expert_fewer, _rope_not_stored,
                                   _half_masks, _state_unchanged,
                                   _token_altered],
                         ids=["top_k_minus_one", "rope_not_stored",
                              "half_masks", "state_unchanged",
                              "token_altered"])
def test_moonlight_faults_are_not_correct(driver, monkeypatch, fault):
    from repro.core import plan as plan_lib
    from repro.serving import server as server_lib
    server_lib._step_fns.cache_clear()
    plan_lib._prefill_runner.cache_clear()
    fault(driver, monkeypatch)
    r = _measure(_info())
    assert not r["correct"], r["checks"]
    assert r["checks"]["window_compiles"]["value"] == 0, r["checks"]
