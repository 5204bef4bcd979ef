"""Drive the serving system once on a TPU, at full width, and check what
comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the multi-host router
    python chip_smoke.py --seed 1       # other weights, data and prompts

One chip runs three phases in this one process:

1. device check: the platform must be ``tpu`` and the kernel tier
   ``pallas-tpu`` (a ``REPRO_KERNEL_BACKEND`` forcing another tier fails);
2. IVIM whole-volume serving, for the 11-value clinical and the 104-value
   dense b-value protocol with N = 4 masks: a few training steps on seeded
   synthetic voxels, ``pack_for_serving``, a 128x128x40 volume streamed
   through ``engine.predict_volume`` (the fused moments kernel) and checked
   against the XLA reference; the per-op ``masked_ffn`` path
   (``ivim.model.packed_apply``) checked the same way;
3. Bayesian LM serving of qwen2-1.5b at its published widths (bf16, N = 4,
   seeded random weights) through ``BayesianLMServer``: 8 requests with
   prompts of 32-256 tokens and 32 new tokens each, checked for finite
   outputs and against an uncached ``transformer.forward``.

``--four-chips`` runs only the router phase: the same qwen2-1.5b requests
through a 4-host ``ServingRouter`` (host i on chip i) and through one
server on chip 0; the tokens must be identical.

The last line of stdout is ``{"ok": true, "device": {...}}``, printed only
when every phase passed; any failure exits nonzero. The seconds printed on
the way are smoke set-up, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# IVIM: a clinical-size volume, streamed in the engine's default chunk.
VOLUME = (128, 128, 40)
CHUNK = 4096
IVIM_TRAIN_STEPS = 20
# The kernels' f32 dots run as bf16 MXU passes (operands rounded to 8
# significant bits) where the reference runs XLA at "highest" precision.
# The rounding reaches the pre-sigmoid sums of three dense layers, and C(.)
# scales the sigmoid (slope <= 1/4) by each parameter's range: on a v5e
# the gap was up to 8.04e-3 of the range for single mask samples and about
# half that for the moments, so the limit leaves 2.5x headroom for other
# seeds and volumes. Each phase also plants faults in the outputs and
# requires the limit to catch them: mask rows rolled by one (about 0.3 of
# the range) and one of the N masks dropped from the moments (4e-2 to 0.1
# of the range for seeds 0 and 1).
IVIM_RANGE_TOL = 2e-2

# LM: the request mix and pool of the smoke.
ARCH = "qwen2-1.5b"
N_MASKS = 4
PROMPT_LENS = (32, 48, 64, 96, 128, 160, 200, 256)
NEW_TOKENS = 32
SLOTS = 4
MAX_SEQ = 512
# The server (bucketed prefill, then decode over a bf16 KV cache) and the
# uncached forward are different bf16 programs. bf16 keeps 8 significant
# bits, so a log-prob near -12 is held to about +-0.03, and 28 layers of
# residual adds compound that. A served token must be the reference's
# argmax, or, where the reference's best two are within LP_TOL nats (a
# near-tie the rounding can swap), within LP_TOL of its best. The relative
# uncertainty u = std/|mean| of that token's log-prob over the masks (0.01
# to 0.1 here) must be within REL_TOL of the reference's, as a share of
# it. On a v5e at seed 0 the gap was 0.0235 of u, while dropping one of
# the N masks moved u by 0.152 and shifting the context by one position
# (a stale cache row) by 4.8: REL_TOL sits between, and both planted
# faults must exceed it.
LP_TOL = 0.1
REL_TOL = 0.08


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def setup_seconds(label: str, seconds: float) -> None:
    print(f"smoke set-up: {label}: {seconds:.2f} s")


def peak_memory(device) -> None:
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak device memory ({device}): "
              f"{stats['peak_bytes_in_use'] / 2 ** 30:.3f} GiB")


def _block(tree):
    import jax
    return jax.block_until_ready(tree)


# ---------------------------------------------------------------------------
# phase 1: the device
# ---------------------------------------------------------------------------

def device_check(min_devices: int):
    import jax
    from repro import compat
    devices = jax.devices()
    d0 = devices[0]
    print(f"jax {jax.__version__}")
    print(f"devices: {devices}")
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}")
    print(f"kernel_backend={compat.kernel_backend()}")
    check(d0.platform == "tpu", f"no TPU: JAX runs on {d0.platform}")
    check(compat.kernel_backend() == "pallas-tpu",
          f"kernel tier is {compat.kernel_backend()}, not pallas-tpu")
    check(len(devices) >= min_devices,
          f"{len(devices)} device(s), this phase needs {min_devices}")
    return devices


# ---------------------------------------------------------------------------
# phase 2: IVIM whole-volume serving
# ---------------------------------------------------------------------------

def _range_err(got, ref, ranges) -> float:
    """Largest |got - ref| as a share of each parameter's output range."""
    import numpy as np
    span = np.asarray([hi - lo for lo, hi in ranges], np.float64)
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    return float((diff / span).max())


def ivim_phase(name: str, b_values, *, volume=VOLUME, chunk=CHUNK,
               train_steps=IVIM_TRAIN_STEPS, seed=0) -> None:
    import jax
    import numpy as np
    from repro.core import plan as plan_lib
    from repro.core import uncertainty as unc_lib
    from repro.ivim import data as ivim_data
    from repro.ivim import model as ivim_model
    from repro.ivim import train as ivim_train
    from repro.obs import registry as obs_registry
    from repro.serving import engine

    nb = len(b_values)
    cfg = ivim_model.IvimConfig(b_values=tuple(b_values), n_masks=N_MASKS)
    t0 = time.perf_counter()
    ds = ivim_data.make_dataset(ivim_data.SyntheticConfig(
        n_voxels=8192, b_values=cfg.b_values, seed=seed))
    params, state, hist = ivim_train.train(
        cfg, ivim_train.TrainConfig(steps=train_steps, batch_size=256,
                                    lr=3e-3, seed=seed), dataset=ds)
    check(all(np.isfinite(hist)), f"ivim {name}: training loss not finite")
    plan = ivim_model.pack_for_serving(cfg, params, state)
    setup_seconds(f"ivim {name} train {train_steps} steps + pack",
                  time.perf_counter() - t0)
    print(f"ivim {name}: {nb} b-values, loss {hist[0]:.5f} -> "
          f"{hist[-1]:.5f}, {plan.sample_axis} kernel rows")

    n_vox = int(np.prod(volume))
    vol = ivim_data.make_dataset(ivim_data.SyntheticConfig(
        n_voxels=n_vox, b_values=cfg.b_values, seed=seed + 1)
    )["signals"].reshape(volume + (nb,))
    registry = obs_registry.REGISTRY
    fallbacks = registry.value("fused_fallback_total")
    spec = plan_lib.lower_fused(plan)[0]
    traces = plan_lib.fused_trace_counts[(spec, None, True)]

    t0 = time.perf_counter()
    mean, std = _block(engine.predict_volume(plan, vol, chunk=chunk))
    setup_seconds(f"ivim {name} volume, first pass (compile + run)",
                  time.perf_counter() - t0)
    t0 = time.perf_counter()
    mean2, std2 = _block(engine.predict_volume(plan, vol, chunk=chunk))
    setup_seconds(f"ivim {name} volume, second pass (run)",
                  time.perf_counter() - t0)
    check(registry.value("fused_fallback_total") == fallbacks,
          f"ivim {name}: the fused moments kernel fell back to per-op")
    check(plan_lib.fused_trace_counts[(spec, None, True)] == traces + 1,
          f"ivim {name}: the fused moments executor did not serve")
    check(mean.shape == volume + (4,) and std.shape == volume + (4,),
          f"ivim {name}: moments shape {mean.shape}")
    check(bool(np.isfinite(np.asarray(mean)).all()
               and np.isfinite(np.asarray(std)).all()),
          f"ivim {name}: non-finite moments")
    check(np.array_equal(np.asarray(mean), np.asarray(mean2))
          and np.array_equal(np.asarray(std), np.asarray(std2)),
          f"ivim {name}: two passes of one executable differ")

    x = vol.reshape(-1, nb)
    with jax.default_matmul_precision("highest"):
        ref_mean, ref_std = _block(engine.predict_packed(
            plan, x, chunk=chunk, backend="xla"))
    e_mean = _range_err(mean.reshape(-1, 4), ref_mean, plan.out_ranges)
    e_std = _range_err(std.reshape(-1, 4), ref_std, plan.out_ranges)
    print(f"ivim {name}: fused moments vs xla reference over {n_vox} "
          f"voxels: max |d mean| {e_mean:.2e}, max |d std| {e_std:.2e} "
          f"of the range (tolerance {IVIM_RANGE_TOL:.0e})")
    check(e_mean <= IVIM_RANGE_TOL and e_std <= IVIM_RANGE_TOL,
          f"ivim {name}: fused moments off the reference")

    xs = x[:8192]
    samples = _block(ivim_model.packed_apply(plan, xs))      # masked_ffn
    with jax.default_matmul_precision("highest"):
        ref_samples = _block(plan_lib.execute(plan, xs, backend="xla"))
    check(samples.shape == (N_MASKS, xs.shape[0], 4),
          f"ivim {name}: per-op samples shape {samples.shape}")
    e_po = _range_err(samples, ref_samples, plan.out_ranges)
    print(f"ivim {name}: per-op masked_ffn samples vs xla reference: "
          f"max |d| {e_po:.2e} of the range (tolerance "
          f"{IVIM_RANGE_TOL:.0e})")
    check(e_po <= IVIM_RANGE_TOL, f"ivim {name}: masked_ffn off the "
                                  f"reference")

    # Planted faults: what the checks above would read had the kernels
    # mixed up the masks or lost one. Each must exceed the tolerance.
    e_roll = _range_err(np.roll(np.asarray(samples), 1, axis=0),
                        ref_samples, plan.out_ranges)
    drop_mean, drop_std = unc_lib.predictive_moments(ref_samples[:-1])
    ref_mean, ref_std = unc_lib.predictive_moments(ref_samples)
    e_drop = max(_range_err(drop_mean, ref_mean, plan.out_ranges),
                 _range_err(drop_std, ref_std, plan.out_ranges))
    print(f"ivim {name}: planted faults: masks rolled by one {e_roll:.2e}, "
          f"one mask dropped from the moments {e_drop:.2e} of the range")
    check(min(e_roll, e_drop) > IVIM_RANGE_TOL,
          f"ivim {name}: the tolerance cannot see a planted fault")


# ---------------------------------------------------------------------------
# phase 3: Bayesian LM serving at published widths
# ---------------------------------------------------------------------------

def lm_setup(cfg, seed: int):
    """(model, params, prompts): seeded random weights and prompts."""
    import jax
    import numpy as np
    from repro.models import build_model
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = _block(model.init(jax.random.PRNGKey(seed)))
    setup_seconds(f"{cfg.arch_id} random init", time.perf_counter() - t0)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    print(f"{cfg.arch_id}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, N={cfg.mask_samples}, "
          f"{nbytes / 2 ** 30:.3f} GiB of {np.dtype(cfg.dtype).name} "
          f"weights")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in PROMPT_LENS]
    return model, params, prompts


def server_config(max_seq: int = MAX_SEQ, new_tokens: int = NEW_TOKENS):
    from repro.serving import ServerConfig
    return ServerConfig(max_slots=SLOTS, max_queue=64,
                        max_prompt_len=max_seq - new_tokens,
                        max_new_tokens=new_tokens)


def serve(server, prompts):
    """Submit every prompt, drain the server; per-request (tokens, unc)."""
    rids = [server.submit(p) for p in prompts]
    server.run()
    return [(list(server.result(r).generated),
             list(server.result(r).uncertainty)) for r in rids]


def lm_forward_check(cfg, params, prompt, tokens, uncs) -> None:
    """Teacher-force one request's prompt + generated tokens through the
    uncached forward (mask-major rows: row j runs mask j) and compare each
    step's posterior with what the server emitted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer
    n, p_len, t_len = cfg.mask_samples, len(prompt), len(tokens)
    ctx = np.asarray(list(prompt) + tokens[:-1], np.int32)

    @jax.jit
    def posterior(params, ctx):
        rows = jnp.tile(ctx[None], (n, 1))
        logits, _ = transformer.forward(cfg, params, {"tokens": rows},
                                        mask_ids=jnp.arange(n))
        lp = jax.nn.log_softmax(
            logits[:, p_len - 1:p_len - 1 + t_len].astype(jnp.float32), -1)
        top2 = jax.lax.top_k(lp.mean(0), 2)[0]
        # [T, V] mean and std over all masks, then over all but the last
        return lp.mean(0), lp.std(0), lp[:-1].mean(0), lp[:-1].std(0), top2

    steps = np.arange(t_len)
    tok = np.asarray(tokens)
    served = np.asarray(uncs)

    def rel_err(m, sd):
        """Largest |served u - reference u| as a share of the reference u."""
        u = sd[steps, tok] / np.maximum(np.abs(m[steps, tok]), 1e-12)
        return float((np.abs(served - u) / u).max())

    mean, std, mean_d, std_d, top2 = (np.asarray(a)
                                      for a in posterior(params, ctx))
    margin = top2[:, 0] - top2[:, 1]
    gap = mean.max(-1) - mean[steps, tok]
    off = gap > 0
    e_rel = rel_err(mean, std)
    print(f"forward check ({p_len}-token prompt, {t_len} steps): "
          f"{t_len - int(off.sum())}/{t_len} tokens are the reference "
          f"argmax, max gap {gap.max():.4f} nats (tolerance {LP_TOL}), "
          f"max |d u|/u {e_rel:.4f} (tolerance {REL_TOL}); reference "
          f"top-2 margin min {margin.min():.4f}, median "
          f"{np.median(margin):.4f} nats, {int((margin < LP_TOL).sum())} "
          f"near-ties")
    check(bool((margin[off] < LP_TOL).all()) and float(gap.max()) <= LP_TOL,
          "LM: a served token is not the reference's argmax")
    check(e_rel <= REL_TOL, "LM: uncertainty off the reference")

    e_drop = rel_err(mean_d, std_d)
    mean_s, std_s = (np.asarray(a) for a in
                     posterior(params, np.roll(ctx, 1))[:2])
    e_shift = rel_err(mean_s, std_s)
    agree_s = int((mean_s.argmax(-1) == tok).sum())
    print(f"planted faults: context shifted by one position max |d u|/u "
          f"{e_shift:.4f} ({agree_s}/{t_len} tokens still the argmax), "
          f"one mask dropped max |d u|/u {e_drop:.4f}")
    check(min(e_shift, e_drop) > REL_TOL,
          "LM: the tolerances cannot see a planted fault")


def lm_phase(cfg, seed: int = 0) -> None:
    import numpy as np
    from repro.obs import registry as obs_registry
    from repro.serving import BayesianLMServer

    model, params, prompts = lm_setup(cfg, seed)
    scfg = server_config()
    server = BayesianLMServer(model, params, scfg)
    t0 = time.perf_counter()
    first = serve(server, prompts)
    setup_seconds(f"{cfg.arch_id} serve {len(prompts)} requests, first pass"
                  f" (compile + run)", time.perf_counter() - t0)
    t0 = time.perf_counter()
    second = serve(BayesianLMServer(model, params, scfg), prompts)
    setup_seconds(f"{cfg.arch_id} serve {len(prompts)} requests, second "
                  f"pass (run)", time.perf_counter() - t0)

    fallbacks = obs_registry.REGISTRY.snapshot()["fused_fallback_total"]
    print(f"decode executor: "
          f"{'fused' if server.steps.fused_live() else 'per-op'} "
          f"(fused lowering {'built' if server.steps.fused_spec else 'none'}"
          f", bucketed prefill "
          f"{'on' if server.steps.prefill_spec is not None else 'off'}); "
          f"fused_fallback_total {fallbacks['values']}")
    for (toks, uncs), n_prompt in zip(first, PROMPT_LENS):
        check(len(toks) == NEW_TOKENS, f"LM: {len(toks)} tokens for a "
                                       f"{n_prompt}-token prompt")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              "LM: token outside the vocabulary")
        check(bool(np.isfinite(uncs).all()) and min(uncs) >= 0,
              "LM: non-finite or negative uncertainty")
    check(first == second, "LM: two passes of one executable differ")
    print(f"LM: {len(first)} requests answered, "
          f"{sum(len(t) for t, _ in first)} tokens, rel-unc "
          f"{min(min(u) for _, u in first):.4f}.."
          f"{max(max(u) for _, u in first):.4f}")
    i = int(np.argmax(PROMPT_LENS))
    lm_forward_check(cfg, params, prompts[i], *first[i])


# ---------------------------------------------------------------------------
# --four-chips: the multi-host router, one chip per host
# ---------------------------------------------------------------------------

def router_phase(cfg, devices, seed: int = 0) -> None:
    import jax
    import numpy as np
    from repro.obs.trace import ManualClock
    from repro.serving import BayesianLMServer, RouterConfig, ServingRouter

    model, params, prompts = lm_setup(cfg, seed)
    scfg = server_config()
    clock = ManualClock()
    router = ServingRouter(model, params, scfg,
                           RouterConfig(n_hosts=4, heartbeat_timeout_s=2.5),
                           clock=clock)
    t0 = time.perf_counter()
    rids = [router.submit(p) for p in prompts]
    router.run(max_steps=10_000, tick=lambda: clock.advance(1.0))
    setup_seconds(f"router 4 hosts, {len(prompts)} requests (compile + run)",
                  time.perf_counter() - t0)
    routed = [(list(router.result(r).generated),
               list(router.result(r).uncertainty)) for r in rids]
    homes = sorted({router.result(r).home for r in rids})
    print(f"router: requests served from hosts {homes}, "
          f"{router.summary().format()}")
    check(homes == [0, 1, 2, 3], f"router: hosts used {homes}")

    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    for i, d in enumerate(devices[:4]):
        used = (d.memory_stats() or {}).get("bytes_in_use")
        print(f"device {i} ({d}): bytes_in_use "
              f"{'not reported' if used is None else used}")
        if used is not None:
            check(used >= nbytes, f"device {i} holds {used} bytes, less "
                                  f"than one copy of the weights")
        server = router.hosts[i].server
        placed = {x for leaf in jax.tree.leaves((server.params,
                                                 server._caches))
                  for x in leaf.devices()}
        check(placed == {d}, f"router host {i} lives on {placed}")

    t0 = time.perf_counter()
    single = serve(BayesianLMServer(model, params, scfg, device=devices[0]),
                   prompts)
    setup_seconds(f"one host on chip 0, {len(prompts)} requests",
                  time.perf_counter() - t0)
    check([t for t, _ in routed] == [t for t, _ in single],
          "router: tokens differ from the single-host server")
    d_unc = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                for (_, a), (_, b) in zip(routed, single))
    print(f"router vs single host: tokens identical, max |d rel-unc| "
          f"{d_unc:.2e}")
    check(d_unc <= 1e-5, "router: uncertainties differ from the single-host"
                         " server")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-host router phase (4 chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, data and prompts; a second "
                         "seed shows the tolerances are not fitted to one")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run from the "
              f"repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro import compat
    print(f"compilation cache: {compat.enable_compilation_cache()}")

    from repro.configs import registry
    devices = device_check(4 if args.four_chips else 1)
    lm_cfg = registry.get_config(ARCH, mask_samples=N_MASKS)
    if args.four_chips:
        router_phase(lm_cfg, devices, args.seed)
    else:
        from repro.ivim import physics
        ivim_phase("clinical", physics.CLINICAL_B_VALUES, seed=args.seed)
        ivim_phase("dense", physics.DENSE_B_VALUES, seed=args.seed)
        peak_memory(devices[0])
        lm_phase(lm_cfg, args.seed)
    peak_memory(devices[0])
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
