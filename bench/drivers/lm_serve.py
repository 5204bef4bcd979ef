"""Open-loop Bayesian LM serving through ``BayesianLMServer.submit/step``.

Requests come from ``bench/traffic.py`` at the mix's fixed rate, each due
at its own time. The loop submits every request that is due, then runs one
``server.step()`` while there is work, and after each step reads which
requests were admitted, which produced a token and which finished. A token
is seen by the client when the step that produced it returns. The load
runs ``warmup_s`` before the window opens, so the pool is at its steady
state; the window is the next ``seconds``.

Weights are random from the seed, made on the device in bfloat16 in one
jitted call, in the program's parameter layout; the masks come from
``bench/reference/masks.py``. ``correct`` teacher-forces a seeded sample of
the finished requests, the longest among them, through
``bench/reference/qwen2.py`` and compares each served token with the
reference posterior.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import numpy as np

from bench import harness, traffic as traffic_lib
from bench.reference import masks as masks_ref
from bench.reference import qwen2 as qwen2_ref


def model_config(config: dict):
    """The program's ModelConfig for the configuration file."""
    from repro.configs import registry
    cfg = registry.get_config(config["registry_arch"])
    cfg = dataclasses.replace(
        cfg, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        qkv_bias=config["attention_bias"],
        tie_embeddings=config["tie_word_embeddings"],
        mask_samples=config["mask_samples"], mask_scale=config["mask_scale"],
        mask_seed=config["mask_seed"])
    if np.dtype(cfg.dtype).name != config["torch_dtype"]:
        raise ValueError(f"program dtype {cfg.dtype} is not "
                         f"{config['torch_dtype']}")
    return cfg


def _init_leaf(path, spec, key, masks):
    import jax
    import jax.numpy as jnp
    name = jax.tree_util.keystr(path)
    if name.endswith("['masks']"):
        return jnp.broadcast_to(masks.astype(spec.dtype), spec.shape)
    if name.endswith("['scale']"):
        val = 1.0 + 0.1 * jax.random.normal(key, spec.shape, jnp.float32)
    elif name.endswith("['b']"):
        val = 0.1 * jax.random.normal(key, spec.shape, jnp.float32)
    elif name.endswith("['embed']['embed']"):
        val = 0.02 * jax.random.normal(key, spec.shape, jnp.float32)
    else:           # a matrix [.., d_in, d_out]
        val = jax.random.normal(key, spec.shape, jnp.float32) \
            / np.sqrt(spec.shape[-2])
    return val.astype(spec.dtype)


@functools.lru_cache(maxsize=None)
def _weight_fn(cfg):
    import jax
    from repro.models import build_model
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fn(key, masks):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(
            treedef, [_init_leaf(p, s, k, masks)
                      for (p, s), k in zip(paths, keys)])

    return jax.jit(fn)


def make_weights(config: dict, cfg, seed: int):
    import jax
    import jax.numpy as jnp
    m = masks_ref.masks(config["intermediate_size"], config["mask_samples"],
                        config["mask_scale"], config["mask_seed"])
    return _weight_fn(cfg)(jax.random.PRNGKey(harness.sub_seed(seed, 1)),
                           jnp.asarray(m, jnp.float32))


def _warm_lengths(lo: int, hi: int) -> list[int]:
    """A prompt in every power-of-two length band that [lo, hi] touches."""
    out, b = [lo], 1
    while b < hi:
        if lo <= b < hi:
            out.append(b + 1)
        b *= 2
    return sorted(set(out))


def _warm_admission_shapes(cfg, pool: dict, lengths) -> None:
    """Admission expands each prompt over the masks and pads it to its
    prefill bucket with eager array ops at the prompt's own length (the
    list-to-array conversion and ``jnp.tile`` in
    ``BayesianLMServer._admit``, the zero pad and
    ``jnp.concatenate`` of the bucketed prefill), and these compile once per
    distinct length. Run the same ops for every length of this run's
    traffic, so that none compiles inside the window."""
    import jax.numpy as jnp
    from repro.core import plan as plan_lib
    n = cfg.mask_samples
    max_seq = pool["max_prompt_len"] + pool["max_new_tokens"]
    for length in sorted(set(lengths)):
        xt = jnp.tile(jnp.asarray([0] * length, jnp.int32)[None], (n, 1))
        bucket = plan_lib.prefill_bucket(length, max_seq)
        if bucket and bucket > length:
            jnp.concatenate([xt, jnp.zeros((n, bucket - length), xt.dtype)],
                            axis=1)


def setup(config: dict, traffic: dict, seed: int) -> dict:
    from repro.models import build_model
    from repro.serving import BayesianLMServer, ServerConfig
    cfg = model_config(config)
    weights = make_weights(config, cfg, seed)
    pool = traffic["pool"]
    server = BayesianLMServer(build_model(cfg), weights, ServerConfig(
        max_slots=pool["max_slots"], max_queue=pool["max_queue"],
        max_prompt_len=pool["max_prompt_len"],
        max_new_tokens=pool["max_new_tokens"]))
    rng = np.random.default_rng(harness.sub_seed(seed, 4))
    for n in _warm_lengths(traffic["prompt"]["min"], traffic["prompt"]["max"]):
        server.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=2)
    server.run()
    cell = {"config": config, "traffic": traffic, "seed": seed,
            "weights": weights, "server": server, "cfg": cfg}
    return cell


def _requests(cell: dict, seconds: float) -> list[dict]:
    return traffic_lib.requests(
        cell["traffic"], harness.sub_seed(cell["seed"], 5),
        cell["traffic"]["warmup_s"] + seconds, cell["config"]["vocab_size"])


def prepare(cell: dict, seconds: float) -> None:
    """Draw the run's requests and warm the shapes their lengths need."""
    cell["requests"] = _requests(cell, seconds)
    _warm_admission_shapes(cell["cfg"], cell["traffic"]["pool"],
                           [len(r["prompt"]) for r in cell["requests"]])


def run(cell: dict, seconds: float, span=None, window=None) -> dict:
    """The load, then the measured window of ``seconds``. ``span`` makes a
    named host span, ``window`` the context of the window."""
    span = span or (lambda name: contextlib.nullcontext())
    win = (window or contextlib.nullcontext)()
    opened = False
    from repro.serving import QueueFullError
    server = cell["server"]
    warm = cell["traffic"]["warmup_s"]
    reqs = cell.pop("requests", None) or _requests(cell, seconds)
    clock = time.perf_counter
    t_load = clock()
    w0, w1 = t_load + warm, t_load + warm + seconds
    recs = [{"due": t_load + r["due"], "prompt": r["prompt"],
             "max_new_tokens": r["max_new_tokens"], "submit": None,
             "admit": None, "tokens": [], "finished": None, "rid": None,
             "rejected": False} for r in reqs]
    active: dict[int, dict] = {}
    steps = []
    nxt = 0
    try:
        while True:
            now = clock()
            if now >= w1:
                break
            if not opened and now >= w0:
                win.__enter__()
                opened = True
            with span("bench.submit"):
                while nxt < len(recs) and recs[nxt]["due"] <= now:
                    r = recs[nxt]
                    r["submit"] = clock()
                    try:
                        r["rid"] = server.submit(
                            r["prompt"], max_new_tokens=r["max_new_tokens"])
                        active[r["rid"]] = r
                    except QueueFullError:
                        r["rejected"] = True
                    nxt += 1
            if not active:
                wait = min(w1, recs[nxt]["due"] if nxt < len(recs) else w1)
                with span("bench.idle"):
                    time.sleep(max(0.0, min(wait - clock(), 0.002)))
                continue
            t0 = clock()
            server.step()
            t1 = clock()
            step = {"t0": t0, "t1": t1, "admitted": [], "live": 0,
                    "attended": 0}
            with span("bench.collect"):
                for rid, r in list(active.items()):
                    st = server.result(rid)
                    if r["admit"] is None and st.status != "queued":
                        r["admit"] = t0
                        step["admitted"].append(len(r["prompt"]))
                    n = len(st.generated)
                    if n > len(r["tokens"]):
                        # one decode at position prompt + n - 1
                        step["live"] += 1
                        step["attended"] += len(r["prompt"]) + n
                        r["tokens"].append(t1)
                    if st.status in ("done", "escalated"):
                        r["finished"] = t1
                        r["served"] = (list(st.generated),
                                       list(st.uncertainty))
                        del active[rid]
            steps.append(step)
    finally:
        if opened:
            win.__exit__(None, None, None)
    return {"window": (w0, w1), "requests": recs, "steps": steps,
            "attempted": sum(1 for r in recs if w0 <= r["due"] < w1),
            "failed": sum(1 for r in recs
                          if r["rejected"] and w0 <= r["due"] < w1),
            "max_slots": cell["traffic"]["pool"]["max_slots"]}


def end_to_end(cell: dict, rec: dict) -> dict:
    from bench import stats
    w0, w1 = rec["window"]
    reqs = [r for r in rec["requests"] if not r["rejected"]]
    return {"ttft_p95_ms": 1e3 * stats.percentile(
                stats.ttft_samples(reqs, w0, w1), 95),
            "itl_p95_ms": 1e3 * stats.percentile(
                stats.itl_samples(reqs, w0, w1), 95),
            "tokens_per_s": stats.rate(stats.tokens_in(reqs, w0, w1),
                                       w0, w1)}


def drain(cell: dict) -> None:
    """Serve what is left, so the next window starts on an empty pool."""
    cell["server"].run()


def release(cell: dict) -> None:
    """Free the server, its pool and its compiled steps' buffers."""
    cell.pop("server", None)
    import gc
    gc.collect()


def sample(cell: dict, rec: dict) -> list[dict]:
    """A seeded sample of ``check.requests`` finished requests, the longest
    among them."""
    done = [r for r in rec["requests"] if r["finished"] is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["served"][0]))
    rng = np.random.default_rng(harness.sub_seed(cell["seed"], 6))
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    return [longest] + rest[:cell["traffic"]["check"]["requests"] - 1]


def readings(cell: dict, rec: dict, control: str | None = None) -> dict:
    """Over the sampled requests' served tokens: the widest gap by which a
    served token's mean log-prob lies below the reference's best
    (``gap_nats``), and the gap of its relative uncertainty std/|mean| from
    the reference's, as a share of it: the median over every compared
    token (``unc_rel_p50``) and the largest (``unc_rel_err``, which one
    near-certain token can set). ``control`` ("int8") puts the reference at
    that precision in the program's place: its own argmax and uncertainty
    are read instead."""
    import jax.numpy as jnp
    config = cell["config"]
    out = {"gap_nats": 0.0, "unc_rel_p50": 0.0, "unc_rel_err": 0.0,
           "tokens_compared": 0, "requests_compared": 0}
    reqs = sample(cell, rec)
    if not reqs:
        out["gap_nats"] = out["unc_rel_p50"] = float("inf")
        return out
    rel = []
    for r in reqs:
        toks, uncs = r["served"]
        t, p = len(toks), len(r["prompt"])
        ctx = list(r["prompt"]) + toks[:-1]
        lp = qwen2_ref.log_probs(cell["weights"], config, ctx, p - 1, t)
        width = lp.shape[1]         # t rounded up; the rows past t are dropped
        if control:
            lpc = qwen2_ref.log_probs(cell["weights"], config, ctx, p - 1, t,
                                      quant=control)
            _, _, _, ctl_tok = qwen2_ref.posterior(lpc, jnp.zeros(
                width, jnp.int32))
            cm, cs, _, _ = qwen2_ref.posterior(lpc, ctl_tok)
            toks = np.asarray(ctl_tok)[:t]
            uncs = (np.asarray(cs) / np.maximum(np.abs(np.asarray(cm)),
                                                1e-12))[:t]
            del lpc
        padded = np.zeros(width, np.int32)
        padded[:t] = toks
        m, s, best, _ = (np.asarray(a, np.float64)[:t] for a in
                         qwen2_ref.posterior(lp, jnp.asarray(padded)))
        del lp
        u = s / np.maximum(np.abs(m), 1e-12)
        rel.append(np.abs(np.asarray(uncs, np.float64) - u) / u)
        out["gap_nats"] = max(out["gap_nats"], float((best - m).max()))
        out["unc_rel_err"] = max(out["unc_rel_err"], float(rel[-1].max()))
        out["tokens_compared"] += t
        out["requests_compared"] += 1
    out["unc_rel_p50"] = float(np.median(np.concatenate(rel)))
    return out
