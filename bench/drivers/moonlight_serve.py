"""Open-loop Bayesian serving of Moonlight-16B-A3B (latent attention, 64
routed + 2 shared experts) through ``BayesianLMServer.submit/step``.

The load loop, the end-to-end metrics, the drain, the release and the
sample that ``correct`` checks are ``lm_serve``'s; this driver brings the
configuration's own program config, weights and reference. Weights are
random from the seed, made on the device in bfloat16 in one jitted call
in the program's parameter layout (the selection bias of the router is
0.01 N(0, 1), float32); the masks -- one set over the dense FFN's hidden
units, one shared by all routed experts, one over the shared experts --
come from ``bench/reference/masks.py``. ``correct`` teacher-forces a
seeded sample of the finished requests, the longest among them, through
``bench/reference/moonlight.py`` and compares each served token with the
reference posterior.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

from bench import harness
from bench.reference import masks as masks_ref
from bench.reference import moonlight as moonlight_ref

lm_serve = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                            "lm_serve.py"))
run, end_to_end, drain, release, sample, prepare = (
    lm_serve.run, lm_serve.end_to_end, lm_serve.drain, lm_serve.release,
    lm_serve.sample, lm_serve.prepare)

# what the program implements of the DeepSeek-V3 configuration keys
_FIXED = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "hidden_act": "silu", "attention_bias": False, "moe_layer_freq": 1}


def model_config(config: dict):
    """The program's ModelConfig for the configuration file."""
    from repro.configs import registry
    for key, want in _FIXED.items():
        if config.get(key) != want:
            raise ValueError(f"{key} {config.get(key)!r}: the program "
                             f"implements {want!r}")
    cfg = registry.get_config(config["registry_arch"])
    cfg = dataclasses.replace(
        cfg, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["v_head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        n_shared_experts=config["n_shared_experts"],
        first_dense_layers=config["first_k_dense_replace"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling=config["routed_scaling_factor"],
        tie_embeddings=config["tie_word_embeddings"],
        mask_samples=config["mask_samples"], mask_scale=config["mask_scale"],
        mask_seed=config["mask_seed"])
    if np.dtype(cfg.dtype).name != config["torch_dtype"]:
        raise ValueError(f"program dtype {cfg.dtype} is not "
                         f"{config['torch_dtype']}")
    return cfg


def mask_tables(config: dict) -> dict:
    """The three mask sets by the name of the leaf that holds them."""
    n, sc, seed = (config["mask_samples"], config["mask_scale"],
                   config["mask_seed"])
    f = config["moe_intermediate_size"]
    return {"['ffn']['masks']": masks_ref.masks(
                config["intermediate_size"], n, sc, seed),
            "['moe']['masks']": masks_ref.masks(f, n, sc, seed),
            "['shared']['masks']": masks_ref.masks(
                config["n_shared_experts"] * f, n, sc, seed)}


def _init_leaf(path, spec, key, masks):
    import jax
    import jax.numpy as jnp
    name = jax.tree_util.keystr(path)
    for suffix, m in masks.items():
        if name.endswith(suffix):
            return jnp.broadcast_to(m.astype(spec.dtype), spec.shape)
    if name.endswith("['router_bias']"):
        val = 0.01 * jax.random.normal(key, spec.shape, jnp.float32)
    elif name.endswith("['scale']"):
        val = 1.0 + 0.1 * jax.random.normal(key, spec.shape, jnp.float32)
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
    masks = {k: jnp.asarray(m, jnp.float32)
             for k, m in mask_tables(config).items()}
    return _weight_fn(cfg)(jax.random.PRNGKey(harness.sub_seed(seed, 1)),
                           masks)


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
    for n in lm_serve._warm_lengths(traffic["prompt"]["min"],
                                    traffic["prompt"]["max"]):
        server.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=2)
    server.run()
    return {"config": config, "traffic": traffic, "seed": seed,
            "weights": weights, "server": server, "cfg": cfg}


def readings(cell: dict, rec: dict, control: str | None = None) -> dict:
    """Over the sampled requests' served tokens: by how far a served
    token's mean log-prob lies below the reference's best, the mean over
    every compared token (``gap_mean_nats``), the worst request's mean
    (``gap_req_max_nats``: a fault in one slot shows in its request) and
    the widest (``gap_nats``); and the gap
    of its relative uncertainty std/|mean| from the reference's, as a share
    of it: the median over every compared token (``unc_rel_p50``) and the
    largest (``unc_rel_err``, which one near-certain token can set).
    ``control`` ("int8") puts the reference at that precision in the
    program's place: its own argmax and uncertainty are read instead."""
    import jax.numpy as jnp
    config = cell["config"]
    ref = moonlight_ref
    out = {"gap_mean_nats": 0.0, "gap_req_max_nats": 0.0, "gap_nats": 0.0,
           "unc_rel_p50": 0.0, "unc_rel_err": 0.0, "tokens_compared": 0,
           "requests_compared": 0}
    reqs = sample(cell, rec)
    if not reqs:
        for k in ("gap_mean_nats", "gap_req_max_nats", "unc_rel_p50"):
            out[k] = float("inf")
        return out
    rel, gaps = [], []
    for r in reqs:
        toks, uncs = r["served"]
        t, p = len(toks), len(r["prompt"])
        ctx = list(r["prompt"]) + toks[:-1]
        if control:
            # one [N, T, V] array at a time: at 8k x 164k they are GBs
            lpc = ref.log_probs(cell["weights"], config, ctx, p - 1, t,
                                quant=control)
            width = lpc.shape[1]
            _, _, _, ctl_tok = ref.posterior(lpc, jnp.zeros(width, jnp.int32))
            cm, cs, _, _ = ref.posterior(lpc, ctl_tok)
            toks = np.asarray(ctl_tok)[:t]
            uncs = (np.asarray(cs) / np.maximum(np.abs(np.asarray(cm)),
                                                1e-12))[:t]
            del lpc
        lp = ref.log_probs(cell["weights"], config, ctx, p - 1, t)
        width = lp.shape[1]         # t rounded up; the rows past t are dropped
        padded = np.zeros(width, np.int32)
        padded[:t] = toks
        m, s, best, _ = (np.asarray(a, np.float64)[:t] for a in
                         ref.posterior(lp, jnp.asarray(padded)))
        del lp
        u = s / np.maximum(np.abs(m), 1e-12)
        rel.append(np.abs(np.asarray(uncs, np.float64) - u) / u)
        gaps.append(best - m)
        out["gap_nats"] = max(out["gap_nats"], float(gaps[-1].max()))
        out["gap_req_max_nats"] = max(out["gap_req_max_nats"],
                                      float(gaps[-1].mean()))
        out["unc_rel_err"] = max(out["unc_rel_err"], float(rel[-1].max()))
        out["tokens_compared"] += t
        out["requests_compared"] += 1
    out["gap_mean_nats"] = float(np.concatenate(gaps).mean())
    out["unc_rel_p50"] = float(np.median(np.concatenate(rel)))
    return out
