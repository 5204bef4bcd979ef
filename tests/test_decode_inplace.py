"""The decode step writes its segment's stacked pool in place.

``transformer.decode_step`` carries each segment's pool through the layer
loop and writes one position per row (K/V, the MLA latent, kpos and the
int8 scales), or, for recurrent and xLSTM state, the layer's whole state.
Here that step is held against a plain reference written in this file,
which decodes one layer at a time on that layer's own cache and restacks
the new caches: logits, posteriors and the pool must agree, and only one
slot per row of a per-position leaf may change. A pool built by prefills
scattered into their rows then decodes the tokens the training forward
gives on each row's whole sequence.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import layers, transformer
from repro.serving import server as server_lib

# rows: 3 requests of 4 mask rows each at their own positions, and one
# request's rows left empty (pos -1)
PROMPTS = (5, 9, 13)
N_MASKS = 4
MAX_SEQ = 24
STEPS = 3

CASES = {
    # GQA with a bf16 cache under an f32 model, scanned over 3 layers
    "gqa_bf16": ("qwen2-1.5b", dict(n_layers=3, n_kv_heads=2,
                                    kv_dtype="bfloat16")),
    # int8 K/V with per-position scales
    "int8": ("qwen2-1.5b", dict(n_layers=3, n_kv_heads=2, kv_dtype="int8")),
    # (rec, rec, local_attn) x 2: the rolling window cache wraps while
    # decoding (window 8, prompts up to 13)
    "rolling_local": ("recurrentgemma-2b", dict(n_layers=6, local_window=8)),
    # MLA latent: a reps == 1 dense segment, then 2 MoE layers scanned
    "mla": ("moonlight-16b-a3b", {}),
    # mLSTM / sLSTM state, whole per layer
    "recurrent": ("xlstm-350m", dict(n_layers=4)),
    # every segment unrolled, one layer at a time
    "reps1": ("qwen2-1.5b", dict(n_layers=2, scan_layers=False)),
}


@functools.lru_cache(maxsize=None)
def _case(name):
    """(cfg, params, pool, mask_ids, pos, first tokens) of a case: each
    prompt prefilled alone under each of its mask rows and scattered into
    the pool, rows mask-major as the server lays them out (row = mask *
    groups + group)."""
    arch, over = CASES[name]
    cfg = registry.smoke_config(arch, **over)
    params = transformer.init(cfg, jax.random.PRNGKey(0))
    prefill = jax.jit(lambda t: transformer.prefill(
        cfg, params, {"tokens": t}, max_seq=MAX_SEQ,
        mask_ids=jnp.arange(N_MASKS)))
    groups = len(PROMPTS) + 1
    rows = N_MASKS * groups
    mask_ids = jnp.repeat(jnp.arange(N_MASKS), groups)
    pool = transformer.init_cache(cfg, rows, MAX_SEQ)
    pos = np.full(rows, -1, np.int32)
    first = np.zeros(rows, np.int32)
    for g, toks in enumerate(_prompts(cfg)):
        idx = np.arange(N_MASKS) * groups + g
        logits, fresh = prefill(jnp.repeat(toks, N_MASKS, 0))
        pool = transformer.cache_scatter_rows(pool, fresh, jnp.asarray(idx))
        pos[idx] = toks.shape[1]
        first[idx] = np.asarray(jnp.argmax(logits, -1))
    return cfg, params, pool, mask_ids, jnp.asarray(pos), jnp.asarray(first)


def _prompts(cfg):
    key = jax.random.PRNGKey(7)
    return [jax.random.randint(k, (1, n), 0, cfg.vocab_size)
            for k, n in zip(jax.random.split(key, len(PROMPTS)), PROMPTS)]


def _reference_decode(cfg, params, pool, tokens, pos, mask_ids):
    """One decode step a layer at a time: each layer's cache taken out of
    its segment's pool, the block run on it alone, the new per-layer
    caches stacked again."""
    x = layers.embed_tokens(params["embed"], tokens)
    rope = transformer._rope(cfg, pos[:, None])
    valid = (pos >= 0)[:, None]
    new = []
    for si, seg in enumerate(cfg.segments()):
        reps = []
        for r in range(seg.reps):
            rep = {}
            for i, kind in enumerate(seg.pattern):
                p = jax.tree.map(lambda a: a[r],
                                 params["segments"][si][f"b{i}"])
                c = jax.tree.map(lambda a: a[r], pool[si][f"b{i}"])
                x, rep[f"b{i}"], _, _ = transformer._block_apply(
                    kind, cfg, p, x, mode="decode", rope=rope,
                    mask_ids=mask_ids, cache=c, pos=pos, valid=valid)
            reps.append(rep)
        new.append(jax.tree.map(lambda *a: jnp.stack(a), *reps))
    x = layers.norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return layers.lm_head(params["embed"], x)[:, 0], new


def _slots(cfg, kind, pos, smax):
    window = cfg.local_window if kind == "local_attn" else 0
    p = np.asarray(pos)
    return ((p % window) if window else p) % smax


def _assert_one_slot_per_row(cfg, old, new, pos):
    """Per-position leaves differ from the old pool only at each row's
    one slot, where kpos now holds the row's position."""
    for si, seg in enumerate(cfg.segments()):
        for i, kind in enumerate(seg.pattern):
            if kind not in ("attn", "local_attn", "moe"):
                continue
            o, n = old[si][f"b{i}"], new[si][f"b{i}"]
            smax = o["kpos"].shape[-1]
            slot = _slots(cfg, kind, pos, smax)
            rows = np.arange(slot.shape[0])
            for name in o:
                a, b = np.asarray(o[name]), np.asarray(n[name])
                keep = np.ones(a.shape[:3], bool)
                keep[:, rows, slot] = False
                np.testing.assert_array_equal(a[keep], b[keep], name)
            np.testing.assert_array_equal(
                np.asarray(n["kpos"])[:, rows, slot],
                np.broadcast_to(np.asarray(pos), (seg.reps, rows.size)))


@pytest.mark.parametrize("case", sorted(CASES))
def test_inplace_decode_matches_per_layer_reference(case):
    cfg, params, pool, mask_ids, pos, tok = _case(case)
    step = jax.jit(lambda c, t, p: transformer.decode_step(
        cfg, params, c, t, p, mask_ids=mask_ids))
    ref_step = jax.jit(lambda c, t, p: _reference_decode(
        cfg, params, c, t, p, mask_ids))
    ref_pool = pool
    for t in range(STEPS):
        p = jnp.where(pos >= 0, pos + t, -1)
        logits, new = step(pool, tok[:, None], p)
        want, ref_pool = ref_step(ref_pool, tok[:, None], p)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        for g, w in zip(server_lib.posterior(logits, N_MASKS),
                        server_lib.posterior(want, N_MASKS)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)
        for g, w in zip(jax.tree.leaves(new), jax.tree.leaves(ref_pool)):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       rtol=1e-5, atol=1e-5)
        _assert_one_slot_per_row(cfg, pool, new, p)
        pool = new
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


@pytest.mark.parametrize("case", sorted(set(CASES) - {"int8", "gqa_bf16"}))
def test_prefilled_pool_decodes_the_forward_tokens(case):
    """Prefill, cache_scatter_rows, then per-row decode: every active row's
    logits at each step are the training forward's at that position of
    the row's prompt and the tokens decoded after it (a cache narrower
    than the model, bf16 or int8, is not exact: those are held to the
    reference above only)."""
    cfg, params, pool, mask_ids, pos, tok = _case(case)
    groups = len(PROMPTS) + 1
    # every row of a group carries its mask-0 row's token
    tok = jnp.tile(tok[:groups], N_MASKS)
    step = jax.jit(lambda c, t, p: transformer.decode_step(
        cfg, params, c, t, p, mask_ids=mask_ids))
    fed, got = [], []
    for t in range(STEPS):
        logits, pool = step(pool, tok[:, None],
                            jnp.where(pos >= 0, pos + t, -1))
        fed.append(np.asarray(tok))
        got.append(np.asarray(logits))
        tok = jnp.tile(jnp.argmax(logits, -1).astype(jnp.int32)[:groups],
                       N_MASKS)
    for g, prompt in enumerate(_prompts(cfg)):
        seq = jnp.concatenate(
            [prompt[0], jnp.asarray([f[g] for f in fed], jnp.int32)])
        full, _ = jax.jit(lambda x: transformer.forward(
            cfg, params, {"tokens": x}, mask_ids=jnp.arange(N_MASKS)))(
                jnp.tile(seq[None], (N_MASKS, 1)))
        idx = np.arange(N_MASKS) * groups + g
        n = prompt.shape[1]
        for t in range(STEPS):
            np.testing.assert_allclose(got[t][idx],
                                       np.asarray(full[:, n + t]),
                                       rtol=2e-4, atol=2e-4)
