"""Segment-scanned model stack for every assigned architecture family.

The layer stack is a sequence of *segments* (configs/base.py): homogeneous
runs of a repeating block pattern. Each segment's repetitions execute under
one ``jax.lax.scan`` over stacked parameters — an 80-layer model compiles a
single block body, keeping HLO size and compile time flat in depth — with
``jax.checkpoint`` (remat) wrapped around the body according to cfg.remat.

Block kinds:
  attn       — global GQA attention + (masked) FFN      [dense/audio/vlm]
  local_attn — sliding-window attention + FFN           [hybrid]
  moe        — GQA attention + mixture-of-experts FFN   [moe]
  rec        — RG-LRU recurrent block + FFN             [hybrid]
  mlstm      — xLSTM matrix-memory block                [ssm]
  slstm      — xLSTM scalar-memory block                [ssm]
With ``cfg.kv_lora_rank`` > 0 the attention of attn and moe blocks is
latent attention (MLA), caching one latent per position.

Three entry points:
  forward(params, tokens/embeds)        — training graph (no caches)
  prefill(params, tokens/embeds)        — forward + build decode caches
  decode_step(params, cache, token,pos) — one-token serving step

Masksembles (the paper's technique) rides through every FFN-bearing block
via ``mask_ids``: fixed masks over hidden units, assigned per batch row.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Segment
from repro.core import masksembles
from repro.models import layers, moe as moe_lib, rglru, xlstm

Params = dict[str, Any]

__all__ = ["init", "forward", "prefill", "decode_step", "init_cache",
           "cache_specs", "cache_scatter_rows", "cache_gather_rows",
           "cache_reset_rows"]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _block_init(kind: str, cfg: ModelConfig, key, dtype) -> Params:
    d = cfg.d_model
    if kind in ("attn", "local_attn", "moe"):
        k1, k2 = jax.random.split(key)
        p: Params = {
            "norm1": layers.norm_init(d, cfg.norm, dtype),
            "attn": (layers.mla_init(k1, cfg, dtype) if cfg.mla
                     else layers.attn_init(k1, cfg, dtype)),
            "norm2": layers.norm_init(d, cfg.norm, dtype),
        }
        if kind == "moe":
            p["moe"] = moe_lib.moe_init(k2, cfg, dtype)
        else:
            p["ffn"] = layers.ffn_init(k2, cfg, dtype=dtype)
        return p
    if kind == "rec":
        k1, k2 = jax.random.split(key)
        return {
            "norm1": layers.norm_init(d, cfg.norm, dtype),
            "rec": rglru.rec_block_init(k1, cfg, dtype),
            "norm2": layers.norm_init(d, cfg.norm, dtype),
            "ffn": layers.ffn_init(k2, cfg, dtype=dtype),
        }
    if kind == "mlstm":
        return xlstm.mlstm_block_init(key, cfg, dtype)
    if kind == "slstm":
        return xlstm.slstm_block_init(key, cfg, dtype)
    raise ValueError(f"unknown block kind {kind}")


def init(cfg: ModelConfig, key) -> Params:
    """Full parameter pytree. Segment params are stacked over reps (leading
    axis = reps) so the stack scans."""
    dtype = cfg.dtype
    keys = jax.random.split(key, len(cfg.segments()) + 1)
    params: Params = {"embed": layers.embed_init(keys[-1], cfg, dtype),
                      "final_norm": layers.norm_init(cfg.d_model, cfg.norm,
                                                     dtype),
                      "segments": []}

    for seg, kseg in zip(cfg.segments(), keys):
        rep_keys = jax.random.split(kseg, seg.reps)

        def init_rep(k):
            bkeys = jax.random.split(k, len(seg.pattern))
            return {f"b{i}": _block_init(kind, cfg, bk, dtype)
                    for i, (kind, bk) in enumerate(zip(seg.pattern, bkeys))}

        reps = [init_rep(k) for k in rep_keys]
        params["segments"].append(
            jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
            if len(reps) > 1 else jax.tree.map(lambda x: x[None], reps[0]))
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _block_cache_spec(kind: str, cfg: ModelConfig, batch: int, max_seq: int,
                      dtype, as_spec: bool):
    dh = cfg.resolved_head_dim
    mk_kv = layers.kv_cache_specs if as_spec else layers.init_kv_cache
    if cfg.mla and kind in ("attn", "moe"):
        if cfg.kv_dtype == "int8":
            raise ValueError("the MLA latent cache has no int8 form")
        return layers.latent_cache(
            batch, max_seq, cfg.kv_lora_rank + cfg.qk_rope_dim,
            layers.kv_store_dtype(dtype, cfg.kv_dtype), as_spec)
    if kind in ("attn", "moe"):
        return mk_kv(batch, cfg.n_kv_heads, max_seq, dh, dtype, cfg.kv_dtype)
    if kind == "local_attn":
        w = min(cfg.local_window or max_seq, max_seq)
        return mk_kv(batch, cfg.n_kv_heads, w, dh, dtype, cfg.kv_dtype)
    if kind == "rec":
        fn = rglru.rec_state_specs if as_spec else rglru.rec_state_init
        return fn(batch, cfg, dtype)
    if kind == "mlstm":
        fn = xlstm.mlstm_state_specs if as_spec else xlstm.mlstm_state_init
        return fn(batch, cfg, dtype)
    if kind == "slstm":
        fn = xlstm.slstm_state_specs if as_spec else xlstm.slstm_state_init
        return fn(batch, cfg, dtype)
    raise ValueError(kind)


def _cache_tree(cfg: ModelConfig, batch: int, max_seq: int, as_spec: bool):
    dtype = cfg.dtype
    out = []
    for seg in cfg.segments():
        one = {f"b{i}": _block_cache_spec(kind, cfg, batch, max_seq, dtype,
                                          as_spec)
               for i, kind in enumerate(seg.pattern)}
        if as_spec:
            stacked = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((seg.reps,) + s.shape,
                                               s.dtype), one)
        else:
            stacked = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (seg.reps,) + x.shape),
                one)
        out.append(stacked)
    return out


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return _cache_tree(cfg, batch, max_seq, as_spec=False)


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int):
    return _cache_tree(cfg, batch, max_seq, as_spec=True)


# Every cache leaf — KV (k/v/kpos), the MLA latent and recurrent state
# alike — is shaped [reps, batch, ...]: batch rides on axis 1. The three
# helpers below are the slot-pool contract the serving subsystem builds on
# (serving/server.py): a pooled cache is just a cache whose batch axis is
# the slot-row axis.

def cache_scatter_rows(pool, fresh, rows: jax.Array):
    """Write the rows of a small cache (batch b) into a pooled cache
    (batch B >= b) at batch indices ``rows`` [b]. Jit-safe (rows may be
    traced); used to prefill newly admitted requests into their slot rows
    while in-flight rows keep decoding."""
    return jax.tree.map(lambda p, f: p.at[:, rows].set(f), pool, fresh)


def cache_gather_rows(pool, rows: jax.Array):
    """View of a pooled cache restricted to batch indices ``rows`` [b] —
    the inverse of :func:`cache_scatter_rows` (debug / slot inspection)."""
    return jax.tree.map(lambda p: p[:, rows], pool)


def cache_reset_rows(pool, row_mask: jax.Array):
    """Clear the rows where ``row_mask`` [B] is True: K/V and recurrent
    state to zero, kpos to -1 (empty). The server runs this when a slot
    group is freed, keeping the invariant that unoccupied rows are
    observably empty (admission would fully overwrite them anyway — this
    makes the pool state inspectable between requests)."""
    from repro import compat
    mask = jnp.asarray(row_mask, bool)

    def reset(path, leaf):
        fill = -1 if "kpos" in jax.tree_util.keystr(path) else 0
        m = mask.reshape((1, mask.shape[0]) + (1,) * (leaf.ndim - 2))
        return jnp.where(m, jnp.asarray(fill, leaf.dtype), leaf)

    return compat.tree_map_with_path(reset, pool)


def cache_trim_positions(caches, length):
    """Invalidate every cache entry at position >= ``length``: kpos to -1,
    K/V, latents and int8 scales to zero — exactly the init-cache state of
    those slots.

    The bucketed-prefill epilogue: a prompt zero-padded to a bucket writes
    (garbage) K/V for the pad tail; trimming makes the caches bitwise
    identical to an exact-length prefill's. Assumes slot == position in
    every KV and latent leaf (global-attention caches with ``s <= smax``,
    the only layouts the bucketed prefill admits — rolling local-window
    caches and recurrent state are rejected upstream by
    ``core.plan.prefill_spec``). Every such leaf is [reps, B, smax, ...]:
    the slot axis is 2. ``length`` may be traced."""
    from repro import compat
    n = jnp.asarray(length, jnp.int32)

    def trim(path, leaf):
        fill = -1 if "kpos" in jax.tree_util.keystr(path) else 0
        keep = (jnp.arange(leaf.shape[2]) < n).reshape(
            (-1,) + (1,) * (leaf.ndim - 3))
        return jnp.where(keep, leaf, jnp.asarray(fill, leaf.dtype))

    return compat.tree_map_with_path(trim, caches)


# ---------------------------------------------------------------------------
# rope helpers
# ---------------------------------------------------------------------------

def _rope(cfg: ModelConfig, positions: jax.Array):
    """positions [S] or [B,S] (or [3,...] for M-RoPE) -> cos/sin shaped
    [..., S, half] broadcastable against [B, H, S, dh]."""
    dh = cfg.qk_rope_dim if cfg.mla else cfg.resolved_head_dim
    rot = int(dh * cfg.rope_pct)
    rot -= rot % 2
    if cfg.m_rope_sections:
        if positions.ndim == 1 or positions.shape[0] != 3:
            positions = jnp.broadcast_to(positions, (3,) + positions.shape)
        cos, sin = layers.mrope_cos_sin(positions, rot, cfg.rope_theta,
                                        cfg.m_rope_sections)
    else:
        cos, sin = layers.rope_cos_sin(positions, rot, cfg.rope_theta)
    # insert head axis
    if cos.ndim == 2:          # [S, half] -> [1, 1, S, half]
        cos, sin = cos[None, None], sin[None, None]
    else:                      # [B, S, half] -> [B, 1, S, half]
        cos, sin = cos[:, None], sin[:, None]
    return cos, sin


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _attention_sublayer(cfg: ModelConfig, p: Params, x: jax.Array, rope,
                        mode: str, kind: str, cache, pos):
    """Shared attention sub-layer for attn/local_attn/moe blocks."""
    if cfg.mla:
        return _mla_sublayer(cfg, p, x, rope, mode, cache, pos)
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    xn = layers.norm_apply(p["norm1"], x, cfg.norm, cfg.norm_eps)
    q = layers._split_heads(layers.dense(p["attn"]["wq"], xn), h)
    k = layers._split_heads(layers.dense(p["attn"]["wk"], xn), hkv)
    v = layers._split_heads(layers.dense(p["attn"]["wv"], xn), hkv)
    cos, sin = rope
    q = layers.apply_rope(q, cos, sin, cfg.rope_pct)
    k = layers.apply_rope(k, cos, sin, cfg.rope_pct)
    # Activation-sharding policy (GSPMD hints; identity without a mesh):
    # * seq_shard (sequence parallelism): queries stay sequence-sharded
    #   (so attention output lands back on the S-sharded residual with no
    #   re-shard) and the small GQA K/V are gathered to full sequence;
    # * else head-TP when the head counts divide the model axis
    #   (Megatron-style, attention fully local), otherwise shard the KV
    #   sequence dim over "model" (distributed-softmax attention).
    msize = layers.axis_size("model")
    if mode != "decode":
        if cfg.seq_shard:
            # sequence-sharded queries + fully gathered (small, GQA) K/V.
            # NOTE a head-TP variant (q/k/v re-sharded onto heads) was tried
            # and REFUTED: GSPMD lowers the S->H re-shard of the projection
            # outputs as replicate+slice, 4x-ing the all-gather bytes
            # (EXPERIMENTS §Perf, qwen2-vl iteration 2).
            q = layers.constrain(q, ("batch", None, "model", None))
            k = layers.constrain(k, ("batch", None, None, None))
            v = layers.constrain(v, ("batch", None, None, None))
        elif h % msize == 0 and hkv % msize == 0:
            q = layers.constrain(q, ("batch", "model", None, None))
            k = layers.constrain(k, ("batch", "model", None, None))
            v = layers.constrain(v, ("batch", "model", None, None))
        else:
            q = layers.constrain(q, ("batch", None, None, None))
            k = layers.constrain(k, ("batch", None, "model", None))
            v = layers.constrain(v, ("batch", None, "model", None))

    window = cfg.local_window if kind == "local_attn" else 0
    new_cache = None
    if mode == "decode":
        new_cache = layers.kv_cache_update(cache, k, v, pos, window)
        kv = layers.cache_layer(new_cache)
        attn = layers.attention_decode(q, kv["k"], kv["v"], kv["kpos"], pos,
                                       kv.get("kscale"), kv.get("vscale"))
    else:
        s = x.shape[1]
        # s == window takes the full path below; attention_banded's own
        # s <= window fallback would compute the identical window-masked
        # full attention, so this boundary and the branch-free cache build
        # beneath agree — pinned by the prefill→decode window-boundary
        # tests in test_models_smoke.py.
        if window and s > window:
            attn = layers.attention_banded(q, k, v, window=window,
                                           unroll=cfg.analysis_unroll)
        elif s > cfg.attn_chunk and cfg.causal:
            attn = layers.attention_chunked(q, k, v, causal=True,
                                            chunk=cfg.attn_chunk,
                                            scores_f32=cfg.attn_scores_f32,
                                            unroll=cfg.analysis_unroll)
        else:
            attn = layers.attention_full(q, k, v, causal=cfg.causal,
                                         window=window,
                                         scores_f32=cfg.attn_scores_f32)
        if mode == "prefill":
            # Branch-free cache build: the last min(s, smax) positions land
            # at slot = pos % smax — kv_cache_update's decode invariant
            # (smax == window for local attention), so the s < window,
            # s == window and s > window prompts all hand decode the same
            # layout. This replaces a linear-pad / rolling branch pair that
            # split at s >= window while the attention path split at
            # s > window — the two boundaries now cannot drift apart.
            smax = cache["kpos"].shape[-1] if cache is not None else s
            if s > smax and (not window or smax < window):
                # Truncating to the last smax positions is only legitimate
                # when every dropped position is already outside the
                # attention window (the rolling local cache); for a global
                # cache — or a window the cache cannot hold — it would
                # silently amputate attendable context.
                raise ValueError(
                    f"prompt length {s} exceeds cache capacity {smax}; "
                    f"raise max_seq")
            keep = min(s, smax)
            kept_pos = jnp.arange(s - keep, s, dtype=jnp.int32)
            slots = kept_pos % smax
            shp = (x.shape[0], smax, k.shape[1], k.shape[-1])
            # position-major, the layout decode reads: [B, keep, Hkv, dh]
            kk = k[:, :, -keep:].swapaxes(1, 2)
            vk = v[:, :, -keep:].swapaxes(1, 2)
            store = layers.kv_store_dtype(k.dtype, cfg.kv_dtype)
            new_cache = {}
            if cfg.kv_dtype == "int8":
                kk, k_sc = layers.quantize_kv(kk)
                vk, v_sc = layers.quantize_kv(vk)
                sshp = shp[:-1]
                new_cache["kscale"] = jnp.zeros(
                    sshp, jnp.float32).at[:, slots].set(k_sc)
                new_cache["vscale"] = jnp.zeros(
                    sshp, jnp.float32).at[:, slots].set(v_sc)
            ks = jnp.zeros(shp, store).at[:, slots].set(kk.astype(store))
            vs = jnp.zeros(shp, store).at[:, slots].set(vk.astype(store))
            kpos = jnp.full((smax,), -1, jnp.int32).at[slots].set(kept_pos)
            kpos = jnp.broadcast_to(kpos[None], (x.shape[0], smax))
            new_cache.update(k=ks, v=vs, kpos=kpos)
    return x + layers.dense(p["attn"]["wo"], layers._merge_heads(attn)), \
        new_cache


def _mla_sublayer(cfg: ModelConfig, p: Params, x: jax.Array, rope,
                  mode: str, cache, pos):
    """Latent attention: expanded and causal over the prompt (train,
    prefill), absorbed over the latent cache (decode). Prefill caches the
    prompt's latents at slot == position."""
    xn = layers.norm_apply(p["norm1"], x, cfg.norm, cfg.norm_eps)
    a = p["attn"]
    q_nope, q_rope, lat = layers.mla_project(a, xn, cfg, *rope)
    new_cache = None
    if mode == "decode":
        new_cache = layers.latent_cache_update(cache, lat, pos)
        lc = layers.cache_layer(new_cache)
        attn = layers.mla_decode(a, q_nope, q_rope, lc["latent"], lc["kpos"],
                                 pos, cfg)
    else:
        s = x.shape[1]
        k, v = layers.mla_expand(a, lat, cfg)
        q = jnp.concatenate([q_nope, q_rope], -1)
        if s > cfg.attn_chunk and cfg.causal:
            attn = layers.attention_chunked(q, k, v, causal=True,
                                            chunk=cfg.attn_chunk,
                                            scores_f32=cfg.attn_scores_f32,
                                            unroll=cfg.analysis_unroll)
        else:
            attn = layers.attention_full(q, k, v, causal=cfg.causal,
                                         scores_f32=cfg.attn_scores_f32)
        if mode == "prefill":
            smax = cache["kpos"].shape[-1] if cache is not None else s
            if s > smax:
                raise ValueError(f"prompt length {s} exceeds cache capacity "
                                 f"{smax}; raise max_seq")
            store = layers.kv_store_dtype(lat.dtype, cfg.kv_dtype)
            slot = jnp.arange(smax, dtype=jnp.int32)
            kpos = jnp.where(slot < s, slot, -1)
            new_cache = {
                "latent": jnp.pad(lat.astype(store),
                                  ((0, 0), (0, smax - s), (0, 0))),
                "kpos": jnp.broadcast_to(kpos[None], (x.shape[0], smax))}
    return x + layers.dense(a["wo"], layers._merge_heads(attn)), new_cache


# block kinds whose cache holds one entry per position (K/V or a latent)
_POSITION_KINDS = ("attn", "local_attn", "moe")


def _block_apply(kind: str, cfg: ModelConfig, p: Params, x: jax.Array, *,
                 mode: str, rope, mask_ids, cache=None, pos=None,
                 valid=None):
    """x: [B,S,D] (train/prefill) or [B,1,D] (decode).
    Returns (x, new_cache, aux_loss, counts): ``counts`` is a dropless MoE
    block's per-expert pair count [E] over the tokens ``valid`` [B,S]
    marks, None for every other block."""
    aux = jnp.zeros((), jnp.float32)
    counts = None
    seqp = ("batch", "model", None) if (cfg.seq_shard and mode != "decode") \
        else None
    if kind in _POSITION_KINDS:
        x, new_cache = _attention_sublayer(cfg, p, x, rope, mode, kind,
                                           cache, pos)
        if seqp:
            x = layers.constrain(x, seqp)
        xn = layers.norm_apply(p["norm2"], x, cfg.norm, cfg.norm_eps)
        if kind == "moe":
            if seqp and not cfg.moe_local_groups:
                # MoE grouping crosses sequence-shard boundaries: gather the
                # normed input to full S for routing, re-scatter the output
                # ([B,S,D] bf16 — far cheaper than the per-layer f32 thrash
                # it replaces; see EXPERIMENTS §Perf arctic iteration 1).
                # With moe_local_groups the groups nest inside sequence
                # shards instead and no gather happens (arctic iteration 3).
                xn = layers.constrain(xn, ("batch", None, None))
            y, aux, counts = moe_lib.moe_apply(p["moe"], xn, cfg,
                                               mask_ids=mask_ids, valid=valid)
        else:
            y = layers.ffn_apply(p["ffn"], xn, cfg, mask_ids=mask_ids)
        out = x + y
        if seqp:
            out = layers.constrain(out, seqp)
        return out, new_cache, aux, counts

    if kind == "rec":
        xn = layers.norm_apply(p["norm1"], x, cfg.norm, cfg.norm_eps)
        if mode == "decode":
            y, new_cache = rglru.rec_block_step(p["rec"], xn[:, 0], cache,
                                                cfg)
            y = y[:, None, :]
        else:
            y, new_cache = rglru.rec_block_apply(p["rec"], xn, cfg)
            if mode == "train":
                new_cache = None
        x = x + y
        xn2 = layers.norm_apply(p["norm2"], x, cfg.norm, cfg.norm_eps)
        return x + layers.ffn_apply(p["ffn"], xn2, cfg, mask_ids=mask_ids), \
            new_cache, aux, None

    if kind in ("mlstm", "slstm"):
        mod = xlstm.mlstm_block_step if kind == "mlstm" else \
            xlstm.slstm_block_step
        par = xlstm.mlstm_block_apply if kind == "mlstm" else \
            xlstm.slstm_block_apply
        if mode == "decode":
            y, new_cache = mod(p, x[:, 0], cache, cfg, mask_ids=mask_ids)
            y = y[:, None, :]
        else:
            y, new_cache = par(p, x, cfg, mask_ids=mask_ids)
            if mode == "train":
                new_cache = None
        return x + y, new_cache, aux, None

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack execution
# ---------------------------------------------------------------------------

def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _run_stack(cfg: ModelConfig, params: Params, x: jax.Array, *, mode: str,
               rope, mask_ids, caches=None, pos=None, valid=None):
    """Run every segment. Returns (x, new_caches, total_aux, counts):
    ``counts`` [n_moe, E] stacks the dropless MoE layers' per-expert pair
    counts in layer order (None without such layers).

    In decode mode each segment's stacked cache rides in the layer loop's
    carry and is written in place (:func:`_decode_block`); train and
    prefill take per-layer caches in and build new ones out."""
    new_caches, counts = [], []
    total_aux = jnp.zeros((), jnp.float32)
    decode = mode == "decode"
    for si, seg in enumerate(cfg.segments()):
        seg_params = params["segments"][si]
        seg_cache = caches[si] if caches is not None else None

        def rep_body(carry, xs, seg=seg):
            h, aux, pool = carry
            rp, rc = xs
            pool = dict(pool)
            new_rc, rep_counts = {}, []
            for i, kind in enumerate(seg.pattern):
                if decode:
                    h, pool[f"b{i}"], a, cnt = _decode_block(
                        kind, cfg, rp[f"b{i}"], h, pool[f"b{i}"], rc,
                        rope=rope, mask_ids=mask_ids, pos=pos, valid=valid)
                else:
                    h, nc, a, cnt = _block_apply(
                        kind, cfg, rp[f"b{i}"], h, mode=mode, rope=rope,
                        mask_ids=mask_ids,
                        cache=rc[f"b{i}"] if rc is not None else None,
                        valid=valid)
                    if nc is not None:
                        new_rc[f"b{i}"] = nc
                aux = aux + a
                if cnt is not None:
                    rep_counts.append(cnt)
            return (h, aux, pool), (new_rc if new_rc else None,
                                    jnp.stack(rep_counts) if rep_counts
                                    else None)

        body = _remat(cfg, rep_body)
        # decode: the carry holds the pool and xs the layer index; else xs
        # holds each layer's cache and the new ones come out as ys
        pool = dict(seg_cache) if decode else {}
        if cfg.scan_layers and seg.reps > 1:
            xs = jnp.arange(seg.reps) if decode else seg_cache
            (x, total_aux, pool), (seg_new_cache, seg_counts) = \
                jax.lax.scan(body, (x, total_aux, pool), (seg_params, xs))
        else:
            outs, cnts = [], []
            for r in range(seg.reps):
                rp = jax.tree.map(lambda a, r=r: a[r], seg_params)
                rc = r if decode else (
                    jax.tree.map(lambda a, r=r: a[r], seg_cache)
                    if seg_cache is not None else None)
                (x, total_aux, pool), (oc, cnt) = body(
                    (x, total_aux, pool), (rp, rc))
                outs.append(oc)
                cnts.append(cnt)
            seg_new_cache = (jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
                             if outs[0] is not None else None)
            seg_counts = jnp.stack(cnts) if cnts[0] is not None else None
        new_caches.append(pool if decode else
                          seg_new_cache if mode != "train" else None)
        if seg_counts is not None:          # [reps, blocks, E]
            counts.append(seg_counts.reshape(-1, seg_counts.shape[-1]))
    return x, new_caches, total_aux, \
        (jnp.concatenate(counts) if counts else None)


def _decode_block(kind: str, cfg: ModelConfig, p: Params, x: jax.Array,
                  pool, layer, *, rope, mask_ids, pos, valid):
    """One block of the decode step against its segment's stacked pool
    ``pool`` [reps, ...] at layer ``layer`` (traced in the layer scan, an
    int when unrolled). Returns (x, pool, aux, counts).

    A block that caches per position (K/V, the MLA latent, kpos and the
    int8 scales) gets a layer view of the pool and writes its one new
    position per row in place (``layers.cache_write``); its attention
    reads that layer of the updated pool. Recurrent and xLSTM state is
    whole-state by nature and small: it is read and written per layer."""
    if kind in _POSITION_KINDS:
        x, view, aux, counts = _block_apply(
            kind, cfg, p, x, mode="decode", rope=rope, mask_ids=mask_ids,
            cache=dict(pool, layer=layer), pos=pos, valid=valid)
        return x, {k: v for k, v in view.items() if k != "layer"}, aux, \
            counts
    x, state, aux, counts = _block_apply(
        kind, cfg, p, x, mode="decode", rope=rope, mask_ids=mask_ids,
        cache=jax.tree.map(lambda a: a[layer], pool), pos=pos, valid=valid)
    pool = jax.tree.map(lambda a, n: a.at[layer].set(n.astype(a.dtype)),
                        pool, state)
    return x, pool, aux, counts


def _positions_default(cfg: ModelConfig, batch: int, seq: int):
    pos = jnp.arange(seq, dtype=jnp.int32)
    if cfg.m_rope_sections:
        pos = jnp.broadcast_to(pos, (3, seq))
    return pos


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def pack_ffn_params(cfg: ModelConfig, params: Params) -> Params:
    """Checkpoint conversion: trained masked-FFN weights -> per-sample packed
    serving weights (mask-zero skipping, paper §V-C / Fig. 4).

    Thin wrapper over the mask-compilation pipeline: every dense gated/plain
    FFN block's leaves are gathered by ``repro.core.plan.pack_ffn_leaves``
    (MoE experts and the recurrent-family block-internal masks keep the
    multiply form). Use with ``dataclasses.replace(cfg,
    packed_ffn_serving=True)``; numerically exact vs the masked form
    (tests/test_models_smoke.py)."""
    from repro.core import plan as plan_lib

    new = jax.tree.map(lambda x: x, params)  # shallow-ish copy
    for seg in new["segments"]:
        for block in seg.values():
            if isinstance(block, dict) and "ffn" in block and \
                    "masks" in block["ffn"]:
                # masks are identical across scan reps (same seed per config)
                block["ffn"] = plan_lib.pack_ffn_leaves(
                    block["ffn"], block["ffn"]["masks"][0])
    return new


def _embed_in(cfg: ModelConfig, params: Params, batch: Params) -> jax.Array:
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
    else:
        x = layers.embed_tokens(params["embed"], batch["tokens"])
    # residual stream: batch-sharded; sequence-sharded over "model" too
    # under sequence parallelism
    if cfg.seq_shard:
        return layers.constrain(x, ("batch", "model", None))
    return layers.constrain(x, ("batch", None, None))


def forward(cfg: ModelConfig, params: Params, batch: Params,
            mask_ids: jax.Array | None = None):
    """Training/eval graph: batch {tokens|embeds [B,S,*]} -> (logits
    [B,S,V], aux_loss). If cfg is Bayesian and mask_ids is None, the
    Masksembles batch-group assignment is used (training form)."""
    x = _embed_in(cfg, params, batch)
    b, s = x.shape[:2]
    if cfg.bayesian and mask_ids is None:
        mask_ids = masksembles.mask_ids_for_batch(b, cfg.mask_samples)
    pos = batch.get("positions", _positions_default(cfg, b, s))
    rope = _rope(cfg, pos)
    x, _, aux, _ = _run_stack(cfg, params, x, mode="train", rope=rope,
                              mask_ids=mask_ids)
    if cfg.seq_shard:
        # one bf16 gather of the final hidden state instead of per-shard
        # partial logits thrash (EXPERIMENTS §Perf qwen2-vl iteration 4)
        x = layers.constrain(x, ("batch", None, None))
    x = layers.norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    return layers.lm_head(params["embed"], x), aux


def prefill(cfg: ModelConfig, params: Params, batch: Params,
            max_seq: int | None = None,
            mask_ids: jax.Array | None = None,
            last_index: jax.Array | None = None,
            return_counts: bool = False):
    """Prefill: consume the prompt, return (last-token logits [B,V], caches)
    (and, with ``return_counts``, the dropless MoE layers' per-expert pair
    counts [n_moe, E] over the positions up to ``last_index``, or None).

    max_seq sizes the KV caches (defaults to prompt length).

    ``last_index`` (scalar, may be traced) selects which position's logits
    to return instead of the literal last — the bucketed-prefill form,
    where the prompt is zero-padded to a fixed bucket length and the true
    last token sits at ``length - 1``. Causal attention makes position
    ``last_index`` blind to the pad tail, so the gathered logits are
    bitwise those of an exact-length prefill; pair with
    :func:`cache_trim_positions` to also clear the pad tail's cache
    entries."""
    x = _embed_in(cfg, params, batch)
    b, s = x.shape[:2]
    if cfg.bayesian and mask_ids is None:
        mask_ids = masksembles.mask_ids_for_batch(b, cfg.mask_samples)
    max_seq = max_seq or s
    caches = init_cache(cfg, b, max_seq)
    pos = batch.get("positions", _positions_default(cfg, b, s))
    rope = _rope(cfg, pos)
    valid = None if last_index is None else \
        (jnp.arange(s) <= jnp.asarray(last_index, jnp.int32))[None]
    x, new_caches, _, counts = _run_stack(
        cfg, params, x, mode="prefill", rope=rope, mask_ids=mask_ids,
        caches=caches, valid=valid)
    if last_index is None:
        x = x[:, -1:, :]
    else:
        x = jax.lax.dynamic_slice_in_dim(
            x, jnp.asarray(last_index, jnp.int32), 1, axis=1)
    x = layers.norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = layers.lm_head(params["embed"], x)[:, 0]
    return (logits, new_caches, counts) if return_counts else \
        (logits, new_caches)


def decode_step(cfg: ModelConfig, params: Params, caches, tokens: jax.Array,
                pos: jax.Array, mask_ids: jax.Array | None = None,
                return_counts: bool = False):
    """One serving step: tokens [B,1] + caches @ pos -> (logits [B,V],
    new caches) (and, with ``return_counts``, the dropless MoE layers'
    per-expert pair counts [n_moe, E] over the rows at pos >= 0, or None).

    ``pos`` is a scalar () shared by the whole batch, or a per-row [B]
    vector — the continuous-batching form where every cache row advances
    at its own position (serving/server.py)."""
    x = layers.embed_tokens(params["embed"], tokens)
    b = x.shape[0]
    if cfg.bayesian and mask_ids is None:
        mask_ids = masksembles.mask_ids_for_batch(b, cfg.mask_samples)
    p = jnp.asarray(pos, jnp.int32)
    if p.ndim == 0:
        pos_arr = p[None] if not cfg.m_rope_sections else \
            jnp.broadcast_to(p, (3, 1))
    else:
        pos_arr = p[:, None] if not cfg.m_rope_sections else \
            jnp.broadcast_to(p[None, :, None], (3, b, 1))
    rope = _rope(cfg, pos_arr)
    valid = (p >= 0)[:, None] if p.ndim else None
    x, new_caches, _, counts = _run_stack(
        cfg, params, x, mode="decode", rope=rope, mask_ids=mask_ids,
        caches=caches, pos=p, valid=valid)
    x = layers.norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = layers.lm_head(params["embed"], x)[:, 0]
    return (logits, new_caches, counts) if return_counts else \
        (logits, new_caches)
