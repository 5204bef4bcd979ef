"""Shared functional layers for the architecture zoo.

Parameters are plain nested dicts (pytrees); every function is pure. Naming
of leaves is load-bearing: repro.distributed.sharding maps leaf *paths* to
PartitionSpecs, so weights follow the conventions
  wq/wk/wv/wo   — attention projections
  wg/wu/wd      — gated FFN (gate/up/down)
  embed/unembed — token embedding / LM head
  masks         — Masksembles constants (never trained)
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import masks as masks_lib
from repro.core import plan as plan_lib

Params = dict[str, Any]

# ---------------------------------------------------------------------------
# activation sharding hints
# ---------------------------------------------------------------------------


def constrain(x: jax.Array, spec: tuple) -> jax.Array:
    """Best-effort with_sharding_constraint against the ambient abstract mesh.

    spec entries: "batch" (-> ("pod","data") as available), a mesh axis name,
    or None. Entries whose axis doesn't exist or doesn't divide the dim are
    dropped, and with no mesh (CPU tests) this is the identity — model code
    stays mesh-agnostic while the dry-run gets GSPMD hints.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001 — no mesh machinery available
        return x
    if mesh is None or not mesh.axis_names:
        return x
    names = set(mesh.axis_names)
    sizes = dict(mesh.shape)
    resolved: list = []
    for i, a in enumerate(spec):
        if a == "batch":
            ba = tuple(ax for ax in ("pod", "data") if ax in names)
            tot = 1
            for ax in ba:
                tot *= sizes[ax]
            resolved.append((ba if len(ba) > 1 else ba[0])
                            if ba and x.shape[i] % tot == 0 else None)
        elif a in names and x.shape[i] % sizes[a] == 0:
            resolved.append(a)
        else:
            resolved.append(None)
    if all(r is None for r in resolved):
        return x
    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*resolved))


def axis_size(name: str) -> int:
    """Size of a mesh axis in the ambient abstract mesh (1 if absent)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return 1
    if mesh is None or name not in mesh.axis_names:
        return 1
    return dict(mesh.shape)[name]


# ---------------------------------------------------------------------------
# initializers / norms
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False,
               scale: float | None = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
               * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jax.Array) -> jax.Array:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def norm_init(width: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.ones((width,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((width,), dtype)
    return p


def norm_apply(p: Params, x: jax.Array, kind: str, eps: float = 1e-6
               ) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE, partial RoPE, M-RoPE)
# ---------------------------------------------------------------------------


def rope_cos_sin(positions: jax.Array, rot_dim: int, theta: float
                 ) -> tuple[jax.Array, jax.Array]:
    """positions [...] -> cos/sin [..., rot_dim/2] (fp32)."""
    half = rot_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_cos_sin(positions: jax.Array, rot_dim: int, theta: float,
                  sections: tuple[int, ...]) -> tuple[jax.Array, jax.Array]:
    """Qwen2-VL M-RoPE. positions [3, ...] (temporal/height/width streams);
    sections partition the rot_dim/2 frequency slots among the streams."""
    if sum(sections) != rot_dim // 2:
        raise ValueError(
            f"mrope sections {sections} must sum to rot_dim/2 = "
            f"{rot_dim // 2} — each frequency slot belongs to exactly "
            "one position stream")
    cos, sin = rope_cos_sin(positions, rot_dim, theta)  # [3, ..., half]
    parts_c, parts_s = [], []
    off = 0
    for i, sec in enumerate(sections):
        parts_c.append(cos[i, ..., off:off + sec])
        parts_s.append(sin[i, ..., off:off + sec])
        off += sec
    return jnp.concatenate(parts_c, -1), jnp.concatenate(parts_s, -1)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               rope_pct: float = 1.0) -> jax.Array:
    """x [..., S, dh] with cos/sin [..., S, rot/2]; split-half convention.
    rope_pct < 1 rotates only the leading fraction (StableLM-2 partial)."""
    dh = x.shape[-1]
    rot = int(dh * rope_pct)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    cos = cos[..., :rot // 2].astype(x.dtype)
    sin = sin[..., :rot // 2].astype(x.dtype)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([out, xp], -1) if rot < dh else out


# ---------------------------------------------------------------------------
# attention (GQA) — grouped einsum, three execution paths
# ---------------------------------------------------------------------------


def attn_init(key, cfg, dtype) -> Params:
    d, dh = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, h * dh, dtype, bias=cfg.qkv_bias),
        "wk": dense_init(kk, d, hkv * dh, dtype, bias=cfg.qkv_bias),
        "wv": dense_init(kv, d, hkv * dh, dtype, bias=cfg.qkv_bias),
        "wo": dense_init(ko, h * dh, d, dtype,
                         scale=1.0 / math.sqrt(h * dh)),
    }


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1).transpose(0, 2, 1, 3)   # [B, n, S, dh]


def _merge_heads(x: jax.Array) -> jax.Array:
    b, h, s, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * dh)


def _grouped_scores(q: jax.Array, k: jax.Array,
                    scores_f32: bool = True) -> jax.Array:
    """q [B,H,Sq,dh], k [B,Hkv,Sk,dh] -> scores [B,Hkv,G,Sq,Sk] without
    materializing the kv-head repeat (G = H/Hkv). scores_f32=False keeps
    the score matrix in bf16 (the MXU accumulates in f32 either way; only
    the stored matrix narrows) — halves the dominant HBM term of the
    XLA attention path (EXPERIMENTS §Perf, qwen2-vl iteration 4)."""
    b, h, sq, dh = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, h // hkv, sq, dh)
    out = jnp.einsum("bkgqd,bksd->bkgqs", qg, k,
                     preferred_element_type=jnp.float32)
    return out if scores_f32 else out.astype(q.dtype)


def _grouped_combine(p: jax.Array, v: jax.Array) -> jax.Array:
    """p [B,Hkv,G,Sq,Sk] x v [B,Hkv,Sk,dh] -> [B,H,Sq,dh]."""
    b, hkv, g, sq, _ = p.shape
    out = jnp.einsum("bkgqs,bksd->bkgqd", p.astype(v.dtype), v)
    return out.reshape(b, hkv * g, sq, -1)


def attention_full(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool, q_offset: int | jax.Array = 0,
                   window: int = 0, scores_f32: bool = True) -> jax.Array:
    """Reference path — materializes [Sq, Sk] scores. Used for small shapes
    and as the oracle for the chunked/flash paths."""
    dh = q.shape[-1]
    s = _grouped_scores(q, k, scores_f32) / math.sqrt(dh)
    sq, sk = s.shape[-2], s.shape[-1]
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_combine(p, v)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, chunk: int = 1024,
                      scores_f32: bool = True,
                      unroll: bool = False) -> jax.Array:
    """XLA path for long prefill: lax.scan over query chunks — peak memory
    O(chunk x S) instead of O(S^2). Exact (per-chunk softmax over the full
    key axis). The Pallas flash kernel replaces this on real TPU."""
    b, h, sq, dh = q.shape
    if sq % chunk:
        return attention_full(q, k, v, causal=causal,
                              scores_f32=scores_f32)
    qc = q.reshape(b, h, sq // chunk, chunk, dh).transpose(2, 0, 1, 3, 4)

    # checkpoint the chunk body: without it the scan stacks every chunk's
    # f32 score matrix as a backward residual (O(S^2) memory again — the
    # exact thing chunking is meant to avoid); with it the backward
    # recomputes one chunk's scores at a time.
    @jax.checkpoint
    def body(_, args):
        i, qi = args
        out = attention_full(qi, k, v, causal=causal, q_offset=i * chunk,
                             scores_f32=scores_f32)
        return None, out

    if unroll:  # cost probes: loop-free graph, same per-chunk structure
        outs = jnp.stack([body(None, (jnp.int32(i), qc[i]))[1]
                          for i in range(sq // chunk)])
    else:
        _, outs = jax.lax.scan(body, None,
                               (jnp.arange(sq // chunk), qc))
    return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, v.shape[-1])


def attention_banded(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     window: int, unroll: bool = False) -> jax.Array:
    """Sliding-window attention, linear in S: scan over query chunks of size
    `window`, each attending to a 2-window key band (RecurrentGemma local
    attention). Exact vs attention_full(window=window)."""
    b, h, sq, dh = q.shape
    w = window
    if sq <= w or sq % w:
        return attention_full(q, k, v, causal=True, window=w)
    hkv = k.shape[1]
    kp = jnp.pad(k, ((0, 0), (0, 0), (w, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (w, 0), (0, 0)))
    qc = q.reshape(b, h, sq // w, w, dh).transpose(2, 0, 1, 3, 4)

    @jax.checkpoint
    def body(_, args):
        i, qi = args
        start = i * w                                   # padded coords
        kb = jax.lax.dynamic_slice_in_dim(kp, start, 2 * w, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, 2 * w, axis=2)
        s = _grouped_scores(qi, kb) / math.sqrt(dh)     # [B,Hkv,G,w,2w]
        qpos = jnp.arange(w)[:, None] + w               # band-local coords
        kpos = jnp.arange(2 * w)[None, :]
        valid = (kpos <= qpos) & (kpos > qpos - w) & (kpos + start >= w)
        s = jnp.where(valid, s, -1e30)
        out = _grouped_combine(jax.nn.softmax(s, -1), vb)
        return None, out

    if unroll:
        outs = jnp.stack([body(None, (jnp.int32(i), qc[i]))[1]
                          for i in range(sq // w)])
    else:
        _, outs = jax.lax.scan(body, None, (jnp.arange(sq // w), qc))
    return outs.transpose(1, 2, 0, 3, 4).reshape(b, h, sq, dh)


def attention_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kpos: jax.Array, pos: jax.Array,
                     k_scale: jax.Array | None = None,
                     v_scale: jax.Array | None = None) -> jax.Array:
    """One-token decode: q [B,H,1,dh] vs cache [B,Smax,Hkv,dh] (position-
    major, the layout the cache is stored in). ``kpos`` [B,Smax] holds the
    global position stored in each row's cache slot (-1 = empty); slots
    with kpos > pos or kpos < 0 are masked (covers both the linear cache
    and the rolling local-window cache). ``pos`` is a scalar (whole batch
    at one position) or per-row [B] (continuous batching: every row
    decodes at its own position). ``k_scale``/``v_scale`` [B,Smax,Hkv]
    dequantize an int8 cache at the gather (per-slot symmetric scales from
    :func:`quantize_kv`)."""
    b, h, sq, dh = q.shape
    hkv = k_cache.shape[2]
    if k_scale is not None:
        k_cache = k_cache.astype(jnp.float32) * k_scale[..., None]
        v_cache = v_cache.astype(jnp.float32) * v_scale[..., None]
    elif k_cache.dtype != q.dtype:
        # a cache narrower than the model (bf16 KV under an f32 model) is
        # read up to the model dtype, as the fused decode kernel does —
        # otherwise the probabilities and the combine round to bf16
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
    qg = q.reshape(b, hkv, h // hkv, sq, dh)
    s = jnp.einsum("bkgqd,bskd->bkgqs", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    pos = jnp.asarray(pos, jnp.int32)
    qpos = pos[:, None] if pos.ndim else pos
    valid = (kpos >= 0) & (kpos <= qpos)                # [B,Smax]
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, h, sq, -1)


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2/V3) — expanded prefill, absorbed decode
# ---------------------------------------------------------------------------
#
# Per position one latent is cached: the RMS-normed c_kv (kv_lora_rank) and
# the roped key part shared by all heads (qk_rope_dim). Prefill expands it to
# per-head K/V through ``wkv_b`` and attends causally; decode keeps the heads
# in latent space: q_nope·W_uk^T is scored against c_kv, q_rope against the
# cached k_rope, and the latent-space output goes through W_uv. The two paths
# are the same function in exact arithmetic and differ in rounding only.
# RoPE pairs dimension i with i + rope/2 (rotate-half) on the projection's
# own output: HF DeepSeek de-interleaves first, which is a fixed permutation
# of the rope columns of ``wq`` and ``wkv_a``.


def mla_init(key, cfg, dtype) -> Params:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {"wq": dense_init(kq, d, h * (nope + rope), dtype),
            "wkv_a": dense_init(ka, d, r + rope, dtype),
            "kv_norm": norm_init(r, "rmsnorm", dtype),
            "wkv_b": dense_init(kb, r, h * (nope + dv), dtype),
            "wo": dense_init(ko, h * dv, d, dtype,
                             scale=1.0 / math.sqrt(h * dv))}


def mla_project(p: Params, xn: jax.Array, cfg, cos: jax.Array,
                sin: jax.Array):
    """Queries and latent of xn [B,S,D] at the positions of cos/sin:
    (q_nope [B,H,S,nope], q_rope [B,H,S,rope] roped, latent [B,S,r+rope]
    = normed c_kv ++ roped shared k_rope)."""
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    q = _split_heads(dense(p["wq"], xn), cfg.n_heads)
    q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], cos, sin)
    kv = dense(p["wkv_a"], xn)
    c = norm_apply(p["kv_norm"], kv[..., :r], "rmsnorm", cfg.norm_eps)
    k_rope = apply_rope(kv[:, None, :, r:], cos, sin)[:, 0]
    return q_nope, q_rope, jnp.concatenate([c, k_rope.astype(c.dtype)], -1)


def mla_expand(p: Params, latent: jax.Array, cfg
               ) -> tuple[jax.Array, jax.Array]:
    """Per-head K [B,H,S,nope+rope] and V [B,H,S,dv] from the latent."""
    r, nope, h = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.n_heads
    kv = _split_heads(dense(p["wkv_b"], latent[..., :r]), h)
    k_rope = jnp.broadcast_to(latent[:, None, :, r:],
                              kv.shape[:-1] + (cfg.qk_rope_dim,))
    return jnp.concatenate([kv[..., :nope], k_rope], -1), kv[..., nope:]


def mla_decode(p: Params, q_nope: jax.Array, q_rope: jax.Array,
               latent: jax.Array, kpos: jax.Array, pos: jax.Array, cfg
               ) -> jax.Array:
    """Absorbed one-token attention: q_nope [B,H,1,nope], q_rope
    [B,H,1,rope] against the latent cache [B,Smax,r+rope] (``kpos``
    masks as in :func:`attention_decode`) -> [B,H,1,dv].

    Both contractions take the whole cached latent: the scores against
    ``[q_nope·W_uk^T, q_rope]``, the combine over every latent column with
    the rope columns dropped after it. Neither slices the cache, so no
    copy of its c_kv part is made."""
    r, h = cfg.kv_lora_rank, cfg.n_heads
    nope, dv = cfg.qk_nope_dim, cfg.v_head_dim
    wkv_b = p["wkv_b"]["w"].reshape(r, h, nope + dv)
    lat = latent.astype(q_nope.dtype)
    q_lat = jnp.einsum("bhqn,rhn->bhqr", q_nope, wkv_b[..., :nope])
    q_all = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], -1)
    s = jnp.einsum("bhqw,bsw->bhqs", q_all, lat,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(nope + cfg.qk_rope_dim)
    pos = jnp.asarray(pos, jnp.int32)
    qpos = pos[:, None] if pos.ndim else pos
    valid = (kpos >= 0) & (kpos <= qpos)                # [B,Smax]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
    o_lat = jnp.einsum("bhqs,bsw->bhqw", pr, lat)[..., :r]
    return jnp.einsum("bhqr,rhv->bhqv", o_lat, wkv_b[..., nope:])


def latent_cache(batch: int, max_seq: int, width: int, dtype,
                 as_spec: bool = False) -> Params:
    """The MLA cache of one layer: ``latent`` [B,Smax,width] (slot ==
    position on axis 1, as for K/V) and ``kpos`` [B,Smax]."""
    if as_spec:
        return {"latent": jax.ShapeDtypeStruct((batch, max_seq, width),
                                               dtype),
                "kpos": jax.ShapeDtypeStruct((batch, max_seq), jnp.int32)}
    return {"latent": jnp.zeros((batch, max_seq, width), dtype),
            "kpos": jnp.full((batch, max_seq), -1, jnp.int32)}


def latent_cache_update(cache: Params, new: jax.Array, pos: jax.Array
                        ) -> Params:
    """Write one step's latent [B,1,width] at slot ``pos`` (scalar or per
    row [B]); a row at pos -1 writes a slot it marks empty. ``cache`` is
    one layer's cache or a layer view of a stacked pool
    (:func:`cache_write`).

    The latent is written row by row. Its width (576 in DeepSeek-V3) is
    not a whole number of 128-lane tiles, so the TPU stores the pool with
    the slot axis minor; one batched scatter of [B, width] rows makes the
    compiler re-lay the whole pool out around the layer loop, while a
    write per row keeps its layout (the compiled-program test in
    tests/test_tpu_compile.py holds this)."""
    pos = jnp.asarray(pos, jnp.int32)
    slot = pos % cache["kpos"].shape[-1]
    out = cache_write(cache, slot, {"latent": new[:, 0]}, by_row=True)
    return cache_write(out, slot,
                       {"kpos": jnp.broadcast_to(pos, new.shape[:1])})


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------


def kv_store_dtype(dtype, kv_dtype: str = ""):
    """Cache storage dtype for a ``ModelConfig.kv_dtype`` tag."""
    return {"": dtype, "bfloat16": jnp.bfloat16, "int8": jnp.int8}[kv_dtype]


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(row, head, position) symmetric int8 of K/V [..., S, dh] ->
    (q int8 same shape, scale f32 [..., S]) — one scale per cached vector,
    the granularity the decode gather dequantizes at."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def init_kv_cache(batch: int, n_kv: int, max_seq: int, dh: int, dtype,
                  kv_dtype: str = "") -> Params:
    """One layer's K/V cache, position-major: ``k``/``v`` [B,Smax,Hkv,dh],
    ``kpos`` [B,Smax] and, for int8, ``kscale``/``vscale`` [B,Smax,Hkv].
    Slot is axis 1 of every leaf, and one slot of one row is contiguous."""
    return jax.tree.map(
        lambda s: jnp.full(s.shape, -1 if s.dtype == jnp.int32 else 0,
                           s.dtype),
        kv_cache_specs(batch, n_kv, max_seq, dh, dtype, kv_dtype))


def kv_cache_specs(batch: int, n_kv: int, max_seq: int, dh: int, dtype,
                   kv_dtype: str = "") -> Params:
    store = kv_store_dtype(dtype, kv_dtype)
    out = {
        "k": jax.ShapeDtypeStruct((batch, max_seq, n_kv, dh), store),
        "v": jax.ShapeDtypeStruct((batch, max_seq, n_kv, dh), store),
        "kpos": jax.ShapeDtypeStruct((batch, max_seq), jnp.int32),
    }
    if kv_dtype == "int8":
        out["kscale"] = jax.ShapeDtypeStruct((batch, max_seq, n_kv),
                                             jnp.float32)
        out["vscale"] = jax.ShapeDtypeStruct((batch, max_seq, n_kv),
                                             jnp.float32)
    return out


def cache_write(cache: Params, slot: jax.Array, new: Params,
                by_row: bool = False) -> Params:
    """Write ``new[name]`` [B,...] at ``slot`` (scalar, or per row [B]) of
    each named leaf of a cache, whose slot axis is the one after the
    batch. ``cache`` is one layer's leaves [B,Smax,...], or a layer view
    of a segment's stacked pool: its leaves [L,B,Smax,...] with
    ``cache["layer"]`` naming the layer. A view is written at
    [layer, row, slot] and nowhere else, so a pool carried through the
    layer scan (or donated) is updated in place, one position per row.
    Per-row slots take one batched scatter, or with ``by_row`` one
    dynamic-update-slice per row. Leaves not in ``new`` pass through."""
    layer = cache.get("layer")
    lead = () if layer is None else (layer,)
    out = dict(cache)
    for name, val in new.items():
        leaf, val = cache[name], val.astype(cache[name].dtype)
        if slot.ndim and not by_row:
            out[name] = leaf.at[lead + (jnp.arange(val.shape[0]), slot)
                                ].set(val)
            continue
        # (first row, slot, rows): every row at the shared slot, or each row
        # at its own
        parts = [(0, slot, val)] if slot.ndim == 0 else \
            [(r, slot[r], val[r:r + 1]) for r in range(val.shape[0])]
        for row, at, rows in parts:
            upd = jnp.expand_dims(rows, (0, 2) if lead else 1)
            leaf = jax.lax.dynamic_update_slice(
                leaf, upd, lead + (row, at) + (0,) * (val.ndim - 1))
        out[name] = leaf
    return out


def cache_layer(cache: Params) -> Params:
    """The leaves of one layer: a view's layer of the stacked pool, or
    ``cache`` itself when it is one layer's."""
    layer = cache.get("layer")
    if layer is None:
        return cache
    return {k: v[layer] for k, v in cache.items() if k != "layer"}


def kv_cache_update(cache: Params, k_new: jax.Array, v_new: jax.Array,
                    pos: jax.Array, window: int = 0) -> Params:
    """Write one step's K/V [B,Hkv,1,dh] at slot ``pos`` (or ``pos % W``
    rolling).

    ``pos`` is a scalar (uniform batch — one dynamic-slice write) or a
    per-row [B] vector (continuous batching — each row writes its own slot
    via a batched scatter). ``cache`` is one layer's cache or a layer view
    of a stacked pool (:func:`cache_write`). The fresh k/v are cast to the
    cache's storage dtype *at commit* (bf16 caches write narrowed values;
    attention reads upcast) — an int8 cache (``kscale``/``vscale`` leaves
    present) quantizes per cached vector via :func:`quantize_kv`
    instead."""
    smax = cache["kpos"].shape[-1]
    pos = jnp.asarray(pos, jnp.int32)
    slot = ((pos % window) if window else pos) % smax
    k_new, v_new = k_new[:, :, 0], v_new[:, :, 0]          # [B,Hkv,dh]
    new = {"kpos": jnp.broadcast_to(pos, k_new.shape[:1])}
    if "kscale" in cache:
        new["k"], new["kscale"] = quantize_kv(k_new)
        new["v"], new["vscale"] = quantize_kv(v_new)
    else:
        new["k"], new["v"] = k_new, v_new
    return cache_write(cache, slot, new)


# ---------------------------------------------------------------------------
# FFNs — gated (SwiGLU/GeGLU), plain MLP, and the paper's Masksembles form
# ---------------------------------------------------------------------------


def ffn_init(key, cfg, d_ff: int | None = None, dtype=None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dtype = dtype or cfg.dtype
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.bayesian and cfg.packed_ffn_serving:
        # serving form (mask-zero skipping, paper §V-C): per-sample packed
        # dense weights over the KEPT hidden units only — no masks in the
        # graph. Shapes [N, d, K]; real deployments convert a trained
        # checkpoint via models.pack_ffn_params (equivalence tested).
        n = cfg.mask_samples
        kk = masks_lib.keep_count(f, n, cfg.mask_scale)
        sc = 1.0 / math.sqrt(d)
        def pinit(k, shape, s):
            return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)
        if cfg.activation in ("silu", "gelu"):
            return {"wgp": pinit(k1, (n, d, kk), sc),
                    "wup": pinit(k2, (n, d, kk), sc),
                    "wdp": pinit(k3, (n, kk, d), 1.0 / math.sqrt(kk))}
        return {"wup": pinit(k1, (n, d, kk), sc),
                "wdp": pinit(k2, (n, kk, d), 1.0 / math.sqrt(kk))}
    if cfg.activation in ("silu", "gelu"):       # gated
        p = {"wg": dense_init(k1, d, f, dtype),
             "wu": dense_init(k2, d, f, dtype),
             "wd": dense_init(k3, f, d, dtype)}
    else:                                        # plain MLP (gelu_mlp)
        p = {"wu": dense_init(k1, d, f, dtype, bias=True),
             "wd": dense_init(k2, f, d, dtype, bias=True)}
    if cfg.bayesian:
        spec = masks_lib.MaskSpec(width=f, n_masks=cfg.mask_samples,
                                  scale=cfg.mask_scale, seed=cfg.mask_seed)
        p["masks"] = jnp.asarray(masks_lib.generate_masks(spec), dtype)
    return p


def ffn_apply(p: Params, x: jax.Array, cfg,
              mask_ids: jax.Array | None = None) -> jax.Array:
    """Gated or plain FFN; if the config is Bayesian and mask_ids [B] are
    given, the fixed Masksembles mask multiplies the hidden units — the
    paper's technique at its transformer integration point. Activations are
    zero-preserving, so the serving path may pack instead (packed leaves,
    mask-zero skipping: rows must be grouped [sample0 rows..., sample1
    rows, ...] as serve_uncertain arranges)."""
    act = plan_lib.activation_fn(cfg.activation)
    if "wdp" in p:                               # packed serving form —
        # executed by the mask-compilation pipeline (one implementation)
        return plan_lib.ffn_leaves_apply(p, x, cfg.activation)
    if "wg" in p:
        h = act(dense(p["wg"], x)) * dense(p["wu"], x)
    else:
        h = act(dense(p["wu"], x))
    if mask_ids is not None and "masks" in p:
        m = p["masks"][mask_ids]                 # [B, F]
        h = h * m[:, None, :] if h.ndim == 3 else h * m
    return dense(p["wd"], h)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


def embed_init(key, cfg, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"embed": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model),
                                     jnp.float32) * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(k2, cfg.d_model, cfg.vocab_size, dtype)
    return p


def embed_tokens(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embed"], tokens, axis=0)


def lm_head(p: Params, x: jax.Array) -> jax.Array:
    if "unembed" in p:
        return dense(p["unembed"], x)
    return x @ p["embed"].T
