"""Fused whole-plan executor: spec IR + pure-jnp oracle tier.

This module owns the *contract* between ``core/plan.lower_fused`` and the
three execution tiers (Pallas-TPU / Pallas-interpret in kernel.py, the
pure-XLA reference here): a :class:`FusedSpec` is a flat, hashable chain of
matmul/elementwise steps over a running hidden state, with every weight
either sample-shared or per-sample-row (``n_rows = groups × n_masks`` packed
weight sets). The oracle executes the chain with plain einsums — same
contraction order as the per-op ``plan.execute`` path — and is what the
equivalence tests assert against.

Params travel as a flat tuple ordered by :func:`param_slots`: for each dense
step, ``w`` then (if present) shared bias ``b`` then per-sample bias ``bp``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["FusedStep", "FusedSpec", "FusedPlanUnsupported", "param_slots",
           "act_fn", "fused_plan_ref", "fused_moments_ref",
           "FusedDecodeSpec", "decode_param_slots", "fused_decode_ref",
           "REL_UNC_EPS"]


class FusedPlanUnsupported(NotImplementedError):
    """Raised when a PackedPlan cannot run through the fused executor
    (unknown op kind, or a footprint the moments kernel cannot hold
    VMEM-resident). Callers fall back to the per-op ``plan.execute`` path."""


#: Same table as core/plan.ACTIVATIONS — duplicated here (not imported) so
#: the kernel tier never has to import the compiler package.
_ACTS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "relu": jax.nn.relu, "gelu": jax.nn.gelu, "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh, "identity": lambda x: x,
}


def act_fn(name: str) -> Callable[[jax.Array], jax.Array]:
    return _ACTS["gelu" if name == "gelu_mlp" else name]


@dataclasses.dataclass(frozen=True)
class FusedStep:
    """One step of the fused chain.

    The feed-forward kinds (:class:`FusedSpec` chains):

    kind='dense': ``h @ w (+ b) (+ bp[n]) -> activation`` with ``w`` indexed
    by the sample row when ``per_sample`` (``[n_rows, d_in, d_out]``) and
    shared (``[d_in, d_out]``) otherwise. kind='act': bare elementwise
    nonlinearity (no params; only emitted when it cannot fuse into the
    preceding dense).

    The serving-decode kinds (:class:`FusedDecodeSpec` chains — the decode
    step of a transformer stack lowered onto the same vocabulary):

    kind='norm': rms/layer norm (``norm`` selects which; params ``scale``
    [+ ``bias`` iff ``shared_bias``]) of the residual stream into the
    working hidden state.

    kind='attn': one whole attention sub-layer on the working state — q/k/v
    projections (+ bias iff ``qkv_bias``), RoPE over the leading ``rot_dim``
    lanes of each head, the KV *gather* over this step's slot-pool cache
    rows, masked softmax attention with the step's fresh k/v appended (the
    slot the per-op path would overwrite is masked out — same attended set,
    no in-kernel cache mutation), output projection, residual add. params:
    ``wq [,bq], wk [,bk], wv [,bv], wo``; the fresh per-row k/v are emitted
    so the caller can commit them to the cache outside the launch.

    kind='ffn': the (optionally ``gated``, optionally Bayesian) FFN
    sub-layer + residual add. Masked-multiply form (``masked``): params
    ``[wg,] wu [,bu], wd [,bd], mask`` where ``mask`` is the pre-gathered
    per-row mask matrix ``[R, d_hidden]``; packed per-sample form
    (``per_sample``): params ``[wgp,] wup, wdp`` shaped ``[N, d, K]`` /
    ``[N, K, d]`` with mask-major row groups (row ``r`` uses sample
    ``r // (R/N)``) — the serving slot-pool layout.
    """
    kind: str                       # 'dense' | 'act' | 'norm' | 'attn' | 'ffn'
    activation: str | None = None
    per_sample: bool = False
    shared_bias: bool = False
    sample_bias: bool = False
    d_in: int = 0
    d_out: int = 0
    # --- decode-chain fields (defaults keep feed-forward specs unchanged) --
    norm: str = "rmsnorm"           # kind='norm': 'rmsnorm' | 'layernorm'
    n_heads: int = 0                # kind='attn'
    n_kv_heads: int = 0
    head_dim: int = 0
    rot_dim: int = 0                # rotated lanes per head (partial RoPE)
    window: int = 0                 # local attention window (0 = global)
    qkv_bias: bool = False
    gated: bool = False             # kind='ffn': gated (SwiGLU/GeGLU) form
    masked: bool = False            # kind='ffn': mask-matrix multiply form
    ffn_bias: bool = False          # kind='ffn': plain-MLP biases on wu/wd
    d_hidden: int = 0               # kind='ffn': hidden width (F or keep K)
    # --- precision (default "" keeps fp32 specs hash/eq-identical) ---------
    w_dtype: str = ""               # kind='dense': "" (native) | "int8" —
    #                                 int8 adds a 'ws' scale slot after 'w'
    #                                 and the tiers dequantize in-kernel


@dataclasses.dataclass(frozen=True)
class FusedSpec:
    """Static description of a whole-plan fused execution (hashable — the
    jit/lru cache key in ``core/plan``)."""
    steps: tuple[FusedStep, ...]
    n_rows: int                     # kernel sample axis (groups × n_masks)
    n_masks: int
    groups: int
    d_in: int                       # chain input width
    d_out: int                      # final per-row output width

    def __post_init__(self) -> None:
        if self.n_rows != self.groups * self.n_masks:
            raise ValueError(f"n_rows {self.n_rows} != groups*n_masks")
        if not any(s.kind == "dense" for s in self.steps):
            raise FusedPlanUnsupported("fused chain has no dense step")

    @property
    def weight_elements(self) -> int:
        """Total (unpadded) weight+bias elements — VMEM sizing input."""
        tot = 0
        for s in self.steps:
            if s.kind != "dense":
                continue
            rows = self.n_rows if s.per_sample else 1
            tot += rows * s.d_in * s.d_out
            if s.shared_bias:
                tot += s.d_out
            if s.sample_bias:
                tot += self.n_rows * s.d_out
        return tot


def param_slots(spec: FusedSpec) -> tuple[tuple[int, str], ...]:
    """Flat param ordering: (step index, 'w'|'ws'|'b'|'bp') per array.

    'ws' (per-output-channel dequant scales, bf16
    ``w.shape[:-2] + (1, d_out)``) is emitted right after 'w' iff the step
    carries a quantized weight (``w_dtype``)."""
    slots: list[tuple[int, str]] = []
    for i, st in enumerate(spec.steps):
        if st.kind != "dense":
            continue
        slots.append((i, "w"))
        if st.w_dtype:
            slots.append((i, "ws"))
        if st.shared_bias:
            slots.append((i, "b"))
        if st.sample_bias:
            slots.append((i, "bp"))
    return tuple(slots)


def _slot_table(spec: FusedSpec, params: tuple[jax.Array, ...]
                ) -> dict[tuple[int, str], jax.Array]:
    slots = param_slots(spec)
    if len(slots) != len(params):
        raise ValueError(f"fused spec expects {len(slots)} params, "
                         f"got {len(params)}")
    return dict(zip(slots, params))


def fused_plan_ref(spec: FusedSpec, x: jax.Array,
                   params: tuple[jax.Array, ...]) -> jax.Array:
    """Oracle tier: x [B, d_in] -> per-row samples [n_rows, B, d_out].

    Shared prefix steps run once on [B, d]; the first per-sample step
    introduces the row axis and the rest of the chain is sample-major
    einsums (the batch-level contraction order).
    """
    table = _slot_table(spec, params)
    h = x
    for i, st in enumerate(spec.steps):
        if st.kind == "act":
            h = act_fn(st.activation)(h)
            continue
        w = table[(i, "w")]
        if st.w_dtype:              # in-place dequant: q * per-channel scale
            w = w.astype(jnp.float32) \
                * table[(i, "ws")].astype(jnp.float32)
        if st.per_sample:
            lead = "bd" if h.ndim == 2 else "nbd"
            y = jnp.einsum(f"{lead},ndk->nbk", h, w)
        elif h.ndim == 2:
            y = h @ w
        else:
            y = jnp.einsum("nbd,dk->nbk", h, w)
        if st.shared_bias:
            y = y + table[(i, "b")]
        if st.sample_bias:
            bp = table[(i, "bp")]
            if y.ndim == 2:             # per-sample bias on a shared value
                y = y[None] + bp[:, None, :]
            else:
                y = y + bp[:, None, :]
        if st.activation:
            y = act_fn(st.activation)(y)
        h = y
    if h.ndim == 2:                     # fully shared chain: rows identical
        h = jnp.broadcast_to(h[None], (spec.n_rows,) + h.shape)
    return h


# ---------------------------------------------------------------------------
# fused serving-decode chain (FusedDecodeSpec)
# ---------------------------------------------------------------------------

#: Same value as core/uncertainty.REL_UNC_EPS — duplicated (not imported) so
#: the kernel tier never has to import the compiler/metrics packages.
REL_UNC_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class FusedDecodeSpec:
    """Static description of one fused serving decode step (hashable — the
    jit/lru cache key of ``core/plan.compile_decode_step``).

    ``steps`` is the unrolled per-layer chain
    ``(norm, attn, norm, ffn) × L + (norm, dense-lm-head)``; scan-stacked
    segments are flattened at lowering so each 'attn' step owns one cache
    entry (in step order). Rows are mask-major: row ``r`` of the pool is
    mask-sample ``r // b`` of request-batch column ``r % b`` with
    ``b = rows / n_samples``; the posterior epilogue reduces the log-prob
    rows of each column over its ``n_samples`` group with a running Welford
    (mean, M2) — the ``kernels/moments`` scheme — and returns
    ``(mean_logp [b, V], rel_unc [b])`` without materializing per-sample
    log-probs in HBM.
    """
    steps: tuple[FusedStep, ...]
    n_samples: int                  # posterior sample count (1 = degenerate)
    d_model: int
    vocab: int
    kv_dtype: str = ""              # cache storage dtype ("" = model dtype;
    #                                 "bfloat16" supported fused — attention
    #                                 upcasts cache reads to f32; "int8"
    #                                 caches serve per-op only)

    def __post_init__(self) -> None:
        if self.n_samples < 1:
            raise ValueError(f"n_samples {self.n_samples} < 1")
        if not any(s.kind == "attn" for s in self.steps):
            raise FusedPlanUnsupported("fused decode chain has no attention")

    @property
    def n_attn(self) -> int:
        """Cache entries consumed (one per 'attn' step, in step order)."""
        return sum(s.kind == "attn" for s in self.steps)


def decode_param_slots(spec: FusedDecodeSpec) -> tuple[tuple[int, str], ...]:
    """Flat param ordering of a decode chain: (step index, name) per array."""
    slots: list[tuple[int, str]] = []
    for i, st in enumerate(spec.steps):
        if st.kind == "norm":
            slots.append((i, "scale"))
            if st.shared_bias:
                slots.append((i, "bias"))
        elif st.kind == "attn":
            for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")):
                slots.append((i, w))
                if st.qkv_bias:
                    slots.append((i, b))
            slots.append((i, "wo"))
        elif st.kind == "ffn":
            if st.per_sample:
                slots += [(i, n) for n in
                          (("wgp",) if st.gated else ()) + ("wup", "wdp")]
            else:
                if st.gated:
                    slots.append((i, "wg"))
                slots.append((i, "wu"))
                if st.ffn_bias:
                    slots.append((i, "bu"))
                slots.append((i, "wd"))
                if st.ffn_bias:
                    slots.append((i, "bd"))
                if st.masked:
                    slots.append((i, "mask"))
        elif st.kind == "dense":
            slots.append((i, "w"))
            if st.shared_bias:
                slots.append((i, "b"))
        elif st.kind != "act":
            raise FusedPlanUnsupported(f"step kind {st.kind!r} in decode "
                                       f"chain")
    return tuple(slots)


def _decode_table(spec: FusedDecodeSpec, params: tuple[jax.Array, ...]
                  ) -> dict[tuple[int, str], jax.Array]:
    slots = decode_param_slots(spec)
    if len(slots) != len(params):
        raise ValueError(f"decode spec expects {len(slots)} params, "
                         f"got {len(params)}")
    return dict(zip(slots, params))


def norm_fn(h: jax.Array, scale: jax.Array, bias: jax.Array | None,
            kind: str, eps: float = 1e-6) -> jax.Array:
    """f32 rms/layer norm — same math as models/layers.norm_apply."""
    hf = h.astype(jnp.float32)
    if kind == "rmsnorm":
        y = hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(hf, -1, keepdims=True)
        var = jnp.var(hf, -1, keepdims=True)
        y = (hf - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y


def rope_rotate(x: jax.Array, cos: jax.Array, sin: jax.Array,
                rot: int) -> jax.Array:
    """Split-half RoPE on one head: x [R, dh], cos/sin [R, rot/2]."""
    if rot == 0:
        return x
    half = rot // 2
    x1, x2, xp = x[:, :half], x[:, half:rot], x[:, rot:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([out, xp], -1) if rot < x.shape[-1] else out


def welford_posterior(logp: jax.Array, n: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Posterior of one decode step via running Welford over the mask axis:
    logp [n·b, V] (mask-major rows) -> (mean_logp [b, V], rel_unc [b]).
    Matches ``serving.server.posterior`` of the same rows (which goes
    through ``uncertainty.predictive_moments``) to fp tolerance."""
    b = logp.shape[0] // n
    mean = logp[:b]
    m2 = jnp.zeros_like(mean)
    for k in range(1, n):
        y = logp[k * b:(k + 1) * b]
        delta = y - mean
        mean = mean + delta / (k + 1)
        m2 = m2 + delta * (y - mean)
    std = jnp.sqrt(m2 / n)
    tok = jnp.argmax(mean, -1)
    onehot = (jnp.arange(mean.shape[-1])[None, :] == tok[:, None])
    std_t = jnp.sum(jnp.where(onehot, std, 0.0), -1)
    mean_t = jnp.sum(jnp.where(onehot, mean, 0.0), -1)
    rel = std_t / jnp.maximum(jnp.abs(mean_t), REL_UNC_EPS)
    return mean, rel


def decode_attn_ref(st: FusedStep, h: jax.Array, p: dict, cache, pos, cos,
                    sin) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One 'attn' step (oracle form): h [R, d] -> (sub-layer output [R, d],
    k_new [R, hkv, dh], v_new [R, hkv, dh]).

    KV gather + attention over the slot-pool cache: the fresh k/v are
    appended as an extra key slot and the cache slot the per-op
    ``kv_cache_update`` would overwrite (``slot = (pos % window) % smax``)
    is masked out, so the attended set is exactly the per-op path's
    post-update cache."""
    hh, hkv, dh, rot = st.n_heads, st.n_kv_heads, st.head_dim, st.rot_dim
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if st.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    kc, vc, kpos = cache                          # kc, vc [R, S, hkv, dh]
    smax = kc.shape[1]
    slot = ((pos % st.window) if st.window else pos) % smax        # [R]
    valid = (kpos >= 0) & (kpos <= pos[:, None]) \
        & (jnp.arange(smax)[None, :] != slot[:, None])             # [R, S]
    scale = 1.0 / math.sqrt(dh)
    k_heads = [rope_rotate(k[:, j * dh:(j + 1) * dh], cos, sin, rot)
               for j in range(hkv)]
    outs = []
    for i in range(hh):
        j = i // (hh // hkv)
        qi = rope_rotate(q[:, i * dh:(i + 1) * dh], cos, sin, rot)
        s_old = jnp.sum(qi[:, None, :].astype(jnp.float32)
                        * kc[:, :, j].astype(jnp.float32), -1) * scale
        s_new = jnp.sum(qi * k_heads[j], -1).astype(jnp.float32) * scale
        s_all = jnp.concatenate(
            [jnp.where(valid, s_old, -1e30), s_new[:, None]], -1)  # [R, S+1]
        pr = jax.nn.softmax(s_all, -1)
        oi = jnp.sum(pr[:, :smax, None] * vc[:, :, j].astype(jnp.float32),
                     1) \
            + pr[:, smax:] * v[:, j * dh:(j + 1) * dh]
        outs.append(oi)
    y = jnp.concatenate(outs, -1) @ p["wo"]
    k_new = jnp.stack(k_heads, 1)                                  # [R,hkv,dh]
    v_new = jnp.stack([v[:, j * dh:(j + 1) * dh] for j in range(hkv)], 1)
    return y, k_new, v_new


def decode_ffn_ref(st: FusedStep, h: jax.Array, p: dict) -> jax.Array:
    """One 'ffn' step: h [R, d] -> sub-layer output [R, d] (pre-residual)."""
    act = act_fn(st.activation)
    if st.per_sample:                   # packed per-sample serving weights
        n = p["wup"].shape[0]
        r = h.shape[0]
        b = r // n
        outs = []
        for m in range(n):
            hm = h[m * b:(m + 1) * b]
            if st.gated:
                mid = act(hm @ p["wgp"][m]) * (hm @ p["wup"][m])
            else:
                mid = act(hm @ p["wup"][m])
            outs.append(mid @ p["wdp"][m])
        return jnp.concatenate(outs, 0)
    up = h @ p["wu"]
    if st.ffn_bias:
        up = up + p["bu"]
    mid = act(h @ p["wg"]) * up if st.gated else act(up)
    if st.masked:
        mid = mid * p["mask"]
    y = mid @ p["wd"]
    if st.ffn_bias:
        y = y + p["bd"]
    return y


def fused_decode_ref(spec: FusedDecodeSpec, x: jax.Array,
                     params: tuple[jax.Array, ...],
                     caches: tuple[jax.Array, ...],
                     pos: jax.Array, cos: jax.Array, sin: jax.Array):
    """Oracle tier of the fused decode step.

    x [R, d_model] (embedded tokens), params per ``decode_param_slots``
    order, caches the flattened ``(k [R,S,hkv,dh], v, kpos [R,S])`` triples
    (one per 'attn' step, in step order), pos [R] (per-row decode
    positions, -1 = inactive row), cos/sin [R, rot/2] ->
    ``(mean_logp [b, V], rel_unc [b], k_new, v_new)`` with k_new/v_new
    ``[n_attn, R, hkv, dh]`` (the caller commits them to the cache). All
    compute in f32 — the serving posterior's dtype.
    """
    table = _decode_table(spec, params)
    resid = x.astype(jnp.float32)
    h = resid
    knews, vnews = [], []
    for i, st in enumerate(spec.steps):
        p = {name: arr for (j, name), arr in table.items() if j == i}
        if st.kind == "norm":
            h = norm_fn(resid, p["scale"], p.get("bias"), st.norm)
        elif st.kind == "attn":
            ai = len(knews)
            y, kn, vn = decode_attn_ref(st, h, p, caches[3 * ai: 3 * ai + 3],
                                        pos, cos, sin)
            resid = resid + y
            h = resid
            knews.append(kn)
            vnews.append(vn)
        elif st.kind == "ffn":
            resid = resid + decode_ffn_ref(st, h, p)
            h = resid
        elif st.kind == "dense":
            h = h @ p["w"]
            if st.shared_bias:
                h = h + p["b"]
            if st.activation:
                h = act_fn(st.activation)(h)
        elif st.kind == "act":
            h = act_fn(st.activation)(h)
        else:
            raise FusedPlanUnsupported(f"step {st!r} in decode chain")
    logp = jax.nn.log_softmax(h.astype(jnp.float32), -1)
    mean, rel = welford_posterior(logp, spec.n_samples)
    return mean, rel, jnp.stack(knews), jnp.stack(vnews)


def fused_moments_ref(spec: FusedSpec, x: jax.Array,
                      params: tuple[jax.Array, ...]
                      ) -> tuple[jax.Array, jax.Array]:
    """Oracle for the in-kernel moments epilogue: x [B, d_in] ->
    (mean [B, groups·d_out], std [B, groups·d_out]); the reduction is over
    the ``n_masks`` rows *within* each group (ddof=0), matching
    ``uncertainty.predictive_moments`` of the group-unflattened samples."""
    s = fused_plan_ref(spec, x, params)          # [G·N, B, do]
    g, n = spec.groups, spec.n_masks
    b, do = s.shape[1], s.shape[2]
    sg = s.reshape(g, n, b, do)
    mean = jnp.moveaxis(jnp.mean(sg, axis=1), 0, 1).reshape(b, g * do)
    std = jnp.moveaxis(jnp.std(sg, axis=1), 0, 1).reshape(b, g * do)
    return mean, std
