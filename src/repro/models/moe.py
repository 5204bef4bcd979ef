"""Mixture-of-Experts FFN — two dispatch paths over one router.

* **GShard capacity** (what trains: phi3.5-moe, arctic, and the smoke
  parity tests). Tokens are split into groups of ``moe_group_size``;
  within each group every token picks its top-k experts and is assigned a
  capacity slot. Dispatch and combine are one-hot einsums, which GSPMD
  turns into all-to-alls when tokens are data-sharded and experts
  model-sharded — the standard expert-parallel lowering on TPU.
  Over-capacity tokens are dropped (their FFN output is zero; the residual
  stream carries them through), matching the classic dropped-token MoE
  used by Switch/GShard and the configs assigned here.

* **Dropless grouped** (``cfg.moe_dropless``; what serves: Moonlight and
  any config that sets it, in training too). Every (token, choice) pair is
  sorted by expert and each projection is one ``jax.lax.ragged_dot`` over
  the sorted rows; the rows are unsorted and combined with their gate
  weights. No capacity, no dropped token, and a row's result does not
  depend on which other rows share the batch -- so a padded prompt or an
  empty pool row routes but adds nothing to the real rows. It also
  returns the per-expert pair counts of the valid rows (serving
  telemetry).

Routers: ``softmax`` (top-k of the softmax) and ``sigmoid_bias``
(DeepSeek-V3 ``noaux_tc`` with one group: sigmoid scores in float32, a
bias added for selection only, the chosen scores renormalised when
``norm_topk_prob`` and scaled by ``routed_scaling``). Shared experts
(``n_shared_experts``) are one always-on FFN of ``n_shared_experts *
moe_d_ff`` hidden units beside the routed ones.

Masksembles over expert hidden units: the mask id of each token rides the
dispatch (the one-hot, or the sorted pairs), so each routed row knows which
fixed mask to apply to its expert's hidden layer — one mask set shared by
all experts, another over the shared expert's hidden units; the paper's
technique survives routing intact (router untouched; see DESIGN
§Arch-applicability).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import masks as masks_lib
from repro.models import layers

Params = dict[str, Any]

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    kr, kg, ku, kd, kres = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p: Params = {
        "router": layers.dense_init(kr, d, e, dtype),
        # experts stacked on a leading E axis -> shard over "model"
        "weg": (jax.random.normal(kg, (e, d, f), jnp.float32) * scale).astype(dtype),
        "weu": (jax.random.normal(ku, (e, d, f), jnp.float32) * scale).astype(dtype),
        "wed": (jax.random.normal(kd, (e, f, d), jnp.float32)
                / math.sqrt(f)).astype(dtype),
    }
    if cfg.router == "sigmoid_bias":   # e_score_correction_bias (f32)
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = layers.ffn_init(jax.random.fold_in(key, 5), cfg,
                                      d_ff=cfg.n_shared_experts * f,
                                      dtype=dtype)
    if cfg.moe_dense_residual:      # arctic: dense FFN in parallel
        p["dense"] = layers.ffn_init(kres, cfg, dtype=dtype)
    if cfg.bayesian:
        spec = masks_lib.MaskSpec(width=f, n_masks=cfg.mask_samples,
                                  scale=cfg.mask_scale, seed=cfg.mask_seed)
        p["masks"] = jnp.asarray(masks_lib.generate_masks(spec), dtype)
    return p


def _capacity(cfg, group: int) -> int:
    c = int(cfg.capacity_factor * cfg.top_k * group / cfg.n_experts)
    return max(cfg.top_k, min(group, c))


def route(p: Params, xt: jax.Array, cfg
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Router over tokens xt [..., D]: (chosen experts [..., k], their gate
    weights [..., k] f32, the per-expert scores the load-balancing loss
    reads [..., E]). The sigmoid router computes its logits in float32, as
    the published gate does; the softmax router rounds them from the
    parameter dtype."""
    k = cfg.top_k
    if cfg.router == "sigmoid_bias":
        logits = xt.astype(jnp.float32) @ \
            p["router"]["w"].astype(jnp.float32)
        scores = jax.nn.sigmoid(logits)
        _, topi = jax.lax.top_k(scores + p["router_bias"], k)
        topv = jnp.take_along_axis(scores, topi, -1)
    else:
        logits = layers.dense(p["router"], xt).astype(jnp.float32)
        scores = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(scores, k)
    if cfg.norm_topk_prob:
        topv = topv / (topv.sum(-1, keepdims=True) + 1e-20)
    return topi, topv * cfg.routed_scaling, scores


def _balance_loss(topi: jax.Array, scores: jax.Array, e: int) -> jax.Array:
    """E * sum_e f_e * P_e over tokens [T, k] / [T, E]."""
    f_e = jnp.mean(jax.nn.one_hot(topi, e, dtype=jnp.float32).sum(-2), 0)
    return jnp.sum(f_e * jnp.mean(scores, 0)) * e


def _dropless(p: Params, x: jax.Array, cfg, mask_ids, valid):
    """Sorted grouped dispatch: (y [B,S,D], aux, counts [E] int32)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(b * s, d)
    topi, topv, scores = route(p, xt, cfg)
    flat = topi.reshape(-1)                                     # [T*k]
    order = jnp.argsort(flat, stable=True)
    tok = order // k
    sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
    xs = xt[tok]
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    h = act(jax.lax.ragged_dot(xs, p["weg"], sizes)) * \
        jax.lax.ragged_dot(xs, p["weu"], sizes)                 # [T*k, F]
    if mask_ids is not None and "masks" in p:
        mid = jnp.broadcast_to(mask_ids[:, None], (b, s)).reshape(-1)
        h = h * p["masks"][mid[tok]]
    ye = jax.lax.ragged_dot(h, p["wed"], sizes)                 # [T*k, D]
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    ye = ye[inv].reshape(b * s, k, d).astype(jnp.float32)
    y = jnp.einsum("tk,tkd->td", topv, ye).astype(x.dtype).reshape(b, s, d)
    ok = jnp.ones((b, s), bool) if valid is None else \
        jnp.broadcast_to(valid, (b, s))
    counts = jnp.zeros((e,), jnp.int32).at[flat].add(
        jnp.repeat(ok.reshape(-1), k).astype(jnp.int32))
    return y, _balance_loss(topi, scores, e), counts


def moe_apply(p: Params, x: jax.Array, cfg,
              mask_ids: jax.Array | None = None,
              valid: jax.Array | None = None):
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar, counts).

    aux_loss is the standard load-balancing loss (mean over groups of
    E * sum_e f_e * P_e), weighted by the caller. ``counts`` is the
    dropless path's per-expert pair count [E] over the rows ``valid``
    [B, S] marks (all rows where None), None on the GShard path.
    """
    if cfg.moe_dropless:
        y, aux, counts = _dropless(p, x, cfg, mask_ids, valid)
    else:
        (y, aux), counts = _gshard(p, x, cfg, mask_ids), None
    if "shared" in p:
        y = y + layers.ffn_apply(p["shared"], x, cfg, mask_ids=mask_ids)
    if "dense" in p:                # arctic's parallel dense residual
        y = y + layers.ffn_apply(p["dense"], x, cfg, mask_ids=mask_ids)
    return y, aux.astype(jnp.float32), counts


def _gshard(p: Params, x: jax.Array, cfg, mask_ids):
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tokens = b * s
    group = min(cfg.moe_group_size, tokens)
    if tokens % group:
        group = tokens // max(1, tokens // group)   # largest divisor <= group
        while tokens % group:
            group += 1
    n_groups = tokens // group
    cap = _capacity(cfg, group)

    xt = x.reshape(n_groups, group, d)
    # top-k selection; slot assignment by prefix-sum position per expert.
    topi, topv, probs = route(p, xt, cfg)                       # [G,T,k]
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)         # [G,T,k,E]
    # position of each (token, choice) within its expert's queue
    pos = jnp.cumsum(onehot.reshape(n_groups, group * k, e), axis=1)
    pos = pos.reshape(n_groups, group, k, e) * onehot - 1.0     # [G,T,k,E]
    keep = (pos >= 0) & (pos < cap)
    gate = topv[..., None] * keep                               # [G,T,k,E]
    slot_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                             dtype=x.dtype) * keep[..., None]
    dispatch = jnp.einsum("gtke,gtkec->gtec", onehot.astype(x.dtype),
                          slot_oh)                              # [G,T,E,C]
    combine = jnp.einsum("gtke,gtkec->gtec",
                         gate.astype(jnp.float32),
                         slot_oh.astype(jnp.float32))           # [G,T,E,C]

    # ---- dispatch -> expert FFN -> combine --------------------------------
    # Expert-parallel activation sharding: slot tensors shard the expert dim
    # over "model" (the dispatch einsum becomes GSPMD's all-to-all) and the
    # group dim over the batch axes. Without these hints the [G,E,C,*]
    # tensors replicate over "model" and blow the per-device HBM budget.
    ep = ("batch", "model", None, None)
    if cfg.moe_local_groups:
        # groups are (batch x model)-sharded; pinning E to "model" too would
        # conflict — let GSPMD pick the dispatch a2a layout
        ep = None
    xe = jnp.einsum("gtec,gtd->gecd", dispatch, xt)             # [G,E,C,D]
    xe = layers.constrain(xe, ep) if ep else xe
    act = jax.nn.silu if cfg.activation == "silu" else jax.nn.gelu
    h = act(jnp.einsum("gecd,edf->gecf", xe, p["weg"])) * \
        jnp.einsum("gecd,edf->gecf", xe, p["weu"])              # [G,E,C,F]
    h = layers.constrain(h, ep) if ep else h
    if mask_ids is not None and "masks" in p:
        # route each token's mask id through the same dispatch
        mid = mask_ids.astype(x.dtype)
        mid = jnp.broadcast_to(mid[:, None], (b, s)).reshape(n_groups, group)
        slot_mid = jnp.einsum("gtec,gt->gec", dispatch, mid)    # [G,E,C]
        slot_mask = p["masks"][slot_mid.astype(jnp.int32)]      # [G,E,C,F]
        h = h * slot_mask
    ye = jnp.einsum("gecf,efd->gecd", h, p["wed"])              # [G,E,C,D]
    ye = layers.constrain(ye, ep) if ep else ye
    y = jnp.einsum("gtec,gecd->gtd", combine.astype(x.dtype), ye)

    # ---- aux load-balancing loss -------------------------------------------
    f_e = jnp.mean(onehot[..., 0, :] if k == 1 else onehot.sum(2), axis=1)
    p_e = jnp.mean(probs, axis=1)
    aux = jnp.mean(jnp.sum(f_e * p_e, axis=-1)) * e

    return y.reshape(b, s, d), aux
