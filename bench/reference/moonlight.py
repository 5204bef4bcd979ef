"""Plain Moonlight-16B-A3B (the DeepSeek-V3 architecture, arXiv:2412.19437;
latent attention from DeepSeek-V2, arXiv:2405.04434) with Masksembles
masks: a float32 ``jax.numpy`` forward over a whole sequence, with no
cache, no kernels and no batching of requests.

Per layer: RMSNorm -> latent attention -> residual; RMSNorm -> FFN ->
residual. Latent attention, expanded: q = x W_q split per head into 128
"nope" and 64 rope dims; [c_kv, k_rope] = x W_kva, c_kv RMS-normed; per
head [k_nope, v] = c_kv W_kvb; the rope parts of q and of the one shared
k_rope rotate (rotate-half, theta from the configuration); softmax of
(q_nope.k_nope + q_rope.k_rope) / sqrt(192) over the causal prefix; the
heads' outputs through W_o. Layer 0's FFN is a SwiGLU whose hidden units
are multiplied by the row's mask. The later layers' FFN is the DeepSeek-V3
MoE: router logits in float32, sigmoid scores, the experts chosen by the
top-6 of score + bias, their scores renormalised to sum 1 and scaled by
``routed_scaling_factor``; each token's output is the gate-weighted sum of
its chosen experts (SwiGLU of width 1408, hidden units masked by the row's
mask), computed directly: the experts run one after another over every
token with the gate weight of the tokens that did not choose one being 0.
Two shared experts act as one masked SwiGLU of width 2816 added to it. A
final RMSNorm and the untied head give the logits. Row n of the N rows
runs mask n; the posterior of a position is the mean over the rows of the
log-softmax, and its uncertainty the population std over the rows.

RoPE pairs dimension i with i + 32 of the rope part as it comes out of the
projection (HF DeepSeek de-interleaves first: a fixed permutation of the
rope columns of W_q and W_kva), as the program does.

The weights are the tree the benchmark made, read one layer (and within a
layer one expert) at a time and raised to float32; the matmuls run at
"highest" precision. ``quant="int8"`` is the control, the forward computed
in int8: every matrix, the embedding and head included, rounded to int8
with one symmetric scale per output channel; every matmul's activation
input rounded with one scale per row (token); the latent [c_kv, k_rope]
rounded with one scale per position, as an int8 latent cache holds it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _q8(w, axis):
    """Round to int8 with one scale per slice along the other axes."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _mat(w, quant):
    w = w.astype(jnp.float32)
    return _q8(w, -2) if quant == "int8" else w


def _act(x, quant):
    """An activation as an int8 matmul reads it: one scale per row."""
    return _q8(x, -1) if quant == "int8" else x


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [N, S, ..., d] at positions 0..S-1, rotate-half convention."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    shape = (1, s) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, f, mask, quant):
    """SwiGLU with its hidden units multiplied by ``mask`` [N, 1, F]."""
    u = jax.nn.silu(x @ _mat(f["wg"]["w"], quant)) \
        * (x @ _mat(f["wu"]["w"], quant))
    return _act(u * mask, quant) @ _mat(f["wd"]["w"], quant)


def _attention(x, p, dims, quant, block):
    """Latent attention, expanded, causal, over queries in blocks."""
    h, nope, rope, dv, r, eps, theta = dims
    n, s, _ = x.shape
    a = p["attn"]
    xn = _act(_rms(x, p["norm1"]["scale"], eps), quant)
    q = (xn @ _mat(a["wq"]["w"], quant)).reshape(n, s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = xn @ _mat(a["wkv_a"]["w"], quant)
    lat = jnp.concatenate([_rms(kv[..., :r], a["kv_norm"]["scale"], eps),
                           _rope(kv[..., r:], theta)], -1)
    lat = _act(lat, quant)                  # the latent cache, per position
    kvb = (lat[..., :r] @ _mat(a["wkv_b"]["w"], quant)).reshape(
        n, s, h, nope + dv)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        lat[:, :, None, r:], (n, s, h, rope))], -1)
    v = kvb[..., nope:]
    qb = q.reshape(n, s // block, block, h, nope + rope).transpose(
        1, 0, 2, 3, 4)

    def one(args):
        i, qi = args
        sc = jnp.einsum("nqhd,nshd->nhqs", qi, k) / math.sqrt(nope + rope)
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("nhqs,nshd->nqhd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(one, (jnp.arange(s // block), qb))
    o = o.transpose(1, 0, 2, 3, 4).reshape(n, s, h * dv)
    return x + _act(o, quant) @ _mat(a["wo"]["w"], quant)


@functools.lru_cache(maxsize=None)
def _dense_fn(dims: tuple, quant: str | None, block: int):
    eps = dims[5]

    def layer(x, p, masks):
        with jax.default_matmul_precision("highest"):
            x = _attention(x, p, dims, quant, block)
            xn = _act(_rms(x, p["norm2"]["scale"], eps), quant)
            return x + _swiglu(xn, p["ffn"], masks[:, None, :], quant)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _moe_fn(dims: tuple, moe: tuple, quant: str | None, block: int):
    eps = dims[5]
    k, scaling, norm_topk = moe

    def layer(x, lw, i, emask, smask):
        with jax.default_matmul_precision("highest"):
            p = jax.tree.map(lambda a: a[i], lw)
            x = _attention(x, p, dims, quant, block)
            m = p["moe"]
            xn = _act(_rms(x, p["norm2"]["scale"], eps), quant)
            logits = xn @ m["router"]["w"].astype(jnp.float32)
            scores = jax.nn.sigmoid(logits)
            _, top = jax.lax.top_k(scores + m["router_bias"], k)
            w = jnp.take_along_axis(scores, top, -1)
            if norm_topk:
                w = w / (w.sum(-1, keepdims=True) + 1e-20)
            e = scores.shape[-1]
            gate = jnp.einsum("nsk,nske->nse", w * scaling,
                              jax.nn.one_hot(top, e, dtype=jnp.float32))
            em = emask[:, None, :]

            def expert(j, y):
                f = {name: {"w": m[leaf][j]} for name, leaf in
                     (("wg", "weg"), ("wu", "weu"), ("wd", "wed"))}
                return y + gate[..., j, None] * _swiglu(xn, f, em, quant)

            y = jax.lax.fori_loop(0, e, expert, jnp.zeros_like(x))
            y = y + _swiglu(xn, m["shared"], smask[:, None, :], quant)
            return x + y

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, quant: str | None, block: int):
    def head(x, final_scale, unembed, pos):
        with jax.default_matmul_precision("highest"):
            u = _mat(unembed, quant)
            hid = _act(_rms(x[:, pos], final_scale, eps), quant)
            n, c, _ = hid.shape

            def one(i, out):           # [N, block, V] at a time
                h = jax.lax.dynamic_slice_in_dim(hid, i * block, block, 1)
                return jax.lax.dynamic_update_slice_in_dim(
                    out, jax.nn.log_softmax(h @ u, -1), i * block, 1)

            return jax.lax.fori_loop(
                0, c // block, one,
                jnp.zeros((n, c, u.shape[1]), jnp.float32))

    return jax.jit(head)


def _dims(config: dict) -> tuple:
    return (config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["kv_lora_rank"], float(config["rms_norm_eps"]),
            float(config["rope_theta"]))


def log_probs(weights: dict, config: dict, context, start: int, count: int,
              quant: str | None = None, pad_to: int = 1024,
              count_to: int = 128, block: int = 256):
    """Log-softmax [N, C, V] (on the device) at positions start ..
    start + count - 1 of ``context`` (token ids) under each of the N masks,
    C being ``count`` rounded up to a multiple of ``count_to``: rows past
    ``count`` repeat the last position and are to be dropped. The context
    is zero-padded at the end to a multiple of ``pad_to``; causal attention
    keeps the padding out of every position read. Both roundings bound the
    shapes compiled, so that a run finds them in the compile cache.
    Queries attend in blocks of ``block`` positions, and the head runs
    ``count_to`` positions at a time, so that an 8k context and a 1k answer
    over a 164k vocabulary fit beside the weights."""
    emb = weights["embed"]["embed"]
    dense, moe = (weights["segments"][0]["b0"],
                  weights["segments"][1]["b0"])
    ffn_masks = dense["ffn"]["masks"][0].astype(jnp.float32)      # [N, f]
    n = ffn_masks.shape[0]
    ctx = np.asarray(context, np.int32)
    s = -(-len(ctx) // pad_to) * pad_to
    ids = jnp.asarray(np.pad(ctx, (0, s - len(ctx))))
    x = jnp.broadcast_to(emb[ids].astype(jnp.float32)[None],
                         (n, s, emb.shape[1]))
    if quant == "int8":
        x = _q8(x, -1)                   # the embedding per output channel
    dims = _dims(config)
    first = jax.tree.map(lambda a: a[0], dense)
    x = _dense_fn(dims, quant, block)(x, first, ffn_masks)
    mk = ("norm1", "attn", "norm2", "moe")
    lw = {key: moe[key] for key in mk}
    emask = moe["moe"]["masks"][0].astype(jnp.float32)
    smask = moe["moe"]["shared"]["masks"][0].astype(jnp.float32)
    spec = (config["num_experts_per_tok"],
            float(config["routed_scaling_factor"]),
            bool(config["norm_topk_prob"]))
    layer = _moe_fn(dims, spec, quant, block)
    for i in range(config["num_hidden_layers"]
                   - config["first_k_dense_replace"]):
        x = layer(x, lw, jnp.int32(i), emask, smask)
    c = -(-count // count_to) * count_to
    pos = jnp.minimum(jnp.arange(start, start + c), start + count - 1)
    return _head_fn(float(config["rms_norm_eps"]), quant, count_to)(
        x, weights["final_norm"]["scale"], weights["embed"]["unembed"]["w"],
        pos)


@jax.jit
def posterior(lp, tokens):
    """From log-probs [N, T, V] and tokens [T]: (mean log-prob of each
    token, its std over the rows, the best mean log-prob, the argmax)."""
    mean = lp.mean(0)
    std = lp.std(0)
    t = jnp.arange(tokens.shape[0])
    return mean[t, tokens], std[t, tokens], mean.max(-1), mean.argmax(-1)
