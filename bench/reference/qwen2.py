"""Plain qwen2 (arXiv:2407.10671) with Masksembles FFN masks: a float32
``jax.numpy`` forward over a whole sequence, with no cache and no kernels.

Per layer: RMSNorm -> q/k/v projections with bias -> rotary embedding
(rotate-half, theta from the configuration) -> causal grouped-query
attention (query head i reads key/value head i // (heads / kv_heads)) ->
output projection -> residual; RMSNorm -> SwiGLU FFN whose hidden units
are multiplied by the row's mask -> residual. A final RMSNorm and the tied
embedding give the logits. Row n of the N rows runs mask n; the posterior
of a position is the mean over the rows of the log-softmax, and its
uncertainty the population std over the rows.

The weights are the tree the benchmark made (``embed``, ``final_norm``, and
the per-layer leaves stacked on a leading layer axis); they are read one
layer at a time and raised to float32, and the matmuls run at "highest"
precision. ``quant="int8"`` is the control, the forward computed in int8:
every matrix, the embedding included, rounded to int8 with one symmetric
scale per output channel; every matmul's activation input rounded to int8
with one scale per row (token); keys and values rounded to int8 with one
scale per position and head, as an int8 KV cache holds them.
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
    """x [N, S, H, dh] at positions 0..S-1, rotate-half convention."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(dims: tuple, quant: str | None):
    h, hkv, dh, eps, theta = dims

    def layer(x, lw, masks, i):
        with jax.default_matmul_precision("highest"):
            p = jax.tree.map(lambda a: a[i], lw)
            n, s, _ = x.shape
            a = p["attn"]
            xn = _rms(x, p["norm1"]["scale"], eps)

            def proj(name, heads):
                y = _act(xn, quant) @ _mat(a[name]["w"], quant) \
                    + a[name]["b"].astype(jnp.float32)
                return y.reshape(n, s, heads, dh)

            q = _rope(proj("wq", h), theta)
            k = _act(_rope(proj("wk", hkv), theta), quant)
            v = _act(proj("wv", hkv), quant)
            g = h // hkv
            q = q.reshape(n, s, hkv, g, dh)
            sc = jnp.einsum("nqkgd,nskd->nkgqs", q, k) / math.sqrt(dh)
            causal = jnp.tril(jnp.ones((s, s), bool))
            sc = jnp.where(causal, sc, -jnp.inf)
            o = jnp.einsum("nkgqs,nskd->nqkgd", jax.nn.softmax(sc, -1), v)
            x = x + _act(o.reshape(n, s, h * dh), quant) \
                @ _mat(a["wo"]["w"], quant)
            f = p["ffn"]
            xn = _act(_rms(x, p["norm2"]["scale"], eps), quant)
            u = jax.nn.silu(xn @ _mat(f["wg"]["w"], quant)) \
                * (xn @ _mat(f["wu"]["w"], quant))
            u = u * masks[:, None, :]
            return x + _act(u, quant) @ _mat(f["wd"]["w"], quant)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, quant: str | None):
    def head(x, final_scale, emb, pos):
        with jax.default_matmul_precision("highest"):
            e = emb.astype(jnp.float32)
            if quant == "int8":
                e = _q8(e, -1)
            hid = _act(_rms(x[:, pos], final_scale, eps), quant)
            return jax.nn.log_softmax(hid @ e.T, -1)      # [N, T, V]

    return jax.jit(head)


def log_probs(weights: dict, config: dict, context, start: int, count: int,
              quant: str | None = None, pad_to: int = 256,
              count_to: int = 64):
    """Log-softmax [N, C, V] (on the device) at positions start ..
    start + count - 1 of ``context`` (token ids) under each of the N masks,
    C being ``count`` rounded up to a multiple of ``count_to``: rows past
    ``count`` repeat the last position and are to be dropped. The context
    is zero-padded at the end to a multiple of ``pad_to``; causal attention
    keeps the padding out of every position read. Both roundings bound the
    shapes compiled, so that a run finds them in the compile cache."""
    emb = weights["embed"]["embed"]
    layers = weights["segments"][0]["b0"]
    masks = layers["ffn"]["masks"][0].astype(jnp.float32)      # [N, f]
    n = masks.shape[0]
    ctx = np.asarray(context, np.int32)
    s = -(-len(ctx) // pad_to) * pad_to
    ids = jnp.asarray(np.pad(ctx, (0, s - len(ctx))))
    x = jnp.broadcast_to(emb[ids].astype(jnp.float32)[None],
                         (n, s, emb.shape[1]))
    dims = (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rms_norm_eps"]),
            float(config["rope_theta"]))
    lw = {k: layers[k] for k in ("norm1", "attn", "norm2", "ffn")}
    lw["ffn"] = {k: lw["ffn"][k] for k in ("wg", "wu", "wd")}
    layer = _layer_fn(dims, quant)
    for i in range(config["num_hidden_layers"]):
        x = layer(x, lw, masks, jnp.int32(i))
    c = -(-count // count_to) * count_to
    pos = jnp.minimum(jnp.arange(start, start + c), start + count - 1)
    return _head_fn(float(config["rms_norm_eps"]), quant)(
        x, weights["final_norm"]["scale"], emb, pos)


@jax.jit
def posterior(lp, tokens):
    """From log-probs [N, T, V] and tokens [T]: (mean log-prob of each
    token, its std over the rows, the best mean log-prob, the argmax)."""
    mean = lp.mean(0)
    std = lp.std(0)
    t = jnp.arange(tokens.shape[0])
    return mean[t, tokens], std[t, tokens], mean.max(-1), mean.argmax(-1)
