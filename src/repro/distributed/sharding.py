"""Sharding rules: parameter/activation PartitionSpecs for the production mesh.

Strategy (DESIGN §4):
  * batch dims shard over ("pod", "data") — pure DP across pods,
  * weight matrices shard TP over "model" on their parallel dim and FSDP over
    "data" on the other (2D sharding: every chip holds 1/(data*model) of every
    weight; GSPMD all-gathers per scanned layer),
  * MoE expert stacks shard the expert dim over "model" (EP),
  * KV caches shard batch over ("pod","data") and the *sequence* dim over
    "model" — decode's softmax over the sharded key axis becomes a GSPMD
    partial-reduction (flash-decode-style distributed attention for free),
  * Masksembles masks and norms replicate (tiny),
  * stacked-layer leading axes (scan reps) never shard.

Rules are keyed on leaf *paths* (layer naming conventions in models/layers),
applied to the trailing dims so the same rule covers scanned [reps, ...] and
unscanned [...] parameters.
"""

from __future__ import annotations

import re
from typing import Any

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat

Params = Any

__all__ = ["batch_axes", "param_pspec", "param_shardings", "tree_shardings",
           "batch_shardings", "cache_shardings", "replicated", "PARAM_RULES"]


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# (path-regex, trailing-dims spec). First match wins. Specs name logical
# trailing dims right-aligned against the leaf shape.
PARAM_RULES: tuple[tuple[str, tuple], ...] = (
    # --- embeddings ---------------------------------------------------------
    (r"embed/embed$",            ("model", "data")),    # [V, D]
    (r"embed/unembed/w$",        ("data", "model")),    # [D, V]
    # --- attention ----------------------------------------------------------
    (r"attn/w[qkv]/w$",          ("data", "model")),    # [D, H*dh]
    (r"attn/w[qkv]/b$",          ("model",)),
    (r"attn/wo/w$",              ("model", "data")),    # [H*dh, D]
    (r"attn/wo/b$",              (None,)),
    # --- gated / plain FFN ---------------------------------------------------
    (r"ffn/w[gu]/w$",            ("data", "model")),    # [D, F]
    (r"ffn/w[gu]/b$",            ("model",)),
    (r"ffn/wd/w$",               ("model", "data")),    # [F, D]
    (r"ffn/wd/b$",               (None,)),
    # packed serving form (mask-zero skipping): [.., N, D, K] / [.., N, K, D]
    (r"ffn/w[gu]p$",             ("data", "model")),
    (r"ffn/wdp$",                ("model", "data")),
    # --- MoE (experts lead) ---------------------------------------------------
    (r"moe/router/w$",           ("data", None)),       # [D, E]
    (r"moe/we[gu]$",             ("model", "data", None)),  # [E, D, F]
    (r"moe/wed$",                ("model", None, "data")),  # [E, F, D]
    (r"moe/dense/w[gu]/w$",      ("data", "model")),
    (r"moe/dense/wd/w$",         ("model", "data")),
    # --- RG-LRU ---------------------------------------------------------------
    (r"rec/wgate/w$",            ("data", "model")),
    (r"rec/win/w$",              ("data", "model")),
    (r"rec/wout/w$",             ("model", "data")),
    (r"rec/(wgate|win|wout)/b$", ("model",)),
    (r"rec/conv$",               (None, "model")),      # [K, W]
    (r"rec/lru/w[ax]/w$",        ("data", "model")),    # [W, W]
    (r"rec/lru/w[ax]/b$",        ("model",)),
    (r"rec/lru/lambda$",         ("model",)),
    # --- xLSTM -----------------------------------------------------------------
    (r"w[ug]/w$",                ("data", "model")),    # block up-projections
    (r"w[ug]/b$",                ("model",)),
    (r"w[qkv]$",                 (None, "data", "model")),  # [H, pdh, pdh]
    (r"wif/w$",                  ("data", None)),
    (r"wzifo/w$",                ("data", "model")),
    (r"wzifo/b$",                ("model",)),
    (r"rzifo$",                  (None, "data", "model")),
    (r"wd/w$",                   ("model", "data")),
    (r"wd/b$",                   (None,)),
    # --- everything else (norms, masks, biases, scalars): replicate ----------
    (r".*",                      ()),
)


def _path_str(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def param_pspec(path, leaf, mesh: Mesh) -> P:
    """PartitionSpec for one parameter leaf (path-matched, right-aligned).

    Optimizer-state trees reuse the same rules transparently: Adam moments
    mirror the parameter paths; Adafactor's factored moments (leaf names
    ``vr``/``vc``) match their *parent* parameter rule with the reduced dim
    removed (vr drops the last dim, vc the second-to-last).
    """
    s = _path_str(path)
    names = set(mesh.axis_names)
    factored = None
    if s.endswith("/vr") or s.endswith("/vc"):
        factored, s = s[-2:], s[:-3]
    for pat, spec in PARAM_RULES:
        if re.search(pat, s):
            spec = tuple(a if (a in names) else None for a in spec)
            if factored == "vr" and spec:
                spec = spec[:-1]
            elif factored == "vc" and len(spec) >= 2:
                spec = spec[:-2] + spec[-1:]
            ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
            spec = spec[-ndim:] if ndim < len(spec) else spec
            full = (None,) * (ndim - len(spec)) + tuple(spec)
            # drop axes that don't divide the dim (e.g. kv-head counts)
            shape = leaf.shape
            fixed = tuple(
                a if (a is not None and shape[i] % mesh.shape[a] == 0)
                else None
                for i, a in enumerate(full))
            return P(*fixed)
    return P()


def param_shardings(mesh: Mesh, params: Params) -> Params:
    """NamedSharding tree matching ``params`` (works on ShapeDtypeStructs)."""
    return compat.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_pspec(path, leaf, mesh)),
        params)


def tree_shardings(mesh: Mesh, tree: Params, pspec_fn) -> Params:
    return compat.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, pspec_fn(path, leaf)), tree)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def batch_shardings(mesh: Mesh, batch: Params) -> Params:
    """Training/serving inputs: shard dim 0 (batch) over ("pod","data");
    positions [3,B,S] shard dim 1; scalars replicate."""
    ba = batch_axes(mesh)
    nshards = 1
    for a in ba:
        nshards *= mesh.shape[a]

    def spec(path, leaf):
        name = _path_str(path)
        if leaf.ndim == 0:
            return P()
        bdim = 1 if name.endswith("positions") and leaf.shape[0] == 3 else 0
        if leaf.shape[bdim] % nshards == 0:
            full: list = [None] * leaf.ndim
            full[bdim] = ba[0] if len(ba) == 1 else ba
            return P(*full)
        return P()

    return compat.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec(path, leaf)), batch)


def cache_shardings(mesh: Mesh, cache: Params) -> Params:
    """KV caches [reps, B, S, Hkv, dh]: batch over ("pod","data"), sequence
    over "model" (distributed decode softmax). Recurrent states
    [reps, B, W]: batch + width over "model". kpos replicates."""
    ba = batch_axes(mesh)
    bspec = ba if len(ba) > 1 else ba[0]
    nshards = 1
    for a in ba:
        nshards *= mesh.shape[a]

    def spec(path, leaf):
        name = _path_str(path)
        if name.endswith("kpos"):
            return P()
        shape = leaf.shape
        s: list = [None] * leaf.ndim
        if name.endswith("/k") or name.endswith("/v"):
            # [reps, B, S, Hkv, dh]
            if shape[1] % nshards == 0:
                s[1] = bspec
            if shape[2] % mesh.shape["model"] == 0:
                s[2] = "model"
            return P(*s)
        # recurrent states: [reps, B, ...] — batch + last dim over model
        if leaf.ndim >= 2 and shape[1] % nshards == 0:
            s[1] = bspec
        if leaf.ndim >= 3 and shape[-1] % mesh.shape["model"] == 0:
            s[-1] = "model"
        return P(*s)

    return compat.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec(path, leaf)), cache)
