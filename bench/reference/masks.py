"""Masksembles masks (Durasov et al., CVPR 2021) as the configurations state
them: ``n`` fixed binary masks over a hidden width, from (width, n, scale,
seed). The benchmark builds the masks it hands to the program and to the
references here, so neither reference takes a table the program made.

This is the rejection construction of the Masksembles reference code, with
a rotated-window fallback and a normalisation to exactly K kept units per
mask; it reproduces the program's ``core/masks.generate_masks`` bit for
bit (checked in bench/tests).
"""

from __future__ import annotations

import math

import numpy as np


def keep_count(width: int, n_masks: int, scale: float) -> int:
    """Units each mask keeps: ``width / (s * (1 - (1 - 1/s)^n))``."""
    if scale == 1.0:
        return width
    rate = 1.0 / (scale * (1.0 - (1.0 - 1.0 / scale) ** n_masks))
    return max(1, min(width, int(round(width * rate))))


def _rotation(width: int, n_masks: int, keep: int, seed: int) -> np.ndarray:
    perm = np.random.default_rng(seed).permutation(width)
    stride = math.ceil(width / n_masks)
    masks = np.zeros((n_masks, width), bool)
    for i in range(n_masks):
        masks[i, perm[[(i * stride + j) % width for j in range(keep)]]] = True
    return masks


def _rejection(width: int, n_masks: int, scale: float, seed: int):
    if scale == 1.0:
        return np.ones((n_masks, width), bool)
    rng = np.random.default_rng(seed)
    m0 = keep_count(width, n_masks, scale)
    order = [m0] + [m for d in range(1, 16) for m in (m0 + d, m0 - d)
                    if m >= 1]
    for m in order:
        total = int(round(m * scale))
        if total < m:
            continue
        for _ in range(20):
            draws = np.zeros((n_masks, total), bool)
            for i in range(n_masks):
                draws[i, rng.choice(total, size=m, replace=False)] = True
            alive = draws.any(axis=0)
            if int(alive.sum()) == width:
                return draws[:, alive]
    return None


def _exact_keep(masks: np.ndarray, keep: int,
                rng: np.random.Generator) -> np.ndarray:
    masks = masks.copy()
    for i in range(masks.shape[0]):
        ones = np.flatnonzero(masks[i])
        if len(ones) > keep:
            need = len(ones) - keep
            cover = masks.sum(axis=0)
            order = ones[np.argsort(-cover[ones], kind="stable")]
            drop = [p for p in order if cover[p] > 1][:need]
            if len(drop) < need:
                dropped = set(drop)
                drop.extend(p for p in ones if p not in dropped)
            masks[i, drop[:need]] = False
        elif len(ones) < keep:
            zeros = np.flatnonzero(~masks[i])
            cover = masks.sum(axis=0)
            order = zeros[np.argsort(cover[zeros], kind="stable")]
            masks[i, order[:keep - len(ones)]] = True
    return masks


def masks(width: int, n_masks: int, scale: float, seed: int) -> np.ndarray:
    """[n_masks, width] bool, each mask keeping exactly keep_count units."""
    keep = keep_count(width, n_masks, scale)
    m = _rejection(width, n_masks, scale, seed)
    if m is None:
        m = _rotation(width, n_masks, keep, seed)
    return _exact_keep(m, min(keep, width), np.random.default_rng(seed + 1))
