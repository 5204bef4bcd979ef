"""Work the uIVIM-NET algorithm requires, whatever implements it.

Per voxel, each of the G sub-networks runs under each of the N masks at
the kept widths (mask-zero skipping): ``W -> K1 -> K2 -> 1``. Bytes are the
f32 signals in, the (mean, std) maps out, and the packed weights once.
"""

from __future__ import annotations

from bench.reference import masks as masks_ref


def kept(config: dict) -> tuple[int, int]:
    k = masks_ref.keep_count(config["width"], config["n_masks"],
                             config["mask_scale"])
    return k, k


def flops(config: dict, voxels: int) -> float:
    g, n, w = config["sub_networks"], config["n_masks"], config["width"]
    k1, k2 = kept(config)
    return float(voxels * g * n * 2 * (w * k1 + k1 * k2 + k2))


def weight_bytes(config: dict) -> float:
    g, n, w = config["sub_networks"], config["n_masks"], config["width"]
    k1, k2 = kept(config)
    return float(4 * g * n * (w * k1 + k1 + k1 * k2 + k2 + k2 + 1))


def io_bytes(config: dict, voxels: int) -> float:
    g, w = config["sub_networks"], config["width"]
    return float(4 * voxels * (w + 2 * g))


def seconds(config: dict, voxels: int, peaks: dict) -> float:
    """Least time on the chip: the larger of the compute and memory bounds
    (the bf16 peak is the MXU's best case for any float matmul)."""
    return max(flops(config, voxels) / peaks["flops_bf16"],
               (io_bytes(config, voxels) + weight_bytes(config))
               / peaks["hbm_bytes_per_s"])
