"""Whole scan: FLOPs the window's voxels required at the kept widths, over
the window at the chip's bf16 peak (%). Tiny by nature -- the model is
bound by memory and the host -- it bounds a claim on the scan even where a
kernel leaves the path."""

from bench import readers
from bench.work import ivim as work


def read(ctx):
    voxels = sum(s["voxels"] for s in ctx["records"]["scans"])
    red = ctx["trace"]
    return readers.share(work.flops(ctx["config"], voxels),
                         red["window_s"] * ctx["peaks"]["flops_bf16"]) \
        if voxels else None
