"""Kernels: the least time the window's voxels need on the chip (signals
in, mean and std out, packed weights once; bench/work/ivim.py), as a share
of the moments executor's device time (%). Lane padding of the 11
b-values and the chunk's padded rows are the waste it shows."""

from bench import readers, trace
from bench.work import ivim as work

MOMENTS = ("jit_run",)


def read(ctx):
    voxels = sum(s["voxels"] for s in ctx["records"]["scans"])
    t = trace.program_seconds(ctx["trace"], MOMENTS)
    n = trace.program_count(ctx["trace"], MOMENTS)
    if not voxels or not n:
        return None
    need = work.seconds(ctx["config"], voxels, ctx["peaks"]) \
        + (n - 1) * work.weight_bytes(ctx["config"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return readers.share(need, t)
