"""Volume engine: the mean device gap between the end of one run of the
moments executor and the start of the next, in us (device trace). The
engine launches the fused moments kernel once per voxel chunk from a host
loop; the gap is the host's share of a chunk. The executor is the jitted
fused runner, ``jit_run`` (core/plan.py ``_fused_runner``)."""

from bench import trace

MOMENTS = ("jit_run",)


def read(ctx):
    gaps = trace.launch_gaps(ctx["trace"], MOMENTS)
    return 1e6 * sum(gaps) / len(gaps) if gaps else None
