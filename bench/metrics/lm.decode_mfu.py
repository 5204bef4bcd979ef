"""Whole decode loop: FLOPs the window's decode steps required, over the
window less the prefill programs' device time, at the chip's bf16 peak
(%). It bounds a claim on decode even where a kernel leaves the path."""

from bench import readers, trace
from bench.work import lm as work

PREFILL = ("jit_run", "jit_prefill_impl")


def read(ctx):
    c, red = ctx["config"], ctx["trace"]
    n = c["mask_samples"]
    flops = sum(work.decode_step(c, n * s["live"], n * s["attended"])[0]
                for s in readers.window_steps(ctx["records"]) if s["live"])
    rest = red["window_s"] - trace.program_seconds(red, PREFILL)
    return readers.share(flops, rest * ctx["peaks"]["flops_bf16"]) \
        if flops else None
