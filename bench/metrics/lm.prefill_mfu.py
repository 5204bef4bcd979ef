"""Whole prefill: FLOPs the window's admissions required at their true
lengths, over the window less the decode programs' device time, at the
chip's bf16 peak (%)."""

from bench import readers, trace
from bench.work import lm as work

DECODE = ("jit_decode_impl",)


def read(ctx):
    c, red = ctx["config"], ctx["trace"]
    flops = sum(work.prefill(c, n)[0]
                for s in readers.window_steps(ctx["records"])
                for n in s["admitted"])
    rest = red["window_s"] - trace.program_seconds(red, DECODE)
    return readers.share(flops, rest * ctx["peaks"]["flops_bf16"]) \
        if flops else None
