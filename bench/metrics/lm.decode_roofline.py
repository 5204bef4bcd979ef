"""Kernels: the least time one decode step needs on the chip, as a share
of its device time (%). Required work (bench/work/lm.py): every weight
once, the KV of the positions the live rows attend to, FLOPs at the kept
FFN width; padding rows and empty cache positions are the waste it shows."""

from bench import readers
from bench.work import lm as work

DECODE = ("jit_decode_impl",)


def read(ctx):
    c, rec = ctx["config"], ctx["records"]
    steps = [s for s in readers.window_steps(rec) if s["live"]]
    t = readers.per_call(ctx["trace"], DECODE)
    if not steps or t is None:
        return None
    n = c["mask_samples"]
    need = sum(work.seconds(work.decode_step(c, n * s["live"],
                                             n * s["attended"]),
                            ctx["peaks"]) for s in steps) / len(steps)
    return readers.share(need, t)
