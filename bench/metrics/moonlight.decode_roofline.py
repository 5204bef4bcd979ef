"""Kernels: the least time one Moonlight decode step needs on the chip, as
a share of its device time (%). Required work (bench/work/moonlight.py):
the non-expert weights once, the routed experts the step's tokens hit
(``experts_hit`` of the step's ``serving.step`` span), the latent cache at
the positions the live rows attend to, FLOPs at the kept widths with
absorbed attention; padding rows, empty cache positions and experts read
for dead rows are the waste it shows."""

from bench import readers, spans
from bench.work import moonlight as work

DECODE = ("jit_decode_impl",)


def _with_hits(ctx, steps):
    """The window steps whose ``serving.step`` span the ring holds, each
    with that span's ``experts_hit``."""
    got = spans.window_spans(ctx)
    marks = [s for s in spans.named(got[0], "serving.step")
             if "experts_hit" in s["attrs"]] if got else []
    out, j = [], 0
    for s in steps:
        while j < len(marks) and marks[j]["t0"] < s["t0"]:
            j += 1
        if j < len(marks) and marks[j]["t0"] <= s["t1"]:
            out.append((s, marks[j]["attrs"]["experts_hit"]))
    return out


def read(ctx):
    c, rec = ctx["config"], ctx["records"]
    steps = _with_hits(ctx, [s for s in readers.window_steps(rec)
                             if s["live"]])
    t = readers.per_call(ctx["trace"], DECODE)
    if not steps or t is None:
        return None
    n = c["mask_samples"]
    need = sum(work.seconds(work.decode_step(c, n * s["live"],
                                             n * s["attended"], e),
                            ctx["peaks"]) for s, e in steps)
    return readers.share(need / len(steps), t)
