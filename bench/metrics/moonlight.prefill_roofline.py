"""Kernels: the least time one Moonlight admission's prefill needs on the
chip -- expanded latent attention at the prompt's true length under each
mask, every weight once -- as a share of the bucketed prefill program's
device time (%). Bucket padding and the routing of pad tokens are the
waste it shows (bench/work/moonlight.py)."""

from bench import readers
from bench.work import moonlight as work

PREFILL = ("jit_run", "jit_prefill_impl")


def read(ctx):
    c, rec = ctx["config"], ctx["records"]
    lengths = [n for s in readers.window_steps(rec) for n in s["admitted"]]
    t = readers.per_call(ctx["trace"], PREFILL)
    if not lengths or t is None:
        return None
    need = sum(work.seconds(work.prefill(c, n), ctx["peaks"])
               for n in lengths) / len(lengths)
    return readers.share(need, t)
