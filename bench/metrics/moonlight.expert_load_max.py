"""MoE routing: how unevenly a decode step's tokens load the experts --
the pairs of the busiest expert over the mean pairs per expert, in the
step's worst MoE layer (``expert_load_max`` of the ``serving.step`` spans,
serving/server.py), averaged over the window's steps. 1 is an even load;
the busiest expert sets the time of a grouped matmul."""

from bench import spans


def read(ctx):
    got = spans.window_spans(ctx)
    loads = [s["attrs"]["expert_load_max"]
             for s in spans.named(got[0], "serving.step")
             if "expert_load_max" in s["attrs"]] if got else []
    return sum(loads) / len(loads) if loads else None
