"""Model step: the prefill programs' share of the device time of all
programs in the window (%). Prefill is the bucketed prefill runner,
``jit_run`` (core/plan.py ``_prefill_runner``), or the exact-length
``jit_prefill_impl`` (serving/server.py)."""

PREFILL = ("jit_run", "jit_prefill_impl")


def read(ctx):
    from bench import readers, trace
    red = ctx["trace"]
    total = sum(p["seconds"] for p in red["programs"].values())
    return readers.share(trace.program_seconds(red, PREFILL), total)
