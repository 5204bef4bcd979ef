"""Model step: device time of one decode step, in ms (device trace). The
decode step is the program the server's per-op decode jits,
``jit_decode_impl`` (serving/server.py ``decode_impl``)."""

from bench import readers

DECODE = ("jit_decode_impl",)


def read(ctx):
    t = readers.per_call(ctx["trace"], DECODE)
    return None if t is None else 1e3 * t
