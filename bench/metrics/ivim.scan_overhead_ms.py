"""Volume engine: the host time per scan outside every ``engine.chunk``
span, in ms -- the assembly of the maps, their fetch to the host, and
anything between scans. Read over the held part of the window from its
first whole scan (the window's start where the ring holds it) to its
close, which is the end of a scan."""

from bench import spans


def read(ctx):
    got = spans.window_spans(ctx)
    if not got:
        return None
    sp, (lo, hi) = got
    scans = spans.named(sp, "engine.predict_volume")
    if not scans:
        return None
    if lo > ctx["records"]["window"][0]:
        lo = scans[0]["t0"]          # the ring starts inside the window
    chunks = sum(spans.duration(s) for s in spans.named(sp, "engine.chunk")
                 if s["t0"] >= lo)
    return 1e3 * ((hi - lo) - chunks) / len(scans)
