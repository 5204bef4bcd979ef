"""Device: the share of the window in which no operation ran on the chip
(%, device trace busy union)."""

from bench import readers


def read(ctx):
    red = ctx["trace"]
    busy = readers.share(red["busy_s"], red["window_s"])
    return None if busy is None else 100.0 - busy
