"""Server and scheduler: the share of the pool's slots that decoded in a
step, averaged over the window's steps (%). Each occupied LM slot emits
exactly one token per step, so the count is read from the tokens the
program returned."""

from bench import readers


def read(ctx):
    rec = ctx["records"]
    steps = readers.window_steps(rec)
    if not steps:
        return None
    return 100.0 * sum(s["live"] for s in steps) / (len(steps)
                                                   * rec["max_slots"])
