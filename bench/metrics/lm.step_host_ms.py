"""Server and scheduler: the host's own time per engine step in ms -- the
mean over the window's ``serving.step`` spans of their time outside every
``serving.sync`` span nested in them (the fetches of the first tokens, the
argmax and the uncertainties), which the device cannot overlap."""

from bench import spans


def read(ctx):
    got = spans.window_spans(ctx)
    steps = spans.named(got[0], "serving.step") if got else []
    if not steps:
        return None
    sync = spans.under(got[0], "serving.step", "serving.sync")
    own = [spans.duration(s) - sync[s["id"]] for s in steps]
    return 1e3 * sum(own) / len(own)
