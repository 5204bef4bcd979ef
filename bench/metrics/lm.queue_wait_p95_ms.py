"""Server and scheduler: from due to admission into a slot (the start of
the step that admitted it), 95th percentile in ms over the window's
requests; one still queued at the close counts at its wait so far."""

from bench import readers, stats


def read(ctx):
    rec = ctx["records"]
    w1 = rec["window"][1]
    waits = [(r["admit"] if r["admit"] is not None else w1) - r["due"]
             for r in readers.in_window(rec) if not r["rejected"]]
    v = stats.percentile(waits, 95)
    return None if v is None else 1e3 * v
