"""Load generator: how late each request of the window was submitted
after it was due, 99th percentile in ms (host clock). A generator starved
by the server's host loop shows here, not as a slow server."""

from bench import readers, stats


def read(ctx):
    lags = [r["submit"] - r["due"] for r in readers.in_window(ctx["records"])
            if r["submit"] is not None]
    v = stats.percentile(lags, 99)
    return None if v is None else 1e3 * v
