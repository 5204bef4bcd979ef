"""Server and scheduler: the 95th percentile in ms of the window's LM
admissions (``serving.admit`` spans of ``serving/server.py``: the prompt's
prefill, the scatter of its cache rows and the fetch of its first token),
which every resident request waits through."""

from bench import spans, stats


def read(ctx):
    got = spans.window_spans(ctx)
    admits = [spans.duration(s) for s in spans.named(got[0], "serving.admit")
              if s["attrs"].get("kind") == "lm"] if got else []
    v = stats.percentile(admits, 95)
    return None if v is None else 1e3 * v
