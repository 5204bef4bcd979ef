"""Volume engine: the host's cost of one voxel chunk, in us -- the mean
duration of the window's ``engine.chunk`` spans (``serving/engine.py``
``predict_packed``: slice, pad, the moments executor's dispatch and its
epilogue). The inside counterpart of ``ivim.chunk_gap_us``."""

from bench import spans


def read(ctx):
    got = spans.window_spans(ctx)
    chunks = spans.named(got[0], "engine.chunk") if got else []
    if not chunks:
        return None
    return 1e6 * sum(map(spans.duration, chunks)) / len(chunks)
