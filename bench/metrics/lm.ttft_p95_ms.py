"""Server and scheduler: from each request's due time to its first token
as the client sees it, 95th percentile in ms over the window's requests;
one without a first token by the close counts at its wait so far (host
clock). The same quantity an end-to-end tail would be; over the ~80
requests of a chat window it spreads too widely from run to run to carry
a bound, so it is read per layer."""

from bench import stats


def read(ctx):
    rec = ctx["records"]
    w0, w1 = rec["window"]
    reqs = [r for r in rec["requests"] if not r["rejected"]]
    v = stats.percentile(stats.ttft_samples(reqs, w0, w1), 95)
    return None if v is None else 1e3 * v
