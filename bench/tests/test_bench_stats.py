"""Arithmetic of the end-to-end metrics on synthetic records: rates over
the whole window, tails over every request due in it, unfinished ones
included at their wait so far."""

import pytest

from bench import stats


def req(due, tokens, finished=None):
    return {"due": due, "tokens": list(tokens), "finished": finished}


W0, W1 = 10.0, 20.0


def test_ttft_counts_a_request_still_waiting_at_the_close():
    reqs = [req(11.0, [11.5, 11.6], 11.6),     # 0.5 s
            req(12.0, []),                     # waiting: 20 - 12 = 8 s
            req(19.0, [20.5]),                 # first token after close: 1 s
            req(5.0, [5.1], 5.1)]              # due before the window
    assert sorted(stats.ttft_samples(reqs, W0, W1)) == \
        pytest.approx([0.5, 1.0, 8.0])


def test_itl_adds_the_open_gap_of_a_request_still_decoding():
    reqs = [req(11.0, [11.5, 11.6, 11.9], 11.9),   # gaps 0.1, 0.3
            req(12.0, [12.5, 13.0])]               # gap 0.5, open gap 7.0
    assert sorted(stats.itl_samples(reqs, W0, W1)) == \
        pytest.approx([0.1, 0.3, 0.5, 7.0])


def test_token_rate_is_over_the_whole_window():
    reqs = [req(5.0, [9.0, 10.0, 15.0], 15.0), req(18.0, [19.0, 21.0])]
    n = stats.tokens_in(reqs, W0, W1)
    assert n == 3
    assert stats.rate(n, W0, W1) == pytest.approx(0.3)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 95) is None
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
