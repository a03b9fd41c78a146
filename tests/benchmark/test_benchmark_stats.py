"""The arithmetic from samples to metrics, on hand-made samples."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402


def req(due, sent, tokens, want=None, error=None):
    return {"due": due, "sent": sent, "tokens": tokens,
            "want": len(tokens) if want is None else want, "error": error}


@pytest.mark.parametrize("q,want", [(0.5, 50), (0.95, 95), (0.99, 99),
                                    (1.0, 100), (0.001, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(list(range(100, 0, -1)), q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 0.95)


def test_tokens_per_second_counts_tokens_inside_the_window_only():
    recs = [req(0, 0, [9.9, 10.0, 10.5, 19.999, 20.0]),
            req(0, 0, [12.0, 25.0])]
    assert stats.tokens_in_window(recs, 10.0, 20.0) == 4
    assert stats.rate(4, 10.0) == pytest.approx(0.4)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_ttft_is_timed_from_due_not_from_sent():
    # due at 10.0, the generator got round to it at 10.4, first token 10.5
    r = req(10.0, 10.4, [10.5, 10.6])
    assert stats.ttft_ms([r], 99.0) == [pytest.approx(500.0)]
    assert stats.lateness_ms([r]) == [pytest.approx(400.0)]


def test_sample_is_requests_due_in_the_window():
    recs = [req(9.9, 9.9, [10.1]), req(10.0, 10.0, [10.2]),
            req(19.9, 20.5, [21.0]), req(20.0, 20.0, [20.1])]
    assert [r["due"] for r in stats.due_in_window(recs, 10.0, 20.0)] \
        == [10.0, 19.9]


def test_a_failed_request_misses():
    ok = req(0.0, 0.0, [0.1, 0.2])
    refused = req(1.0, 1.0, [], want=8, error="refused: full")
    cut = req(2.0, 2.0, [2.05], want=8)  # undrained: short of its tokens
    sample = [ok, refused, cut]
    assert [stats.failed(r) for r in sample] == [False, True, True]
    ttft = stats.ttft_ms(sample, 30.0)
    # the refused one is counted at the whole time it was watched: the
    # longest in the sample, so it can only lengthen a tail
    assert ttft == [pytest.approx(100.0), pytest.approx(29000.0),
                    pytest.approx(50.0)]
    assert stats.percentile(ttft, 0.95) == pytest.approx(29000.0)


def test_gaps_are_pooled_over_requests():
    a = req(0, 0, [1.0, 1.02, 1.05])
    b = req(0, 0, [2.0, 2.3])
    assert stats.gaps_ms([a, b]) == [pytest.approx(20.0), pytest.approx(30.0),
                                     pytest.approx(300.0)]
