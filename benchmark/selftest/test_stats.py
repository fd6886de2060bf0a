"""Percentile and due-time arithmetic on a synthetic log."""

import pytest

from benchmark.harness import stats
from benchmark.harness.stats import RequestRecord


def test_percentile_interpolates_like_numpy():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def rec(i, due, commits, **kw):
    return RequestRecord(index=i, req_id=f"r{i}", prompt_len=10, budget=99, due_s=due,
                         commits=commits, **kw)


def test_ttft_counts_from_due_not_from_send():
    # due at 1.0 s, sent late at 1.4 s, first token at 2.0 s: TTFT is 1000 ms
    r = rec(0, 1.0, [(2.0, 1)], sent_s=1.4, admitted_s=1.5)
    s = stats.summarize([r], window_s=10.0)
    assert s["ttft_p50_ms"] == pytest.approx(1000.0)
    assert s["late_p95_ms"] == pytest.approx(400.0)
    assert s["queue_wait_mean_ms"] == pytest.approx(500.0)
    assert (s["attempted"], s["failed"]) == (1, 0)


def test_failed_and_unanswered_requests_count_against_attempted():
    good = rec(0, 0.0, [(1.0, 1)])
    lost = rec(1, 0.5, [], failed="refused:prompt_too_long")
    silent = rec(2, 0.7, [])
    before = rec(3, None, [(0.5, 1)])  # first round: in no latency sample
    s = stats.summarize([good, lost, silent, before], window_s=10.0)
    assert (s["attempted"], s["failed"], s["ttft_n"]) == (3, 2, 1)


def test_tpot_is_over_tokens_inside_the_window_only():
    # 1 token per commit every 0.1 s from t=-0.5 (before the window) to t=2.4
    commits = [(-0.5 + 0.1 * k, 1) for k in range(30)]
    r = rec(0, None, commits)
    inside = [c for c in commits if 0.0 <= c[0] <= 2.0]
    assert stats.tpot_ms(r, 2.0) == pytest.approx(
        (inside[-1][0] - inside[0][0]) / (len(inside) - 1) * 1e3
    )
    # fewer than TPOT_MIN_TOKENS inside the window: no sample
    assert stats.tpot_ms(rec(1, 0.0, commits[:20]), 1.0) is None
    # a commit of several tokens: the first commit's tokens mark the start
    multi = rec(2, 0.0, [(0.0, 4), (1.0, 8), (2.0, 8)])
    assert stats.tpot_ms(multi, 5.0) == pytest.approx(2.0 / 16 * 1e3)


def test_out_tok_s_is_all_tokens_of_the_window_over_the_window():
    a = rec(0, None, [(-1.0, 5), (1.0, 3), (9.0, 2), (11.0, 7)])
    s = stats.summarize([a], window_s=10.0)
    assert s["out_tokens"] == 5 and s["out_tok_s"] == pytest.approx(0.5)


def test_span_stats():
    spans = [("step", 0.0, 0.5), ("step", 0.5, 1.5), ("admit", 0.4, 0.41), ("step", 12.0, 13.0)]
    out = stats.span_stats(spans, window_s=10.0)
    assert out["step"]["count"] == 2 and out["step"]["mean_ms"] == pytest.approx(750.0)
    assert out["admit"]["count"] == 1
