"""End-to-end metric arithmetic on hand-made records."""

import pytest

from perf import e2e


def rec(due, times, ok=True, **kw):
    return {"due": due, "sent": due, "ok": ok, "end": times[-1] if times else None,
            "events": [[t, 1] for t in times], **kw}


def test_percentile_interpolates_like_numpy():
    assert e2e.percentile([1, 2, 3, 4], 50) == 2.5
    assert e2e.percentile(range(101), 95) == 95
    assert e2e.percentile([], 95) is None


def test_tpot_is_per_request_and_over_requests_that_ended_in_the_window():
    records = [rec(0.0, [1.0, 1.1, 1.2, 1.3]),     # 0.3 / 3 = 100 ms
               rec(0.0, [2.0, 2.4]),                # 400 ms
               rec(0.0, [9.0, 11.0])]               # ended outside: not counted
    assert e2e.tpot_p95_ms("open", records, 10.0) == pytest.approx(100 + 0.95 * 300)


def test_tpot_mean_weighs_every_output_token_alike():
    records = [rec(0.0, [1.0, 1.1, 1.2, 1.3]),     # 0.3 s over 3 tokens
               rec(0.0, [2.0, 2.4]),                # 0.4 s over 1 token
               rec(0.0, [9.0, 11.0])]               # ended outside: not counted
    assert e2e.tpot_mean_ms("open", records, 10.0) == pytest.approx(700 / 4)


def test_out_tok_s_counts_tokens_received_inside_the_window():
    records = [rec(-1.0, [-0.5, 0.5, 1.5]), rec(0.0, [9.9, 10.1])]
    assert e2e.out_tok_s("closed", records, 10.0) == pytest.approx(3 / 10.0)


def test_gap_p99_pools_gaps_over_requests():
    records = [rec(0.0, [1.0, 1.01, 1.02, 2.02]), rec(0.0, [3.0, 3.5])]
    gaps = sorted([0.01, 0.01, 1.0, 0.5])
    assert e2e.gap_p99_ms("open", records, 10.0) == pytest.approx(
        e2e.percentile(gaps, 99) * 1e3)


def test_a_failed_request_misses_and_is_counted():
    records = [rec(1.0, [1.2, 1.3]) for _ in range(9)] + [rec(2.0, [], ok=False)]
    assert e2e.counts("open", records, 10.0) == (10, 1)
    # The failed one counts as the whole drain limit: (10 + 30 - 2) s.
    assert e2e.ttft_p95_ms("open", records, 10.0, 30.0) > 10_000


def test_a_stream_cut_after_the_window_still_gives_its_first_token():
    cut = {**rec(9.5, [10.4], ok=False), "cut": True, "end": None}
    records = [rec(1.0, [1.5, 1.6]), cut]
    assert e2e.counts("open", records, 10.0) == (2, 0)
    assert e2e.ttft_p95_ms("open", records, 10.0) == pytest.approx(500 + 0.95 * 400)
    assert e2e.tpot_p95_ms("open", records, 10.0) == pytest.approx(100.0)


def test_closed_loop_counts_requests_that_ended_in_the_window():
    records = [rec(None, [1.0, 2.0]), rec(None, [-2.0, -1.0]),
               {**rec(None, [8.0, 9.9], ok=False), "cut": True, "end": None}]
    assert e2e.counts("closed", records, 10.0) == (1, 0)
