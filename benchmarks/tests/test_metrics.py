"""Metric arithmetic on hand-made token timelines."""

import math

from harness.metrics import Record, end_to_end, percentile, ttft_s, tpot_s


def steady(idx, start, n=11, first=0.2, gap=0.05, stall_at=None, stall=0.0):
    r = Record(idx, prompt_len=100, max_new=n, start=start)
    t = start + first
    for i in range(n):
        if stall_at is not None and t >= stall_at:
            t += stall
            stall_at = None
        r.token_times.append(t)
        r.tokens.append(i)
        t += gap
    r.final_ids = list(r.tokens)
    r.gave_up = r.token_times[-1]
    return r


def test_percentile_is_nearest_rank():
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert percentile([5.0], 90) == 5.0
    assert math.isnan(percentile([], 50))


def test_steady_timeline():
    recs = [steady(i, 0.5 * i) for i in range(20)]
    m = end_to_end(recs, 12.0)
    assert abs(m["ttft_p90_ms"] - 200.0) < 1e-6
    assert abs(m["tpot_p90_ms"] - 50.0) < 1e-6
    assert abs(m["tokens_per_s"] - 20 * (100 + 11) / 12.0) < 1e-9
    # Tokens that arrive after the close are not the window's work.
    assert end_to_end(recs, 10.0)["tokens_per_s"] == (20 * 111 - 5) / 10.0


def test_a_stall_inside_the_window_moves_all_three():
    base = [steady(i, 0.5 * i, n=41) for i in range(20)]
    # One second in which no token arrives anywhere, from t = 4.
    stalled = []
    for i in range(20):
        start = 0.5 * i
        if start >= 4.0:  # due during or after the stall: the first token waits
            r = steady(i, start, n=41, first=0.2 + max(0.0, 5.0 - start))
        else:
            r = steady(i, start, n=41, stall_at=4.0, stall=1.0)
        stalled.append(r)
    a, b = end_to_end(base, 6.0), end_to_end(stalled, 6.0)
    assert b["ttft_p90_ms"] > a["ttft_p90_ms"] + 400
    assert b["tpot_p90_ms"] > a["tpot_p90_ms"] + 20
    assert b["tokens_per_s"] < a["tokens_per_s"]


def test_a_failed_request_misses():
    recs = [steady(i, 0.5 * i) for i in range(9)]
    bad = Record(9, 100, 11, start=4.5, error="http 500", gave_up=64.5)
    m = end_to_end(recs + [bad], 10.0)
    assert ttft_s(bad) == 60.0
    assert abs(m["ttft_p90_ms"] - 200.0) < 1e-6  # nine of ten still meet
    worse = end_to_end(recs[:8] + [bad, Record(10, 100, 11, 4.6, error="x", gave_up=64.6)], 10.0)
    assert abs(worse["ttft_p90_ms"] - 60000.0) < 1e-6  # two of ten missing: the tail is a miss
    assert abs(tpot_s(bad) - 6.0) < 1e-9


def test_unfinished_request_is_not_complete():
    r = steady(0, 0.0)
    r.tokens.pop()
    assert not r.complete
