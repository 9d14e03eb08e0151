"""Percentile, gap, pace and attainment arithmetic on hand-made timelines."""

import math

import pytest

from chipbench import timeline


def rec(due, chunks, out_tokens, ok=True, sent=None):
    return {"id": "r", "due": due, "sent": due if sent is None else sent,
            "chunks": [list(c) for c in chunks],
            "end": chunks[-1][0] if chunks else None, "ok": ok,
            "out_tokens": out_tokens, "finish": "length" if ok else None,
            "error": None if ok else "http 503"}


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 90, 19), (list(range(101)), 99, 99), ([7], 95, 7),
    ([0, 10], 0, 0), ([0, 10], 100, 10)])
def test_percentile_interpolates_like_numpy(values, q, want):
    import numpy as np

    assert timeline.percentile(values, q) == pytest.approx(want)
    assert timeline.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_of_nothing_is_nan():
    assert math.isnan(timeline.percentile([], 50))


def test_ttft_counts_from_the_due_time_not_the_send_time():
    r = rec(1.0, [(1.5, 1), (1.6, 1)], 2, sent=1.2)
    assert timeline.ttft(r) == pytest.approx(0.5)
    assert timeline.late_ms([r]) == [pytest.approx(200.0)]


def test_tpot_is_span_over_tokens_minus_one():
    r = rec(0.0, [(1.0, 1), (1.1, 1), (1.4, 2)], 4)
    assert timeline.tpot(r) == pytest.approx(0.4 / 3)
    assert math.isinf(timeline.tpot(rec(0.0, [(1.0, 1)], 1)))


def test_gaps_pool_over_requests():
    a = rec(0.0, [(1.0, 1), (1.1, 1), (1.4, 1)], 3)
    b = rec(0.0, [(2.0, 1), (2.5, 1)], 2)
    assert sorted(timeline.gaps([a, b])) == [
        pytest.approx(0.1), pytest.approx(0.3), pytest.approx(0.5)]


def test_a_failed_request_is_infinitely_slow_and_misses():
    good = rec(0.0, [(0.1, 1), (0.12, 1)], 2)
    bad = rec(0.0, [], 2, ok=False)
    assert math.isinf(timeline.ttft(bad)) and math.isinf(timeline.tpot(bad))
    assert timeline.slo_met_pct([good, bad], 0.5, 0.04) == 50.0
    assert timeline.slo_met_pct([good], 0.5, 0.04) == 100.0
    assert math.isnan(timeline.slo_met_pct([], 0.5, 0.04))


@pytest.mark.parametrize("first,pace,met", [
    (0.4, 0.03, True), (0.6, 0.03, False), (0.4, 0.05, False)])
def test_slo_needs_both_limits(first, pace, met):
    r = rec(0.0, [(first, 1), (first + pace, 1)], 2)
    assert timeline.slo_met_pct([r], 0.5, 0.04) == (100.0 if met else 0.0)


def test_window_selects_by_due_time_and_tokens_by_stream_time():
    inside = rec(5.0, [(5.2, 1), (11.0, 3)], 4)
    before = rec(4.9, [(5.5, 2)], 2)
    records = [inside, before]
    assert timeline.in_window(records, 5.0, 10.0) == [inside]
    assert timeline.tokens_between(records, 5.0, 10.0) == 3
    out = timeline.end_to_end(records, 5.0, 10.0)
    assert out["out_tokens_per_s"] == pytest.approx(3 / 5.0)
    assert out["ttft_p90_s"] == pytest.approx(0.2)
    assert out["tpot_p50_s"] == pytest.approx(5.8 / 3)
    assert out["itl_p99_s"] == pytest.approx(5.8)


def test_tail_of_all_requests_goes_infinite_with_failures():
    good = [rec(1.0 + i * 0.01, [(1.5, 1), (1.6, 1)], 2) for i in range(8)]
    bad = [rec(1.2, [], 2, ok=False), rec(1.3, [], 2, ok=False)]
    out = timeline.end_to_end(good + bad, 0.0, 10.0)
    assert math.isinf(out["ttft_p95_s"])
    assert math.isfinite(out["ttft_p50_s"])


def test_spreads_three_ways():
    from chipbench.tools.sets import spreads

    out = spreads([10.0, 10.1, 10.2, 10.3, 10.4, 12.0])
    assert out["quartiles"] == pytest.approx(0.725 / 10.25)
    # 12.0 is left out; the rest are 10.0 .. 10.4 around the median 10.25
    assert out["range_less_farthest"] == pytest.approx(0.4 / 10.25)
    assert out["less_farthest"] == pytest.approx(0.3 / 10.25)
    assert out["less_farthest"] < out["quartiles"]
    assert set(spreads([1.0, 1.1])) == {"quartiles"}
