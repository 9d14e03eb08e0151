"""From the generator's request records to the numbers a user feels.

A record is what ``loadgen`` writes for one request: ``due`` (seconds
after traffic start at which it was scheduled), ``sent``, ``chunks`` (a
list of [time, tokens] for every streamed chunk that carried tokens),
``end``, ``ok`` and ``out_tokens`` (the scheduled answer length). All
times are on the generator's clock, relative to traffic start. Pure
Python: the generator process and the tests import it without JAX.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks, as numpy's default does; nan for no values."""
    data = sorted(values)
    if not data:
        return math.nan
    pos = (len(data) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(data) - 1)
    if pos == lo or data[lo] == data[hi]:
        return data[lo]
    if math.isinf(data[hi]):
        return math.inf  # between a time and a failure: a failure
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)


def in_window(records, start: float, end: float):
    """Requests that were due inside [start, end)."""
    return [r for r in records if start <= r["due"] < end]


def ttft(record) -> float:
    """Due time to first streamed token; infinite for a request that
    failed or streamed nothing (it misses every limit)."""
    if not record["ok"] or not record["chunks"]:
        return math.inf
    return record["chunks"][0][0] - record["due"]


def tpot(record) -> float:
    """(last token time - first token time) / (output tokens - 1)."""
    if not record["ok"] or not record["chunks"] or record["out_tokens"] < 2:
        return math.inf
    span = record["chunks"][-1][0] - record["chunks"][0][0]
    return span / (record["out_tokens"] - 1)


def gaps(records):
    """Every gap between consecutive token-carrying chunks of a request,
    pooled over the requests."""
    out = []
    for r in records:
        times = [t for t, _ in r["chunks"]]
        out.extend(b - a for a, b in zip(times, times[1:]))
    return out


def tokens_between(records, start: float, end: float) -> int:
    """Output tokens streamed inside [start, end), whatever request they
    belong to."""
    return sum(n for r in records for t, n in r["chunks"]
               if start <= t < end)


def slo_met_pct(records, ttft_limit_s: float, tpot_limit_s: float) -> float:
    """Share of the requests that met both limits; a failed request is a
    miss. nan for no requests."""
    if not records:
        return math.nan
    met = sum(1 for r in records
              if ttft(r) <= ttft_limit_s
              and (r["out_tokens"] < 2 or tpot(r) <= tpot_limit_s))
    return 100.0 * met / len(records)


def late_ms(records):
    """How late each request left the generator, in milliseconds."""
    return [1000.0 * (r["sent"] - r["due"]) for r in records
            if r.get("sent") is not None]


def end_to_end(records, start: float, end: float) -> dict:
    """Every end-to-end number the records support, by metric name. A
    tail is the tail of all requests due in the window: a failed one
    counts as infinitely slow, so enough failures make the tail
    infinite (and the run not correct)."""
    due = in_window(records, start, end)
    out = {}
    if due:
        first = [ttft(r) for r in due]
        out["ttft_p50_s"] = percentile(first, 50)
        out["ttft_p90_s"] = percentile(first, 90)
        out["ttft_p95_s"] = percentile(first, 95)
        pace = [tpot(r) for r in due if r["out_tokens"] >= 2]
        if pace:
            out["tpot_p50_s"] = percentile(pace, 50)
        pooled = gaps(due)
        if pooled:
            out["itl_p99_s"] = percentile(pooled, 99)
    if end > start:
        out["out_tokens_per_s"] = tokens_between(records, start, end) / (
            end - start)
    return out
