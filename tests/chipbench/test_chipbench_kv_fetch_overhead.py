"""``kv_fetch_overhead_pct.*``: what the decode attention kernel copies
beyond what its rows hold, from hand-made step records, and nothing (no
exception) from the records of a program that does not count it. ``reg``
(``conftest``) is the repo's own root, then its copy with a later PR's
addition."""

import types

import pytest


def _read(reg, metric, steps):
    spec = reg.load_json("metrics", metric)
    ctx = types.SimpleNamespace(steps=steps)
    return reg.module("readers", spec["reader"]).read(ctx, spec["params"])


def _step(kind, live, fetched=None):
    # kv_read_tokens, the recorder's count at the burst's start, is above
    # the live tokens where rows finish inside the burst: not read here
    rec = {"kind": kind, "tokens": 256, "forwards": 8, "wall_s": 0.09,
           "kv_read_tokens": live + 4096}
    if fetched is not None:
        rec.update(kv_fetch_tokens=fetched, kv_live_tokens=live)
    return rec


@pytest.mark.parametrize("suffix", ["serve", "batch"])
def test_fetch_overhead(reg, suffix):
    metric = "kv_fetch_overhead_pct." + suffix
    steps = [_step("decode_burst", 8 * 23200, 8 * 24200),
             _step("decode_burst", 8 * 25000, 8 * 26100),
             # a prefill step reads its prefix through another kernel
             _step("prefill", 512, 0)]
    live, fetched = 8 * (23200 + 25000), 8 * (24200 + 26100)
    assert _read(reg, metric, steps) == pytest.approx(
        100.0 * (fetched - live) / live)
    assert _read(reg, metric, [_step("decode_burst", 800, 800)]) == 0.0
    assert _read(reg, metric, [_step("decode_burst", 800, 900)]) == 12.5
    # the parent commit's records (and the XLA path's) carry neither
    # count; a window without a decode step has nothing either
    assert _read(reg, metric, [_step("decode_burst", 800),
                               _step("prefill", 512)]) is None
    assert _read(reg, metric, []) is None


def test_both_names_are_in_the_benchmark_with_their_cells(reg):
    per_layer = {m["name"]: m for m in reg.bench["per_layer"]}
    end_to_end = {m["name"]: m for m in reg.bench["end_to_end"]}
    for name, cell, moves in (
            ("kv_fetch_overhead_pct.serve", "mistral7b-sessions",
             "tpot_p50_s"),
            ("kv_fetch_overhead_pct.batch", "mistral7b-backlog",
             "out_tokens_per_s")):
        entry = per_layer[name]
        assert cell in entry["workloads"] and entry["moves"] == moves
        # every cell listed reports the end-to-end metric it should move
        reports = end_to_end[moves].get("workloads")
        assert reports is None or set(entry["workloads"]) <= set(reports)
        assert entry["layer"] == "attention kernels"
        assert entry["better"] == "lower"
        assert entry["source"] == "program_counter"
