"""``prefill_padding_pct.*``: the share of the prefill programs' token
positions that was padding, from hand-made step records, and nothing (no
exception) from the records of a program that does not count it. ``reg``
(``conftest``) is the repo's own root, then its copy with a later PR's
addition."""

import types

import pytest


def _read(reg, metric, steps):
    spec = reg.load_json("metrics", metric)
    ctx = types.SimpleNamespace(steps=steps)
    return reg.module("readers", spec["reader"]).read(ctx, spec["params"])


def _step(kind, tokens, padded=None):
    rec = {"kind": kind, "tokens": tokens, "forwards": 1, "wall_s": 0.05}
    if padded is not None:
        rec["padded_tokens"] = padded
    return rec


@pytest.mark.parametrize("suffix", ["serve", "batch"])
def test_padding_share(reg, suffix):
    metric = "prefill_padding_pct." + suffix
    steps = [_step("prefill", 586, 640), _step("prefill_chunk", 300, 384),
             _step("prefill", 1100, 1024 + 128),
             # a decode burst pads nothing and is no prefill step
             _step("decode_burst", 256, 0)]
    padded, real = 640 + 384 + 1152, 586 + 300 + 1100
    assert _read(reg, metric, steps) == pytest.approx(
        100.0 * (padded - real) / padded)
    assert _read(reg, metric, [_step("prefill", 512, 512)]) == 0.0
    # the parent commit's records carry no padded_tokens; a window without
    # a prefill step has nothing to read either
    assert _read(reg, metric, [_step("prefill", 586),
                               _step("decode_burst", 8)]) is None
    assert _read(reg, metric, []) is None


def test_both_names_are_in_the_benchmark_with_their_cells(reg):
    per_layer = {m["name"]: m for m in reg.bench["per_layer"]}
    end_to_end = {m["name"]: m for m in reg.bench["end_to_end"]}
    for name, cell, moves in (
            ("prefill_padding_pct.serve", "mistral7b-sessions", "itl_p99_s"),
            ("prefill_padding_pct.batch", "mistral7b-backlog",
             "out_tokens_per_s")):
        entry = per_layer[name]
        assert cell in entry["workloads"] and entry["moves"] == moves
        # every cell listed reports the end-to-end metric it should move
        reports = end_to_end[moves].get("workloads")
        assert reports is None or set(entry["workloads"]) <= set(reports)
        assert entry["layer"] == "step programs"
        assert entry["better"] == "lower"
        assert entry["source"] == "program_counter"
