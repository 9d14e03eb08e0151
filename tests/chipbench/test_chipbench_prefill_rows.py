"""``prefill_rows_mean.batch``: the mean prompts of a prefill step, from
hand-made step records; 1.0 from a program that prefills one prompt a step
(the parent commit's), and nothing (no exception) where the window holds
no prefill step. ``reg`` (``conftest``) is the repo's own root, then its
copy with a later PR's addition."""

import types

import pytest

METRIC = "prefill_rows_mean.batch"


def _read(reg, steps):
    spec = reg.load_json("metrics", METRIC)
    ctx = types.SimpleNamespace(steps=steps)
    return reg.module("readers", spec["reader"]).read(ctx, spec["params"])


def _step(kind, rows=None):
    rec = {"kind": kind, "tokens": 500, "forwards": 1, "wall_s": 0.05}
    if rows is not None:
        rec["rows"] = rows
    return rec


@pytest.mark.parametrize("steps,want", [
    ([_step("prefill", 4), _step("prefill", 2), _step("prefill", 1),
      _step("prefill", 1), _step("decode_burst", 128)], 2.0),
    ([_step("prefill", 1)] * 3, 1.0),
    # the chunked step plan's steps are another kind, with another metric
    ([_step("prefill_chunk", 4), _step("prefill", 2)], 2.0),
    ([_step("decode_burst", 32)], None),
    ([_step("prefill")], None),
    ([], None),
])
def test_mean_rows_of_the_prefill_steps(reg, steps, want):
    got = _read(reg, steps)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_name_is_in_the_benchmark_with_the_backlog_cells(reg):
    per_layer = {m["name"]: m for m in reg.bench["per_layer"]}
    end_to_end = {m["name"]: m for m in reg.bench["end_to_end"]}
    entry = per_layer[METRIC]
    for cell in ("mistral7b-backlog", "laguna-s-backlog-wide"):
        assert cell in entry["workloads"]
    assert "mistral7b-sessions" not in entry["workloads"]
    assert entry["moves"] == "out_tokens_per_s"
    assert set(entry["workloads"]) <= set(
        end_to_end["out_tokens_per_s"]["workloads"])
    assert entry["layer"] == "step programs"
    assert (entry["better"], entry["source"], entry["unit"]) == (
        "higher", "program_counter", "rows")
