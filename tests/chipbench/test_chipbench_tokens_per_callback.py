"""The reader of ``emit_tokens_per_callback.*`` (PR 40) on hand-made step
records: with the count of deliveries, without it (the parent commit's
program delivers per token and writes none: nothing, and no exception),
and where no burst was flushed in the window."""

import types

import pytest

SUFFIXES = ("batch", "serve")

# a decode burst's record as the parent commit's program writes it
OLD_STEP = {"kind": "decode_burst", "wall_s": 0.1, "forwards": 8,
            "phases": {"build": 0.002, "emit": 0.1},
            "emit_tokens": 1024, "emit_rows": 128, "emit_finished": 0,
            "emit_callback_s": 0.012, "emit_callback_samples": 128}
STEPS = [
    # 128 rows x 8 steps, a delivery a row
    {**OLD_STEP, "emit_callback_samples": 1024, "emit_callbacks": 128},
    # three answers ended mid-burst: fewer tokens, the same deliveries
    {**OLD_STEP, "emit_tokens": 1009, "emit_callback_samples": 1009,
     "emit_callbacks": 128, "emit_finished": 3},
    # a prefill's flush: a row's first token, in neither count
    {"kind": "prefill", "wall_s": 0.05, "forwards": 1,
     "phases": {"emit": 0.001}, "emit_tokens": 0, "emit_rows": 1,
     "emit_finished": 0},
]


def _read(reg, metric, steps):
    spec = reg.load_json("metrics", metric)
    ctx = types.SimpleNamespace(
        steps=steps, traced_steps=[], traces=[], device=None, records=[],
        due=[], window=(0.0, 51.0))
    return reg.module("readers", spec["reader"]).read(
        ctx, spec.get("params", {}))


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_tokens_per_callback(reg, suffix):
    metric = f"emit_tokens_per_callback.{suffix}"
    assert _read(reg, metric, STEPS) == pytest.approx((1024 + 1009) / 256)
    assert _read(reg, metric, [OLD_STEP] * 3) is None
    assert _read(reg, metric, STEPS[2:]) is None
    assert _read(reg, metric, []) is None
    # a program that handed every token over alone would read 1.0
    per_token = {**OLD_STEP, "emit_callbacks": 1024}
    assert _read(reg, metric, [per_token]) == pytest.approx(1.0)
