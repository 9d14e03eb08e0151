"""The schedule is drawn from the traffic file alone. ``reg``
(``conftest``) is the repo's own root, then its copy with a later PR's
addition."""

import json
import os
import random

import chipbench_rules as rules
import later_pr
import pytest

from chipbench import schedule
from chipbench.registry import Registry

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.mark.parametrize("traffic", ["sessions", "backlog"])
def test_same_file_same_bytes(reg, traffic):
    t = reg.traffic(traffic)
    a = json.dumps(schedule.build(reg, t, 20, 32000), sort_keys=True)
    random.seed(12345)  # the global generator is not what it draws from
    b = json.dumps(schedule.build(reg, t, 20, 32000), sort_keys=True)
    assert a == b


def test_seed_never_reaches_the_schedule():
    """``schedule.build`` has no seed argument, and a run's plan (what the
    generator child is handed) is the schedule: ``Cell.measure`` builds
    it from the registry, the traffic file and ``--seconds`` only."""
    import inspect

    assert "seed" not in inspect.signature(schedule.build).parameters
    from chipbench import run

    src = inspect.getsource(run.Cell.measure)
    assert "schedule.build(self.registry, traffic, seconds" in src
    assert "self.seed" not in src.split("_drive(")[0]


def test_a_longer_window_extends_the_same_schedule(reg):
    t = reg.traffic("sessions")
    short = schedule.build(reg, t, 10, 32000)["requests"]
    long = schedule.build(reg, t, 20, 32000)["requests"]
    assert long[:len(short)] == short and len(long) > len(short)


def test_sessions_schedule_has_the_stated_shape(reg):
    t = reg.traffic("sessions")
    p = t["params"]
    s = schedule.build(reg, t, 30, 32000)
    assert len(s["preload"]) == p["sessions"]
    starts = {tuple(h[:p["system_prompt_tokens"]]) for h in s["preload"]}
    assert len(starts) == p["sessions"] // p["sessions_per_system_prompt"]
    dues = [r["due"] for r in s["requests"]]
    assert dues == sorted(dues) and dues[-1] < s["window"][1] + t["tail_s"]
    for r in s["requests"]:
        assert (p["answer_tokens"]["min"] <= r["max_tokens"]
                <= p["answer_tokens"]["max"])
        assert len(r["prompt"]) + r["max_tokens"] <= p["max_history_tokens"]
        assert tuple(r["prompt"][:p["system_prompt_tokens"]]) in starts
        assert all(259 <= tok < 32000 for tok in r["prompt"])
    rate = len(s["requests"]) / (s["window"][1] + t["tail_s"])
    assert rate == pytest.approx(p["rate_per_s"], rel=0.25)
    # a turn's prompt extends what the session sent before
    seen = {}
    for r in s["requests"]:
        prev = seen.get(r["session"])
        if prev is not None and len(r["prompt"]) > len(prev):
            assert r["prompt"][:len(prev)] == prev
        seen[r["session"]] = r["prompt"]


def test_backlog_prompts_share_nothing(reg):
    t = reg.traffic("backlog")
    s = schedule.build(reg, t, 20, 32000)
    assert len(s["requests"]) == t["params"]["requests"]
    assert all(r["due"] == 0.0 for r in s["requests"])
    firsts = [tuple(r["prompt"][:64]) for r in s["requests"]]
    assert len(set(firsts)) == len(firsts)
    p = t["params"]
    for r in s["requests"]:
        assert (p["prompt_tokens"]["min"] <= len(r["prompt"])
                <= p["prompt_tokens"]["max"])
        assert len(r["prompt"]) + r["max_tokens"] <= 4096


def test_contexts_stay_within_the_assumed_window(reg):
    """Every cell's longest context fits its ``--max-model-len``, and a
    published ``sliding_window`` that the program does not apply
    (``assumed`` says so) where there is one. Both hold for both cells of
    the configuration the benchmark has."""
    assert rules.schedule_faults(reg) == []
    cells = [c for c in reg.bench["workloads"]
             if c["config"] == "mistral-7b-l16"]
    assert len(cells) >= 2
    config = reg.config("mistral-7b-l16")
    assert rules.window_binds(config)
    assert config["sliding_window"] == rules.max_model_len(config) == 4096


def _root_with(tmp_path, **changes):
    """The added configuration with ``changes`` laid over its file; its
    cell runs the tiny sessions mix (contexts up to 200)."""
    return Registry(later_pr.root_with_configuration(tmp_path / "root",
                                                     **changes))


WINDOWS = {
    # no such key: nothing to read, nothing to hold (it was a KeyError)
    "no sliding_window": ({}, False, None),
    "a null one": ({"sliding_window": None,
                    "assumed": {**later_pr.WIDE_ASSUMED,
                                "sliding_window": "null as published"}},
                   False, None),
    "one the program applies": ({"sliding_window": 64}, False, None),
    "one it does not apply, wide enough": (
        {"sliding_window": 256,
         "assumed": {**later_pr.WIDE_ASSUMED,
                     "sliding_window": "not applied; contexts stay in it"}},
        True, None),
    "one it does not apply, too narrow": (
        {"sliding_window": 64,
         "assumed": {**later_pr.WIDE_ASSUMED,
                     "sliding_window": "not applied; contexts stay in it"}},
        True, "passes the sliding_window 64"),
    "contexts over --max-model-len": (
        {"server_flags": ["--max-model-len", "100", "--no-warmup"]},
        False, "passes --max-model-len 100"),
}


@pytest.mark.parametrize("case", sorted(WINDOWS))
def test_contexts_stay_within_what_the_configuration_states(tmp_path, case):
    changes, binds, wanted = WINDOWS[case]
    reg = _root_with(tmp_path, **changes)
    assert rules.window_binds(reg.config(later_pr.WIDE)) is binds
    faults = [f for f in rules.schedule_faults(reg, 3)
              if f.startswith(later_pr.WIDE_CELL)]
    if wanted is None:
        assert faults == []
    else:
        assert len(faults) == 1 and wanted in faults[0]


def test_tokens_come_from_the_rows_held(tmp_path):
    """A sliced vocabulary is a smaller vocabulary: the schedule draws
    its ids from the rows the configuration holds."""
    reg = _root_with(tmp_path)
    config = reg.config(later_pr.WIDE)
    s = schedule.build(reg, reg.traffic("sessions-tiny"), 3,
                       config["vocab_size"])
    top = max(t for r in s["requests"] for t in r["prompt"])
    assert 259 <= top < config["vocab_size"] < config["published"][
        "vocab_size"]
