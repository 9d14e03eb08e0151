"""The schedule is drawn from the traffic file alone."""

import json
import os
import random

import pytest

from chipbench import schedule
from chipbench.registry import REPO, Registry

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def real():
    return Registry(REPO)


@pytest.mark.parametrize("traffic", ["sessions", "backlog"])
def test_same_file_same_bytes(real, traffic):
    t = real.traffic(traffic)
    a = json.dumps(schedule.build(real, t, 20, 32000), sort_keys=True)
    random.seed(12345)  # the global generator is not what it draws from
    b = json.dumps(schedule.build(real, t, 20, 32000), sort_keys=True)
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


def test_a_longer_window_extends_the_same_schedule(real):
    t = real.traffic("sessions")
    short = schedule.build(real, t, 10, 32000)["requests"]
    long = schedule.build(real, t, 20, 32000)["requests"]
    assert long[:len(short)] == short and len(long) > len(short)


def test_sessions_schedule_has_the_stated_shape(real):
    t = real.traffic("sessions")
    p = t["params"]
    s = schedule.build(real, t, 30, 32000)
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


def test_backlog_prompts_share_nothing(real):
    t = real.traffic("backlog")
    s = schedule.build(real, t, 20, 32000)
    assert len(s["requests"]) == t["params"]["requests"]
    assert all(r["due"] == 0.0 for r in s["requests"])
    firsts = [tuple(r["prompt"][:64]) for r in s["requests"]]
    assert len(set(firsts)) == len(firsts)
    p = t["params"]
    for r in s["requests"]:
        assert (p["prompt_tokens"]["min"] <= len(r["prompt"])
                <= p["prompt_tokens"]["max"])
        assert len(r["prompt"]) + r["max_tokens"] <= 4096


def test_contexts_stay_within_the_assumed_window(real):
    for cell in real.bench["workloads"]:
        config = real.config(cell["config"])
        traffic = real.traffic(cell["traffic"])
        s = schedule.build(real, traffic, 15, config["vocab_size"])
        longest = max(len(r["prompt"]) + r["max_tokens"]
                      for r in s["requests"])
        assert longest <= config["sliding_window"]
        assert longest <= int(config["server_flags"][
            config["server_flags"].index("--max-model-len") + 1])

