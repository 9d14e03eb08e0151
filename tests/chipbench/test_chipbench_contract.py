"""``BENCHMARK.json`` against the contract, and every name it gives
against the files the harness will look for."""

import json
import os
import re

import pytest

from chipbench.registry import REPO, Registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def reg():
    return Registry(REPO)


def test_top_level_keys(reg):
    assert set(reg.bench) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert reg.bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= reg.bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_configs(reg):
    for c in reg.bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        body = reg.config(c["name"])
        assert body["reduced"] == c["reduced"] and body["source"] == c["source"]
        # no width is ever cut: the published Mistral-7B-v0.1 sizes
        assert (body["hidden_size"], body["intermediate_size"],
                body["num_attention_heads"], body["num_key_value_heads"],
                body["vocab_size"]) == (4096, 14336, 32, 8, 32000)
        assert any(w["config"] == c["name"] for w in reg.bench["workloads"])


def test_workloads(reg):
    seen = set()
    for w in reg.bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        traffic = reg.traffic(w["traffic"])
        assert reg.module("generators", traffic["generator"]).generate
        assert {"traffic_seed", "ramp_s", "params"} <= set(traffic)


def test_metrics(reg):
    cells = {w["name"] for w in reg.bench["workloads"]}
    e2e = {m["name"]: m for m in reg.bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in reg.bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in reg.bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"]
        spec = reg.load_json("metrics", m["name"])
        assert reg.module("readers", spec["reader"]).read
    for m in reg.bench["end_to_end"] + reg.bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in reg.bench["end_to_end"] + reg.bench["per_layer"]]
    assert len(names) == len(set(names))
    for cell in cells:
        assert len(reg.metrics_for("end_to_end", cell)) >= 2
        assert len(reg.metrics_for("per_layer", cell)) >= 1


def test_files_under_paths_have_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in ("chipbench", "tests/chipbench"):
        for root, dirs, files in os.walk(os.path.join(REPO, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(root, name), REPO)
                assert ok.match(rel), rel
