"""``BENCHMARK.json`` against the contract, and every name it gives
against the files the harness will look for."""

import json
import os
import re

import chipbench_rules as rules
import later_pr
import pytest

from chipbench.registry import HARNESS_KEYS, REPO, Registry, model_keys

NAME = rules.NAME
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
    """Every configuration keeps to the sizing rules (``chipbench_rules``:
    only counts of what is held here are cut, each cut states the
    published value, the floors, a deployment it stands for, a cell),
    and the one every accepted line of the ledger rests on keeps its
    source's widths number by number."""
    assert rules.contract_faults(reg) == []
    pinned = rules.PINNED["mistral-7b-l16"]
    body = reg.config("mistral-7b-l16")
    assert len(pinned) == 5 and {k: body[k] for k in pinned} == pinned
    assert body["published"] == {"num_hidden_layers": 32}
    assert set(rules.PINNED) <= {c["name"] for c in reg.bench["configs"]}


def _broken(tmp_path, **changes):
    """The faults of the added configuration with ``changes`` laid over
    its file."""
    reg = Registry(later_pr.root_with_configuration(tmp_path / "root",
                                                    **changes))
    return rules.config_faults(reg, reg.bench["configs"][-1])


def test_configs_pass_an_added_configuration_with_a_cut(tmp_path):
    assert _broken(tmp_path) == []


BROKEN = {
    "a width in reduced": (
        {"reduced": ["num_hidden_layers", "vocab_size", "hidden_size"],
         "published": {**later_pr.WIDE_PUBLISHED, "hidden_size": 384}},
        ["reduced names hidden_size: a width",
         "published names hidden_size: a width"]),
    "an expert width in reduced": (
        {"moe_intermediate_size": 64,
         "reduced": ["num_hidden_layers", "vocab_size",
                     "moe_intermediate_size"],
         "published": {**later_pr.WIDE_PUBLISHED,
                       "moe_intermediate_size": 128}},
        ["reduced names moe_intermediate_size: a width"]),
    "experts per token in reduced": (
        {"num_experts_per_tok": 2,
         "reduced": ["num_hidden_layers", "vocab_size",
                     "num_experts_per_tok"],
         "published": {**later_pr.WIDE_PUBLISHED, "num_experts_per_tok": 8}},
        ["reduced names num_experts_per_tok: a width"]),
    "a rank under published alone": (
        {"published": {**later_pr.WIDE_PUBLISHED, "kv_lora_rank": 512},
         "assumed": {**later_pr.WIDE_ASSUMED, "kv_lora_rank": "halved"}},
        ["published names kv_lora_rank: a width"]),
    "a key that counts nothing held here": (
        {"reduced": ["num_hidden_layers", "vocab_size",
                     "max_position_embeddings"],
         "published": {**later_pr.WIDE_PUBLISHED,
                       "max_position_embeddings": 4096}},
        ["reduced names max_position_embeddings: no count"]),
    "7 experts held": (
        {"n_routed_experts": 7,
         "reduced": ["num_hidden_layers", "vocab_size", "n_routed_experts"],
         "published": {**later_pr.WIDE_PUBLISHED, "n_routed_experts": 64}},
        ["7 routed experts held: fewer than 8"]),
    "a ninth of the vocabulary": (
        {"vocab_size": 1024,
         "published": {**later_pr.WIDE_PUBLISHED, "vocab_size": 9216}},
        ["under an eighth of the published 9216"]),
    "three layers after the leading dense one": (
        {"first_k_dense_replace": 1},
        ["4 layers of which 1 dense: fewer than 4 after"]),
    "a published value missing": (
        {"published": {"vocab_size": 2048, "chips_per_layer": 1}},
        ["reduced names num_hidden_layers, published does not give"]),
    "no published block": (
        {"published": later_pr.DROP}, ["no published block"]),
    "a cut that holds all": (
        {"published": {**later_pr.WIDE_PUBLISHED, "num_hidden_layers": 4}},
        ["num_hidden_layers is reduced and holds 4, not less"]),
    "a deployment key nobody explains": (
        {"assumed": {}},
        ["published names chips_per_layer, which is neither in reduced"]),
    "no deployment": (
        {"stands_for": " "}, ["stands_for is empty"]),
}


@pytest.mark.parametrize("case", sorted(BROKEN))
def test_configs_refuse(tmp_path, case):
    changes, wanted = BROKEN[case]
    faults = _broken(tmp_path, **changes)
    for text in wanted:
        assert any(text in f for f in faults), (text, faults)


def test_configs_hold_the_floors_to_eight_experts_and_an_eighth(tmp_path):
    """Right at the floors: 8 experts of 64, an eighth of the rows, four
    layers after one dense."""
    assert _broken(
        tmp_path, n_routed_experts=8, first_k_dense_replace=1,
        num_hidden_layers=5, vocab_size=1024,
        layer_types=["full_attention"] * 5,
        reduced=["num_hidden_layers", "vocab_size", "n_routed_experts"],
        published={**later_pr.WIDE_PUBLISHED, "n_routed_experts": 64,
                   "vocab_size": 8192}) == []


def test_configs_refuse_a_pinned_width_changed(tmp_path, reg):
    """The table of pinned configurations: the repo's own BENCHMARK.json
    over a file whose hidden size is not its source's."""
    body = reg.config("mistral-7b-l16")
    body["hidden_size"] = 2048
    path = tmp_path / reg.bench["configs"][0]["file"]
    os.makedirs(path.parent)
    path.write_text(json.dumps(body))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (tmp_path / "BENCHMARK.json").write_text(f.read())
    faults = rules.contract_faults(Registry(str(tmp_path)))
    assert faults == ["mistral-7b-l16: hidden_size is 2048, its source "
                      "says 4096"]


def test_configs_refuse_one_without_a_cell(tmp_path):
    root = later_pr.checkout(tmp_path / "root")
    later_pr.add_configuration(root)
    reg = Registry(root)
    reg.bench["workloads"] = [w for w in reg.bench["workloads"]
                              if w["config"] != later_pr.WIDE]
    assert rules.contract_faults(reg) == [
        f"{later_pr.WIDE}: no cell runs it"]


# -- which keys are the model's ---------------------------------------

def _parents_config_json(config: dict) -> str:
    """``config.json`` as ``write_model_dir`` of the parent commit wrote
    it: told apart by type, three strings by name."""
    hf = {k: v for k, v in config.items()
          if not isinstance(v, (dict, list))
          and k not in ("source", "stands_for", "reference")}
    return json.dumps(hf, indent=1, sort_keys=True)


def test_config_json_is_byte_for_byte_the_parents(reg, tmp_path):
    from chipbench.stack import write_model_dir

    for c in reg.bench["configs"]:
        config = reg.config(c["name"])
        path = write_model_dir(config, str(tmp_path), c["name"])
        with open(os.path.join(path, "config.json")) as f:
            assert f.read() == _parents_config_json(config), c["name"]


def test_reference_is_given_what_the_parent_gave_it(reg):
    """The parent's rule in ``run_check`` let three of the harness's
    strings through, which no reference reads; every other key is the
    same."""
    for c in reg.bench["configs"]:
        config = reg.config(c["name"])
        parents = {k: v for k, v in config.items()
                   if not isinstance(v, (dict, list))}
        hf = model_keys(config)
        assert set(parents) - set(hf) == {"source", "stands_for",
                                          "reference"}
        assert hf == {k: parents[k] for k in hf}


def test_harness_keys_are_told_by_name_not_by_type():
    config = {"hidden_size": 8, "rope_scaling": {"factor": 64.0},
              "layer_types": ["a", "b"], "eos_token_id": [1, 2],
              **{k: {"x": 1} for k in HARNESS_KEYS}}
    assert model_keys(config) == {
        "hidden_size": 8, "rope_scaling": {"factor": 64.0},
        "layer_types": ["a", "b"], "eos_token_id": [1, 2]}
    assert HARNESS_KEYS == {
        "source", "reduced", "published", "assumed", "stands_for",
        "reference", "server_flags", "server_flag_notes", "controls",
        "check"}


@pytest.mark.parametrize("key, width", [
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("head_dim", True),
    ("qk_rope_head_dim", True), ("v_head_dim", True),
    ("q_lora_rank", True), ("kv_lora_rank", True),
    ("num_experts_per_tok", True), ("sliding_window", True),
    ("ssm_state_size", True), ("conv_kernel", True), ("expand", True),
    ("routed_scaling_factor", True), ("d_model", True),
    ("num_hidden_layers", False), ("num_attention_heads", False),
    ("num_key_value_heads", False), ("n_routed_experts", False),
    ("num_local_experts", False), ("vocab_size", False),
    ("chips_per_layer", False), ("first_k_dense_replace", False)])
def test_a_width_is_told_by_its_name(key, width):
    assert rules.is_width(key) is width


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
