"""``BENCHMARK.json`` against the contract, and every name it gives
against the files the harness will look for. ``reg`` (``conftest``) is
the repo's own root, then its copy with a later PR's addition."""

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

# The configurations that existed at PR 27's parent, whose rule dropped
# every dict- or list-valued key: they stay held to that parent's bytes.
AT_PR27_PARENT = ("mistral-7b-l16",)


def test_top_level_keys(reg):
    assert set(reg.bench) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert reg.bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= reg.bench["run_seconds"] <= 51
    assert os.path.getsize(
        os.path.join(reg.root, "BENCHMARK.json")) < 64 * 1024


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
    "a dense layer named by mlp_only_layers": (
        {"mlp_only_layers": [0]},
        ["4 layers of which 1 dense: fewer than 4 after"]),
    "a leading dense run in mlp_layer_types": (
        {"mlp_layer_types": ["dense"] + ["sparse"] * 7},
        ["4 layers of which 1 dense: fewer than 4 after"]),
    "a per-layer list cut to the layers held": (
        {"layer_types": ["full_attention"] * 4},
        ["layer_types has 4 entries, one for each layer held: keep "
         "per-layer lists as published (8 entries): the program and the "
         "reference read the first num_hidden_layers entries"]),
    "a reference module that is not there": (
        {"reference": "nowhere"},
        ["reference names 'nowhere': no such module under reference/"]),
    "a layer's cache compared that is not held": (
        {"check": {"kv_layers": [0, 4], "limits": {}}},
        ["check.kv_layers is [0, 4]: not a list of different layers "
         "among the 4 held"]),
    "a per-layer limit on a layer that is not compared": (
        {"check": {"kv_layers": [0, 1],
                   "limits": {"kv_small_rel_rms_layer2": 0.01}}},
        ["check.limits names kv_small_rel_rms_layer2"]),
    "a per-layer limit with one layer compared": (
        {"check": {"limits": {"kv_small_rel_rms_layer0": 0.01}}},
        ["check.limits names kv_small_rel_rms_layer0"]),
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
        mlp_layer_types=["dense"] + ["sparse"] * 7,
        reduced=["num_hidden_layers", "vocab_size", "n_routed_experts"],
        published={**later_pr.WIDE_PUBLISHED, "n_routed_experts": 64,
                   "vocab_size": 8192}) == []


PASSING = {
    "a per-layer list of the held length where no layer is cut": {
        "num_hidden_layers": 8, "reduced": ["vocab_size"],
        "published": {"vocab_size": 2048, "chips_per_layer": 1}},
    "a list as long as the layers held that is no per-layer list, uncut": {
        "num_hidden_layers": 8, "eos_token_id": list(range(8)),
        "reduced": ["vocab_size"],
        "published": {"vocab_size": 2048, "chips_per_layer": 1}},
    "two layers' caches compared, each with a limit of its own": {
        "check": {"kv_layers": [0, 3],
                  "limits": {"kv_small_rel_rms_layer0": 0.003,
                             "kv_small_rel_rms_layer3": 0.02}}},
    "five layers behind a dense one named three ways": {
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "mlp_only_layers": [0],
        "mlp_layer_types": ["dense"] + ["sparse"] * 7},
}


@pytest.mark.parametrize("case", sorted(PASSING))
def test_configs_pass(tmp_path, case):
    assert _broken(tmp_path, **PASSING[case]) == []


@pytest.mark.parametrize("body, dense", [
    ({}, 0), ({"first_k_dense_replace": 3}, 3),
    ({"mlp_only_layers": [0]}, 1), ({"mlp_only_layers": []}, 0),
    ({"mlp_layer_types": ["dense", "dense", "sparse", "dense"]}, 2),
    ({"mlp_layer_types": ["sparse", "dense"]}, 0),
    ({"mlp_layer_types": ["dense"] * 3}, 3),
    ({"first_k_dense_replace": 1, "mlp_only_layers": [0, 1],
      "mlp_layer_types": ["dense", "sparse"]}, 2)])
def test_leading_dense_layers_by_whichever_key_the_file_has(body, dense):
    assert rules.leading_dense_layers(body) == dense


def test_configs_refuse_a_pinned_width_changed(tmp_path, fresh_root):
    """The table of pinned configurations: a copy of the root in which
    the pinned configuration's file, found by its name, has a hidden
    size that is not its source's. Every other configuration's file is
    there, and none of them is at fault."""
    broken = Registry(fresh_root(tmp_path / "root"))
    entry = next(c for c in broken.bench["configs"]
                 if c["name"] == "mistral-7b-l16")
    body = broken.config(entry["name"])
    body["hidden_size"] = 2048
    with open(os.path.join(broken.root, entry["file"]), "w") as f:
        json.dump(body, f)
    assert rules.contract_faults(broken) == [
        "mistral-7b-l16: hidden_size is 2048, its source says 4096"]


def _files(root) -> dict:
    found = {}
    for base, _, names in os.walk(root):
        for name in names:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                found[os.path.relpath(path, root)] = f.read()
    return found


def test_the_addition_to_the_repos_root_edits_nothing_that_was_there(
        tmp_path):
    """``chipbench/README.md``, "Adding things", held to its word on a
    copy of the repo's own root: after a second configuration has gone
    in with a cell on each traffic mix, every file that was there has
    the bytes it had, and every entry of ``BENCHMARK.json`` that was
    there is unchanged but for ``workloads`` lists that gained names."""
    root = later_pr.repo_checkout(tmp_path / "root")
    files, bench = _files(root), Registry(root).bench
    for rel, data in files.items():  # the copy is the repo's own bytes
        with open(os.path.join(REPO, rel), "rb") as f:
            assert f.read() == data, rel
    later_pr.add_second_configuration(root)
    now_files, now = _files(root), Registry(root)
    assert set(now_files) - set(files) == {
        f"chipbench/configs/{later_pr.SECOND}.json",
        f"chipbench/reference/{later_pr.SECOND_REFERENCE}.py"}
    assert {rel for rel in files if now_files[rel] != files[rel]} == {
        "BENCHMARK.json"}
    for key in ("command", "paths", "run_seconds"):
        assert now.bench[key] == bench[key]
    for group in ("configs", "workloads"):
        assert now.bench[group][:len(bench[group])] == bench[group]
    assert len(now.bench["configs"]) == len(bench["configs"]) + 1
    assert len(now.bench["workloads"]) == len(bench["workloads"]) + 2
    likes = {next(w["name"] for w in bench["workloads"]
                  if w["traffic"] == traffic): cell
             for traffic, cell in later_pr.SECOND_CELLS.items()}
    for group in ("end_to_end", "per_layer"):
        assert len(now.bench[group]) == len(bench[group])
        for was, entry in zip(bench[group], now.bench[group]):
            assert ({k: v for k, v in entry.items() if k != "workloads"}
                    == {k: v for k, v in was.items() if k != "workloads"})
            assert ("workloads" in entry) == ("workloads" in was)
            listed, had = entry.get("workloads", []), was.get("workloads", [])
            assert listed[:len(had)] == had
            assert sorted(listed[len(had):]) == sorted(
                likes[cell] for cell in had if cell in likes)
    # each new cell reports what the cell already on its mix reports
    for like, cell in likes.items():
        assert now.workload(cell)["config"] == later_pr.SECOND
        for group in ("end_to_end", "per_layer"):
            assert ([m["name"] for m in now.metrics_for(group, cell)]
                    == [m["name"] for m in now.metrics_for(group, like)])
    # and it is of the shape that PR 27's parent could not take
    model = model_keys(now.config(later_pr.SECOND))
    assert set(model["rope_parameters"]) == {"full_attention",
                                             "sliding_attention"}
    published = now.config(later_pr.SECOND)["published"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert len(model[key]) == published["num_hidden_layers"] > model[
            "num_hidden_layers"]
    assert model["mlp_only_layers"] == [0]
    assert now.config(later_pr.SECOND)["check"]["kv_layers"] == [0, 1]
    assert rules.contract_faults(now) == []


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


def _by_name(config: dict) -> dict:
    """The model's keys told by name, written out: what ``model_keys``
    has to give, nested blocks and lists as the file has them."""
    return {k: v for k, v in config.items() if k not in HARNESS_KEYS}


def test_config_json_is_byte_for_byte_the_parents(reg, tmp_path):
    """The program's model directory. A configuration that PR 27's
    parent had: the bytes that parent wrote. Any other: exactly the
    model's keys by name, nested blocks and lists read back equal."""
    from chipbench.stack import write_model_dir

    names = [c["name"] for c in reg.bench["configs"]]
    assert set(AT_PR27_PARENT) <= set(names)
    for name in names:
        config = reg.config(name)
        path = write_model_dir(config, str(tmp_path), name)
        with open(os.path.join(path, "config.json")) as f:
            text = f.read()
        if name in AT_PR27_PARENT:
            assert text == _parents_config_json(config), name
        else:
            assert json.loads(text) == _by_name(config), name


def test_reference_is_given_what_the_parent_gave_it(reg):
    """The reference's ``hf``. A configuration that PR 27's parent had:
    that parent's rule in ``run_check`` let three of the harness's
    strings through, which no reference reads, and every other key is
    the same. Any other: every key of the file but the harness's, by
    name, nested blocks and lists as the file has them."""
    names = [c["name"] for c in reg.bench["configs"]]
    assert set(AT_PR27_PARENT) <= set(names)
    for name in names:
        config = reg.config(name)
        hf = model_keys(config)
        if name in AT_PR27_PARENT:
            parents = {k: v for k, v in config.items()
                       if not isinstance(v, (dict, list))}
            assert set(parents) - set(hf) == {"source", "stands_for",
                                              "reference"}
            assert hf == {k: parents[k] for k in hf}
        else:
            assert hf == _by_name(config), name


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
