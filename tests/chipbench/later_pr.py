"""What a later PR does to the benchmark, for the tests: it adds files
and entries and edits nothing that is there. Each function takes a root
that :func:`checkout` made from the tests' tiny data, but
:func:`add_second_configuration`, which takes one that
:func:`repo_checkout` made from the repo's own root."""

import json
import os
import shutil

from chipbench.registry import REPO, model_keys

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# The added configuration: other widths than the tiny preset's, a nested
# block and a list among the model's keys, no ``sliding_window``, a cut
# in layers and in the rows of the vocabulary held (the per-layer list
# stays as long as published), a reference module of its own.
WIDE = "wide-l4"
WIDE_CELL = "wide-cell"
WIDE_SIZES = {
    "hidden_size": 192, "intermediate_size": 384, "num_attention_heads": 6,
    "num_key_value_heads": 3, "head_dim": 32, "num_hidden_layers": 4,
    "vocab_size": 1024, "chips_per_layer": 2,
    "rope_scaling": {"rope_type": "linear", "factor": 1.0},
    "layer_types": ["full_attention"] * 8,
}
WIDE_PUBLISHED = {"num_hidden_layers": 8, "vocab_size": 2048,
                  "chips_per_layer": 1}
WIDE_ASSUMED = {"chips_per_layer": "two chips share each layer: each holds "
                                   "half of the vocabulary's rows"}

# ``reference/<WIDE_REFERENCE>.py``: reads the nested block and the list
# (a ``KeyError`` if the harness had dropped them), leaves what it was
# given where a test can read it, and computes with the decoder block the
# tiny preset's reference has.
WIDE_REFERENCE = "wide"
_REFERENCE_SOURCE = '''\
"""Plain reference of the added configuration."""
import importlib
import json
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEEN = os.path.join(_ROOT, ".chipbench_work", "reference_hf.json")


def forward(hf, seed, tokens, lens, **kwargs):
    if (hf["rope_scaling"]["rope_type"], hf["rope_scaling"]["factor"]) != (
            "linear", 1.0):
        raise ValueError("this reference has positions divided by 1 only")
    held = hf["layer_types"][:hf["num_hidden_layers"]]
    if held != ["full_attention"] * hf["num_hidden_layers"]:
        raise ValueError("this reference has full attention only")
    os.makedirs(os.path.dirname(SEEN), exist_ok=True)
    with open(SEEN, "w") as f:
        json.dump(hf, f)
    block = importlib.import_module("chipbench.reference.{block}")
    return block.forward(hf, seed, tokens, lens, **kwargs)
'''


def checkout(root) -> str:
    """A checkout-like directory: the tiny data's ``BENCHMARK.json`` and
    ``chipbench/`` files."""
    root = str(root)
    shutil.copytree(os.path.join(DATA, "chipbench"),
                    os.path.join(root, "chipbench"))
    shutil.copy(os.path.join(DATA, "BENCHMARK.json"), root)
    return root


def _first_config(root):
    """(name, file body) of the root's first configuration: the tiny
    preset in the tests' data, the pinned one in the repo's own root."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = json.load(f)["configs"][0]
    with open(os.path.join(root, entry["file"])) as f:
        return entry["name"], json.load(f)


def _bench(root, edit) -> None:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    edit(bench)
    with open(path, "w") as f:
        json.dump(bench, f)


def _write(root, kind, filename, text) -> None:
    path = os.path.join(root, "chipbench", kind, filename)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "x") as f:  # a later PR edits no file that is there
        f.write(text)


def _report(bench, cell, like, groups=("end_to_end",)) -> None:
    """``cell`` reports the metrics of ``groups`` that ``like`` does."""
    for group in groups:
        for m in bench[group]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)


def add_cell_and_metric(root) -> None:
    """A cell on the tiny preset under a traffic mix of its own, and a
    per-layer metric with a reader of its own."""
    with open(os.path.join(root, "chipbench", "traffic",
                           "sessions-tiny.json")) as f:
        mix = json.load(f)
    mix["traffic_seed"] = 99
    mix["params"]["rate_per_s"] = 4.0
    _write(root, "traffic", "extra-mix.json", json.dumps(mix))
    _write(root, "metrics", "answers_total.json",
           json.dumps({"reader": "count_ok", "params": {"scale": 1}}))
    _write(root, "readers", "count_ok.py",
           "def read(ctx, params):\n"
           "    return float(params['scale'] * sum(r['ok'] for r in ctx.due))\n")

    def edit(bench):
        bench["workloads"].append({"name": "extra-cell",
                                   "config": _first_config(root)[0],
                                   "traffic": "extra-mix", "chips": 1,
                                   "why": "added by a test"})
        _report(bench, "extra-cell", "tiny-sessions")
        bench["per_layer"].append({
            "name": "answers_total", "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "load generator",
            "moves": "ttft_p90_s", "workloads": ["extra-cell"]})

    _bench(root, edit)


def wide_config(root) -> dict:
    """The added configuration's file: the tiny preset's with the model's
    sizes replaced and the cut stated."""
    _, tiny = _first_config(root)
    body = model_keys(tiny)
    body.update(WIDE_SIZES)
    body.update({
        "source": "a test: no model is published at these sizes",
        "reduced": ["num_hidden_layers", "vocab_size"],
        "published": dict(WIDE_PUBLISHED),
        "assumed": dict(WIDE_ASSUMED),
        "stands_for": "one chip of four: two stages of a pipeline, two "
                      "chips to a layer",
        "reference": WIDE_REFERENCE,
        "server_flags": tiny["server_flags"],
        "check": tiny["check"],
        "controls": tiny["controls"]})
    return body


def add_configuration(root, body=None) -> None:
    """The added configuration (``body``: its file, :func:`wide_config`
    unless a test breaks one), its reference module and a cell on it
    that reports what the tiny sessions cell reports."""
    body = body or wide_config(root)
    _write(root, "configs", WIDE + ".json", json.dumps(body, indent=1))
    _write(root, "reference", WIDE_REFERENCE + ".py",
           _REFERENCE_SOURCE.format(
               block=_first_config(root)[1]["reference"]))

    def edit(bench):
        bench["configs"].append({
            "name": WIDE, "source": body.get("source"),
            "file": f"chipbench/configs/{WIDE}.json",
            "reduced": body.get("reduced"), "why": "added by a test"})
        bench["workloads"].append({
            "name": WIDE_CELL, "config": WIDE, "traffic": "sessions-tiny",
            "chips": 1, "why": "added by a test"})
        _report(bench, WIDE_CELL, "tiny-sessions")
        for m in bench["per_layer"]:
            if m["name"] == "gen_late_p99_ms":
                m["workloads"].append(WIDE_CELL)

    _bench(root, edit)


DROP = object()  # as a value of ``changes``: the key is left out


def root_with_configuration(path, **changes) -> str:
    """A root whose added configuration's file has ``changes`` laid over
    :func:`wide_config`."""
    root = checkout(path)
    body = {**wide_config(root), **changes}
    add_configuration(root, {k: v for k, v in body.items() if v is not DROP})
    return root


# -- the repo's own root -------------------------------------------------

# A second configuration of the shape the drawn architectures have, at
# made-up tiny widths and under a made-up name: window and full layers
# three to one with their own head counts and rotary blocks, sparse
# experts beside a shared one behind one leading dense layer; cut in
# layers, experts and rows of the vocabulary to one chip of four that
# share each layer. No program serves it: it is held to the rules, and
# never run.
SECOND = "mixed-moe-l9"
SECOND_REFERENCE = "mixed_moe"
SECOND_CELLS = {"sessions": "mixed-moe-sessions",
                "backlog": "mixed-moe-backlog"}
SECOND_MODEL = {
    "model_type": "made-up-mixed-moe",
    "hidden_size": 96, "intermediate_size": 384, "head_dim": 16,
    "num_attention_heads": 6, "num_key_value_heads": 2,
    "num_hidden_layers": 9, "vocab_size": 2048,
    "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "gating": "per-head",
    "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "mlp_only_layers": [0], "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 8, "partial_rotary_factor": 0.5,
                           "original_max_position_embeddings": 8192},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    # per-layer lists keep their published length (12), not the 9 held
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 3,
    "mlp_layer_types": ["dense"] + ["sparse"] * 11,
    "num_attention_heads_per_layer": [6, 9, 9, 9] * 3,
    # the deployment: how many chips share a layer, which share this is
    "chips_per_layer": 4, "layer_share": 1,
}
SECOND_PUBLISHED = {"num_hidden_layers": 12, "num_experts": 64,
                    "vocab_size": 8192, "chips_per_layer": 1,
                    "layer_share": 0}
_SECOND_REFERENCE_SOURCE = '''\
"""Plain reference of the made-up configuration: a module to be found
under its name. No program serves these sizes, so nothing is computed."""


def forward(hf, seed, tokens, lens, **kwargs):
    raise NotImplementedError("a made-up configuration is never run")
'''


def repo_checkout(path) -> str:
    """A checkout-like directory: the repo's own ``BENCHMARK.json`` and
    its data files (configurations, traffic mixes, metric specs). Code
    (generators, readers, references) is found beside the ``chipbench``
    module, ``Registry.find``'s second base."""
    root = str(path)
    for kind in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "chipbench", kind),
                        os.path.join(root, "chipbench", kind))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return root


def second_config(root) -> dict:
    """The second configuration's file: :data:`SECOND_MODEL` with the
    cut stated, served and checked as the root's first configuration is,
    a window layer's cache compared beside the full layer's."""
    _, first = _first_config(root)
    limits = first["check"]["limits"]
    return {
        **SECOND_MODEL,
        "source": "a test: no model is published at these sizes",
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
        "published": dict(SECOND_PUBLISHED),
        "assumed": {
            "chips_per_layer": "four chips share each layer: each holds a "
                               "quarter of the experts and of the "
                               "vocabulary's rows",
            "layer_share": "the second of the four shares"},
        "stands_for": "one chip of four that share each layer: the dense "
                      "layer and 8 of the 11 that follow, 16 of 64 experts, "
                      "a quarter of the vocabulary",
        "reference": SECOND_REFERENCE,
        "server_flags": first["server_flags"],
        "controls": first["controls"],
        # made-up limits: the window layer at 1 reads above the full
        # layer at 0, which keeps the limit the first configuration has
        "check": {**{k: v for k, v in first["check"].items()
                     if k != "limit_notes"},
                  "kv_layers": [0, 1],
                  "limits": {"logprob_rms": limits["logprob_rms"],
                             "kv_small_rel_rms_layer0":
                                 limits["kv_small_rel_rms"],
                             "kv_small_rel_rms":
                                 4 * limits["kv_small_rel_rms"]}}}


def add_second_configuration(root) -> str:
    """What a ``model_config`` PR does to the repo's own root: the second
    configuration's file and reference module, and one cell on each
    traffic mix the root has, joined to every ``workloads`` list,
    end-to-end and per-layer, that the cell already on that mix is in."""
    body = second_config(root)
    _write(root, "configs", SECOND + ".json", json.dumps(body, indent=1))
    _write(root, "reference", SECOND_REFERENCE + ".py",
           _SECOND_REFERENCE_SOURCE)

    def edit(bench):
        bench["configs"].append({
            "name": SECOND, "source": body["source"],
            "file": f"chipbench/configs/{SECOND}.json",
            "reduced": body["reduced"], "why": "added by a test"})
        for traffic, cell in SECOND_CELLS.items():
            like = next(w["name"] for w in bench["workloads"]
                        if w["traffic"] == traffic)
            bench["workloads"].append({
                "name": cell, "config": SECOND, "traffic": traffic,
                "chips": 1, "why": "added by a test"})
            _report(bench, cell, like, ("end_to_end", "per_layer"))

    _bench(root, edit)
    return root
