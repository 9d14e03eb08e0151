"""What a later PR does to the benchmark, for the tests: it adds files
and entries and edits nothing that is there. Each function takes a root
that :func:`checkout` made from the tests' tiny data."""

import json
import os
import shutil

from chipbench.registry import model_keys

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# The added configuration: other widths than the tiny preset's, a nested
# block and a list among the model's keys, no ``sliding_window``, a cut
# in layers and in the rows of the vocabulary held, a reference module of
# its own.
WIDE = "wide-l4"
WIDE_CELL = "wide-cell"
WIDE_SIZES = {
    "hidden_size": 192, "intermediate_size": 384, "num_attention_heads": 6,
    "num_key_value_heads": 3, "head_dim": 32, "num_hidden_layers": 4,
    "vocab_size": 1024, "chips_per_layer": 2,
    "rope_scaling": {"rope_type": "linear", "factor": 1.0},
    "layer_types": ["full_attention"] * 4,
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
    if hf["layer_types"] != ["full_attention"] * hf["num_hidden_layers"]:
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


def _tiny(root):
    """(name, file body) of the tiny preset, the root's first
    configuration."""
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


def _report(bench, cell, like) -> None:
    """``cell`` reports the end-to-end metrics that ``like`` does."""
    for m in bench["end_to_end"]:
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
                                   "config": _tiny(root)[0],
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
    _, tiny = _tiny(root)
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
           _REFERENCE_SOURCE.format(block=_tiny(root)[1]["reference"]))

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
