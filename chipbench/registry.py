"""Finds every piece of the benchmark by its name in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds ``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.json`` (and, where it
needs new code, ``generators/<name>.py``, ``readers/<name>.py`` or
``reference/<arch>.py``) plus the entries in ``BENCHMARK.json``, and
edits no file that is there. Files are looked for under
``<root>/chipbench`` first (``root`` is where ``BENCHMARK.json`` lies)
and then beside this module. No JAX here: the generator process imports
it.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# The keys of a configuration file that are the harness's own, by name.
# Every other key is the model's, as its source publishes it.
HARNESS_KEYS = frozenset((
    "source", "reduced", "published", "assumed", "stands_for", "reference",
    "server_flags", "server_flag_notes", "controls", "check"))


def model_keys(config: dict) -> dict:
    """The model's part of a configuration file: every key but the
    harness's own, nested blocks and lists included. It is what the
    program finds in ``config.json`` and what the reference is given as
    ``hf``."""
    return {k: v for k, v in config.items() if k not in HARNESS_KEYS}


class Registry:
    def __init__(self, root: str = REPO):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self._modules = {}

    # -- files ---------------------------------------------------------
    def find(self, kind: str, filename: str) -> str:
        for base in (os.path.join(self.root, "chipbench"), HERE):
            path = os.path.join(base, kind, filename)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"no {kind}/{filename} under {self.root}/chipbench or {HERE}")

    def load_json(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name + ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        """``<kind>/<name>.py``, imported from its file."""
        path = self.find(kind, name + ".py")
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    # -- entries of BENCHMARK.json ---------------------------------------
    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has: {[w['name'] for w in self.bench['workloads']]})")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self.load_json("traffic", name)

    def metrics_for(self, group: str, workload: str):
        """Entries of ``end_to_end`` or ``per_layer`` that this cell
        reports: those without a ``workloads`` key, and those that list
        it."""
        return [m for m in self.bench[group]
                if "workloads" not in m or workload in m["workloads"]]
