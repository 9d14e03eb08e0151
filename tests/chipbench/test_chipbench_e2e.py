"""The one command, end to end, on the tiny preset on the CPU; what a
later PR adds as files; what must fail."""

import io
import json
import os
import subprocess
import sys
import types
from contextlib import redirect_stderr, redirect_stdout

import chipbench_rules as rules
import later_pr
import pytest

from chipbench.registry import HARNESS_KEYS, REPO, Registry, model_keys

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A checkout-like directory in which later PRs have added, as new
    files plus entries in BENCHMARK.json and with no edit to a file that
    was there: a cell, a traffic mix and a per-layer metric with a reader
    of its own; and a configuration of other widths with its reference
    module and a cell on it."""
    root = later_pr.checkout(tmp_path_factory.mktemp("later_pr") / "root")
    later_pr.add_cell_and_metric(root)
    later_pr.add_configuration(root)
    return root


STDERR = {}  # a cell's standard error, by _run


def _run(root, cell):
    """One run of a cell through the command's own entry."""
    from chipbench import run

    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.main(["--workload", cell, "--seed", str(2 ** 31 + 12345),
                  "--seconds", "3", "--trace", "0", "--root", root],
                 platform="cpu")
    lines = out.getvalue().strip().splitlines()
    STDERR[cell] = err.getvalue().strip().splitlines()
    return lines, json.loads(lines[-1])


@pytest.fixture(scope="module")
def result(added):
    return _run(added, "extra-cell")


@pytest.fixture(scope="module")
def wide_result(added):
    return _run(added, later_pr.WIDE_CELL)


def test_last_line_has_exactly_the_contract_keys(result):
    lines, obj = result
    assert set(obj) - {"extra", "compared"} == {
        "correct", "attempted", "failed", "metrics", "device"}
    # each number compared beside its limit, last in the line
    assert list(obj)[-1] == "compared"
    assert [f"compared: {name} = {c['value']} (limit {c['limit']})"
            for name, c in obj["compared"].items()
            ] == [ln for ln in lines if ln.startswith("compared: ")
                  ] == STDERR["extra-cell"][-len(obj["compared"]):]
    assert set(obj["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert obj["device"]["platform"] == "cpu" and obj["device"]["count"] == 1
    assert isinstance(obj["correct"], bool)


def test_metrics_are_the_cells_end_to_end_metrics(result):
    _, obj = result
    assert set(obj["metrics"]) == {"ttft_p90_s", "itl_p99_s", "tpot_p50_s",
                                   "setup_s"}
    for name, m in obj["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert obj["metrics"]["setup_s"]["unit"] == "s"


def test_run_is_correct_and_every_answer_had_its_length(result):
    lines, obj = result
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] >= 5
    compared = [ln for ln in lines if ln.startswith("compared: ")]
    names = {ln.split()[1] for ln in compared}
    assert names == {"logprob_rms", "kv_small_rel_rms", "failed_requests",
                     "answers_not_of_scheduled_length"}
    assert all("(limit " in ln for ln in compared)


def test_generator_ran_in_its_own_process_without_jax(added):
    with open(os.path.join(added, ".chipbench_work", "extra-cell",
                           "result.json")) as f:
        child = json.load(f)
    assert child["generator_modules_jax"] is False
    assert all(r["ok"] and r["finish"] == "length" for r in child["records"])
    # every chunk count adds up to the scheduled length
    with open(os.path.join(added, ".chipbench_work", "extra-cell",
                           "plan.json")) as f:
        plan = json.load(f)
    want = {r["id"]: r["max_tokens"] for r in plan["requests"]}
    for r in child["records"]:
        assert sum(n for _, n in r["chunks"]) <= want[r["id"]]
        assert r["out_tokens"] == want[r["id"]]


def test_loadgen_module_never_imports_jax():
    code = ("import sys, chipbench.loadgen, chipbench.schedule, "
            "chipbench.timeline, chipbench.registry; "
            "sys.exit(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules))")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO).returncode == 0


def test_added_metric_is_found_and_read_from_its_own_files(added):
    reg = Registry(added)
    names = [m["name"] for m in reg.metrics_for("per_layer", "extra-cell")]
    assert names == ["answers_total"]
    spec = reg.load_json("metrics", "answers_total")
    reader = reg.module("readers", spec["reader"])
    ctx = types.SimpleNamespace(due=[{"ok": True}, {"ok": False},
                                     {"ok": True}])
    assert reader.read(ctx, spec["params"]) == 2.0
    # and the cells that were there still find theirs beside the package
    assert reg.module("readers", "late") is not None
    assert reg.traffic("extra-mix")["traffic_seed"] == 99


def test_added_configuration_differs_as_the_tiny_preset_cannot(added):
    """What the added configuration's file has that nothing in the data
    had: other widths, a nested block and a list among the model's keys,
    no ``sliding_window``, a cut under ``reduced`` and ``published``."""
    reg = Registry(added)
    tiny = reg.config(reg.bench["configs"][0]["name"])
    wide = reg.config(later_pr.WIDE)
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "vocab_size", "num_hidden_layers"):
        assert wide[key] != tiny[key]
    model = model_keys(wide)
    assert isinstance(model["rope_scaling"], dict)
    assert isinstance(model["layer_types"], list)
    assert "sliding_window" not in wide
    assert wide["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert all(wide[k] < wide["published"][k] for k in wide["reduced"])
    assert wide["reference"] != tiny["reference"]
    assert reg.find("reference", wide["reference"] + ".py").startswith(added)


def test_added_configuration_runs_through_run_cell(wide_result):
    lines, obj = wide_result
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] >= 5
    assert set(obj["metrics"]) == {"ttft_p90_s", "itl_p99_s", "tpot_p50_s",
                                   "setup_s"}
    compared = {ln.split()[1] for ln in lines if ln.startswith("compared: ")}
    assert {"logprob_rms", "kv_small_rel_rms"} <= compared


def test_nested_keys_reach_the_program_and_the_reference(added, wide_result):
    """The ``config.json`` the program was started on and the ``hf`` the
    reference received are the model's keys, nested block and list
    included, and none of the harness's."""
    want = model_keys(Registry(added).config(later_pr.WIDE))
    work = os.path.join(added, ".chipbench_work")
    with open(os.path.join(work, later_pr.WIDE_CELL, "models", later_pr.WIDE,
                           "config.json")) as f:
        program = json.load(f)
    with open(os.path.join(work, "reference_hf.json")) as f:
        reference = json.load(f)
    for got in (program, reference):
        assert got == want
        assert got["rope_scaling"] == {"rope_type": "linear", "factor": 1.0}
        assert got["layer_types"] == ["full_attention"] * 8
        assert not HARNESS_KEYS & set(got)


def test_added_root_keeps_the_contracts_and_the_schedules_rules(added):
    """The rules the repo's own root is held to
    (``test_chipbench_contract``, ``test_chipbench_schedule``) pass on a
    root in which a later PR has only added."""
    reg = Registry(added)
    assert rules.contract_faults(reg) == []
    assert rules.schedule_faults(reg, 3) == []
    assert {c["name"] for c in reg.bench["configs"]} > {later_pr.WIDE}
    assert not rules.window_binds(reg.config(later_pr.WIDE))


def test_every_init_of_the_program_takes_the_adapter_sizes():
    """``stack.seeded_weights`` hands the adapter slots to whichever init
    the configuration's architecture has, as the engine does for the one
    that has adapters; the others drop them."""
    import inspect

    from production_stack_tpu.models import build_model
    from production_stack_tpu.models.config import _PRESETS

    one_of_each = {mc.arch: mc for mc in _PRESETS.values()}
    assert len(one_of_each) >= 2
    for arch, mc in one_of_each.items():
        params = inspect.signature(build_model(mc)[0]).parameters
        assert "lora_slots" in params or any(
            p.kind is p.VAR_KEYWORD for p in params.values()), arch


def test_a_reader_that_finds_nothing_is_left_out(reg):
    ctx = types.SimpleNamespace(device=None, due=[], steps=[], traces=[],
                                traffic={"limits": {}})
    for name in ("kernel_share", "device_idle", "paged_attn_roofline",
                 "late", "queue_wait", "decode_rows", "slo_met", "ttft_ms"):
        reader = reg.module("readers", name)
        assert reader.read(ctx, {"q": 90, "kernels": [], "kernel": "x",
                                 "kinds": [], "per": "step"}) is None


def test_no_tpu_no_result():
    """The measurement path does not fall back to the CPU."""
    from chipbench import run

    with pytest.raises(RuntimeError, match="needs a tpu"):
        run.run_cell("tiny-sessions", 1, 1.0, False, root=DATA)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "tiny-sessions", "--seed", "1", "--seconds", "1", "--trace", "0",
         "--root", DATA], cwd=REPO, env=env, capture_output=True, text=True)
    assert done.returncode != 0
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_unknown_device_has_no_peaks():
    from chipbench import peaks

    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_roofline_bytes_are_the_live_kv(reg):
    from chipbench import peaks

    hf = reg.config("mistral-7b-l16")
    assert peaks.kv_bytes_per_token_per_layer(hf) == 2 * 8 * 128 * 2
