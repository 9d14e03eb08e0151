"""The one command, end to end, on the tiny preset on the CPU; what a
later PR adds as files; what must fail."""

import io
import json
import os
import shutil
import subprocess
import sys
import types
from contextlib import redirect_stdout

import pytest

from chipbench.registry import REPO, Registry

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A checkout-like directory in which a later PR has added a cell, a
    traffic mix and a per-layer metric (with a reader of its own) as new
    files plus entries in BENCHMARK.json, and edited nothing."""
    root = tmp_path_factory.mktemp("later_pr")
    shutil.copytree(os.path.join(DATA, "chipbench"),
                    root / "chipbench")
    bench = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    mix = json.load(open(root / "chipbench" / "traffic" /
                         "sessions-tiny.json"))
    mix["traffic_seed"] = 99
    mix["params"]["rate_per_s"] = 4.0
    (root / "chipbench" / "traffic" / "extra-mix.json").write_text(
        json.dumps(mix))
    bench["workloads"].append({"name": "extra-cell", "config": "tiny-llama",
                               "traffic": "extra-mix", "chips": 1,
                               "why": "added by a test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "tiny-sessions" in m["workloads"]:
            m["workloads"].append("extra-cell")
    bench["per_layer"].append({
        "name": "answers_total", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "ttft_p90_s", "workloads": ["extra-cell"]})
    os.makedirs(root / "chipbench" / "metrics")
    os.makedirs(root / "chipbench" / "readers")
    (root / "chipbench" / "metrics" / "answers_total.json").write_text(
        json.dumps({"reader": "count_ok", "params": {"scale": 1}}))
    (root / "chipbench" / "readers" / "count_ok.py").write_text(
        "def read(ctx, params):\n"
        "    return float(params['scale'] * sum(r['ok'] for r in ctx.due))\n")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.fixture(scope="module")
def result(added):
    """One run of the added cell through the command's own entry."""
    from chipbench import run

    out = io.StringIO()
    with redirect_stdout(out):
        run.main(["--workload", "extra-cell", "--seed", str(2 ** 31 + 12345),
                  "--seconds", "3", "--trace", "0", "--root", added],
                 platform="cpu")
    lines = out.getvalue().strip().splitlines()
    return lines, json.loads(lines[-1])


def test_last_line_has_exactly_the_contract_keys(result):
    _, obj = result
    assert set(obj) - {"extra"} == {"correct", "attempted", "failed",
                                    "metrics", "device"}
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


def test_a_reader_that_finds_nothing_is_left_out():
    reg = Registry(REPO)
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


def test_roofline_bytes_are_the_live_kv():
    from chipbench import peaks

    hf = Registry(REPO).config("mistral-7b-l16")
    assert peaks.kv_bytes_per_token_per_layer(hf) == 2 * 8 * 128 * 2
