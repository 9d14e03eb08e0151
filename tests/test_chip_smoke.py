"""CPU rehearsal of ``chip_smoke.py`` and of the compile-cache helper.

The script itself has no CPU leg and no option for one: it refuses any
platform but the TPU. The rehearsal steers that from here — a ``Plan`` for
a tiny model, the CPU as the expected platform, the Pallas kernels in
interpret mode — and drives the same code the chip run drives: the engine
server from its own flags behind the router, the request sequence, the
comparisons, the counters, the result line.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _plan(model="tiny-llama", **overrides):
    return chip_smoke.Plan(
        model=model, platform="cpu", interpret=True, kernel_path="xla",
        engine_flags=("--max-model-len", "512", "--num-blocks", "64"),
        long_prompt_tokens=300, batch_prompt_tokens=300, max_tokens=8,
        **overrides)


@pytest.fixture(autouse=True)
def placed_cache(monkeypatch, tmp_path):
    """With the cache placed from outside the helper sets nothing, and
    this process (which read the variable at import) keeps running
    without a persistent cache, as every other test file expects."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("TPU_STACK_FORCE_XLA_ATTENTION", raising=False)


def _phases(capsys):
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return lines, [line.get("phase") for line in lines]


def test_one_chip_rehearsal(capsys):
    result = chip_smoke.run(_plan(), chips=1)
    lines, phases = _phases(capsys)
    assert phases == ["kernel_parity", "engine_start", "probes",
                      "repeat_request", "chat_stream", "concurrent",
                      "engine_counters", "device_memory"]
    by_phase = dict(zip(phases, lines))
    errors = by_phase["kernel_parity"]["max_abs_err"]
    assert set(errors) == {"decode_bf16", "prefill_bf16", "decode_int8",
                           "prefill_int8"}
    assert max(errors.values()) <= chip_smoke.KERNEL_TOL
    assert by_phase["engine_start"]["warmup_variants"]["prefill"] > 0
    assert by_phase["repeat_request"]["prefix_hits_by_repeat"] > 0
    assert by_phase["repeat_request"]["max_logprob_diff"] <= chip_smoke.LOGPROB_TOL
    counters = by_phase["engine_counters"]
    assert counters["prefill_attention_dispatch_total"]["xla"] > 0
    assert counters["prefill_attention_dispatch_total"]["pallas"] == 0
    assert by_phase["concurrent"]["prefill_group_rows"] == 4
    assert counters["requests_finished"] == 11
    assert counters["preemptions"] == 0
    # The last line the script prints is this object and nothing else.
    assert result == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert list(result) == ["ok", "device"]
    assert list(result["device"]) == ["platform", "kind", "count"]


def test_four_chip_rehearsal_runs_only_that_path(capsys):
    # tiny-opt: four kv heads, so the pool really shards four ways; its
    # vocabulary dominates its parameters, hence the wider share bound.
    result = chip_smoke.run(_plan("tiny-opt", max_param_share=0.7), chips=4)
    lines, phases = _phases(capsys)
    assert phases == ["engine_start", "resident_bytes",
                      "engine_start", "resident_bytes",
                      "tp4_vs_tp1", "tp4_vs_tp1"]
    tp4, tp1 = (line for line in lines if line["phase"] == "resident_bytes")
    assert tp4["tensor_parallel_size"] == 4 and tp1["tensor_parallel_size"] == 1
    assert len(set(tp4["kv_pages"]["per_device"])) == 1
    assert (tp4["kv_pages"]["per_device"][0] * 4
            == tp4["kv_pages"]["logical_bytes"])
    assert tp4["decode_step_collectives"].get("all-reduce", 0) > 0
    assert tp1["decode_step_collectives"] == {}
    assert result["ok"] is True


def test_a_failed_phase_fails_the_run(capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "KERNEL_TOL", 0.0)
    with pytest.raises(AssertionError, match="kernel parity"):
        chip_smoke.run(_plan(), chips=1)
    out = capsys.readouterr().out
    assert '"ok"' not in out and "engine_start" not in out


def test_forced_reference_path_fails_the_run(monkeypatch):
    monkeypatch.setenv("TPU_STACK_FORCE_XLA_ATTENTION", "1")
    with pytest.raises(AssertionError, match="FORCE_XLA"):
        chip_smoke.run(_plan(), chips=1)


def test_script_refuses_to_run_without_a_tpu():
    """As the driver runs it first: no accelerator, so a non-zero exit
    and no result. There is no option that makes it run on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a tpu" in proc.stderr
    for option in ("--cpu", "--platform", "--interpret"):
        bad = subprocess.run([sys.executable, "chip_smoke.py", option],
                             cwd=REPO, env=env, capture_output=True,
                             text=True, timeout=300)
        assert bad.returncode == 2 and "unrecognized" in bad.stderr


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    from production_stack_tpu.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        # Placed from outside: the helper reports it and sets nothing.
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # Not placed: the fixed directory in the checkout, git-ignored.
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.configure_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
