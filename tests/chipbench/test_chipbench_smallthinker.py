"""What ``smallthinker-21b-a3b-l8`` brings to the benchmark (PR 51):
``chipbench/reference/smallthinker.py`` at the tiny size (its own recipe
draws the program's weights, the blocked head and attention are the whole
ones, it refuses what it does not compute), the cell through ``run_cell``
on a tiny configuration whose contexts pass the tiny window (``correct``
true, every control not correct), the configuration file's rules, the
traffic's contexts, and the six new readers on hand-made step records
and a small trace in the recorded format (``data/moe.xplane.pbtxt`` /
``mla_agent.xplane.pbtxt``: byte and operation counts worked by hand
below).
"""

import io
import json
import os
import types
from contextlib import redirect_stderr, redirect_stdout

import chipbench_rules as rules
import jax
import jax.numpy as jnp
import later_pr
import numpy as np
import pytest

from chipbench import schedule
from chipbench.reference import smallthinker as ref
from chipbench.registry import Registry, model_keys
from test_chipbench_moe_readers import _burst, _read, _trace

SEED = 11
CONFIG = "smallthinker-21b-a3b-l8"
CELL = "smallthinker-mixed-sessions"
TRAFFIC = "sessions-mixed-long"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTERS = ("primary_experts_hit_pct.serve", "window_spared_kv_pct.serve",
            "kv_window_dead_pct.serve")
ROOFLINES = ("reglu_expert_roofline_pct.serve",
             "window_full_attn_roofline_pct.serve",
             "sparse_decode_step_roofline_pct.serve")
NEW = COUNTERS + ROOFLINES
# The last entry of each list of BENCHMARK.json before this PR.
LAST_BEFORE = {"configs": "ouro-2.6b", "workloads": "ouro-reasoning-sessions",
               "per_layer": "loop_passes_per_forward.serve"}
DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def hf():
    with open(os.path.join(DATA, "tiny_smallthinker_config.json")) as f:
        return json.load(f)


# --------------------------------------------------------------------- #
# The reference
# --------------------------------------------------------------------- #

def test_own_recipe_draws_the_programs_weights():
    """Key ``i`` of 12, layer ``n`` of a stacked leaf at elements ``n x
    size ..``, an expert at ``(layer x experts + e) x size``; the
    embedding's rows and the head's columns drawn by index are the whole
    leaf's."""
    from production_stack_tpu.models import get_model_config, smallthinker
    from production_stack_tpu.models.llama import fuse_qkv

    cfg = get_model_config("tiny-smallthinker")
    p = smallthinker.init_params(cfg, jax.random.key(SEED))
    keys = ref.split(ref.seed_key(SEED), ref.KEYS)
    bf16 = jnp.bfloat16

    def same(mine, theirs):
        return bool(jnp.array_equal(jnp.asarray(mine, jnp.float32),
                                    jnp.asarray(theirs, jnp.float32)))

    def mat(key, index, shape, fan_in):
        return ref._stacked(keys[key], index, shape, fan_in, bf16)

    assert same(p["layers"]["wqkv"][2], fuse_qkv(
        mat(ref.WQ, 2, (128, 448), 128), mat(ref.WK, 2, (128, 64), 128),
        mat(ref.WV, 2, (128, 64), 128), 2))
    assert same(p["layers"]["wo"][1], mat(ref.WO, 1, (448, 128), 448))
    assert same(p["layers"]["router"][3], mat(ref.ROUTER, 3, (128, 8), 128))
    assert same(p["moe"]["w_gate"][2, 5],
                mat(ref.GATE, 2 * 8 + 5, (128, 64), 128))
    assert same(p["moe"]["w_up"][0, 7], mat(ref.UP, 7, (128, 64), 128))
    assert same(p["moe"]["w_down"][3, 1],
                mat(ref.DOWN, 3 * 8 + 1, (64, 128), 64))
    tokens = jnp.asarray([[3, 500, 17]])
    assert same(p["embed"][tokens], ref._embed(
        keys[ref.EMBED], tokens, hidden=128, dtype="bfloat16"))
    states = jax.random.normal(jax.random.key(1), (4, 128), jnp.float32)
    want = jnp.matmul(states, p["lm_head"].astype(jnp.float32),
                      precision="highest")
    got = ref._logits(keys[ref.HEAD], states, vocab=512, first=128,
                      width=256, dtype="bfloat16")
    np.testing.assert_allclose(got, want[:, 128:384], atol=1e-5)


def test_the_blocked_forward_is_the_whole_one(hf, monkeypatch):
    """Queries in blocks, the head in blocks of columns and of positions
    with the log-softmax on the host, a sequence at a time at its own
    length: the same numbers as one block of everything; padding beyond
    a row's length changes nothing before it; the log-probabilities
    sum to one."""
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 512, (2, 61))
    whole, kv = ref.forward(hf, SEED, tokens, [61, 40], keep_from=5,
                            dtype="float32", kv_layers=(0, 1))
    assert whole.shape == (2, 56, 512) and sorted(kv) == [0, 1]
    assert kv[1][0].shape == (2, 61, 2, 32)
    np.testing.assert_allclose(np.exp(whole[0]).sum(-1), 1.0, atol=1e-4)
    assert not whole[1, 35:].any() and not kv[0][0][1, 40:].any()
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "HEAD_BLOCK", 200)
    monkeypatch.setattr(ref, "HEAD_ROWS", 7)
    blocked, _ = ref.forward(hf, SEED, tokens, [61, 40], keep_from=5,
                             dtype="float32")
    np.testing.assert_allclose(blocked, whole, atol=2e-5)
    alone, _ = ref.forward(hf, SEED, tokens[1:, :40], [40], keep_from=5,
                           dtype="float32")
    np.testing.assert_allclose(alone[0], whole[1, :35], atol=2e-5)


def test_each_switch_of_the_layout_changes_the_answer(hf):
    """The two lists are read separately: a window on the NoPE layers, a
    rotation on them, no window anywhere: each another model at contexts
    past the tiny window."""
    tokens = np.random.default_rng(1).integers(0, 512, (1, 48))
    base, kv = ref.forward(hf, SEED, tokens, [48], keep_from=30,
                           dtype="float32", kv_layers=(0, 1))
    for change in ({"rope_layout": [1] * 6},
                   {"sliding_window_layout": [1] * 6},
                   {"sliding_window_layout": [0] * 6},
                   {"sliding_window_size": 12}):
        other, kv2 = ref.forward({**hf, **change}, SEED, tokens, [48],
                                 keep_from=30, dtype="float32",
                                 kv_layers=(0, 1))
        assert np.abs(other - base).max() > 1e-3, change
        if "rope_layout" in change:  # layer 0's keys are now rotated
            assert np.abs(kv2[0][0] - kv[0][0]).max() > 0.1
            np.testing.assert_array_equal(kv2[0][1], kv[0][1])


@pytest.mark.parametrize("change", [
    {"tie_word_embeddings": True}, {"rope_scaling": {"type": "linear"}},
    {"moe_primary_router_apply_softmax": False}, {"norm_topk_prob": False}],
    ids=lambda c: next(iter(c)))
def test_the_reference_refuses_what_it_does_not_compute(hf, change):
    with pytest.raises(ValueError, match="published"):
        ref.forward({**hf, **change}, SEED, np.zeros((1, 8), np.int32), [8],
                    keep_from=0)
    with pytest.raises(ValueError, match="quantization"):
        ref.forward(hf, SEED, np.zeros((1, 8), np.int32), [8], keep_from=0,
                    quantization="int8")


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "production_stack_tpu" not in text.split('"""')[2]
    assert "float32" in text and "HIGHEST" in text
    assert 'default_matmul_precision("highest")' in text
    for item in ("assumed (a)", "assumed (b)", "assumed (c)", "assumed (e)"):
        assert item in text, item


# --------------------------------------------------------------------- #
# The cell through run_cell, tiny
# --------------------------------------------------------------------- #

TINY, TINY_CELL = "tiny-smallthinker", "tiny-mixed-sessions"
TINY_LIMITS = {"logprob_rms": 0.15, "kv_small_rel_rms_layer0": 0.0032,
               "kv_small_rel_rms_layer1": 0.085}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory, hf):
    """The tests' tiny data with this PR's shape of addition: a
    configuration whose model keys are the tiny SmallThinker's (window
    24, contexts to ~190, prompts of the check past the window), a
    sessions mix whose histories lie on both sides of the window, a
    cell. The reference is found beside the package.

    The limits, from readings at these sizes on the CPU over five seeds
    (bf16 weights and activations against float32): ``logprob_rms`` sound
    0.013-0.081, fp8 activations 0.247-0.301; layer 0's pages sound
    0.00166-0.00175, int8 pages 0.0056-0.0057; layer 1's sound 0.007 or,
    where a router near-tie of layer 0 flips an expert between bf16 and
    float32 (two seeds of five at 8 experts of 128), 0.037-0.048, fp8
    0.137-0.172. Each between the sound readings and the nearest
    control's."""
    root = later_pr.checkout(tmp_path_factory.mktemp("smallthinker") / "root")
    _, tiny = later_pr._first_config(root)
    body = {**hf,
            "source": "a test: the tiny preset of models/smallthinker.py",
            "reduced": [], "published": {}, "assumed": {},
            "stands_for": "nothing that is deployed: the CPU tests' preset",
            "reference": "smallthinker",
            "server_flags": tiny["server_flags"],
            "controls": {
                "int8_pages": {"server_flags": ["--kv-cache-dtype", "int8"]},
                "fp8_activations": {
                    "reference_activations": "float8_e4m3fn"}},
            "check": {"shared_prefix": 32, "prompt_tokens": [50, 61, 70],
                      "gen_tokens": 6, "top_logprobs": 3,
                      "kv_layers": [0, 1], "limits": TINY_LIMITS}}
    later_pr._write(root, "configs", TINY + ".json", json.dumps(body))
    with open(os.path.join(root, "chipbench", "traffic",
                           "sessions-tiny.json")) as f:
        mix = json.load(f)
    mix["params"].update(sessions=4, max_history_tokens=160)
    later_pr._write(root, "traffic", "mixed-tiny.json", json.dumps(mix))

    def edit(bench):
        bench["configs"].append({
            "name": TINY, "source": body["source"],
            "file": f"chipbench/configs/{TINY}.json", "reduced": [],
            "why": "added by a test"})
        bench["workloads"].append({
            "name": TINY_CELL, "config": TINY, "traffic": "mixed-tiny",
            "chips": 1, "why": "added by a test"})
        later_pr._report(bench, TINY_CELL, "tiny-sessions")
        for m in bench["per_layer"]:
            if m["name"] == "gen_late_p99_ms":
                m["workloads"].append(TINY_CELL)

    later_pr._bench(root, edit)
    return root


def test_the_tiny_cell_runs_through_run_cell_and_is_correct(tiny_root):
    from chipbench import run

    reg = Registry(tiny_root)
    sched = schedule.build(reg, reg.traffic("mixed-tiny"), 3, 512)
    lengths = [len(h) for h in sched["preload"]]
    assert min(lengths) <= 32 < 24 + 32 < max(lengths)  # both sides
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.main(["--workload", TINY_CELL, "--seed", str(2 ** 31 + 4321),
                  "--seconds", "3", "--trace", "0", "--root", tiny_root],
                 platform="cpu")
    obj = json.loads(out.getvalue().strip().splitlines()[-1])
    assert obj["correct"] is True and obj["failed"] == 0, obj["compared"]
    assert obj["attempted"] >= 5
    assert set(obj["compared"]) == set(TINY_LIMITS) | {
        "failed_requests", "answers_not_of_scheduled_length"}
    assert set(obj["metrics"]) == {"ttft_p90_s", "itl_p99_s", "tpot_p50_s",
                                   "setup_s"}


@pytest.mark.parametrize("control", ["int8_pages", "fp8_activations"])
def test_each_control_comes_out_not_correct(tiny_root, control):
    from chipbench import control as tool

    lines = list(tool.read_seeds(TINY, [SEED], control, root=tiny_root,
                                 platform="cpu"))
    assert len(lines) == 1 and lines[0]["correct"] is False, lines
    failed = [name for name, limit in TINY_LIMITS.items()
              if lines[0][name] > limit]
    assert failed, lines
    if control == "int8_pages":  # by the page format's limit, not by each
        assert failed == ["kv_small_rel_rms_layer0"], lines


def test_the_sound_program_is_correct_over_seeds(tiny_root):
    from chipbench import control as tool

    lines = list(tool.read_seeds(TINY, [SEED, SEED + 1, 2 ** 31 + 7], None,
                                 root=tiny_root, platform="cpu"))
    assert all(line["correct"] for line in lines), lines


# --------------------------------------------------------------------- #
# The configuration file and the traffic
# --------------------------------------------------------------------- #

def test_the_root_with_the_addition_keeps_the_rules(reg):
    assert rules.contract_faults(reg) == []
    assert [f for f in rules.schedule_faults(reg) if CELL in f] == []


def _entries_of_this_pr(bench):
    """Every entry of BENCHMARK.json that names this configuration or
    this cell's own metrics, with the list it stands in."""
    return ([("configs", c) for c in bench["configs"] if c["name"] == CONFIG]
            + [("workloads", w) for w in bench["workloads"]
               if w["name"] == CELL]
            + [("per_layer", m) for m in bench["per_layer"]
               if m["name"] in NEW])


def test_the_additions_keep_the_form_of_the_benchmarks_file(reg):
    """What the driver refuses before any run (a ``why`` of 214
    characters cost this PR a check): a line is 1 to 200 printable
    characters with no tab, a name at most 64 of ``[A-Za-z0-9_.-]``, a
    unit at most 16, each entry has just its list's keys, and what this
    PR adds stands behind what PR 48 added last."""
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}}
    entries = _entries_of_this_pr(reg.bench)
    assert [kind for kind, _ in entries] == (
        ["configs", "workloads"] + ["per_layer"] * len(NEW))
    for kind, entry in entries:
        assert set(entry) == keys[kind], entry
        assert rules.NAME.match(entry["name"])
        for key in ("why", "source", "layer"):
            line = entry.get(key, "x")
            assert 1 <= len(line) <= 200 and line.isprintable(), (
                entry["name"], key, len(line))
        names = [e["name"] for e in reg.bench[kind]]
        assert names.index(entry["name"]) > names.index(LAST_BEFORE[kind])
    for _, metric in entries[2:]:
        assert 1 <= len(metric["unit"]) <= 16
        assert metric["workloads"] == [CELL]
    assert os.path.getsize(os.path.join(reg.root, "BENCHMARK.json")) <= 65536


def test_the_configuration_is_the_catalogs_but_for_its_depth(reg):
    config = reg.config(CONFIG)
    entry = next(c for c in reg.bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 52}
    assert entry["source"] == config["source"]
    model = model_keys(config)
    assert (model["num_hidden_layers"], model["hidden_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], model["moe_ffn_hidden_size"],
            model["moe_num_primary_experts"],
            model["moe_num_active_primary_experts"],
            model["sliding_window_size"], model["vocab_size"],
            model["rope_theta"], model["max_position_embeddings"]) == (
        8, 2560, 28, 4, 128, 768, 64, 6, 4096, 151936, 1500000, 16384)
    assert model["rope_layout"] == model["sliding_window_layout"] == [
        0, 1, 1, 1] * 13
    assert model["model_type"] == "smallthinker"
    assert "7.93 GB" in config["stands_for"]
    for key in ("router_input", "hidden_act", "secondary_experts",
                "model_type", "attention", "weights",
                "max_position_embeddings", "kv_pages"):
        assert key in config["assumed"], key
    assert "sliding_window" not in config["assumed"]  # the program applies it
    flags = config["server_flags"]
    assert flags[:6] == ["--max-model-len", "16384", "--max-num-seqs", "32",
                         "--block-size", "64"]
    chk = config["check"]
    assert (chk["shared_prefix"], chk["prompt_tokens"], chk["gen_tokens"],
            chk["kv_layers"]) == (4096, [4500, 5300, 6200], 16, [0, 1])
    assert min(chk["prompt_tokens"]) > model["sliding_window_size"]
    assert set(chk["limits"]) == {"logprob_rms", "kv_small_rel_rms_layer0",
                                  "kv_small_rel_rms_layer1"}
    for name, limit in chk["limits"].items():
        note = chk["limit_notes"][name]
        assert 0 < limit < 1 and "sound" in note and "control" in note, name
    assert set(config["controls"]) == {"int8_pages", "fp8_activations"}
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert row["source_url"] == config["source"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert model[key] == value, key


def test_the_programs_config_reader_takes_the_file(reg, tmp_path):
    from production_stack_tpu.engine.core import kv_bytes_per_block
    from production_stack_tpu.models import get_model_config
    from production_stack_tpu.models.config import (
        NOPE_FULL_ATTENTION,
        SLIDING_ATTENTION,
    )

    (tmp_path / "config.json").write_text(
        json.dumps(model_keys(reg.config(CONFIG))))
    cfg = get_model_config(str(tmp_path))
    assert (cfg.arch, cfg.num_layers, cfg.num_experts,
            cfg.experts_per_token) == ("smallthinker", 8, 64, 6)
    assert cfg.layer_types == (NOPE_FULL_ATTENTION,
                               *[SLIDING_ATTENTION] * 3) * 2
    assert cfg.rope_of(NOPE_FULL_ATTENTION) is None
    assert cfg.rope_of(SLIDING_ATTENTION).rope_theta == 1500000
    assert cfg.window_of(SLIDING_ATTENTION) == 4096
    assert cfg.window_of(NOPE_FULL_ATTENTION) is None
    assert kv_bytes_per_block(cfg, 64) == 64 * 16384  # 16 KiB a token


def test_the_traffics_contexts_lie_on_both_sides_of_the_window(reg):
    """The schedule is pure: the issue's parameters letter for letter;
    every context within ``--max-model-len``; the 32 starting histories
    spread from 1,024 to over 14,000, a quarter inside the window;
    ~224k distinct tokens to preload; a turn due inside the traced
    seconds."""
    config, traffic = reg.config(CONFIG), reg.traffic(TRAFFIC)
    params = traffic["params"]
    assert {k: params[k] for k in (
        "sessions", "system_prompt_tokens", "sessions_per_system_prompt",
        "max_history_tokens", "think_s_min", "think_s_per_token")} == {
        "sessions": 32, "system_prompt_tokens": 1024,
        "sessions_per_system_prompt": 8, "max_history_tokens": 14336,
        "think_s_min": 0.5, "think_s_per_token": 0.025}
    assert params["user_tokens"] == {"median": 256, "sigma": 0.8, "min": 32,
                                     "max": 2048}
    assert params["answer_tokens"] == {"median": 192, "sigma": 0.6,
                                       "min": 32, "max": 768}
    assert traffic["limits"] == {"ttft_limit_s": 0.5, "tpot_limit_s": 0.02}
    assert (traffic["max_outstanding"], traffic["ramp_s"],
            traffic["tail_s"]) == (0, 5.0, 2.0)
    assert 0 < params["rate_per_s"] <= 3.0
    sched = schedule.build(reg, traffic, 51, config["vocab_size"])
    start, end = sched["window"]
    assert all(len(r["prompt"]) + r["max_tokens"] <= 14336 < 16384
               for r in sched["requests"])
    lengths = sorted(len(h) for h in sched["preload"])
    assert len(lengths) == 32 and lengths[0] == 1024 and lengths[-1] > 13500
    window = config["sliding_window_size"]
    assert 6 <= sum(n <= window for n in lengths) <= 10
    distinct = sum(lengths) - 28 * 1024  # four shared prompts held once
    assert 200_000 < distinct < 240_000
    due = [r for r in sched["requests"] if start <= r["due"] < end]
    assert len(due) >= 20
    assert any(len(r["prompt"]) <= window for r in due)
    assert any(len(r["prompt"]) > 2 * window for r in due)
    assert any(2.4 <= r["due"] - start <= 3.3 for r in sched["requests"])


# --------------------------------------------------------------------- #
# The readers
# --------------------------------------------------------------------- #

def _ctx(reg, **over):
    base = dict(steps=[], traced_steps=[], device=None,
                device_kind="TPU v5 lite", kv_cache_dtype="bfloat16",
                config=reg.config(CONFIG))
    base.update(over)
    return types.SimpleNamespace(**base)


@pytest.fixture(scope="module")
def moe_trace(tmp_path_factory):
    return _trace(tmp_path_factory, "moe.xplane.pbtxt")


@pytest.fixture(scope="module")
def agent_trace(tmp_path_factory):
    return _trace(tmp_path_factory, "mla_agent.xplane.pbtxt")


def test_the_cell_reports_the_new_metrics_and_not_the_others_readers(reg):
    named = {m["name"] for m in reg.metrics_for("per_layer", CELL)}
    assert set(NEW) <= named
    assert {"attn_kernel_share_pct.serve", "moe_share_pct.serve",
            "cached_prompt_share_pct", "compiles_in_window.serve",
            "kv_pool_live_pct.serve", "kv_fetch_overhead_pct.serve",
            "device_idle_pct.serve"} <= named
    for other in ("experts_hit_pct.serve", "expert_matmul_roofline_pct.serve",
                  "paged_attn_roofline_pct.serve",
                  "mixed_attn_roofline_pct.batch",
                  "decode_step_hbm_roofline_pct.serve",
                  "mla_decode_roofline_pct.serve", "lora_share_pct.serve",
                  "conv_state_share_pct.serve"):
        assert other not in named, other
    for m in reg.bench["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["unit"] == "%"
            assert m["moves"] == ("itl_p99_s" if m["name"].startswith(
                "kv_window_dead") else "tpot_p50_s")
    e2e = {m["name"] for m in reg.metrics_for("end_to_end", CELL)}
    assert e2e == {"itl_p99_s", "tpot_p50_s", "setup_s"}
    entry = reg.workload(CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counts_or_another_model_reads_nothing(
        reg, metric, moe_trace):
    """The parent's records (no counts), an empty window, no trace, and
    another model's configuration: nothing, and no exception."""
    path, reduced = moe_trace
    assert _read(reg, metric, _ctx(reg)) is None
    plain = [_burst(8, tokens=100)]
    assert _read(reg, metric, _ctx(
        reg, steps=plain, traced_steps=plain, device=reduced,
        profile=path)) is None
    full = [_burst(8, stats_forwards=8, moe_assignments=8 * 8 * 192,
                   moe_experts_hit=8 * 8 * 40, moe_max_expert_load=8 * 64,
                   kv_live_tokens=8 * 3000, kv_live_tokens_window=8 * 2000,
                   kv_window_dead_tokens=500, kv_blocks_live=100)]
    for other in ("mistral-7b-l16", "laguna-s-2.1-l8e64"):
        assert _read(reg, metric, _ctx(
            reg, config=reg.config(other), steps=full, traced_steps=full,
            device=reduced, profile=path)) is None


def test_the_counters_readers(reg):
    """8 forwards of 8 layers x 64 experts = 4,096 expert slots, 1,536
    hit: 37.5%. 40,000 live tokens of which a window layer reads 25,000:
    37.5% spared. 3,000 dead tokens of 200 live blocks of 64 = 12,800
    tokens: 23.4375%, and 0 dead of 100 blocks: the mean is 11.71875."""
    steps = [
        _burst(8, stats_forwards=8, moe_experts_hit=1000, moe_assignments=1,
               kv_live_tokens=30000, kv_live_tokens_window=20000,
               kv_window_dead_tokens=3000, kv_blocks_live=200),
        _burst(8, stats_forwards=0, moe_experts_hit=0,
               kv_live_tokens=10000, kv_live_tokens_window=5000,
               kv_window_dead_tokens=0, kv_blocks_live=100),
        {"kind": "prefill", "forwards": 1, "stats_forwards": 8,
         "moe_experts_hit": 536, "kv_blocks_live": 300},
        _burst(8)]  # a burst off the Pallas path carries no count
    ctx = _ctx(reg, steps=steps)
    assert _read(reg, "primary_experts_hit_pct.serve", ctx) == \
        pytest.approx(100 * 1536 / (16 * 8 * 64))
    assert _read(reg, "window_spared_kv_pct.serve", ctx) == \
        pytest.approx(37.5)
    assert _read(reg, "kv_window_dead_pct.serve", ctx) == \
        pytest.approx((100 * 3000 / 12800 + 0) / 2)


def test_reglu_expert_roofline_at_this_models_widths(reg, moe_trace):
    """One layer and forward with 35 experts hit and 192 assignments:
    35 x 3 x 2560 x 768 x 2 B = 412.9 MB of weights and 192 x (3 x 2560 +
    4 x 768) x 2 B = 4.1 MB of rows: 0.509 ms at 819 GB/s, against
    0.0115 ms of operations: the bytes bound it."""
    path, reduced = moe_trace
    module = reg.module("readers", "sparse_decode_roofline")
    config = reg.config(CONFIG)
    assert module.expert_bytes(config) == 3 * 2560 * 768 * 2 == 11_796_480
    forwards, layers = 16, 8
    steps = [_burst(8, stats_forwards=8,
                    moe_assignments=8 * layers * 192,
                    moe_experts_hit=8 * layers * 35,
                    moe_max_expert_load=8 * layers * 9) for _ in range(2)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    got = _read(reg, "reglu_expert_roofline_pct.serve", ctx)
    floor = (35 * 11_796_480 + 192 * (3 * 2560 + 4 * 768) * 2) / 819e9
    assert floor == pytest.approx(0.509e-3, rel=2e-3)
    assert floor > 2 * 3 * 2560 * 768 * 192 / 197e12
    # the accepted reader's reckoning under this model's key names: the
    # same trace, the same counts, LFM2's widths replaced by these
    from chipbench.readers import routed_experts
    want = routed_experts.matmul_roofline_pct(
        ctx, {"hidden_size": 2560, "moe_intermediate_size": 768}, layers,
        {"moe_experts", "ragged-dot-none", "ragged-dot-metadata"},
        "decode_k")
    assert got == pytest.approx(want) and got > 0
    assert forwards * layers == 128


def test_window_full_attn_roofline_counts_each_kind_of_layer(reg, moe_trace):
    """Two NoPE full layers read every live token, six window layers the
    last 4,096 of a row: per forward (2 x 24,000 + 6 x 10,000) tokens x
    2 KiB over 8 calls = 27.6 MB a call, 33.8 us at 819 GB/s."""
    path, reduced = moe_trace
    module = reg.module("readers", "sparse_decode_roofline")
    config = reg.config(CONFIG)
    assert module.page_bytes_a_token(config, "bfloat16") == 2048
    assert module.page_bytes_a_token(config, "int8") == 1024
    assert module.window_layers(config) == 6
    steps = [_burst(8, kv_live_tokens=8 * 24000,
                    kv_live_tokens_window=8 * 10000)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    calls = sum(n for k, n in reduced["op_counts"].items()
                if "pallas_paged_attention" in k)
    from chipbench import xplane
    seconds = xplane.kernel_seconds(reduced, ["pallas_paged_attention"])
    got = _read(reg, "window_full_attn_roofline_pct.serve", ctx)
    if not calls:
        assert got is None
        return
    bytes_a_call = (2 * 24000 + 6 * 10000) * 2048 / 8
    assert bytes_a_call == 27_648_000
    assert got == pytest.approx(
        100 * bytes_a_call / 819e9 / (seconds / calls), rel=1e-6)


def test_sparse_decode_step_roofline_counts_every_byte_of_a_forward(
        reg, agent_trace):
    """A decode forward reads, per layer, q k v o (2560 x 4608 + 3584 x
    2560) and the router (2560 x 64) in bf16 = 42.3 MB, the experts hit
    (35 a layer: 412.9 MB), once the head (2560 x 151,936 x 2 B = 777.9
    MB), and the live pages by kind (221 MB): 4.64 GB in all, 5.67 ms at
    819 GB/s; the trace's one decode program takes 16,000 us over its 8
    forwards."""
    path, reduced = agent_trace
    module = reg.module("readers", "sparse_decode_roofline")
    config = reg.config(CONFIG)
    dense = module.dense_bytes_a_layer(config)
    assert dense == 2 * (2560 * 4608 + 3584 * 2560 + 2560 * 64) == 42_270_720
    steps = [_burst(8, stats_forwards=8, moe_experts_hit=8 * 8 * 35,
                    moe_assignments=8 * 8 * 192, moe_max_expert_load=1,
                    kv_live_tokens=8 * 24000,
                    kv_live_tokens_window=8 * 10000),
             {"kind": "prefill_chunk", "forwards": 1}]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    total = (8 * dense + 8 * 35 * 11_796_480 + 2 * 2560 * 151936
             + (2 * 24000 + 6 * 10000) * 2048)
    assert round(total / 1e9, 2) == 4.64
    got = _read(reg, "sparse_decode_step_roofline_pct.serve", ctx)
    assert got == pytest.approx(100 * total / 819e9 / (16000e-6 / 8),
                                rel=1e-6)
    # int8 pages halve the pages' bytes and nothing else
    ctx.kv_cache_dtype = "int8"
    assert _read(reg, "sparse_decode_step_roofline_pct.serve", ctx) == \
        pytest.approx(100 * (total - (2 * 24000 + 6 * 10000) * 1024)
                      / 819e9 / 2000e-6, rel=1e-6)
