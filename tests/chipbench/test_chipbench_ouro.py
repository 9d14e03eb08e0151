"""What ``ouro-2.6b`` brings to the benchmark (PR 48):
``chipbench/reference/ouro.py`` at the tiny size (a pass more changes
the answer, a pass's keys are its own, it refuses what it does not
compute; the check's sample served by an engine, whose second prompt
prefills behind cached pages of every pass, reads close to it where fp8
activations do not), the configuration file's rules, the traffic's
contexts, and the new readers on hand-made step records and a small
trace in the recorded format (``data/mla_agent.xplane.pbtxt``: one
``jit_decode_k8`` program whose operations' self times add up to
16,000 us, 1,000 of them under ``mlp``, beside two prefill programs of
2,000 us).
"""

import json
import os
import types

import jax
import numpy as np
import pytest

from chipbench import check, peaks, schedule
from chipbench.reference import ouro as ref
from chipbench.registry import model_keys
from test_chipbench_moe_readers import _burst, _read, _trace

SEED = 11
CHECK = {"shared_prefix": 16, "prompt_tokens": [40, 50, 60], "gen_tokens": 8,
         "top_logprobs": 5, "kv_layers": [0, 1, 2, 3]}
CONFIG = "ouro-2.6b"
CELL = "ouro-reasoning-sessions"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("decode_step_hbm_roofline_pct.serve", "weights_matmul_share_pct.serve",
       "loop_passes_per_forward.serve")


@pytest.fixture(scope="module")
def hf():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny_ouro_config.json")) as f:
        return json.load(f)


# --------------------------------------------------------------------- #
# The reference
# --------------------------------------------------------------------- #

def test_a_pass_more_is_another_model_and_a_passs_keys_are_its_own(hf):
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 512, (2, 24))
    two, kv = ref.forward(hf, SEED, tokens, [24, 17], keep_from=5,
                          dtype="float32", kv_layers=(0, 1, 4, 5))
    assert two.shape == (2, 19, 512) and sorted(kv) == [0, 1, 4, 5]
    assert kv[0][0].shape == (2, 24, 4, 32)
    # page layer l x 2 + u: layer 0's two passes differ, and so do the
    # first pass's keys of layers 0 and 2
    assert np.abs(kv[0][0] - kv[1][0]).max() > 0.1
    assert np.abs(kv[0][0] - kv[4][0]).max() > 0.1
    one, first = ref.forward({**hf, "total_ut_steps": 1}, SEED, tokens,
                             [24, 17], keep_from=5, dtype="float32",
                             kv_layers=(0, 2))
    assert np.abs(one - two).max() > 0.01
    # the first pass does not know how many follow: l x 1 + 0 of one pass
    # is l x 2 + 0 of two
    np.testing.assert_allclose(first[0][0], kv[0][0], atol=1e-6)
    np.testing.assert_allclose(first[2][1], kv[4][1], atol=1e-6)
    # padding beyond a row's length changes nothing before it
    alone, _ = ref.forward(hf, SEED, tokens[1:, :17], [17], keep_from=5,
                           dtype="float32")
    np.testing.assert_allclose(alone[0], two[1, :12], atol=2e-5)


@pytest.mark.parametrize("change", [
    {"attention_bias": True}, {"use_sliding_window": True},
    {"rope_scaling": {"type": "linear"}}, {"hidden_act": "gelu"},
    {"tie_word_embeddings": True}], ids=lambda c: next(iter(c)))
def test_the_reference_refuses_what_it_does_not_compute(hf, change):
    with pytest.raises(ValueError, match=next(iter(change))):
        ref.forward({**hf, **change}, SEED, np.zeros((1, 8), np.int32), [8],
                    keep_from=0)


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "production_stack_tpu" not in text.split('"""')[2]
    assert "float32" in text and "HIGHEST" in text


def test_the_check_tells_the_sound_engine_from_its_controls(hf):
    """The check's sample through an engine at the tiny size: the second
    prompt prefills behind cached pages of both passes; page layers 0-3
    are layer 0's and layer 1's two passes. fp8 activations in the
    reference's place and the engine's own int8 pages read worse than
    the sound engine on the numbers the limits name."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.core import EngineCore

    prompts = check.sample_prompts(CHECK, hf["vocab_size"], SEED)

    def served(**flags):
        core = EngineCore(EngineConfig(
            model="tiny-ouro", seed=SEED, max_model_len=128, max_num_seqs=4,
            block_size=8, num_blocks=64, decode_steps=4, **flags),
            devices=jax.devices()[:1])
        core.start()
        try:
            outputs = check.engine_outputs(
                core, prompts, CHECK["gen_tokens"], CHECK["top_logprobs"])
            cached = core.cached_tokens_total
            return cached, check.compare(
                ref, hf, SEED, None, prompts, outputs,
                check.engine_pages(core, prompts), CHECK["kv_layers"])
        finally:
            core.stop()

    cached, sound = served()
    assert cached >= 16
    assert sound["logprob_rms"] < 0.1, sound
    assert sound["kv_small_rel_rms_layer0"] < 0.01, sound
    # whole pages of 8: 40 + 48 + 56 tokens, 4 page layers, keys and
    # values of 4 heads x 32
    assert sound["kv_entries_compared"] == 4 * 144 * 2 * 4 * 32
    # a later pass's pages lie behind the layers before it
    assert sound["kv_small_rel_rms_layer1"] > sound["kv_small_rel_rms_layer0"]
    in_place = check.reference_in_place(ref, hf, SEED, CHECK, prompts,
                                        "float8_e4m3fn")
    fp8 = check.compare(ref, hf, SEED, None, prompts, *in_place,
                        CHECK["kv_layers"])
    assert fp8["logprob_rms"] > max(0.1, 2.5 * sound["logprob_rms"]), (
        sound, fp8)
    assert fp8["kv_small_rel_rms_layer0"] > 5 * sound[
        "kv_small_rel_rms_layer0"]
    _, int8 = served(kv_cache_dtype="int8")
    assert int8["kv_small_rel_rms_layer0"] > 2 * sound[
        "kv_small_rel_rms_layer0"], (sound, int8)


# --------------------------------------------------------------------- #
# The configuration file and the traffic
# --------------------------------------------------------------------- #

def test_the_configuration_is_the_catalogs_with_nothing_cut(reg):
    config = reg.config(CONFIG)
    entry = next(c for c in reg.bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == []
    assert config["published"] == {}
    assert entry["source"] == config["source"]
    model = model_keys(config)
    assert (model["num_hidden_layers"], model["total_ut_steps"],
            model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            model["intermediate_size"], model["vocab_size"]) == (
        48, 4, 2048, 16, 16, 128, 5632, 49152)
    assert len(model["layer_types"]) == 48
    assert "48 layers x 4 passes" in config["stands_for"]
    for key in ("output_norms", "loop_norm", "attention_bias", "exit_gate",
                "early_exit_threshold", "shared_last_pass_pages",
                "norm_weights", "sliding_window", "max_position_embeddings",
                "modeling_file", "torch_dtype"):
        assert key in config["assumed"], key
    limits, notes = config["check"]["limits"], config["check"]["limit_notes"]
    assert config["check"]["kv_layers"] == [0, 1, 3, 4]
    assert set(limits) == {"logprob_rms"} | {
        f"kv_small_rel_rms_layer{n}" for n in (0, 1, 3, 4)}
    for name, limit in limits.items():
        assert 0 < limit < 1 and "sound" in notes[name] \
            and "control" in notes[name], name
    assert set(config["controls"]) == {"int8_pages", "int8_weights",
                                       "fp8_activations"}
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert row["source_url"] == config["source"]
    for key, value in row["config"].items():
        assert model[key] == value, key


def test_the_programs_config_reader_takes_the_file(reg, tmp_path):
    from production_stack_tpu.models import get_model_config
    from production_stack_tpu.models.registry import page_layers

    (tmp_path / "config.json").write_text(
        json.dumps(model_keys(reg.config(CONFIG))))
    cfg = get_model_config(str(tmp_path))
    assert (cfg.arch, cfg.num_layers, cfg.loop_passes) == ("ouro", 48, 4)
    assert page_layers(cfg) == 192
    assert peaks.kv_bytes_per_token_per_layer(
        model_keys(reg.config(CONFIG))) * 192 == 1572864


def test_the_traffics_contexts_fit_the_server_and_the_pool(reg):
    """The schedule is pure: every context stays within
    ``--max-model-len`` (and within 1,024, the history's bound), the
    histories at traffic start are one 256-token prompt and eight
    histories spread evenly to 1,024 (~3.3k distinct tokens, ~56 blocks
    of the pool's ~78), answers outweigh questions, and over half of a
    window's prompt tokens lie behind a prefix the cache can hold."""
    config, traffic = reg.config(CONFIG), reg.traffic("sessions-reasoning")
    flags = config["server_flags"]
    limit = int(flags[flags.index("--max-model-len") + 1])
    assert limit == 2048 and flags[flags.index("--max-num-seqs") + 1] == "8"
    params = traffic["params"]
    assert (params["sessions"], params["system_prompt_tokens"],
            params["sessions_per_system_prompt"],
            params["max_history_tokens"]) == (8, 256, 8, 1024)
    assert params["user_tokens"] == {"median": 64, "sigma": 0.6, "min": 16,
                                     "max": 256}
    assert params["answer_tokens"] == {"median": 128, "sigma": 0.6,
                                       "min": 32, "max": 384}
    assert (params["think_s_per_token"], params["think_s_min"]) == (0.05, 0.5)
    assert traffic["limits"] == {"ttft_limit_s": 0.5, "tpot_limit_s": 0.05}
    assert traffic["max_outstanding"] == 0 and traffic["ramp_s"] == 5.0
    assert params["rate_per_s"] in (0.25, 0.5, 0.75, 1, 1.25, 1.5)
    sched = schedule.build(reg, traffic, 51, config["vocab_size"])
    start, end = sched["window"]
    assert all(len(r["prompt"]) + r["max_tokens"] <= 1024 < limit
               for r in sched["requests"])
    distinct = 256 + sum(len(h) - 256 for h in sched["preload"])
    assert 2_500 < distinct < 4_200
    blocks = 4 + sum(-(-(len(h) - 256) // 64) for h in sched["preload"])
    assert 45 <= blocks <= 70
    window = [r for r in sched["requests"] if start <= r["due"] < end]
    assert len(window) >= 10
    assert (sum(r["max_tokens"] for r in window)
            > sum(len(r["prompt"]) for r in window) / 4)
    # a turn is due inside the traced seconds 2-4 of the window
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
def trace(tmp_path_factory):
    return _trace(tmp_path_factory, "mla_agent.xplane.pbtxt")


def test_the_cell_reports_the_new_metrics_and_not_the_others_readers(reg):
    named = {m["name"] for m in reg.metrics_for("per_layer", CELL)}
    assert set(NEW) <= named
    assert {"attn_kernel_share_pct.serve", "paged_attn_roofline_pct.serve",
            "cached_prompt_share_pct", "compiles_in_window.serve",
            "kv_pool_live_pct.serve"} <= named
    for other in ("lora_share_pct.serve", "unscoped_share_pct.serve",
                  "moe_share_pct.serve", "experts_hit_pct.serve",
                  "short_conv_share_pct.serve", "conv_state_share_pct.serve",
                  "mla_decode_roofline_pct.serve",
                  "latent_attn_share_pct.serve", "dense_mlp_share_pct.serve",
                  "state_restored_prefill_pct.serve"):
        assert other not in named
    for m in reg.bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_s"
    e2e = {m["name"] for m in reg.metrics_for("end_to_end", CELL)}
    assert e2e == {"itl_p99_s", "tpot_p50_s", "setup_s"}
    entry = reg.workload(CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "sessions-reasoning", 1)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counts_or_another_model_reads_nothing(
        reg, metric, trace):
    """The parent's records (no ``loop_passes``), an empty window, no
    trace, and another model's configuration: nothing, and no
    exception."""
    path, reduced = trace
    assert _read(reg, metric, _ctx(reg)) is None
    plain = [_burst(8, tokens=100, stats_forwards=8)]
    assert _read(reg, metric, _ctx(reg, steps=plain,
                                   traced_steps=plain)) is None
    if metric.startswith("decode_step"):
        steps = [_burst(8, kv_live_tokens=8 * 3000)]
        assert _read(reg, metric, _ctx(
            reg, config=reg.config("mistral-7b-l16"), steps=steps,
            traced_steps=steps, device=reduced, profile=path)) is None
        # a burst off the Pallas kernel carries no live-token count
        assert _read(reg, metric, _ctx(
            reg, steps=plain, traced_steps=plain, device=reduced,
            profile=path)) is None


def test_decode_step_hbm_roofline_counts_every_pass_and_page_layer(
        reg, trace):
    path, reduced = trace
    module = reg.module("readers", "looped_decode_roofline")
    config = reg.config(CONFIG)
    # q, k, v (2048 x 6144), o (2048 x 2048), three of 2048 x 5632, bf16
    assert module.layer_bytes(config) == 2 * (
        2048 * 6144 + 2048 * 2048 + 3 * 2048 * 5632) == 102_760_448
    weights = 192 * 102_760_448 + 2 * 2048 * 49152
    assert round(weights / 1e9, 2) == 19.93
    assert module.forward_bytes(config, 0) == weights
    assert module.forward_bytes(config, 2560) == weights + 2560 * 1572864
    steps = [_burst(8, kv_live_tokens=8 * 2000),
             _burst(8, kv_live_tokens=8 * 3120),
             {"kind": "prefill_chunk", "forwards": 1}]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    # the trace's one decode program: 16,000 us over its 8 forwards
    floor = (weights + 2560 * 1572864) / 819e9
    assert _read(reg, "decode_step_hbm_roofline_pct.serve", ctx) == \
        pytest.approx(100 * floor / (16000e-6 / 8), rel=1e-6)
    ctx.kv_cache_dtype = "int8"
    assert _read(reg, "decode_step_hbm_roofline_pct.serve", ctx) == \
        pytest.approx(100 * (weights + 2560 * 786432) / 819e9 / 2000e-6,
                      rel=1e-6)


def test_loop_passes_per_forward(reg):
    steps = [_burst(8, stats_forwards=8, loop_passes=32),
             {"kind": "prefill", "forwards": 1, "stats_forwards": 16,
              "loop_passes": 64, "prefill_stats_forwards": 1,
              "prefill_loop_passes": 4},
             _burst(8)]
    assert _read(reg, "loop_passes_per_forward.serve",
                 _ctx(reg, steps=steps)) == 4.0


def test_weights_matmul_share_is_the_batch_files_reader(reg, trace):
    path, reduced = trace
    assert reg.load_json("metrics", "weights_matmul_share_pct.serve") == \
        reg.load_json("metrics", "weights_matmul_share_pct.batch")
    ctx = _ctx(reg, device=reduced, profile=path)
    # 1,000 us under ``mlp`` of the trace's 18,000 busy
    assert _read(reg, "weights_matmul_share_pct.serve", ctx) == \
        pytest.approx(100 * 1000 / 18000, rel=1e-6)
