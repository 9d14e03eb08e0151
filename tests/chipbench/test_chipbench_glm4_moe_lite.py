"""What ``glm-4.7-flash-e8v8`` brings to the benchmark (PR 44):
``chipbench/reference/glm4_moe_lite.py`` at the tiny size (its blocked
dense MLP is the whole one; it refuses what it does not compute; the
check's sample served by an engine, whose second prompt prefills behind
cached latent pages, reads close to it where fp8 activations do not),
the configuration file's rules, the traffic's contexts, and the new
readers on hand-made step records and a small trace in the recorded
format (``data/mla_agent.xplane.pbtxt``):

  XLA Modules   jit_prefill_cached(1)  0 .. 1500 us  (up-projected)
                jit_prefill_cached(1)  2000 .. 3000  (absorbed)
                jit_decode_k8(2)       4000 .. 20000
  XLA Ops       fusion.2 0..600 (attention), fusion.3 700..1000
                (mla_proj/mla_up_context), fusion.4 1000..1400 (mla_proj)
                fusion.5 2000..2500 (attention), fusion.6 2500..2700
                (mla_absorb)
                while.3 4000..20000 (parent of the rest; 5300 us its own)
                fusion.7 1000 us (mla_proj), fusion.9 300 (mla_absorb)
                pallas_mla_decode.10 2000 + 2200 (attention; two calls)
                fusion.21 500 (moe_router), pallas_grouped_matmul.30 3000
                (moe_experts), fusion.30 700 (moe_shared), fusion.40 1000
                (mlp)
"""

import json
import os
import types

import jax
import numpy as np
import pytest

from chipbench import check, schedule
from chipbench.reference import glm4_moe_lite as ref
from chipbench.registry import model_keys
from test_chipbench_moe_readers import _burst, _read, _trace

SEED = 11
CHECK = {"shared_prefix": 16, "prompt_tokens": [40, 50, 60], "gen_tokens": 8,
         "top_logprobs": 5, "kv_layers": [0, 1, 2]}
CONFIG = "glm-4.7-flash-e8v8"
CELL = "glm47flash-agent-sessions"
BUSY_US = 18000.0  # 600 + 300 + 400 + 500 + 200 + the while's 16000
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def hf():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny_glm4_moe_lite_config.json")) as f:
        return json.load(f)


def test_the_blocked_dense_mlp_is_the_whole_one(hf, monkeypatch):
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 512, (2, 24))
    whole = ref.forward(hf, SEED, tokens, [24, 17], keep_from=5,
                        dtype="float32", kv_layers=(0, 3))
    monkeypatch.setattr(ref, "MLP_BLOCK", 64)  # four blocks of 256
    ref._dense_mlp.clear_cache()
    blocked = ref.forward(hf, SEED, tokens, [24, 17], keep_from=5,
                          dtype="float32", kv_layers=(0, 3))
    ref._dense_mlp.clear_cache()
    assert whole[0].shape == (2, 19, 512)
    np.testing.assert_allclose(whole[0], blocked[0], atol=1e-4)
    for layer in (0, 3):
        c, k_r = whole[1][layer]
        assert c.shape == (2, 24, 1, 128) and k_r.shape == (2, 24, 1, 16)
        np.testing.assert_allclose(c, blocked[1][layer][0], atol=1e-4)


@pytest.mark.parametrize("change", [
    {"n_group": 2}, {"topk_group": 4}, {"norm_topk_prob": False},
    {"attention_bias": True}, {"hidden_act": "gelu"},
    {"partial_rotary_factor": 0.5}, {"rope_scaling": {"factor": 2}}])
def test_the_reference_refuses_what_it_does_not_compute(hf, change):
    tokens = np.zeros((1, 4), np.int32)
    with pytest.raises(ValueError):
        ref.forward({**hf, **change}, SEED, tokens, [4], keep_from=0)


def test_the_reference_has_no_quantised_form(hf):
    with pytest.raises(ValueError):
        ref.forward(hf, SEED, np.zeros((1, 4), np.int32), [4], keep_from=0,
                    quantization="int8")


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "production_stack_tpu" not in text.split('"""')[2]
    assert "float32" in text and '"highest"' in text


def test_the_check_tells_the_sound_engine_from_fp8_activations(hf):
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.core import EngineCore

    prompts = check.sample_prompts(CHECK, hf["vocab_size"], SEED)
    core = EngineCore(EngineConfig(
        model="tiny-glm4-moe-lite", seed=SEED, max_model_len=128,
        max_num_seqs=4, block_size=8, num_blocks=64, decode_steps=4),
        devices=jax.devices()[:1])
    core.start()
    try:
        outputs = check.engine_outputs(core, prompts, CHECK["gen_tokens"],
                                       CHECK["top_logprobs"])
        cached = core.cached_tokens_total
        sound = check.compare(ref, hf, SEED, None, prompts, outputs,
                              check.engine_pages(core, prompts), (0, 1, 2))
    finally:
        core.stop()
    assert cached >= 16  # the second prompt prefilled behind latent pages
    in_place = check.reference_in_place(ref, hf, SEED, CHECK, prompts,
                                        "float8_e4m3fn")
    fp8 = check.compare(ref, hf, SEED, None, prompts, *in_place, (0, 1, 2))
    # bf16 against float32 through every mode; layer 0 shows the page
    # format, layer 2 lies behind the first expert layer
    assert sound["logprob_rms"] < 0.1, sound
    assert sound["kv_small_rel_rms_layer0"] < 0.01, sound
    assert sound["kv_small_rel_rms"] < 0.1, sound
    # whole pages of 8: 40 + 48 + 56 tokens, 3 layers, a 128-wide latent
    # and a 16-wide key
    assert sound["kv_entries_compared"] == 3 * 144 * (128 + 16)
    assert fp8["logprob_rms"] > max(0.1, 2.5 * sound["logprob_rms"]), (
        sound, fp8)
    assert fp8["kv_small_rel_rms_layer0"] > 5 * sound[
        "kv_small_rel_rms_layer0"]


# --------------------------------------------------------------------- #
# The configuration file and the traffic
# --------------------------------------------------------------------- #

def test_the_configuration_is_the_catalogs_but_for_its_stated_cut(reg):
    """Every number of the catalog's ``config`` under the same key; the
    two cut keys hold less than ``published`` says, at the guide's floors
    (8 routed experts, an eighth of the vocabulary); no width is cut and
    every layer is held."""
    config = reg.config(CONFIG)
    entry = next(c for c in reg.bench["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "n_routed_experts", "vocab_size"]
    model = model_keys(config)
    assert (model["num_hidden_layers"], model["hidden_size"],
            model["num_attention_heads"]) == (47, 2048, 20)
    assert (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"], model["q_lora_rank"],
            model["kv_lora_rank"]) == (192, 64, 256, 768, 512)
    assert (model["moe_intermediate_size"], model["intermediate_size"],
            model["num_experts_per_tok"]) == (1536, 10240, 4)
    published = config["published"]
    assert published["n_routed_experts"] == 64 == 8 * model[
        "n_routed_experts"] == model["chips_per_layer"] * model[
        "n_routed_experts"]
    assert published["vocab_size"] == 154880 == 8 * model["vocab_size"]
    assert entry["source"] == config["source"]
    assert "47 layers" in config["stands_for"]
    for key in ("rotary", "e_score_correction_bias", "hidden_act",
                "num_nextn_predict_layers", "norm_weights"):
        assert key in config["assumed"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    assert row["source_url"] == config["source"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert published[key] == value > model[key]
        else:
            assert model[key] == value, key


def test_the_programs_config_reader_takes_the_file(reg, tmp_path):
    from production_stack_tpu.models import get_model_config
    from production_stack_tpu.models.registry import page_layers

    (tmp_path / "config.json").write_text(
        json.dumps(model_keys(reg.config(CONFIG))))
    cfg = get_model_config(str(tmp_path))
    assert (cfg.arch, cfg.num_layers, cfg.dense_layers) == (
        "glm4_moe_lite", 47, 1)
    assert (cfg.num_experts, cfg.published_experts, cfg.layer_share) == (
        8, 64, 0)
    assert cfg.shared_expert_size == 1536 and cfg.routed_scaling == 1.8
    assert page_layers(cfg) == 47


def test_the_traffics_contexts_fit_the_server_and_the_pool(reg):
    """The schedule is pure: every context stays within
    ``--max-model-len``, the live histories at traffic start are the ~49k
    tokens PERF.md states (three 4,096-token prompts shared by 8 and 24
    histories spread evenly to 7,168), and about 94% of the prompt tokens
    of a window lie behind a prefix the cache can hold."""
    config, traffic = reg.config(CONFIG), reg.traffic("sessions-agent")
    flags = config["server_flags"]
    limit = int(flags[flags.index("--max-model-len") + 1])
    params = traffic["params"]
    assert params["sessions"] == 24 and params["system_prompt_tokens"] == 4096
    assert params["max_history_tokens"] == 7168 < limit
    sched = schedule.build(reg, traffic, 51, config["vocab_size"])
    assert all(len(r["prompt"]) + r["max_tokens"] <= limit
               for r in sched["requests"])
    distinct = 3 * 4096 + sum(len(h) - 4096 for h in sched["preload"])
    assert 40_000 < distinct < 58_000
    new = [len(r["prompt"]) for r in sched["requests"]]
    assert 4096 < min(new) and max(new) <= 7168


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
def agent_trace(tmp_path_factory):
    return _trace(tmp_path_factory, "mla_agent.xplane.pbtxt")


NEW = ("mla_decode_roofline_pct.serve", "mla_kernel_share_pct.serve",
       "latent_attn_share_pct.serve", "dense_mlp_share_pct.serve",
       "routed_experts_hit_pct.serve", "routed_matmul_roofline_pct.serve",
       "mla_cached_prefill_roofline_pct.serve",
       "latent_prefill_absorbed_pct.serve")


def test_the_cell_reports_the_new_metrics_and_not_the_others_readers(reg):
    named = {m["name"] for m in reg.metrics_for("per_layer", CELL)}
    assert set(NEW) <= named
    for other in ("experts_hit_pct.serve", "paged_attn_roofline_pct.serve",
                  "attn_kernel_share_pct.serve", "lora_share_pct.serve",
                  "expert_matmul_roofline_pct.serve",
                  "unscoped_share_pct.serve", "short_conv_share_pct.serve",
                  "conv_state_share_pct.serve",
                  "state_restored_prefill_pct.serve"):
        assert other not in named
    for m in reg.bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] or CELL in m["workloads"]
            assert m["moves"] == ("itl_p99_s" if "prefill" in m["name"]
                                  else "tpot_p50_s")
    e2e = {m["name"] for m in reg.metrics_for("end_to_end", CELL)}
    assert e2e == {"itl_p99_s", "tpot_p50_s", "setup_s"}


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counts_or_another_model_reads_nothing(
        reg, metric, agent_trace):
    """The parent's records and traces (no form counts, no kernel of this
    name), an empty window, and another model's configuration: nothing,
    and no exception."""
    path, reduced = agent_trace
    assert _read(reg, metric, _ctx(reg)) is None
    assert _read(reg, metric, _ctx(reg, steps=[_burst(tokens=100)],
                                   traced_steps=[_burst(tokens=100)])) is None
    other = reg.config("mistral-7b-l16")
    if "share_pct" not in metric and "absorbed" not in metric:
        steps = [_burst(8, 100, 10, 5, tokens=100, kv_live_tokens=99,
                        attn_pairs=9)]
        assert _read(reg, metric, _ctx(
            reg, config=other, steps=steps, traced_steps=steps,
            device=reduced, profile=path)) is None


def test_mla_decode_roofline_at_twenty_heads(reg, agent_trace):
    path, reduced = agent_trace
    steps = [_burst(8, kv_live_tokens=8 * 60_000),
             _burst(8, kv_live_tokens=8 * 80_000)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    tokens = 70_000
    floor = max(tokens * 1152 / 819e9, tokens * 20 * (576 + 512) * 2 / 197e12)
    assert floor == pytest.approx(tokens * 1152 / 819e9)  # the bytes bind
    got = _read(reg, "mla_decode_roofline_pct.serve", ctx)
    assert got == pytest.approx(100 * floor / (4200e-6 / 2), rel=1e-6)


def test_mla_cached_prefill_roofline_has_one_boundary_for_both_forms(
        reg, agent_trace):
    path, reduced = agent_trace
    steps = [{"kind": "prefill_chunk", "forwards": 1, "attn_pairs": 1_700_000},
             {"kind": "prefill_chunk", "forwards": 1, "attn_pairs": 300_000},
             _burst(8)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    flops = 2_000_000 * 2 * 20 * (192 + 64 + 256) * 47
    # in the prefill programs: attention 600 + 500, the context's
    # up-projection 300, the absorbing matmuls 200; the chunk's own
    # projections (400) and the decode program's attention are not in it
    assert _read(reg, "mla_cached_prefill_roofline_pct.serve", ctx) == \
        pytest.approx(100 * flops / 197e12 / 1600e-6, rel=1e-6)
    ctx.traced_steps = [_burst(8)]  # no prefill in the traced span
    assert _read(reg, "mla_cached_prefill_roofline_pct.serve", ctx) is None


def test_latent_prefill_absorbed_share(reg):
    steps = [{"kind": "prefill_chunk", "latent_prefill_absorbed": 3},
             {"kind": "prefill_chunk", "latent_prefill_up_projected": 8,
              "latent_prefill_absorbed": 1}, _burst(8)]
    assert _read(reg, "latent_prefill_absorbed_pct.serve",
                 _ctx(reg, steps=steps)) == pytest.approx(100 * 4 / 12)
    # every one up-projected: 0 is a reading, and stays on the line
    assert _read(reg, "latent_prefill_absorbed_pct.serve", _ctx(
        reg, steps=[{"kind": "prefill_chunk",
                     "latent_prefill_up_projected": 2}])) == 0.0


def test_the_scope_shares(reg, agent_trace):
    path, reduced = agent_trace
    ctx = _ctx(reg, device=reduced, profile=path)
    # mla_proj 300 + 400 + 1000, mla_absorb 200 + 300, attention 600 +
    # 500 + 4200
    assert _read(reg, "latent_attn_share_pct.serve", ctx) == pytest.approx(
        100 * 7500 / BUSY_US, rel=1e-6)
    assert _read(reg, "dense_mlp_share_pct.serve", ctx) == pytest.approx(
        100 * 1000 / BUSY_US, rel=1e-6)
    assert _read(reg, "mla_kernel_share_pct.serve", ctx) == pytest.approx(
        100 * 4200 / BUSY_US, rel=1e-6)
    # the accepted share of the expert layer is on the cell: router 500,
    # experts 3000, the shared expert 700
    assert CELL in next(m for m in reg.bench["per_layer"]
                        if m["name"] == "moe_share_pct.serve")["workloads"]
    assert _read(reg, "moe_share_pct.serve", ctx) == pytest.approx(
        100 * 4200 / BUSY_US, rel=1e-6)


def test_routed_experts_after_the_first_k_dense_layers(reg, agent_trace):
    path, reduced = agent_trace
    # 46 sparse layers x 8 forwards = 368 layer calls a burst
    steps = [_burst(8, 368 * 30, 368 * 5, 368 * 9),
             _burst(8, 368 * 34, 368 * 6, 368 * 11)]
    ctx = _ctx(reg, steps=steps, traced_steps=steps[:1], device=reduced,
               profile=path)
    assert _read(reg, "routed_experts_hit_pct.serve", ctx) == pytest.approx(
        100.0 * (5 + 6) / (2 * 8))
    weights = 5 * 3 * 2048 * 1536 * 2
    rows = 30 * (3 * 2048 + 4 * 1536) * 2
    floor = (weights + rows) / 819e9
    # one traced decode_k8: 368 layer calls, 3000 us under moe_experts
    assert _read(reg, "routed_matmul_roofline_pct.serve", ctx) == \
        pytest.approx(100 * floor / (3000e-6 / 368), rel=1e-6)
    with pytest.raises(ValueError):
        reg.module("readers", "experts_first_k_dense").read(
            ctx, {"what": "other"})
