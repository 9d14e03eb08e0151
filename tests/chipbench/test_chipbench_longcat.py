"""What ``longcat-flash-l4e16`` brings to the benchmark (PR 41):
``chipbench/reference/longcat.py`` at the tiny size (its blocked dense
MLP is the whole one; it refuses what it does not compute; the check's
sample served by an engine, whose second prompt prefills behind cached
latent pages, reads close to it where fp8 activations do not, with two
cache sides of unequal width going through ``check.py`` as they are),
and the new readers on hand-made step records and a small trace in the
recorded format (``data/mla.xplane.pbtxt``):

  XLA Modules   jit_prefill_cached(1)  0 .. 1500 us
                jit_decode_k8(2)       2000 .. 20000 us
  XLA Ops       fusion.2 0..800 (attention), fusion.3 1000..1400 (mla_proj)
                while.3 2000..20000 (parent of the rest; 3900 us its own)
                fusion.7 1000 us (mla_proj), fusion.9 300 (mla_absorb)
                pallas_mla_decode.10 2000 + 2200 (attention; two calls)
                fusion.21 500 (moe_router), pallas_grouped_matmul.30 3000
                (moe_experts), fusion.30 100 (moe_zero), fusion.40 5000 (mlp)
"""

import json
import os
import types

import jax
import numpy as np
import pytest

from chipbench import check
from chipbench.reference import longcat as ref
from test_chipbench_moe_readers import _burst, _read, _trace

SEED = 11
CHECK = {"shared_prefix": 16, "prompt_tokens": [40, 50, 60], "gen_tokens": 8,
         "top_logprobs": 5, "kv_layers": [0, 1, 2]}
CONFIG = "longcat-flash-l4e16"
CELL = "longcat-backlog-long"
BUSY_US = 19200.0  # 800 + 400 + the while's 18000


@pytest.fixture(scope="module")
def hf():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny_longcat_config.json")) as f:
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
    {"attention_method": "MHA"}, {"zero_expert_type": "constant"},
    {"attention_bias": True}, {"norm_topk_prob": True}])
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
        model="tiny-longcat", seed=SEED, max_model_len=128, max_num_seqs=4,
        block_size=8, num_blocks=64, decode_steps=4),
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
    # bf16 against float32 through every mode; the first page layer shows
    # the page format, the third lies behind an expert layer's shortcut
    assert sound["logprob_rms"] < 0.1, sound
    assert sound["kv_small_rel_rms_layer0"] < 0.01, sound
    assert sound["kv_small_rel_rms"] < 0.1, sound
    # whole pages of 8: 40 + 48 + 56 tokens, 3 page layers, a 128-wide
    # latent and a 16-wide key
    assert sound["kv_entries_compared"] == 3 * 144 * (128 + 16)
    assert fp8["logprob_rms"] > max(0.1, 2.5 * sound["logprob_rms"]), (
        sound, fp8)
    assert fp8["kv_small_rel_rms_layer0"] > 5 * sound[
        "kv_small_rel_rms_layer0"]


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
def mla_trace(tmp_path_factory):
    return _trace(tmp_path_factory, "mla.xplane.pbtxt")


LISTED = ("mla_decode_roofline_pct.batch",
          "latent_attn_share_pct.batch", "dense_mlp_share_pct.batch",
          "mla_kernel_share_pct.batch", "routed_experts_hit_pct.batch",
          "routed_matmul_roofline_pct.batch", "zero_expert_share_pct.batch")
# Read from the traced two seconds' prefill programs, which at the cell's
# ramp hold one in some runs only (PERF.md section 7): a metric a line may
# lack cannot be listed, so the reader and its file wait for a trace that
# sits in the turn-over, and the accepted metric does not list the cell.
NOT_LISTED = ("mla_prefill_roofline_pct.batch",
              "prefill_device_share_pct.batch")
NEW = LISTED + NOT_LISTED[:1]


def test_the_cell_reports_the_new_metrics_and_not_the_others_readers(reg):
    named = {m["name"] for m in reg.metrics_for("per_layer", CELL)}
    assert set(LISTED) <= named
    assert not set(NOT_LISTED) & named
    assert NOT_LISTED[0] not in {m["name"] for m in reg.bench["per_layer"]}
    for other in ("experts_hit_pct.batch", "paged_attn_roofline_pct.batch",
                  "mixed_attn_roofline_pct.batch", "lora_share_pct.batch",
                  "expert_matmul_roofline_pct.batch",
                  "weights_matmul_share_pct.batch",
                  "unscoped_share_pct.batch", "attn_kernel_share_pct.batch",
                  "expert_load_max_over_mean.batch"):
        assert other not in named
    for m in reg.bench["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["moves"] == "out_tokens_per_s"
    e2e = {m["name"] for m in reg.metrics_for("end_to_end", CELL)}
    assert e2e == {"out_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counts_or_another_model_reads_nothing(
        reg, metric, mla_trace):
    """The parent's records and traces (no ``attn_pairs``, no
    ``moe_zero_assignments``, no kernel of this name), an empty window,
    and another model's configuration: nothing, and no exception."""
    path, reduced = mla_trace
    assert _read(reg, metric, _ctx(reg)) is None
    assert _read(reg, metric, _ctx(reg, steps=[_burst(tokens=100)],
                                   traced_steps=[_burst(tokens=100)])) is None
    other = reg.config("mistral-7b-l16")
    if "share_pct" not in metric or "zero" in metric:
        steps = [_burst(8, 100, 10, 5, tokens=100, kv_live_tokens=99,
                        moe_zero_assignments=7, attn_pairs=9)]
        assert _read(reg, metric, _ctx(
            reg, config=other, steps=steps, traced_steps=steps,
            device=reduced, profile=path)) is None


def test_mla_decode_roofline(reg, mla_trace):
    path, reduced = mla_trace
    # two bursts of 8 forwards; 8 x 250k live tokens each in all
    steps = [_burst(8, kv_live_tokens=8 * 250_000),
             _burst(8, kv_live_tokens=8 * 350_000)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    tokens = 300_000
    floor = max(tokens * 1152 / 819e9, tokens * 64 * (576 + 512) * 2 / 197e12)
    assert floor == pytest.approx(tokens * 1152 / 819e9)  # the bytes bind
    got = _read(reg, "mla_decode_roofline_pct.batch", ctx)
    assert got == pytest.approx(100 * floor / (4200e-6 / 2), rel=1e-6)
    # the XLA path: records without the count
    ctx.traced_steps = [_burst(8)]
    assert _read(reg, "mla_decode_roofline_pct.batch", ctx) is None


def test_mla_prefill_roofline(reg, mla_trace):
    path, reduced = mla_trace
    steps = [{"kind": "prefill_chunk", "forwards": 1, "attn_pairs": 700_000},
             {"kind": "prefill", "forwards": 2, "attn_pairs": 300_000},
             _burst(8)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps)
    flops = 1_000_000 * 2 * 64 * (128 + 64 + 128) * 8
    # the operations under ``attention`` in the prefill programs: 800 us
    assert _read(reg, "mla_prefill_roofline_pct.batch", ctx) == \
        pytest.approx(100 * flops / 197e12 / 800e-6, rel=1e-6)


def test_the_scope_shares(reg, mla_trace):
    path, reduced = mla_trace
    ctx = _ctx(reg, device=reduced, profile=path)
    # mla_proj 400 + 1000, mla_absorb 300, attention 800 + 4200
    assert _read(reg, "latent_attn_share_pct.batch", ctx) == pytest.approx(
        100 * 6700 / BUSY_US, rel=1e-6)
    # the two dense MLPs a layer: 5000; the decode kernel alone: 4200
    assert _read(reg, "dense_mlp_share_pct.batch", ctx) == pytest.approx(
        100 * 5000 / BUSY_US, rel=1e-6)
    assert _read(reg, "mla_kernel_share_pct.batch", ctx) == pytest.approx(
        100 * 4200 / BUSY_US, rel=1e-6)
    # the accepted share of the expert layer stays on the cell (router
    # 500, experts 3000; the identities' 100 are under moe_zero)
    assert CELL in next(m for m in reg.bench["per_layer"]
                        if m["name"] == "moe_share_pct.batch")["workloads"]
    assert _read(reg, "moe_share_pct.batch", ctx) == pytest.approx(
        100 * 3500 / BUSY_US, rel=1e-6)


def test_routed_experts(reg, mla_trace):
    path, reduced = mla_trace
    # 4 layers x 8 forwards = 32 layer calls a burst
    steps = [_burst(8, 32 * 30, 32 * 14, 32 * 5),
             _burst(8, 32 * 34, 32 * 15, 32 * 6)]
    ctx = _ctx(reg, steps=steps, traced_steps=steps[:1], device=reduced,
               profile=path)
    assert _read(reg, "routed_experts_hit_pct.batch", ctx) == pytest.approx(
        100.0 * (14 + 15) / (2 * 16))
    weights = 14 * 3 * 6144 * 2048 * 2
    rows = 30 * (3 * 6144 + 4 * 2048) * 2
    floor = (weights + rows) / 819e9
    # one traced decode_k8: 32 layer calls, 3000 us under moe_experts
    assert _read(reg, "routed_matmul_roofline_pct.batch", ctx) == \
        pytest.approx(100 * floor / (3000e-6 / 32), rel=1e-6)
    with pytest.raises(ValueError):
        reg.module("readers", "routed_experts").read(ctx, {"what": "other"})


def test_zero_expert_share(reg):
    # 1000 decode tokens x 12 picks x 4 layers; a prefill's counts apart
    steps = [_burst(8, 900, 60, 20, tokens=600, moe_zero_assignments=9600),
             _burst(8, 600, 50, 20, tokens=400, moe_zero_assignments=6400),
             {"kind": "prefill", "forwards": 1, "tokens": 5000,
              "prefill_stats_forwards": 1,
              "prefill_moe_zero_assignments": 99999}]
    assert _read(reg, "zero_expert_share_pct.batch",
                 _ctx(reg, steps=steps)) == pytest.approx(
        100.0 * 16000 / (1000 * 12 * 4))
