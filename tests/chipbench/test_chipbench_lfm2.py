"""What ``lfm2-24b-a2b-l10`` brings to the benchmark (PR 36):
``chipbench/reference/lfm2.py`` against ``models/lfm2.py`` at the tiny
size (its own copy of the init recipe draws the program's weights bit for
bit; its blocked forward is its unblocked one; the check's sample served
by an engine, whose second prompt prefills from a cached block's state,
reads close to it where fp8 activations do not), and the new readers on
hand-made step records and the recorded MoE trace."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.reference import lfm2 as ref
from test_chipbench_moe_readers import _burst, _read, _trace

SEED = 11
CHECK = {"shared_prefix": 16, "prompt_tokens": [40, 50, 60], "gen_tokens": 8,
         "top_logprobs": 5, "kv_layers": [0, 1]}
CONFIG = "lfm2-24b-a2b-l10"


@pytest.fixture(scope="module")
def hf():
    """tiny-lfm2's sizes as a ``config.json`` (``layer_types`` of 12
    entries, 6 layers held), which ``tests/test_lfm2.py`` reads too."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny_lfm2_config.json")) as f:
        return json.load(f)


def test_own_recipe_draws_the_programs_weights(hf):
    from production_stack_tpu.models import get_model_config, lfm2

    cfg = get_model_config("tiny-lfm2")
    p = lfm2.init_params(cfg, jax.random.key(SEED))
    keys = ref.split(ref.seed_key(SEED), 20)
    bf16 = jnp.bfloat16

    def same(mine, theirs):
        return bool(jnp.array_equal(mine.astype(theirs.dtype), theirs))

    assert same(ref._stacked(keys[2], 3, (128, 384), 128, bf16),
                p["conv"]["w_in"][3])
    assert same(ref._stacked(keys[3], 1, (128, 3), 3, bf16),
                p["conv"]["w_conv"][1])
    assert same(ref._stacked(keys[8], 1, (16 * 64, 128), 16 * 64, bf16),
                p["attn"]["wo"][1])
    assert same((1.0 + ref.SPREAD * ref._draw(keys[10], 1, (64,))
                 ).astype(bf16), p["attn"]["k_norm"][1])
    assert same(ref._stacked(keys[12], 1, (128, 256), 128, bf16),
                p["dense"]["w_up"][1])
    # expert 5 of sparse layer 2 is entry 2 * 8 + 5 of the stack
    assert same(ref._stacked(keys[17], 2 * 8 + 5, (64, 128), 64, bf16),
                p["moe"]["w_down"][2, 5])
    assert same(ref._stacked(keys[14], 3, (128, 8), 128, bf16),
                p["moe"]["router"][3])
    assert same(ref.SPREAD * ref._draw(keys[18], 2, (8,)),
                p["moe"]["router_bias"][2])
    assert same(ref._table(keys[0], 512, 128, "bfloat16"), p["embed"])
    assert "lm_head" not in p  # tied


def test_the_blocked_forward_is_the_unblocked_one(hf):
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 512, (3, 24))
    lens = [24, 17, 9]
    one = ref.forward(hf, SEED, tokens, lens, keep_from=5, dtype="float32",
                      kv_layers=(0, 1), sequence_block=1)
    whole = ref.forward(hf, SEED, tokens, lens, keep_from=5, dtype="float32",
                        kv_layers=(0, 1), sequence_block=3)
    assert one[0].shape == (3, 19, 512)
    np.testing.assert_allclose(one[0], whole[0], atol=1e-4)
    for n in (0, 1):  # the n-th layer that has keys: model layers 2 and 5
        for mine, theirs in zip(one[1][n], whole[1][n]):
            assert mine.shape == (3, 24, 8, 64)
            np.testing.assert_allclose(mine, theirs, atol=1e-4)


def test_the_reference_refuses_what_it_does_not_compute(hf):
    tokens = np.zeros((1, 4), np.int32)
    for change in ({"tie_word_embeddings": False}, {"conv_bias": True},
                   {"norm_topk_prob": False}):
        with pytest.raises(ValueError):
            ref.forward({**hf, **change}, SEED, tokens, [4], keep_from=0)
    with pytest.raises(ValueError):
        ref.forward(hf, SEED, tokens, [4], keep_from=0, quantization="int8")


def test_the_check_tells_the_sound_engine_from_fp8_activations(hf):
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.core import EngineCore

    prompts = check.sample_prompts(CHECK, hf["vocab_size"], SEED)
    core = EngineCore(EngineConfig(
        model="tiny-lfm2", seed=SEED, max_model_len=128, max_num_seqs=4,
        block_size=8, num_blocks=64, decode_steps=4),
        devices=jax.devices()[:1])
    core.start()
    try:
        outputs = check.engine_outputs(core, prompts, CHECK["gen_tokens"],
                                       CHECK["top_logprobs"])
        restores = core.stats()["state_restores_total"]
        sound = check.compare(ref, hf, SEED, None, prompts, outputs,
                              check.engine_pages(core, prompts), (0, 1))
    finally:
        core.stop()
    assert restores >= 1  # the second prompt began from a block's state
    in_place = check.reference_in_place(ref, hf, SEED, CHECK, prompts,
                                        "float8_e4m3fn")
    fp8 = check.compare(ref, hf, SEED, None, prompts, *in_place, (0, 1))
    # bf16 against float32 through every mode. The tied head's logits are
    # small at this width (0.02 sqrt(128)), so the log-probabilities move
    # little; the second page layer lies behind three expert layers, whose
    # router near-ties flip an expert between the two (the reference
    # itself with bf16 activations reads 0.057-0.077 there, two seeds).
    assert sound["logprob_rms"] < 0.05, sound
    assert sound["kv_small_rel_rms_layer0"] < 0.03, sound
    assert sound["kv_small_rel_rms"] < 0.15, sound
    # whole pages of 8: 40 + 48 + 56 tokens, the 2 layers that hold pages
    # x 2 sides x 8 heads of 64
    assert sound["kv_entries_compared"] == 2 * 2 * 144 * 8 * 64
    assert fp8["logprob_rms"] > max(0.08, 2.5 * sound["logprob_rms"]), (
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
def moe_trace(tmp_path_factory):
    return _trace(tmp_path_factory, "moe.xplane.pbtxt")


def test_sparse_layers_are_the_held_ones_less_the_leading_dense(reg):
    module = reg.module("readers", "experts_after_dense")
    assert module.sparse_layers(reg.config(CONFIG)) == 8
    assert module.sparse_layers({"num_hidden_layers": 2,
                                 "num_dense_layers": 4}) == 0
    assert module.sparse_layers({"num_hidden_layers": 6}) == 6


def test_experts_hit(reg):
    # 8 sparse layers x 8 forwards = 64 layer calls a burst
    steps = [_burst(8, 64 * 40, 64 * 25, 64 * 4),
             _burst(8, 64 * 64, 64 * 41, 64 * 5)]
    assert _read(reg, "experts_hit_pct.serve", _ctx(reg, steps=steps)) == \
        pytest.approx(100.0 * (25 + 41) / (2 * 64))
    # records without the counts (the parent's), an empty window
    assert _read(reg, "experts_hit_pct.serve",
                 _ctx(reg, steps=[_burst()])) is None
    assert _read(reg, "experts_hit_pct.serve", _ctx(reg)) is None


def test_expert_matmul_roofline(reg, moe_trace):
    path, reduced = moe_trace
    steps = [_burst(8, 64 * 40, 64 * 25, 64 * 4)]
    ctx = _ctx(reg, device=reduced, profile=path, traced_steps=steps,
               steps=steps)
    # one traced decode_k8 = 8 forwards x 8 sparse layers; its grouped
    # matmuls and moe_experts operations took 5400 + 4200 + 200 us
    seconds = 9800e-6 / 64
    weights = 25 * 3 * 2048 * 1536 * 2
    rows = 40 * (3 * 2048 + 4 * 1536) * 2
    floor = (weights + rows) / 819e9
    got = _read(reg, "expert_matmul_roofline_pct.serve", ctx)
    assert got == pytest.approx(100 * floor / seconds, rel=1e-6)
    ctx.traced_steps = []  # the window's counts stand in
    assert _read(reg, "expert_matmul_roofline_pct.serve", ctx) == \
        pytest.approx(got)
    ctx.steps = [_burst()]
    assert _read(reg, "expert_matmul_roofline_pct.serve", ctx) is None
    assert _read(reg, "expert_matmul_roofline_pct.serve",
                 _ctx(reg, steps=steps)) is None
    with pytest.raises(ValueError):
        reg.module("readers", "experts_after_dense").read(
            ctx, {"what": "other"})


def test_state_restored_prefill_share(reg):
    steps = [{"kind": "prefill", "state_rows": 1, "state_restores": 1},
             {"kind": "prefill", "state_rows": 4, "state_restores": 0,
              "state_blocks_written": 36},
             {"kind": "prefill_chunk", "state_rows": 3, "state_restores": 3},
             {"kind": "decode_burst", "state_blocks_written": 64}]
    assert _read(reg, "state_restored_prefill_pct.serve",
                 _ctx(reg, steps=steps)) == pytest.approx(50.0)
    # a program without such a state writes no such counts
    assert _read(reg, "state_restored_prefill_pct.serve", _ctx(
        reg, steps=[{"kind": "prefill", "rows": 1}])) is None
    assert _read(reg, "state_restored_prefill_pct.serve", _ctx(reg)) is None


@pytest.mark.parametrize("metric, want_us", [
    ("moe_share_pct.serve", 11800), ("short_conv_share_pct.serve", None),
    ("conv_state_share_pct.serve", None)])
def test_the_scope_shares(reg, moe_trace, metric, want_us):
    """The recorded trace has Laguna's scopes and none of the convolution's:
    the expert layer's share reads as Laguna's metric does, the others
    nothing (what a program without the scopes gives)."""
    path, reduced = moe_trace
    got = _read(reg, metric, _ctx(reg, device=reduced, profile=path))
    if want_us is None:
        assert got is None
    else:
        assert got == pytest.approx(100 * want_us / 17000.0, rel=1e-6)
    assert _read(reg, metric, _ctx(reg)) is None
