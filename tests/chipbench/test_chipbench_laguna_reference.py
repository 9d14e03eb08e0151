"""``chipbench/reference/laguna.py`` against ``models/laguna.py`` at the
tiny size, as the benchmark holds them together: its own copy of the init
recipe draws the program's weights bit for bit, and the check's sample
served by an engine (uncached prefill, prefill behind a cached prefix,
decode bursts through the paged cache, bf16) reads close to the float32
reference where the reference with fp8 activations in the program's
place does not."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.reference import laguna as ref

SEED = 11
CHECK = {"shared_prefix": 16, "prompt_tokens": [40, 50, 60], "gen_tokens": 8,
         "top_logprobs": 5, "kv_layers": [0, 1]}


@pytest.fixture(scope="module")
def hf():
    """tiny-laguna's sizes as a ``config.json`` (per-layer lists of 12
    entries, 6 layers held), which ``tests/test_laguna.py`` reads too."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny_laguna_config.json")) as f:
        return json.load(f)


def test_own_recipe_draws_the_programs_weights(hf):
    from production_stack_tpu.models import get_model_config, laguna

    cfg = get_model_config("tiny-laguna")
    p = laguna.init_params(cfg, jax.random.key(SEED))
    keys = ref.split(ref.seed_key(SEED), 24)
    bf16 = jnp.bfloat16

    def same(mine, theirs):
        return bool(jnp.array_equal(mine.astype(bf16), theirs))

    sliding = p["attn"]["sliding_attention"]
    assert same(ref._stacked(keys[11], 2, (6 * 32, 128), 6 * 32, bf16),
                sliding["wo"][2])
    assert same(ref._stacked(keys[10], 3, (128, 6), 128, bf16),
                sliding["wg"][3])
    assert same(ref._stacked(keys[6], 1, (4 * 32, 128), 4 * 32, bf16),
                p["attn"]["full_attention"]["wo"][1])
    # expert 2 of sparse layer 3 is entry 3 * 4 + 2 of the stack
    assert same(ref._stacked(keys[18], 3 * 4 + 2, (64, 128), 64, bf16),
                p["moe"]["w_down"][3, 2])
    assert same(ref._stacked(keys[15], 4, (128, 8), 128, bf16),
                p["moe"]["router"][4])
    assert same(ref._stacked(keys[20], 1, (128, 64), 128, bf16),
                p["moe"]["shared_up"][1])
    assert same(ref._stacked(keys[13], 0, (128, 256), 128, bf16),
                p["dense"]["w_up"][0])
    assert same(ref._stacked(keys[1], 0, (128, 512), 128, bf16),
                p["lm_head"])


def test_the_check_tells_the_sound_engine_from_fp8_activations(hf):
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.core import EngineCore

    prompts = check.sample_prompts(CHECK, hf["vocab_size"], SEED)
    core = EngineCore(EngineConfig(
        model="tiny-laguna", seed=SEED, max_model_len=128, max_num_seqs=4,
        block_size=8, num_blocks=64, decode_steps=4),
        devices=jax.devices()[:1])
    core.start()
    try:
        outputs = check.engine_outputs(core, prompts, CHECK["gen_tokens"],
                                       CHECK["top_logprobs"])
        sound = check.compare(ref, hf, SEED, None, prompts, outputs,
                              check.engine_pages(core, prompts), (0, 1))
    finally:
        core.stop()
    in_place = check.reference_in_place(ref, hf, SEED, CHECK, prompts,
                                        "float8_e4m3fn")
    fp8 = check.compare(ref, hf, SEED, None, prompts, *in_place, (0, 1))
    # bf16 against float32 through every mode, both layer kinds' pages. A
    # near-tie in a router flips an expert between the two: the reference
    # itself with bf16 activations reads 0.12-0.29 here (three seeds),
    # where the dense tiny-llama reads a few hundredths.
    assert sound["logprob_rms"] < 0.3, sound
    assert sound["kv_small_rel_rms_layer0"] < 0.004, sound
    assert sound["kv_small_rel_rms"] < 0.02, sound
    # whole pages of 8: 40 + 48 + 56 tokens, 2 layers x 2 sides x 2 x 32
    assert sound["kv_entries_compared"] == 2 * 2 * 144 * 2 * 32
    assert fp8["logprob_rms"] > max(0.45, 2.5 * sound["logprob_rms"]), (
        sound, fp8)
    assert fp8["kv_small_rel_rms_layer0"] > 5 * sound[
        "kv_small_rel_rms_layer0"]
