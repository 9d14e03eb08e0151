"""The plain reference against ``models/llama.py`` at the tiny size."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import llama as ref
from chipbench.registry import Registry

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def tiny():
    return Registry(DATA).config("tiny-llama")


@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 + 77])
def test_own_generator_draws_the_programs_weights(seed):
    """Bit for bit: the reference's Threefry and normal transform against
    ``jax.random`` as the program's init uses it."""
    from production_stack_tpu.models.config import get_model_config
    from production_stack_tpu.models.llama import init_params

    cfg = get_model_config("tiny-llama")
    p = init_params(cfg, jax.random.key(seed))
    keys = ref.split(ref.seed_key(seed), 10)
    Hd, I = cfg.hidden_size, cfg.intermediate_size
    for layer in range(cfg.num_layers):
        mine = ref._matrix(keys[7], jnp.uint32(layer), (I, Hd), I,
                           jnp.bfloat16)
        assert bool(jnp.array_equal(mine, p["layers"]["w_down"][layer]))
    head = ref._matrix(keys[8], jnp.uint32(0), (Hd, cfg.vocab_size), Hd,
                       jnp.bfloat16)
    assert bool(jnp.array_equal(head, p["lm_head"]))


def _program_logprobs(tiny, seed, tokens, quantization):
    """The program's own forward (uncached prefill through the pages)."""
    from production_stack_tpu.models.config import get_model_config
    from production_stack_tpu.models.llama import apply, init_params
    from production_stack_tpu.models.quantize import quantize_tree

    cfg = get_model_config("tiny-llama")
    params = init_params(cfg, jax.random.key(seed))
    if quantization == "int8":
        params = quantize_tree(params, "llama")
    S, T = tokens.shape
    bs, nb = 16, S * ((T + 15) // 16)
    pages = jnp.zeros((cfg.num_layers, nb, bs, cfg.num_kv_heads,
                       cfg.head_dim), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(T), (S, T))
    per = (T + 15) // 16
    tables = jnp.arange(nb, dtype=jnp.int32).reshape(S, per)
    slots = (tables[:, :, None] * bs + jnp.arange(bs)).reshape(S, -1)[:, :T]
    logits, _ = apply(params, cfg, jnp.asarray(tokens), positions,
                      (pages, pages), slots, tables,
                      jnp.full((S,), T, jnp.int32),
                      jnp.full((S,), T, jnp.int32), mode="prefill")
    return np.asarray(jax.nn.log_softmax(logits, -1))


@pytest.mark.parametrize("quantization", [None, "int8"])
def test_reference_agrees_with_the_program_forward(tiny, quantization):
    """bf16 program against float32 reference: a few hundredths of a nat
    at two layers; a wrong block (or other weights) is off by whole
    nats."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(259, 512, size=(2, 40)).astype(np.int32)
    hf = {k: v for k, v in tiny.items() if not isinstance(v, (dict, list))}
    mine, kv = ref.forward(hf, 9, tokens, [40, 40], keep_from=0,
                           quantization=quantization)
    theirs = _program_logprobs(tiny, 9, tokens, quantization)
    top = np.argsort(theirs, -1)[..., -5:]
    diff = (np.take_along_axis(mine, top, -1)
            - np.take_along_axis(theirs, top, -1))
    assert float(np.sqrt(np.mean(diff ** 2))) < 0.05
    assert kv[0][0].shape == (2, 40, 2, 32)
    other, _ = ref.forward(hf, 10, tokens, [40, 40], keep_from=0,
                           quantization=quantization)
    wrong = (np.take_along_axis(other, top, -1)
             - np.take_along_axis(theirs, top, -1))
    assert float(np.sqrt(np.mean(wrong ** 2))) > 0.5


def test_int8_weights_are_a_different_model_than_bf16(tiny):
    rng = np.random.default_rng(4)
    tokens = rng.integers(259, 512, size=(1, 32)).astype(np.int32)
    hf = {k: v for k, v in tiny.items() if not isinstance(v, (dict, list))}
    a, _ = ref.forward(hf, 1, tokens, [32], keep_from=0)
    b, _ = ref.forward(hf, 1, tokens, [32], keep_from=0,
                       quantization="int8")
    assert 1e-4 < float(np.sqrt(np.mean((a - b) ** 2))) < 0.2


def test_padding_does_not_reach_a_shorter_sequence(tiny):
    rng = np.random.default_rng(5)
    tokens = rng.integers(259, 512, size=(2, 24)).astype(np.int32)
    hf = {k: v for k, v in tiny.items() if not isinstance(v, (dict, list))}
    both, _ = ref.forward(hf, 2, tokens, [24, 12], keep_from=0)
    alone, _ = ref.forward(hf, 2, tokens[1:, :12], [12], keep_from=0)
    np.testing.assert_allclose(both[1, :12], alone[0], atol=1e-4)


@pytest.mark.parametrize("control", [None, "int8_pages", "int8_weights",
                                     "fp8_activations"])
def test_check_passes_sound_and_fails_every_control(control):
    """The controls kept as a test: the sound engine passes both limits;
    the engine with int8 KV pages, the engine with int8 weights held
    against the bf16 reference, and the reference itself with fp8
    activations in the program's place each come out as not correct."""
    from chipbench.control import read_seeds

    lines = list(read_seeds("tiny-llama", [21, 22], control, root=DATA,
                            platform="cpu"))
    assert [line["correct"] for line in lines] == [control is None] * 2
    for line in lines:
        over = {name for name, limit in line["limits"].items()
                if line[name] > limit}
        if control == "int8_pages":
            # the page format shows in the pages, not in log-probabilities
            assert over == {"kv_small_rel_rms"}
        elif control == "fp8_activations":
            assert over == {"logprob_rms", "kv_small_rel_rms"}


def test_rounded_activations_leave_the_reference_alone_when_off(tiny):
    rng = np.random.default_rng(6)
    tokens = rng.integers(259, 512, size=(1, 24)).astype(np.int32)
    hf = {k: v for k, v in tiny.items() if not isinstance(v, (dict, list))}
    a, kv_a = ref.forward(hf, 3, tokens, [24], keep_from=0)
    b, kv_b = ref.forward(hf, 3, tokens, [24], keep_from=0, activations=None)
    c, kv_c = ref.forward(hf, 3, tokens, [24], keep_from=0,
                          activations="float8_e4m3fn")
    assert np.array_equal(a, b) and np.array_equal(kv_a[0][0], kv_b[0][0])
    assert float(np.sqrt(np.mean((a - c) ** 2))) > 0.05
