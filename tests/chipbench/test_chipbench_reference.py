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


# -- ``check.kv_layers``: which layers' cache is compared ----------------

@pytest.fixture(scope="module")
def tiny_engine(tmp_path_factory):
    """(registry, configuration, engine) of the tiny preset, built as
    ``chipbench.control`` builds one."""
    from chipbench.stack import Stack, write_model_dir

    registry = Registry(DATA)
    config = registry.config("tiny-llama")
    model_dir = write_model_dir(config, str(tmp_path_factory.mktemp("kv")),
                                "tiny-llama")
    stack = Stack(model_dir, "tiny-llama", config["server_flags"], 31,
                  devices=jax.devices()[:1])
    yield registry, config, stack.core
    stack.core.stop()
    stack.free_device_memory()


class _KeysOff:
    """The engine with the keys of one layer's pages off by a quarter
    where the check reads them: the fault a cache of another kind (a
    window's ring behind the full layer at 0) would bring."""

    def __init__(self, core, layer):
        self._core, self._layer = core, layer

    def __getattr__(self, name):
        return getattr(self._core, name)

    def extract_kv(self, tokens):
        got = dict(self._core.extract_kv(tokens))
        keys = np.array(got["k"], np.float32)
        keys[:, self._layer] *= 1.25
        got["k"] = keys
        return got


def _two_layers(config: dict) -> dict:
    """The configuration with both layers' pages compared. A sound run
    reads the second layer's at 0.0077-0.0084 where the first reads
    0.0017-0.0018 (three seeds, CPU): the bf16 activations before it.
    So the largest over the layers gets a limit of its own, twice that,
    and the first layer keeps the one it had."""
    check = config["check"]
    first = check["limits"]["kv_small_rel_rms"]
    return {**config, "check": {**check, "kv_layers": [0, 1], "limits": {
        **check["limits"], "kv_small_rel_rms": 0.016,
        "kv_small_rel_rms_layer0": first}}}


def test_kv_layers_names_the_layers_whose_pages_are_compared(tiny_engine):
    from chipbench.check import run_check

    registry, config, core = tiny_engine
    assert "kv_layers" not in config["check"]
    both = _two_layers(config)
    first = run_check(registry, config, 31, core)
    two = run_check(registry, both, 31, core)
    assert first["ok"] and two["ok"], (first["numbers"], two["numbers"])
    assert not any(name.startswith("kv_small_rel_rms_layer")
                   for name in first["numbers"])
    assert (two["numbers"]["kv_small_rel_rms_layer0"]
            == first["numbers"]["kv_small_rel_rms"])
    assert two["numbers"]["kv_small_rel_rms"] == max(
        two["numbers"]["kv_small_rel_rms_layer0"],
        two["numbers"]["kv_small_rel_rms_layer1"])
    assert (two["numbers"]["kv_entries_compared"]
            == 2 * first["numbers"]["kv_entries_compared"])
    # a fault in layer 1's pages: seen only where the block names layer 1
    faulty = _KeysOff(core, 1)
    assert run_check(registry, config, 31, faulty)["ok"]
    seen = run_check(registry, both, 31, faulty)
    over = {name for name, limit in seen["limits"].items()
            if seen["numbers"][name] > limit}
    assert not seen["ok"] and over == {"kv_small_rel_rms"}
    assert seen["numbers"]["kv_small_rel_rms_layer1"] > 3 * 0.016
    # the same fault in layer 0 is seen by the default block too
    assert not run_check(registry, config, 31, _KeysOff(core, 0))["ok"]
    in_first = run_check(registry, both, 31, _KeysOff(core, 0))
    assert (in_first["numbers"]["kv_small_rel_rms_layer0"]
            > in_first["limits"]["kv_small_rel_rms_layer0"])


def test_kv_layers_reach_the_control_in_the_programs_place(tiny):
    """The reference in the program's place hands out the pages of every
    layer named, and the control fails on each."""
    from chipbench.check import run_check

    verdict = run_check(Registry(DATA), _two_layers(tiny), 5, None,
                        reference_activations="float8_e4m3fn")
    numbers, limits = verdict["numbers"], verdict["limits"]
    assert not verdict["ok"]
    assert numbers["kv_small_rel_rms_layer0"] > limits[
        "kv_small_rel_rms_layer0"]
    assert numbers["kv_small_rel_rms_layer1"] > limits["kv_small_rel_rms"]


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_pages_of_a_layer_are_that_layers(layer):
    """[N, L, bs, KVH, D] pages, plain and as (int8 data, scales)."""
    from chipbench.check import _pages_to_tokens

    rng = np.random.default_rng(7)
    pages = rng.standard_normal((3, 4, 2, 2, 8)).astype(np.float32)
    want = pages[:, layer].reshape(6, 2, 8)
    assert np.array_equal(_pages_to_tokens(pages, layer), want)
    data = rng.integers(-127, 128, size=pages.shape).astype(np.int8)
    scales = rng.random((3, 4, 2, 2)).astype(np.float32)
    got = _pages_to_tokens((data, scales), layer)
    assert np.array_equal(got, (data[:, layer].astype(np.float32)
                                * scales[:, layer][..., None]
                                ).reshape(6, 2, 8))
    if layer == 0:
        assert np.array_equal(_pages_to_tokens(pages), want)
