"""The ouro family (PR 48; ByteDance/Ouro-2.6B): one stack of
sandwich-normed layers applied ``total_ut_steps`` times over the same
weights, the model's final norm closing every pass, a page layer for
every pass of every layer (``l x total_ut_steps + u``), and the exit
gate's mathematics.

The program (``models/ouro.py`` through ``decoder.scan_passes`` around
``decoder.scan_layers``, ``llama.attention_half`` and ``decoder.attend``)
is held to the plain reference (``chipbench/reference/ouro.py``: float32,
whole passes over a whole sequence, no cache) on seeded random weights at
the tiny size, in float32. Tolerances: 2e-4 absolute on log-probabilities
and on the cached keys and values, which float32 accumulation order
accounts for and which bf16 in place of the test dtype fails by two orders
of magnitude (``test_bf16_fails_the_tolerance``).
"""

import json
import os
import queue
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import (
    EngineCore,
    kv_bytes_per_block,
    kv_page_sides,
)
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import build_model, get_model_config, ouro
from production_stack_tpu.models.registry import (
    arch_of_model_type,
    forward_weight_bytes,
    get_family,
    page_layers,
    page_sides,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench.reference import ouro as reference  # noqa: E402

SEED = 13
TOL = 2e-4
BS = 16

with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny_ouro_config.json")) as _f:
    HF = json.load(_f)
with open(os.path.join(REPO, "chipbench", "configs", "ouro-2.6b.json")) as _f:
    PUBLISHED = json.load(_f)


def _model_dir(tmp_path, **changes):
    path = tmp_path / "model"
    path.mkdir(exist_ok=True)
    (path / "config.json").write_text(json.dumps({**HF, **changes}))
    return str(path)


# --------------------------------------------------------------------- #
# The config reader and the record
# --------------------------------------------------------------------- #

def test_config_json_reads_as_the_preset(tmp_path):
    assert arch_of_model_type("ouro") == "ouro"
    read = get_model_config(_model_dir(tmp_path))
    preset = get_model_config("tiny-ouro")
    assert read.replace(name=preset.name) == preset
    assert read.loop_passes == 2 and read.early_exit_threshold == 1.0


@pytest.mark.parametrize("changes", [
    {"attention_bias": True}, {"use_sliding_window": True},
    {"rope_scaling": {"type": "yarn"}}, {"hidden_act": "gelu"},
    {"tie_word_embeddings": True}, {"total_ut_steps": 0},
    {"early_exit_threshold": 0.9}], ids=lambda c: next(iter(c)))
def test_a_config_the_family_does_not_serve_is_refused(tmp_path, changes):
    """No flag, and no value of the config either, turns an early exit
    on: a threshold under one is refused by name."""
    with pytest.raises(ValueError, match=next(iter(changes))):
        get_model_config(_model_dir(tmp_path, **changes))


def test_the_published_sizes_without_allocating_them(tmp_path):
    """192 page layers of 16 heads x 128, 1,572,864 bytes a token; 2,668M
    parameters, 5.34 GB, of which a forward reads the 48 layers four
    times: 19.93 GB."""
    path = tmp_path / "model"
    path.mkdir()
    from chipbench.registry import model_keys
    (path / "config.json").write_text(json.dumps(model_keys(PUBLISHED)))
    cfg = get_model_config(str(path))
    assert (cfg.num_layers, cfg.loop_passes, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.vocab_size) == (
        48, 4, 16, 16, 128, 5632, 49152)
    assert page_layers(cfg) == 192 and page_sides(cfg) is None
    assert kv_page_sides(cfg) == (192, (16, 128), (16, 128))
    assert kv_bytes_per_block(cfg, 64) == 64 * 1572864
    tree = jax.eval_shape(lambda: ouro.init_params(cfg, jax.random.key(0)))
    held = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))
    assert round(held / 1e9, 2) == 5.34
    layers = sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                 for leaf in jax.tree_util.tree_leaves(tree["layers"]))
    assert forward_weight_bytes(cfg, tree) == held + 3 * layers
    assert round(forward_weight_bytes(cfg, tree) / 1e9, 2) == 20.14
    # a family that runs its stack once reads its tree once
    llama_cfg = get_model_config("tiny-llama")
    llama_tree = jax.eval_shape(
        lambda: build_model(llama_cfg)[0](llama_cfg, jax.random.key(0)))
    assert forward_weight_bytes(llama_cfg, llama_tree) == sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(llama_tree))


def test_the_record_says_what_the_family_is():
    family = get_family("ouro")
    assert family.stats == ("loop_passes",)
    assert family.quant_keys == get_family("llama").quant_keys
    assert not family.lora and not family.pipeline and family.load is None
    cfg = get_model_config("tiny-ouro")
    assert family.layer_passes(cfg) == 2 and page_layers(cfg) == 6


def test_own_recipe_draws_the_programs_weights():
    """The reference's copy of the init recipe gives the program's
    weights bit for bit: layer l is entry l of a stacked leaf, ``wqkv``
    the three projections' columns joined, the norms and the gate's bias
    drawn, not constants."""
    cfg = get_model_config("tiny-ouro")
    p = ouro.init_params(cfg, jax.random.key(SEED))
    keys = reference.split(reference.seed_key(SEED), 16)
    bf16 = jnp.bfloat16

    def same(mine, theirs):
        return bool(jnp.array_equal(mine.astype(jnp.float32),
                                    theirs.astype(jnp.float32)))

    def mat(key, layer, shape, fan_in):
        return reference._matrix(keys[key], jnp.uint32(layer), shape,
                                 fan_in, bf16)

    from production_stack_tpu.models.llama import fuse_qkv
    assert same(p["layers"]["wqkv"][2], fuse_qkv(
        mat(1, 2, (128, 128), 128), mat(2, 2, (128, 128), 128),
        mat(3, 2, (128, 128), 128), 4))
    assert same(p["layers"]["wo"][1], mat(4, 1, (128, 128), 128))
    assert same(p["layers"]["w_gate"][2], mat(5, 2, (128, 256), 128))
    assert same(p["layers"]["w_up"][0], mat(6, 0, (128, 256), 128))
    assert same(p["layers"]["w_down"][1], mat(7, 1, (256, 128), 256))
    assert same(p["lm_head"], mat(8, 0, (128, 512), 128))
    assert same(p["final_norm"], reference._near_one(
        keys[reference.FINAL_NORM], jnp.uint32(0), 128, "bfloat16"))
    for key, name in zip(reference.NORMS, ouro.NORMS):
        assert same(p["layers"][name][2], reference._near_one(
            keys[key], jnp.uint32(2 * 128), 128, "bfloat16"))
        assert float(jnp.std(p["layers"][name].astype(jnp.float32))) > 0.05
    assert same(p["exit_gate"]["w"], (reference.normal_rows(
        keys[reference.GATE_W], jnp.uint32(0), 128)
        / jnp.sqrt(jnp.float32(128))).astype(bf16))
    assert float(p["exit_gate"]["b"]) == float(
        reference.SPREAD * reference.normal_rows(
            keys[reference.GATE_B], jnp.uint32(0), 1)[0])


# --------------------------------------------------------------------- #
# The passes against the reference
# --------------------------------------------------------------------- #

def _sequences():
    rng = np.random.default_rng(2)
    return [rng.integers(0, 512, n) for n in (61, 45)]


def _padded(sequences):
    tokens = np.zeros((len(sequences), max(map(len, sequences))), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
    return tokens


def _prefill(cfg, params, sequences, width=64):
    """Plain prefill of each sequence into its own blocks: (log-probs [S,
    width, V], the pool's two sides)."""
    S, blocks = len(sequences), width // BS
    layers = page_layers(cfg)
    kv = tuple(jnp.zeros((layers, S * blocks, BS, cfg.num_kv_heads,
                          cfg.head_dim), cfg.jnp_dtype) for _ in range(2))
    tokens = np.zeros((S, width), np.int32)
    slots = np.full((S, width), -1, np.int32)
    lens = np.asarray([len(s) for s in sequences], np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
        slots[i, :len(s)] = i * width + np.arange(len(s))
    positions = np.broadcast_to(np.arange(width), (S, width))
    tables = np.arange(S * blocks, dtype=np.int32).reshape(S, blocks)
    logits, kv, stats = ouro.apply(
        params, cfg, jnp.asarray(tokens), jnp.asarray(positions), kv,
        jnp.asarray(slots), jnp.asarray(tables), jnp.asarray(lens),
        jnp.asarray(lens), mode="prefill", with_stats=True)
    return np.asarray(jax.nn.log_softmax(logits, -1)), kv, np.asarray(stats)


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_prefill_holds_the_reference_at_any_number_of_passes(passes):
    """Log-probabilities of every position, and pass ``u`` of layer ``l``
    in page layer ``l x passes + u`` with the keys and values the
    reference computed in that pass; the forward counts its passes."""
    cfg = get_model_config("tiny-ouro").replace(dtype="float32",
                                                loop_passes=passes)
    hf = {**HF, "total_ut_steps": passes}
    params = ouro.init_params(cfg, jax.random.key(SEED))
    sequences = _sequences()
    lens = [len(s) for s in sequences]
    want, kv_want = reference.forward(
        hf, SEED, _padded(sequences), lens, keep_from=0, dtype="float32",
        kv_layers=tuple(range(3 * passes)))
    logp, kv, stats = _prefill(cfg, params, sequences)
    assert stats.tolist() == [passes]
    assert kv[0].shape[0] == 3 * passes == len(kv_want)
    for row, n in enumerate(lens):
        np.testing.assert_allclose(logp[row, :n], want[row, :n], atol=TOL)
        for page_layer, sides in kv_want.items():
            for mine, theirs in zip(kv, sides):
                got = np.asarray(mine[page_layer]).reshape(
                    2, 64, cfg.num_kv_heads, cfg.head_dim)[row, :n]
                np.testing.assert_allclose(got, theirs[row, :n], atol=TOL)
    if passes > 1:  # a pass's pages are its own, not another's
        assert np.abs(kv_want[0][0] - kv_want[1][0]).max() > 0.1


def test_bf16_fails_the_tolerance():
    cfg = get_model_config("tiny-ouro")
    params = ouro.init_params(cfg, jax.random.key(SEED))
    sequences = _sequences()
    want, _ = reference.forward(HF, SEED, _padded(sequences), [61, 45],
                                keep_from=0)
    logp, _, _ = _prefill(cfg, params, sequences)
    assert np.abs(logp[0, :61].astype(np.float32) - want[0]).max() > 50 * TOL


def test_exit_pdf_against_the_reference():
    """The gate's distribution over the passes and the pass a token
    leaves at, on the reference's own closing states: at the published
    threshold of 1 that is the last pass for every token; under it, the
    first pass whose running sum reaches it."""
    cfg = get_model_config("tiny-ouro").replace(loop_passes=4)
    hf = {**HF, "total_ut_steps": 4}
    params = ouro.init_params(cfg, jax.random.key(SEED))
    tokens = np.asarray([_sequences()[1]], np.int32)
    states, _ = reference.forward(hf, SEED, tokens, [45], keep_from=0,
                                  states=True)
    assert states.shape == (4, 1, 45, 128)
    want, leaves = reference.exit_pdf(hf, SEED, states)
    pdf, exits = ouro.exit_pdf(params["exit_gate"], jnp.asarray(states))
    np.testing.assert_allclose(pdf, want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pdf).sum(axis=0), 1.0, atol=1e-5)
    assert want.min() > 0 and want[1:].max() > 0.3  # the gate is drawn
    assert (np.asarray(exits) == 3).all() and (leaves == 3).all()
    early = {**hf, "early_exit_threshold": 0.6}
    _, leaves = reference.exit_pdf(early, SEED, states)
    _, exits = ouro.exit_pdf(params["exit_gate"], jnp.asarray(states), 0.6)
    np.testing.assert_array_equal(np.asarray(exits), leaves)
    assert len(set(leaves.ravel().tolist())) > 1


def test_the_familys_scopes_are_on_its_programs_operations():
    """Llama's scopes and ``loop_norm`` on the pass's closing norm, and
    one body of the layer however many passes run: a pass more adds no
    operation to the program."""
    def lowered(passes):
        cfg = get_model_config("tiny-ouro").replace(loop_passes=passes)
        params = ouro.init_params(cfg, jax.random.key(0))
        kv = tuple(jnp.zeros((3 * passes, 8, BS, 4, 32), cfg.jnp_dtype)
                   for _ in range(2))
        ints = jnp.zeros((2, 64), jnp.int32)
        return jax.jit(lambda p, kv: ouro.apply(
            p, cfg, ints, ints, kv, ints, jnp.zeros((2, 4), jnp.int32),
            jnp.full((2,), 61), jnp.full((2,), 61), mode="prefill")
        ).lower(params, kv).as_text(debug_info=True)

    text = lowered(2)
    for scope in ("embed", "attn_proj", "attention", "mlp", "loop_norm",
                  "head"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope
    count = lambda t: sum("stablehlo.dot_general" in line  # noqa: E731
                          for line in t.splitlines())
    assert count(text) == count(lowered(4))


# --------------------------------------------------------------------- #
# The engine
# --------------------------------------------------------------------- #

def _engine(**overrides):
    config = dict(
        model="tiny-ouro", max_model_len=256, max_num_seqs=4,
        block_size=BS, num_blocks=96, decode_steps=4, prefill_batch=1,
        prefill_chunk_size=32, dtype="float32")
    config.update(overrides)
    return EngineCore(EngineConfig(**config), devices=jax.devices()[:1])


def _submit(eng, rid, prompt, max_tokens):
    q: "queue.Queue" = queue.Queue()
    eng.add_request(
        rid, list(prompt),
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True, logprobs=3),
        lambda token, finish: q.put((token, finish)))
    return q


def _collect(q):
    """(tokens, [{token: logprob} per position])."""
    tokens, tops = [], []
    while True:
        token, finish = q.get(timeout=300)
        if token is not None:
            tok, lp = token
            tokens.append(int(tok))
            tops.append({int(t): float(v) for t, v in lp["top"]}
                        | {int(tok): float(lp["logprob"])})
        if finish is not None:
            return tokens, tops


def _prompt(n, salt=0):
    return [(7 * i + salt) % 200 + 1 for i in range(n)]


def _holds_the_reference(seed, prompt, tokens, tops, **reference_args):
    """Every log-probability the engine reported (logits, not tokens: the
    sampled token's and the top 3) against the reference's full forward
    over prompt and answer. Returns the reference's keys and values."""
    both = np.asarray([list(prompt) + tokens], np.int32)
    logp, kv = reference.forward(
        HF, seed, both, [both.shape[1]], keep_from=len(prompt) - 1,
        dtype="float32", kv_layers=tuple(range(6)), **reference_args)
    for j, entries in enumerate(tops):
        for tok, lp in entries.items():
            assert lp == pytest.approx(float(logp[0, j, tok]), abs=TOL)
    return kv


def test_the_engine_holds_the_reference_through_a_prefix_hit():
    """Chunked prefill (75 tokens in chunks of 32: the cached path),
    burst decode, then a second prompt that shares the first one's
    64-token prefix and finds it cached. The reported log-probabilities
    are the reference's; ``extract_kv`` gives ``[blocks, 6 page layers,
    ...]`` with each pass's keys and values under ``l x 2 + u``; the
    step records count two passes a forward; the recorder's weight bytes
    a forward count the stack twice."""
    eng = _engine(min_prefill_bucket=16)
    eng.start()
    try:
        first = _prompt(75)
        got = _collect(_submit(eng, "a", first, 6))
        kv = _holds_the_reference(eng.config.seed, first, *got)
        cached_before = eng.cached_tokens_total
        second = first[:64] + _prompt(9, salt=9)
        hit = _collect(_submit(eng, "b", second, 6))
        assert eng.cached_tokens_total - cached_before == 64
        _holds_the_reference(eng.config.seed, second, *hit)
        held = eng.extract_kv(first)
        n = held["num_tokens"]
        assert n == 64 and np.asarray(held["k"]).shape == (4, 6, BS, 4, 32)
        for page_layer, sides in kv.items():
            for side, theirs in zip("kv", sides):
                mine = np.asarray(held[side])[:, page_layer].reshape(
                    -1, 4, 32)[:n]
                np.testing.assert_allclose(mine, theirs[0, :n], atol=TOL)
        # another engine takes the blocks over the KV-transfer surface
        # ([page layers, blocks, ...]) and serves the prompt from them
        other = _engine()
        other.start()
        try:
            k, v = (np.swapaxes(np.asarray(held[side]), 0, 1)
                    for side in "kv")
            assert other.inject_kv_blocks(held["hashes"], k, v) == 4
            moved = _collect(_submit(other, "c", first, 6))
            assert other.cached_tokens_total == 64
            _holds_the_reference(other.config.seed, first, *moved)
        finally:
            other.stop()
        stats = eng.stats()
        # six page layers x two sides of 4 x 32 float32, in 8 x 128 tiles
        assert stats["kv_cache_bytes_per_token"] == 6 * 2 * 8 * 128 * 4
        records = eng.step_recorder.snapshot()
        bursts = [s for s in records if s.get("stats_forwards")]
        assert bursts and all(
            s["loop_passes"] == 2 * s["stats_forwards"] for s in bursts)
        assert eng._steps.param_bytes == forward_weight_bytes(
            eng.model_config, eng.params)
        tree = sum(leaf.nbytes
                   for leaf in jax.tree_util.tree_leaves(eng.params))
        assert tree < eng._steps.param_bytes < 2 * tree
    finally:
        eng.stop()


def test_a_preempted_row_holds_the_reference():
    """A pool too small for three long answers at once (as the published
    sizes' pool of ~78 blocks is never far from): a sequence is
    preempted and recomputed through every pass's pages, and every row's
    log-probabilities are still the reference's."""
    eng = _engine(num_blocks=14, max_num_seqs=3)
    eng.start()
    try:
        prompts = {rid: _prompt(40, salt)
                   for rid, salt in (("x", 1), ("y", 2), ("z", 5))}
        queues = {rid: _submit(eng, rid, p, 40) for rid, p in prompts.items()}
        results = {rid: _collect(q) for rid, q in queues.items()}
        assert eng.stats()["num_preempted_total"] >= 1
        for rid, (tokens, tops) in results.items():
            assert len(tokens) == 40
            _holds_the_reference(eng.config.seed, prompts[rid], tokens, tops)
    finally:
        eng.stop()


CARRIED = [
    ("speculation", {"speculative_num_tokens": 2}, {}),
    ("a draft model of the family", {
        "speculative_draft_model": "tiny-ouro",
        "speculative_num_tokens": 2}, {}),
    ("int8 weights", {"quantization": "int8"}, {"quantization": "int8"}),
    ("two devices", {"tensor_parallel_size": 2}, {}),
]


@pytest.mark.parametrize("flags, reference_args",
                         [case[1:] for case in CARRIED],
                         ids=[case[0] for case in CARRIED])
def test_the_surfaces_behind_the_pool_carry_every_pass(flags, reference_args):
    """What moves whole blocks or runs the family's own forward carries a
    page layer for every pass: the answers stay the reference's (int8
    weights against the reference's own int8 matrices)."""
    devices = 2 if "tensor_parallel_size" in flags else 1
    eng = EngineCore(EngineConfig(
        model="tiny-ouro", max_model_len=256, max_num_seqs=4, block_size=BS,
        num_blocks=96, decode_steps=4, prefill_chunk_size=32,
        dtype="float32", **flags), devices=jax.devices()[:devices])
    eng.start()
    try:
        prompt = _prompt(75)
        got = _collect(_submit(eng, "a", prompt, 6))
        _holds_the_reference(eng.config.seed, prompt, *got, **reference_args)
        if "speculative_draft_model" in flags:
            assert eng._draft.kv[0].shape[0] == 6  # a page layer a pass
    finally:
        eng.stop()


def test_blocks_come_back_from_host_offload_with_every_pass():
    """A pool of twelve blocks: two other prompts push the first one's
    cached blocks to host memory, and its repeat restores them, all six
    page layers of each, and reads the reference's log-probabilities."""
    eng = _engine(kv_offload_bytes=1 << 24, num_blocks=12, max_num_seqs=1)
    eng.start()
    try:
        first = _prompt(75)
        _collect(_submit(eng, "a", first, 6))
        for rid, salt in (("b", 3), ("c", 11)):
            _collect(_submit(eng, rid, _prompt(90, salt), 6))
        assert eng.offload.stats()["stored"] >= 4
        again = _collect(_submit(eng, "d", first, 6))
        assert eng.offload.stats()["hits"] >= 4
        _holds_the_reference(eng.config.seed, first, *again)
    finally:
        eng.stop()


def test_int8_pages_hold_every_pass_within_their_format():
    eng = _engine(kv_cache_dtype="int8")
    eng.start()
    try:
        prompt = _prompt(75)
        tokens, tops = _collect(_submit(eng, "a", prompt, 6))
        both = np.asarray([prompt + tokens], np.int32)
        logp, _ = reference.forward(HF, eng.config.seed, both, [81],
                                    keep_from=74, dtype="float32")
        worst = max(abs(lp - float(logp[0, j, tok]))
                    for j, entries in enumerate(tops)
                    for tok, lp in entries.items())
        assert TOL < worst < 0.1
    finally:
        eng.stop()


def test_pipeline_stages_are_refused_by_name():
    with pytest.raises(ValueError, match="pipeline_parallel_size"):
        EngineCore(EngineConfig(
            model="tiny-ouro", max_model_len=128, block_size=BS,
            num_blocks=32, dtype="float32", pipeline_parallel_size=2),
            devices=jax.devices()[:2])
