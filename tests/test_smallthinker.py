"""The smallthinker family (PR 51; PowerInfer/SmallThinker-21BA3B-Instruct):
every layer alike but for two switches read separately (a window, a
rotation: the published layout has NoPE full layers and rotary window
layers), and ReGLU experts on the post-attention stream under the weights
a router read from the layer's input, before attention.

The program (``models/smallthinker.py`` through ``decoder.scan_layers``,
``decoder.by_layer``, ``decoder.attend``, ``moe.route`` and
``moe.expert_layer(routed=...)``) is held to the plain reference
(``chipbench/reference/smallthinker.py``: float32, whole sequences, no
cache, every expert dense) on seeded random weights at the tiny size, in
float32, at contexts that pass the tiny window of 24. Tolerance: 2e-4
absolute on log-probabilities and on the cached keys and values, which
float32 accumulation order accounts for (the readings are ~6e-6) and
which each neighbouring mechanism (router on the post-attention stream,
``silu``, a rotation on the NoPE layers, no window) and bf16 in place of
the test dtype fail by one to three orders of magnitude
(``test_a_neighbouring_mechanism_fails_the_tolerance``).
"""

import asyncio
import json
import os
import queue
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import (
    get_model_config,
    llama,
    moe,
    smallthinker,
)
from production_stack_tpu.models.config import (
    FULL_ATTENTION,
    NOPE_FULL_ATTENTION,
    NOPE_SLIDING_ATTENTION,
    SLIDING_ATTENTION,
)
from production_stack_tpu.models.registry import (
    arch_of_model_type,
    get_family,
    page_layers,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench.reference import smallthinker as reference  # noqa: E402

SEED = 13
TOL = 2e-4
BS = 16
WINDOW = 24

with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny_smallthinker_config.json")) as _f:
    HF = json.load(_f)


def _model_dir(tmp_path, **changes):
    path = tmp_path / "model"
    path.mkdir(exist_ok=True)
    (path / "config.json").write_text(json.dumps({**HF, **changes}))
    return str(path)


# --------------------------------------------------------------------- #
# The config reader and the record
# --------------------------------------------------------------------- #

def test_config_json_reads_as_the_preset(tmp_path):
    assert arch_of_model_type("smallthinker") == "smallthinker"
    read = get_model_config(_model_dir(tmp_path))
    preset = get_model_config("tiny-smallthinker")
    assert read.replace(name=preset.name) == preset
    assert read.layer_types == (NOPE_FULL_ATTENTION, SLIDING_ATTENTION) * 2
    assert read.rope_of(NOPE_FULL_ATTENTION) is None
    assert read.rope_of(SLIDING_ATTENTION).rope_theta == 1500000.0
    assert read.window_of(SLIDING_ATTENTION) == WINDOW
    assert read.window_of(NOPE_FULL_ATTENTION) is None


def test_the_two_layouts_are_read_separately(tmp_path):
    """A window without a rotation, a rotation without a window: each of
    the four pairs is a kind the program runs, none is refused."""
    cfg = get_model_config(_model_dir(
        tmp_path, rope_layout=[0, 0, 1, 1, 0, 0],
        sliding_window_layout=[0, 1, 0, 1, 0, 0]))
    assert cfg.layer_types == (NOPE_FULL_ATTENTION, NOPE_SLIDING_ATTENTION,
                               FULL_ATTENTION, SLIDING_ATTENTION)
    assert cfg.window_of(NOPE_SLIDING_ATTENTION) == WINDOW
    assert cfg.rope_of(NOPE_SLIDING_ATTENTION) is None
    assert cfg.rope_of(FULL_ATTENTION).rope_theta == 1500000.0
    hf = {**HF, "rope_layout": [0, 0, 1, 1, 0, 0],
          "sliding_window_layout": [0, 1, 0, 1, 0, 0]}
    cfg = cfg.replace(dtype="float32")
    params = smallthinker.init_params(cfg, jax.random.key(SEED))
    sequences = _sequences()
    want, kv_want = reference.forward(
        hf, SEED, _padded(sequences), [61, 45], keep_from=0,
        dtype="float32", kv_layers=(0, 1, 2, 3))
    logp, kv, _ = _prefill(cfg, params, sequences)
    _same(cfg, logp, kv, want, kv_want, [61, 45])


@pytest.mark.parametrize("changes, named", [
    ({"moe_primary_router_apply_softmax": False},
     "moe_primary_router_apply_softmax"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tied head"),
    ({"moe_num_secondary_experts": 8}, "secondary"),
    ({"sliding_window_size": 0}, "sliding_window_size"),
    ({"rope_layout": [0, 2, 0, 1]}, "0 or 1"),
    ({"rope_layout": [0, 1]}, "rope_layout")], ids=lambda c: str(c)[:40])
def test_a_config_the_family_does_not_serve_is_refused(tmp_path, changes,
                                                       named):
    with pytest.raises(ValueError, match=named):
        get_model_config(_model_dir(tmp_path, **changes))


def test_the_record_says_what_the_family_is():
    family = get_family("smallthinker")
    assert family.stats == moe.STATS
    assert family.per_layer_keys == ("sliding_window_layout", "rope_layout")
    assert family.quant_keys == () and not family.lora
    assert not family.pipeline and family.load is None
    assert family.loop is smallthinker.run_layers and family.layer is None
    cfg = get_model_config("tiny-smallthinker")
    assert page_layers(cfg) == 4
    assert smallthinker.ACTIVATION == "relu"


@pytest.mark.parametrize("flags, named", [
    ({"quantization": "int8"}, "int8 quantization"),
    ({"pipeline_parallel_size": 2}, "pipeline_parallel_size")],
    ids=["int8 weights", "pipeline stages"])
def test_what_the_family_does_not_take_is_refused_at_start_up(flags, named):
    with pytest.raises(ValueError, match=named):
        EngineCore(EngineConfig(
            model="tiny-smallthinker", max_model_len=128, block_size=BS,
            num_blocks=32, dtype="float32", **flags),
            devices=jax.devices()[:2])


def test_lora_slots_are_not_built_for_the_family():
    """``--max-loras`` asks for slots only of a family that has them
    (``Family.lora``): the tree has none."""
    eng = EngineCore(EngineConfig(
        model="tiny-smallthinker", max_model_len=128, block_size=BS,
        num_blocks=32, dtype="float32", max_loras=2),
        devices=jax.devices()[:1])
    assert "lora" not in eng.params


# --------------------------------------------------------------------- #
# Prefill against the reference
# --------------------------------------------------------------------- #

def _sequences():
    rng = np.random.default_rng(2)
    return [rng.integers(0, 512, n) for n in (61, 45)]


def _padded(sequences):
    tokens = np.zeros((len(sequences), max(map(len, sequences))), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
    return tokens


def _prefill(cfg, params, sequences, width=64, apply=None):
    """Plain prefill of each sequence into its own blocks: (log-probs [S,
    width, V], the pool's two sides, the expert layers' counts)."""
    S, blocks = len(sequences), width // BS
    kv = tuple(jnp.zeros((cfg.num_layers, S * blocks, BS, cfg.num_kv_heads,
                          cfg.head_dim), cfg.jnp_dtype) for _ in range(2))
    tokens = np.zeros((S, width), np.int32)
    slots = np.full((S, width), -1, np.int32)
    lens = np.asarray([len(s) for s in sequences], np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
        slots[i, :len(s)] = i * width + np.arange(len(s))
    positions = np.broadcast_to(np.arange(width), (S, width))
    tables = np.arange(S * blocks, dtype=np.int32).reshape(S, blocks)
    logits, kv, stats = (apply or smallthinker.apply)(
        params, cfg, jnp.asarray(tokens), jnp.asarray(positions), kv,
        jnp.asarray(slots), jnp.asarray(tables), jnp.asarray(lens),
        jnp.asarray(lens), mode="prefill", with_stats=True)
    return np.asarray(jax.nn.log_softmax(logits, -1)), kv, np.asarray(stats)


def _worst(cfg, logp, kv, want, kv_want, lens, width=64):
    """(largest log-probability error, largest page error) over rows."""
    err_logp = err_kv = 0.0
    for row, n in enumerate(lens):
        err_logp = max(err_logp, np.abs(logp[row, :n] - want[row, :n]).max())
        for layer, sides in kv_want.items():
            for mine, theirs in zip(kv, sides):
                got = np.asarray(mine[layer]).reshape(
                    len(lens), width, cfg.num_kv_heads, cfg.head_dim)[row, :n]
                err_kv = max(err_kv, np.abs(got - theirs[row, :n]).max())
    return float(err_logp), float(err_kv)


def _same(cfg, logp, kv, want, kv_want, lens):
    err_logp, err_kv = _worst(cfg, logp, kv, want, kv_want, lens)
    assert err_logp < TOL and err_kv < TOL, (err_logp, err_kv)


@pytest.fixture(scope="module")
def sound():
    """(cfg, params, sequences, the reference's log-probabilities and
    every layer's keys and values) at the tiny size, float32."""
    cfg = get_model_config("tiny-smallthinker").replace(dtype="float32")
    params = smallthinker.init_params(cfg, jax.random.key(SEED))
    sequences = _sequences()
    want, kv_want = reference.forward(
        HF, SEED, _padded(sequences), [61, 45], keep_from=0,
        dtype="float32", kv_layers=(0, 1, 2, 3))
    return cfg, params, sequences, want, kv_want


def test_prefill_holds_the_reference_past_the_window(sound):
    """Log-probabilities of every position of two sequences of 61 and 45
    tokens (window 24), every layer's pages of both kinds, and the expert
    layers' counts: 106 tokens x top 3 x 4 layers, no idle layer."""
    cfg, params, sequences, want, kv_want = sound
    logp, kv, stats = _prefill(cfg, params, sequences)
    _same(cfg, logp, kv, want, kv_want, [61, 45])
    assert stats.tolist()[0] == 106 * 3 * 4 and stats.tolist()[3] == 0
    # the NoPE layers' keys are position-free: what a token's key is does
    # not depend on where it stands (a rotary layer's does)
    moved, kv_moved = reference.forward(
        HF, SEED, _padded([sequences[0][1:]]), [60], keep_from=0,
        dtype="float32", kv_layers=(0, 1))
    np.testing.assert_allclose(kv_moved[0][0][0, :59], kv_want[0][0][0, 1:60],
                               atol=1e-5)
    assert np.abs(kv_moved[1][0][0, :59] - kv_want[1][0][0, 1:60]).max() > 0.1


def _router_on_m(params):
    """``moe.expert_layer`` as the neighbouring model would call it: the
    routing recomputed from the tensor the experts compute on (the
    post-attention ``m``), what the caller handed dropped."""
    inner = moe.expert_layer

    def moved(h, p, *, at, k, routed, **rest):
        router = jax.lax.dynamic_index_in_dim(
            params["layers"]["router"], at, 0, keepdims=False)
        again = moe.route(h.reshape(-1, h.shape[-1]), router, k,
                          scoring="softmax", renormalise=True)
        return inner(h, p, at=at, k=k, routed=again, **rest)

    return moved


NEIGHBOURS = ["router on m", "silu", "rotation on the NoPE layers",
              "no window", "bf16"]


@pytest.mark.parametrize("neighbour", NEIGHBOURS)
def test_a_neighbouring_mechanism_fails_the_tolerance(sound, monkeypatch,
                                                      neighbour):
    """Each mechanism swapped for its neighbour in the PROGRAM reads far
    outside the tolerance the sound program keeps: what the equality
    above holds is each of them."""
    cfg, params, sequences, want, kv_want = sound
    if neighbour == "router on m":
        monkeypatch.setattr(smallthinker.moe, "expert_layer",
                            _router_on_m(params))
    elif neighbour == "silu":
        monkeypatch.setattr(smallthinker, "ACTIVATION", "silu")
    elif neighbour == "rotation on the NoPE layers":
        cfg = cfg.replace(rope_by_kind=())  # every kind rotates
    elif neighbour == "no window":
        cfg = cfg.replace(sliding_window=0)
    else:
        cfg = cfg.replace(dtype="bfloat16")
        params = smallthinker.init_params(cfg, jax.random.key(SEED))
    # a fresh trace: the patched module is read at trace time
    logp, kv, _ = _prefill(cfg, params, sequences,
                           apply=lambda *a, **k: smallthinker.apply(*a, **k))
    err_logp, err_kv = _worst(cfg, logp.astype(np.float32),
                              [np.asarray(s, np.float32) for s in kv], want,
                              kv_want, [61, 45])
    assert err_logp > 10 * TOL, (neighbour, err_logp)
    if neighbour == "rotation on the NoPE layers":
        assert err_kv > 0.1  # layer 0's keys, as the pages hold them
        # its values are untouched: only the keys are rotated
        got = np.asarray(kv[1][0]).reshape(2, 64, 2, 32)[0, :61]
        np.testing.assert_allclose(got, kv_want[0][1][0, :61], atol=TOL)


def test_the_router_reads_the_layers_input_before_attention(monkeypatch):
    """Run eagerly (no jit: the scan steps through concrete values) on one
    NoPE layer: what ``moe.route`` is handed is ``RMS(embedding)``, the
    layer's normed input, exactly, and the experts compute on another
    tensor (``RMS(x + attention)``) under those weights."""
    cfg = get_model_config("tiny-smallthinker").replace(
        dtype="float32", num_layers=1,
        layer_types=(NOPE_FULL_ATTENTION,))
    params = smallthinker.init_params(cfg, jax.random.key(SEED))
    seen = {}
    route, experts = moe.route, moe.expert_layer

    def watched_route(h, router, k, **kwargs):
        seen["router_input"] = np.asarray(h)
        seen["routed"] = route(h, router, k, **kwargs)
        return seen["routed"]

    def watched_experts(h, p, **kwargs):
        seen["expert_input"] = np.asarray(h)
        seen["handed"] = kwargs["routed"]
        return experts(h, p, **kwargs)

    monkeypatch.setattr(smallthinker.moe, "route", watched_route)
    monkeypatch.setattr(smallthinker.moe, "expert_layer", watched_experts)
    tokens = _sequences()[1][:12]
    with jax.disable_jit():
        _prefill(cfg, params, [tokens], width=16)
    embedded = np.asarray(params["embed"])[np.pad(tokens, (0, 4))]
    want = np.asarray(llama.rms_norm(jnp.asarray(embedded),
                                     jnp.ones((128,)), cfg.rms_norm_eps))
    np.testing.assert_allclose(seen["router_input"][:12], want[:12],
                               atol=1e-6)
    assert seen["handed"] is seen["routed"]
    assert np.abs(seen["expert_input"][0, :12] - want[:12]).max() > 0.05


def test_the_familys_scopes_are_on_its_programs_operations():
    """``moe_router`` lies ahead of ``attention`` in the layer body and
    ``moe_experts`` behind it; no logistic is left in a ReGLU program
    (``silu`` would bring one); one body of the layer whatever the depth."""
    def lowered(layers):
        cfg = get_model_config("tiny-smallthinker").replace(
            num_layers=layers,
            layer_types=(NOPE_FULL_ATTENTION, SLIDING_ATTENTION)
            * (layers // 2))
        params = smallthinker.init_params(cfg, jax.random.key(0))
        kv = tuple(jnp.zeros((layers, 8, BS, 2, 32), cfg.jnp_dtype)
                   for _ in range(2))
        ints = jnp.zeros((2, 64), jnp.int32)
        return jax.jit(lambda p, kv: smallthinker.apply(
            p, cfg, ints, ints, kv, ints, jnp.zeros((2, 4), jnp.int32),
            jnp.full((2,), 61), jnp.full((2,), 61), mode="prefill")
        ).lower(params, kv).as_text(debug_info=True)

    text = lowered(4)
    for scope in ("embed", "attn_proj", "moe_router", "attention", "mlp",
                  "moe_experts", "head"):
        assert f"{scope}/" in text or f"/{scope}" in text, scope
    # the order of the layer body's equations, by their name stacks
    cfg = get_model_config("tiny-smallthinker")
    params = smallthinker.init_params(cfg, jax.random.key(0))
    kv = tuple(jnp.zeros((4, 8, BS, 2, 32), cfg.jnp_dtype) for _ in range(2))
    ints = jnp.zeros((2, 64), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, kv: smallthinker.apply(
        p, cfg, ints, ints, kv, ints, jnp.zeros((2, 4), jnp.int32),
        jnp.full((2,), 61), jnp.full((2,), 61), mode="prefill"))(params, kv)
    body = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"
                ).params["jaxpr"].jaxpr
    stacks = [str(e.source_info.name_stack) for e in body.eqns]
    last_router = max(i for i, stack in enumerate(stacks)
                      if "moe_router" in stack.split("/"))
    first_experts = next(i for i, stack in enumerate(stacks)
                         if "moe_experts" in stack.split("/"))
    # the conditional over the two kinds holds the attention
    cond = next(i for i, e in enumerate(body.eqns)
                if e.primitive.name == "cond")
    branch = str(body.eqns[cond].params["branches"][0].jaxpr)
    assert "attention" in "".join(
        str(e.source_info.name_stack)
        for e in body.eqns[cond].params["branches"][0].jaxpr.eqns), branch
    assert last_router < cond < first_experts
    assert "stablehlo.logistic" not in text
    count = lambda t: sum("stablehlo.dot_general" in line  # noqa: E731
                          for line in t.splitlines())
    assert count(text) == count(lowered(2))


# --------------------------------------------------------------------- #
# The engine
# --------------------------------------------------------------------- #

def _engine(**overrides):
    config = dict(
        model="tiny-smallthinker", max_model_len=256, max_num_seqs=4,
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


def _holds_the_reference(seed, prompt, tokens, tops):
    """Every log-probability the engine reported (logits, not tokens: the
    sampled token's and the top 3) against the reference's full forward
    over prompt and answer. Returns the reference's keys and values of a
    NoPE full layer (0) and a rotary window layer (1)."""
    both = np.asarray([list(prompt) + tokens], np.int32)
    logp, kv = reference.forward(
        HF, seed, both, [both.shape[1]], keep_from=len(prompt) - 1,
        dtype="float32", kv_layers=(0, 1))
    for j, entries in enumerate(tops):
        for tok, lp in entries.items():
            assert lp == pytest.approx(float(logp[0, j, tok]), abs=TOL)
    return kv


def test_the_engine_holds_the_reference_through_a_prefix_hit_past_the_window():
    """Chunked prefill across the window's edge (75 tokens in chunks of
    32, window 24: the second and third chunks' window layers reach back
    into pages the first wrote, and not to their start), burst decode,
    then a second prompt that shares the first one's 64-token prefix (a
    history longer than the window) and finds it cached. The reported
    log-probabilities are the reference's; ``extract_kv`` gives both
    kinds' pages, the NoPE layer's keys unrotated; the step records carry
    the expert layers' counts and the dead tokens behind the window."""
    eng = _engine(min_prefill_bucket=16)
    eng.start()
    try:
        first = _prompt(75)
        got = _collect(_submit(eng, "a", first, 6))
        kv = _holds_the_reference(eng.config.seed, first, *got)
        cached_before = eng.cached_tokens_total
        second = first[:64] + _prompt(9, salt=9)
        hit = _collect(_submit(eng, "b", second, 6))
        assert eng.cached_tokens_total - cached_before == 64 > WINDOW
        _holds_the_reference(eng.config.seed, second, *hit)
        held = eng.extract_kv(first)
        n = held["num_tokens"]
        assert n == 64 and np.asarray(held["k"]).shape == (4, 4, BS, 2, 32)
        for layer, sides in kv.items():
            for side, theirs in zip("kv", sides):
                mine = np.asarray(held[side])[:, layer].reshape(
                    -1, 2, 32)[:n]
                np.testing.assert_allclose(mine, theirs[0, :n], atol=TOL)
        records = eng.step_recorder.snapshot()
        bursts = [s for s in records if s.get("stats_forwards")]
        assert bursts and all(
            s["moe_assignments"] <= 4 * 3 * 4 * s["stats_forwards"]
            for s in bursts)
        dead = [s["kv_window_dead_tokens"] for s in records
                if s["kind"] == "decode_burst"]
        # one row a burst: the first burst of the first request starts
        # at context 76 (the prompt and its first token), 52 tokens past
        # the window in 2 of the 4 page layers
        assert round((76 - WINDOW) * 2 / 4) in dead and min(dead) > 0
        assert eng.stats()["kv_window_dead_tokens"] == 0  # nothing runs
    finally:
        eng.stop()


def test_a_window_binds_for_one_row_of_a_decode_batch_and_not_for_another():
    """Two rows decode together: one whose whole context stays inside the
    window (10 + 8 < 24), one far past it (70). Each reads the reference;
    while they run the gauge counts the long row's dead tokens alone."""
    eng = _engine(decode_steps=2)
    eng.start()
    try:
        prompts = {"short": _prompt(10, salt=3), "long": _prompt(70, salt=5)}
        queues = {rid: _submit(eng, rid, p, 8) for rid, p in prompts.items()}
        results = {rid: _collect(q) for rid, q in queues.items()}
        for rid, (tokens, tops) in results.items():
            assert len(tokens) == 8
            _holds_the_reference(eng.config.seed, prompts[rid], tokens, tops)
        together = [s for s in eng.step_recorder.snapshot()
                    if s["kind"] == "decode_burst" and s["rows"] == 2]
        assert together
        for s in together:  # the short row adds nothing: max(0, ctx - 24)
            assert round((71 - WINDOW) / 2) <= s[
                "kv_window_dead_tokens"] <= round((79 - WINDOW) / 2)
    finally:
        eng.stop()


def test_a_preempted_row_holds_the_reference():
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


def test_int8_pages_hold_both_kinds_within_their_format():
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


def test_the_gauge_is_on_metrics_and_zero_for_a_model_without_a_window():
    """``tpu:kv_window_dead_tokens`` beside ``tpu:hbm_kv_usage_perc``; a
    model without a window counts nothing, on its records or its gauge."""
    from production_stack_tpu.engine.server import EngineServer

    server = EngineServer(EngineConfig(
        model="tiny-smallthinker", max_model_len=128, max_num_seqs=2,
        block_size=BS, num_blocks=32, dtype="float32"))
    text = asyncio.run(server.handle_metrics(None)).text
    lines = text.splitlines()
    at = next(i for i, line in enumerate(lines)
              if line.startswith("tpu:hbm_kv_usage_perc"))
    assert lines[at + 1] == "# TYPE tpu:kv_window_dead_tokens gauge"
    assert lines[at + 2].startswith("tpu:kv_window_dead_tokens{") and \
        lines[at + 2].endswith(" 0")
    assert server.core._window_layer_share == 0.5
    assert server.core._window_dead_tokens([10, 30, 100]) == round(
        (6 + 76) * 0.5)
    plain = EngineCore(EngineConfig(
        model="tiny-llama", max_model_len=128, block_size=BS, num_blocks=32),
        devices=jax.devices()[:1])
    assert plain._window_layer_share == 0
    assert plain._window_dead_tokens([500]) == 0
    assert plain.stats()["kv_window_dead_tokens"] == 0
