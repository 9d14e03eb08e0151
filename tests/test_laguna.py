"""The Laguna family (models/laguna.py) against its plain reference
(chipbench/reference/laguna.py) at a tiny size with everything the
published model has: both layer kinds with their own head counts and
rotary blocks, a window shorter than the contexts, a dense first layer, a
shared expert, the second of two shares of the experts. CPU, float32,
seeded random weights."""

import functools
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
from production_stack_tpu.models import build_model, get_model_config, moe
from production_stack_tpu.models import laguna
from production_stack_tpu.models.registry import arch_of_model_type

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench.reference import laguna as reference  # noqa: E402

BS = 8
SEED = 5

# tiny-laguna as a config.json: per-layer lists at their "published"
# length (12), 6 layers held.
with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny_laguna_config.json")) as _f:
    HF = json.load(_f)


def _model_dir(tmp_path, **changes):
    body = {k: v for k, v in {**HF, **changes}.items() if v is not None}
    path = tmp_path / "model"
    path.mkdir(exist_ok=True)
    (path / "config.json").write_text(json.dumps(body))
    return str(path)


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("tiny-laguna").replace(dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return build_model(cfg)[0](cfg, jax.random.key(SEED))


@functools.lru_cache(maxsize=None)
def _jitted_apply(cfg):
    _, apply = build_model(cfg)
    return jax.jit(
        lambda params, *args, mode: apply(params, cfg, *args, mode=mode),
        static_argnames=("mode",))


def _serve(cfg, params, tokens, *, prefill: int, cached: int):
    """One sequence through the three modes as the engine runs them:
    ``prefill`` tokens uncached, the next ``cached`` as a cached prefill
    over the pages, the rest one decode step each. Returns the
    log-probabilities after every position, and the pages."""
    apply = _jitted_apply(cfg)
    T = len(tokens)
    L, NB = cfg.num_layers, 2 * (T // BS + 1)
    kv = tuple(jnp.zeros((L, NB, BS, cfg.num_kv_heads, cfg.head_dim))
               for _ in range(2))
    table = np.random.default_rng(0).permutation(NB)[:T // BS + 1]
    slot = lambda pos: table[pos // BS] * BS + pos % BS  # noqa: E731
    bt = jnp.asarray(table[None], jnp.int32)
    tokens = np.asarray(tokens, np.int32)
    out = []

    def span(mode, lo, hi):
        nonlocal kv
        pos = np.arange(lo, hi)
        logits, kv = apply(
            params, tokens[None, lo:hi], pos[None], kv,
            slot(pos)[None], bt, jnp.asarray([hi], jnp.int32),
            jnp.asarray([hi - lo], jnp.int32), mode=mode)
        out.append(np.asarray(jax.nn.log_softmax(logits[0], -1)))

    span("prefill", 0, prefill)
    span("prefill_cached", prefill, prefill + cached)
    for t in range(prefill + cached, T):
        span("decode", t, t + 1)
    pages = [np.asarray(side).reshape(L, NB * BS, *side.shape[3:])[
        :, slot(np.arange(T))] for side in kv]
    return np.concatenate(out), pages


@pytest.fixture(scope="module")
def sequence():
    return np.random.default_rng(1).integers(0, 512, 72)


@pytest.fixture(scope="module")
def wanted(sequence):
    return reference.forward(
        HF, SEED, sequence[None], [len(sequence)], keep_from=0,
        dtype="float32", kv_layers=(0, 1, 5))


def test_config_json_reads_as_the_preset(tmp_path, cfg):
    read = get_model_config(_model_dir(tmp_path))
    assert read.replace(name=cfg.name, dtype="float32",
                        rope_theta=cfg.rope_theta) == cfg
    assert arch_of_model_type("laguna") == "laguna"
    # 12-entry lists, 6 layers held: the first six entries are read
    assert read.layer_types == tuple(HF["layer_types"][:6])
    assert read.heads_per_layer == (4, 6, 6, 4, 6, 6)
    assert (read.published_experts, read.num_experts) == (8, 4)


@pytest.mark.parametrize("key", [
    "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"])
def test_a_laguna_file_without_its_per_layer_lists_is_refused(tmp_path, key):
    with pytest.raises(ValueError, match=key):
        get_model_config(_model_dir(tmp_path, **{key: None}))
    with pytest.raises(ValueError, match=key):  # shorter than the layers
        get_model_config(_model_dir(tmp_path, **{key: HF[key][:5]}))


def test_program_matches_reference_through_the_three_modes(
        cfg, params, sequence, wanted):
    """Prefill 40, cached prefill 16, decode 16: every context from 24
    on passes the window of the sliding layers."""
    logp, pages = _serve(cfg, params, sequence, prefill=40, cached=16)
    want, kept = wanted
    np.testing.assert_allclose(logp, want[0], atol=2e-4)
    for layer, (k, v) in kept.items():
        np.testing.assert_allclose(pages[0][layer], k[0], atol=2e-5)
        np.testing.assert_allclose(pages[1][layer], v[0], atol=2e-5)


@pytest.mark.parametrize("prefill,cached", [(64, 8), (8, 48)])
def test_the_modes_agree_with_each_other(cfg, params, sequence, wanted,
                                         prefill, cached):
    logp, _ = _serve(cfg, params, sequence, prefill=prefill, cached=cached)
    np.testing.assert_allclose(logp, wanted[0][0], atol=2e-4)


def test_a_window_layer_that_ignores_its_window_fails(cfg, params, sequence,
                                                      wanted):
    logp, _ = _serve(cfg.replace(sliding_window=0), params, sequence,
                     prefill=40, cached=16)
    diff = np.abs(logp - wanted[0][0]).max(axis=-1)
    assert diff[:24].max() < 2e-4  # within the window nothing differs
    assert diff[40:].min() > 1e-3  # past it every position does


def test_the_shares_add_up_to_the_uncut_layer():
    """One sparse layer: the routed parts of the four shares, each from
    its own call of the program's expert layer, plus the shared expert
    once, are the uncut reference's layer (all 8 experts held)."""
    hidden, width, experts, top_k, held = 128, 64, 8, 3, 2
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 9, hidden)), jnp.float32)
    keys = reference.split(reference.seed_key(SEED), 24)
    mat = lambda i, at, shape, fan: reference._stacked(  # noqa: E731
        keys[i], at, shape, fan, jnp.float32)
    router = mat(15, 0, (hidden, experts), hidden)
    stack = lambda i, shape, fan: jnp.stack(  # noqa: E731
        [mat(i, e, shape, fan) for e in range(experts)])
    w = {"w_gate": stack(16, (hidden, width), hidden),
         "w_up": stack(17, (hidden, width), hidden),
         "w_down": stack(18, (width, hidden), width)}
    h = reference.rms_norm(x, 1e-6)
    total = x + moe.swiglu(h, mat(19, 0, (hidden, width), hidden),
                           mat(20, 0, (hidden, width), hidden),
                           mat(21, 0, (width, hidden), width))
    hit = 0
    for share in range(experts // held):
        mine = {k: v[None, share * held:(share + 1) * held]
                for k, v in w.items()}
        routed, stats = moe.expert_layer(
            h, {"router": router, **mine}, k=top_k, at=0, share=share,
            scaling=2.5)
        total = total + routed
        hit += int(stats[0])
    assert hit == 2 * 9 * top_k  # every assignment landed on one share
    want = reference._sparse_mlp(
        keys, x, at=0, dims=(hidden, width, width, experts, experts, 0,
                             top_k, 2.5, 1e-6, "float32"),
        activations=None)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


def test_stacked_leaves_and_a_layer_index_give_the_layers_own_experts():
    """``at``: the stacks go to the grouped matmul whole, and only the
    layer's block of groups has rows."""
    rng = np.random.default_rng(4)
    layers, held, hidden, width = 3, 4, 32, 16
    h = jnp.asarray(rng.normal(size=(1, 11, hidden)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(hidden, 8)), jnp.float32)
    stacks = {
        "w_gate": jnp.asarray(rng.normal(size=(layers, held, hidden, width)),
                              jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(layers, held, hidden, width)),
                            jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(layers, held, width, hidden)),
                              jnp.float32)}
    valid = jnp.arange(11)[None] < 9  # two padded positions
    for at in range(layers):
        own = {k: v[at][None] for k, v in stacks.items()}  # a stack of one
        want, want_stats = moe.expert_layer(
            h, {"router": router, **own}, k=3, at=0, share=1, valid=valid)
        for index in (at, jnp.int32(at)):
            got, stats = jax.jit(
                lambda i: moe.expert_layer(h, {"router": router, **stacks},
                                           k=3, share=1, at=i,
                                           valid=valid))(index)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)
            assert list(np.asarray(stats)) == list(np.asarray(want_stats))
    assert np.all(np.asarray(want)[0, 9:] == 0)  # padding routes nowhere


def test_yarn_frequencies_are_hugging_faces():
    """``_compute_yarn_parameters`` at the published block, written out:
    dims below the fast correction dim extrapolate (plain frequencies),
    dims above the slow one interpolate (divided by the factor)."""
    block = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
             "original_max_position_embeddings": 8192, "beta_slow": 1,
             "beta_fast": 32, "attention_factor": 1.4852030263919618,
             "partial_rotary_factor": 0.5}
    from production_stack_tpu.models.config import rope_params

    mine, factor = laguna.rope_frequencies(rope_params(block), 128)
    theirs, their_factor = reference.inverse_frequencies(block, 128)
    np.testing.assert_allclose(mine, theirs, rtol=1e-6)
    assert factor == their_factor == 1.4852030263919618
    assert abs(factor - (0.1 * np.log(128) + 1)) < 1e-12
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    low = int(np.floor(64 * np.log(8192 / (32 * 2 * np.pi))
                       / (2 * np.log(500000))))
    high = int(np.ceil(64 * np.log(8192 / (2 * np.pi))
                       / (2 * np.log(500000))))
    np.testing.assert_allclose(mine[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(mine[high:], plain[high:] / 128, rtol=1e-6)
    assert mine.shape == (32,)  # half of each head's 128 dims rotate


def _generate(eng, n, max_tokens, rid):
    q: "queue.Queue" = queue.Queue()
    eng.add_request(
        rid, [(7 * i) % 200 + 1 for i in range(n)],
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True),
        lambda token, finish: q.put((token, finish)))
    while q.get(timeout=120)[1] is None:
        pass


def test_step_records_carry_the_expert_layers_counts(monkeypatch):
    """The step programs return the held experts' counts beside their
    tokens; the step that finds them ready carries them: a decode burst's
    under ``moe_*`` with ``stats_forwards``, a prefill program's behind
    ``prefill_``; the lifetime totals are in ``stats()``. Where the decode
    kernel runs, a burst's record also counts a sliding layer's live and
    copied tokens beside a full layer's."""
    monkeypatch.setattr(EngineCore, "_paged_attn_path", lambda self: "pallas")
    eng = EngineCore(EngineConfig(
        model="tiny-laguna", max_model_len=256, max_num_seqs=4,
        block_size=BS, num_blocks=64, decode_steps=4, prefill_batch=1,
        enable_prefix_caching=False), devices=jax.devices()[:1])
    eng.start()
    try:
        _generate(eng, 30, 13, "one")
    finally:
        eng.stop()
    records = eng.step_recorder.snapshot()[::-1]
    decode = [r for r in records if r.get("stats_forwards")]
    prefill = [r for r in records if r.get("prefill_stats_forwards")]
    assert decode and prefill
    cfg = get_model_config("tiny-laguna")
    sparse = cfg.num_layers - cfg.dense_layers
    for r in decode:
        per_layer = r["stats_forwards"] * sparse
        # one live row: its 3 choices land on the 4 held of 8 experts
        assert 0 <= r["moe_assignments"] <= 3 * per_layer
        assert r["moe_experts_hit"] == r["moe_assignments"]
        assert r["moe_max_expert_load"] <= per_layer
        # a layer is idle or hits an expert, never both
        assert 0 <= r["moe_idle_layers"] <= per_layer - (
            r["moe_experts_hit"] + 2) // 3
        assert per_layer - r["moe_idle_layers"] <= r["moe_experts_hit"]
    assert prefill[0]["prefill_moe_assignments"] <= 30 * 3 * sparse
    assert prefill[0]["prefill_moe_max_expert_load"] <= 30 * sparse
    totals = eng.stats()["family_stats_total"]
    assert totals["moe_assignments"] == (
        sum(r["moe_assignments"] for r in decode)
        + sum(r["prefill_moe_assignments"] for r in prefill))
    bursts = [r for r in records if r["kind"] == "decode_burst"]
    assert all(r["kv_live_tokens_window"] <= r["kv_live_tokens"]
               for r in bursts)
    # every context passes the window of 24: a sliding layer's call holds
    # 24 live tokens a step, and copies the pages of 8 from the one that
    # holds token ``context - 24`` to the one that holds the last
    first = (bursts[0]["kv_live_tokens"] - 6) // 4  # c + (c+1) + (c+2) + (c+3)
    assert first > 24
    assert bursts[0]["kv_live_tokens_window"] == 24 * 4
    assert bursts[0]["kv_fetch_tokens_window"] == sum(
        (-(-c // BS) - (c - 24) // BS) * BS for c in range(first, first + 4))
    assert bursts[0]["kv_fetch_tokens"] == sum(
        -(-c // BS) * BS for c in range(first, first + 4))


def test_a_family_without_counts_keeps_its_programs():
    eng = EngineCore(EngineConfig(
        model="tiny-llama", max_model_len=128, max_num_seqs=2,
        block_size=BS, num_blocks=32, max_loras=0), devices=jax.devices()[:1])
    try:
        assert not hasattr(eng._prefill_fn, "_program")  # no tap
        assert eng.stats()["family_stats_total"] == {}
    finally:
        eng.stop()
