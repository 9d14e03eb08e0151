"""The LFM2 family (models/lfm2.py) against its plain reference
(chipbench/reference/lfm2.py) at a tiny size with everything the
published model has: short convolutions 3:1 with attention layers of
eight 64-wide kv heads (the pool's packed rows), two dense layers, then
sigmoid-routed experts with a selection bias that flips selections, q/k
norms whose weights are not one, a tied head. CPU, float32, seeded random
weights. The convolution's state lives in the cache block: every path
that begins from one is held to the reference, and the engine's own
scheduler, prefix cache and preemption are driven over it."""

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
from production_stack_tpu.engine.core import (
    EngineCore,
    kv_bytes_per_block,
    kv_page_dims,
)
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import build_model, get_model_config
from production_stack_tpu.models import decoder, lfm2, llama, moe
from production_stack_tpu.models.registry import (
    arch_of_model_type,
    block_state_shape,
    page_layers,
)
from production_stack_tpu.ops import attention as att

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench.reference import lfm2 as reference  # noqa: E402

BS = 8
SEED = 11

# tiny-lfm2 as a config.json: ``layer_types`` at its "published" length
# (12), 6 layers held.
with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny_lfm2_config.json")) as _f:
    HF = json.load(_f)


def _model_dir(tmp_path, **changes):
    body = {k: v for k, v in {**HF, **changes}.items() if v is not None}
    path = tmp_path / "model"
    path.mkdir(exist_ok=True)
    (path / "config.json").write_text(json.dumps(body))
    return str(path)


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("tiny-lfm2").replace(dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return build_model(cfg)[0](cfg, jax.random.key(SEED))


@functools.lru_cache(maxsize=None)
def _jitted_apply(cfg):
    _, apply = build_model(cfg)
    return jax.jit(
        lambda params, *args, mode: apply(params, cfg, *args, mode=mode),
        static_argnames=("mode",))


def _pool(cfg, num_blocks):
    layers, rows, lanes = kv_page_dims(cfg)
    state = block_state_shape(cfg)
    return (jnp.zeros((layers, num_blocks, BS, rows, lanes)),
            jnp.zeros((layers, num_blocks, BS, rows, lanes)),
            jnp.zeros((state[0], num_blocks) + state[1:]))


class _Rows:
    """Rows of one pool, each a sequence with a block table of its own,
    driven span by span as the engine drives them."""

    def __init__(self, cfg, params, sequences):
        self.cfg, self.params = cfg, params
        self.tokens = [np.asarray(s, np.int32) for s in sequences]
        per_row = max(len(s) for s in sequences) // BS + 1
        self.kv = _pool(cfg, 2 * per_row * len(sequences))
        order = np.random.default_rng(0).permutation(
            2 * per_row * len(sequences))
        self.tables = order[:per_row * len(sequences)].reshape(
            len(sequences), per_row)
        self.logp = [{} for _ in sequences]

    def span(self, mode, spans, width=None):
        """One program call: ``spans`` = [(row, lo, hi)], padded to
        ``width`` positions; the log-probabilities after each span's last
        token are kept."""
        width = width or max(hi - lo for _, lo, hi in spans)
        R = len(spans)
        tokens = np.zeros((R, width), np.int32)
        positions = np.zeros((R, width), np.int32)
        slots = np.full((R, width), -1, np.int64)
        tables = np.zeros((R, self.tables.shape[1]), np.int32)
        ends, takes = np.zeros(R, np.int32), np.zeros(R, np.int32)
        for i, (row, lo, hi) in enumerate(spans):
            pos = np.arange(lo, hi)
            tokens[i, :hi - lo] = self.tokens[row][lo:hi]
            positions[i] = lo + np.arange(width)
            slots[i, :hi - lo] = (self.tables[row][pos // BS] * BS
                                  + pos % BS)
            tables[i] = self.tables[row]
            ends[i], takes[i] = hi, hi - lo
        logits, self.kv = _jitted_apply(self.cfg)(
            self.params, tokens, positions, self.kv, slots, tables, ends,
            takes, mode=mode)
        for i, (row, lo, hi) in enumerate(spans):
            for t in range(lo, hi):
                self.logp[row][t] = np.asarray(
                    jax.nn.log_softmax(logits[i, t - lo]))

    def pages(self, row):
        """(k, v) [page layers, T, KVH, D] of a row's tokens."""
        T = len(self.tokens[row])
        pos = np.arange(T)
        slot = self.tables[row][pos // BS] * BS + pos % BS
        out = []
        for side in self.kv[:2]:
            side = np.asarray(side)
            flat = side.reshape(side.shape[0], -1, self.cfg.num_kv_heads,
                                self.cfg.head_dim)
            out.append(flat[:, slot])
        return out


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(1)
    return [rng.integers(0, 512, n) for n in (61, 45, 37)]


@pytest.fixture(scope="module")
def wanted(sequences):
    T = max(len(s) for s in sequences)
    tokens = np.zeros((len(sequences), T), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
    return reference.forward(
        HF, SEED, tokens, [len(s) for s in sequences], keep_from=0,
        dtype="float32", kv_layers=(0, 1))


def _hold(rows, wanted, row, positions, tol=2e-4):
    logp, kv = wanted
    for t in positions:
        np.testing.assert_allclose(rows.logp[row][t], logp[row, t],
                                   atol=tol, rtol=0)
    k, v = rows.pages(row)
    n = len(rows.tokens[row])
    for layer in (0, 1):
        np.testing.assert_allclose(k[layer], kv[layer][0][row, :n], atol=tol)
        np.testing.assert_allclose(v[layer], kv[layer][1][row, :n], atol=tol)


# --------------------------------------------------------------------- #
# The config reader and the record
# --------------------------------------------------------------------- #

def test_config_json_reads_as_the_preset(tmp_path, cfg):
    read = get_model_config(_model_dir(tmp_path))
    assert read.replace(name="tiny-lfm2", dtype="float32") == cfg
    assert arch_of_model_type("lfm2_moe") == "lfm2"
    assert read.tie_word_embeddings  # the family's default, no key
    assert read.rms_norm_eps == 1e-5 and read.rope_theta == 1e6


@pytest.mark.parametrize("changes, says", [
    ({"layer_types": None}, "layer_types"),
    ({"layer_types": ["conv", "sliding_attention"] * 6}, "conv or full"),
    ({"conv_bias": True}, "conv_bias"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_parameters": {"rope_type": "yarn"}}, "rope_type"),
])
def test_a_config_the_family_does_not_serve_is_refused(tmp_path, changes,
                                                       says):
    with pytest.raises(ValueError, match=says):
        get_model_config(_model_dir(tmp_path, **changes))


def test_the_record_says_which_layers_hold_what(cfg):
    assert page_layers(cfg) == 2
    assert block_state_shape(cfg) == (4, 2, 128)
    assert page_layers(get_model_config("tiny-llama")) == 2
    assert block_state_shape(get_model_config("tiny-laguna")) is None
    # eight heads of 64 lie two to a 128-lane row; int8 pages and a model
    # whose heads do not fill rows keep one head a row
    assert kv_page_dims(cfg) == (2, 4, 128)
    assert kv_page_dims(cfg, "int8") == (2, 8, 64)
    assert kv_page_dims(get_model_config("tiny-llama")) == (2, 2, 32)
    assert kv_page_dims(get_model_config("facebook/opt-125m")) == (12, 12, 64)


def test_a_block_costs_its_pages_and_its_state():
    """At the published widths a token holds 2 KiB a layer of pages in
    the 2 of 10 held layers that have them (not the 8 KiB of a page
    padded to (16, 128) tiles), and a block 8 KiB of state in each of
    the 8 that hold one."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "lfm2-24b-a2b-l10.json")) as f:
        from chipbench.registry import model_keys
        hf = model_keys(json.load(f))
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(hf, f)
        mc = get_model_config(d)
    assert (page_layers(mc), block_state_shape(mc)) == (2, (8, 2, 2048))
    assert kv_bytes_per_block(mc, 64) == 2 * 64 * 2048 + 8 * 2 * 2048 * 2
    assert kv_bytes_per_block(mc, 64) // 64 == 5 * 1024  # 5 KiB a token


# --------------------------------------------------------------------- #
# The program against the reference, path by path
# --------------------------------------------------------------------- #

def test_plain_prefill_matches_the_reference(cfg, params, sequences, wanted):
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 61)])
    _hold(rows, wanted, 0, range(61))


def test_cached_prefill_begins_from_a_blocks_state(cfg, params, sequences,
                                                   wanted):
    """The first 40 tokens plain, the rest as a cached prefill that
    begins at a block's boundary (5 blocks of 8): the halo is the block's
    state, the prefix the pages."""
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 40)])
    rows.span("prefill_cached", [(0, 40, 61)], width=32)
    _hold(rows, wanted, 0, range(61))


def test_chunked_prefill_over_two_chunks_off_a_boundary(cfg, params,
                                                        sequences, wanted):
    """A second chunk that begins in the middle of a block: its halo is
    the entry the first chunk wrote for that block's last written token,
    and the entry it writes replaces it."""
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill_cached", [(0, 0, 29)], width=32)
    rows.span("prefill_cached", [(0, 29, 61)], width=32)
    _hold(rows, wanted, 0, range(61))


def test_a_group_of_rows_of_different_lengths(cfg, params, sequences, wanted):
    """PR 35's [R, rung] plain program: each padded row writes the state
    of its own tail."""
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 61), (1, 0, 41), (2, 0, 30)], width=64)
    # each row goes on alone from its own state
    rows.span("prefill_cached", [(1, 41, 45), (2, 30, 37)], width=8)
    for row, n in ((0, 61), (1, 45), (2, 37)):
        _hold(rows, wanted, row, range(n))


def test_decode_past_a_block_boundary(cfg, params, sequences, wanted):
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 37)])
    for t in range(37, 61):  # crosses 40, 48 and 56
        rows.span("decode", [(0, t, t + 1)])
    _hold(rows, wanted, 0, range(61))


def test_decode_rows_that_hold_nothing_write_nothing(cfg, params, sequences,
                                                     wanted):
    """A decode row with slot -1 (no sequence, or a burst step its
    sequence may not use) leaves every block's state as it was."""
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 61)])
    before = np.asarray(rows.kv[2])
    apply = _jitted_apply(cfg)
    _, kv = apply(
        params, np.asarray([[5]], np.int32), np.asarray([[61]], np.int32),
        rows.kv, np.asarray([[-1]], np.int64), rows.tables[:1],
        np.asarray([62], np.int32), np.asarray([1], np.int32), mode="decode")
    np.testing.assert_array_equal(np.asarray(kv[2]), before)


# --------------------------------------------------------------------- #
# Planted faults: each must fail the comparison it passes sound
# --------------------------------------------------------------------- #

def _fails(rows, wanted, row, positions):
    with pytest.raises(AssertionError):
        _hold(rows, wanted, row, positions)


def test_a_zeroed_halo_fails(cfg, params, sequences, wanted, monkeypatch):
    monkeypatch.setattr(
        decoder, "read_block_state",
        lambda state, at, batch, bs: jnp.zeros(
            (batch.positions.shape[0],) + state.shape[2:], state.dtype))
    _jitted_apply.cache_clear()
    try:
        rows = _Rows(cfg, params, sequences)
        rows.span("prefill", [(0, 0, 40)])
        rows.span("prefill_cached", [(0, 40, 61)], width=32)
        _fails(rows, wanted, 0, range(40, 61))
    finally:
        _jitted_apply.cache_clear()


def test_a_dropped_selection_bias_fails(cfg, params, sequences, wanted):
    dropped = {**params, "moe": {**params["moe"], "router_bias":
                                 jnp.zeros_like(params["moe"]["router_bias"])}}
    rows = _Rows(cfg, dropped, sequences)
    rows.span("prefill", [(0, 0, 61)])
    _fails(rows, wanted, 0, range(61))


def test_weights_taken_with_the_bias_fail(cfg, params, sequences, wanted,
                                          monkeypatch):
    sound = moe.route

    def biased_weights(h, router, k, *, bias=None, eps=0.0, scaling=1.0,
                       **kw):
        scores = jax.nn.sigmoid(
            jnp.dot(h, router, preferred_element_type=jnp.float32)) + bias
        weights, experts = jax.lax.top_k(scores, k)
        return (weights / (jnp.sum(weights, -1, keepdims=True) + eps)
                * scaling, experts)

    monkeypatch.setattr(moe, "route", biased_weights)
    _jitted_apply.cache_clear()
    try:
        rows = _Rows(cfg, params, sequences)
        rows.span("prefill", [(0, 0, 61)])
        _fails(rows, wanted, 0, range(61))
    finally:
        monkeypatch.setattr(moe, "route", sound)
        _jitted_apply.cache_clear()


def test_qk_norm_after_the_rotation_fails(cfg, params, sequences, wanted,
                                          monkeypatch):
    """With weights that are not one the norm and the rotation do not
    commute."""
    norm, rope = llama.rms_norm, llama.rope
    pending = []  # q's weight, then k's, as the rotations follow

    def late_norm(x, weight, eps):
        if x.ndim == 4:  # a head's dims: hold the weight for the rotation
            pending.append((weight, eps))
            return x
        return norm(x, weight, eps)

    def rope_then_norm(x, positions, theta):
        weight, eps = pending.pop(0)
        return norm(rope(x, positions, theta), weight, eps)

    monkeypatch.setattr(lfm2.llama, "rms_norm", late_norm)
    monkeypatch.setattr(lfm2.llama, "rope", rope_then_norm)
    _jitted_apply.cache_clear()
    try:
        rows = _Rows(cfg, params, sequences)
        rows.span("prefill", [(0, 0, 61)])
        _fails(rows, wanted, 0, range(61))
    finally:
        monkeypatch.undo()
        _jitted_apply.cache_clear()


# --------------------------------------------------------------------- #
# The router (models/moe.py::route)
# --------------------------------------------------------------------- #

def test_route_scores_selects_and_weights_as_published():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    scores = 1 / (1 + np.exp(-np.asarray(h @ router)))
    weights, experts = moe.route(h, router, 3, scoring="sigmoid", bias=bias,
                                 eps=1e-6, scaling=2.0)
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
    picked = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, 2.0 * picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # the bias flips selections: some token's experts differ without it
    _, plain = moe.route(h, router, 3, scoring="sigmoid")
    assert (np.sort(plain, -1) != np.sort(experts, -1)).any()
    with pytest.raises(ValueError, match="scoring"):
        moe.route(h, router, 3, scoring="tanh")


def test_routes_defaults_trace_what_they_traced():
    """Laguna and Mixtral call ``route`` without the new arguments and get
    the program they had: a softmax, top k, renormalised, no epsilon."""
    h = jnp.ones((4, 16), jnp.float32)
    router = jnp.ones((16, 8), jnp.float32)

    def old(h, router, k, scaling=1.0):
        logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
        weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights * scaling, experts

    new = jax.jit(lambda h, r: moe.route(h, r, 2, scaling=2.5))
    was = jax.jit(lambda h, r: old(h, r, 2, scaling=2.5))
    text = lambda f: f.lower(h, router).as_text().split("\n", 1)[1]  # noqa: E731
    assert text(new) == text(was)


# --------------------------------------------------------------------- #
# The engine: scheduler, prefix cache, preemption, surfaces
# --------------------------------------------------------------------- #

def _engine(**overrides):
    config = dict(
        model="tiny-lfm2", max_model_len=256, max_num_seqs=4, block_size=BS,
        num_blocks=96, decode_steps=4, prefill_batch=1, dtype="float32")
    config.update(overrides)
    return EngineCore(EngineConfig(**config), devices=jax.devices()[:1])


def _generate(eng, prompt, max_tokens, rid):
    q: "queue.Queue" = queue.Queue()
    eng.add_request(
        rid, list(prompt),
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True, logprobs=1),
        lambda token, finish: q.put((token, finish)))
    tokens, logprobs = [], []
    while True:
        token, finish = q.get(timeout=180)
        if token is not None:
            tokens.append(int(token[0]))
            logprobs.append(float(token[1]["logprob"]))
        if finish is not None:
            return tokens, logprobs


def _prompt(n, salt=0):
    return [(7 * i + salt) % 200 + 1 for i in range(n)]


@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    eng.start()
    yield eng
    eng.stop()


def test_a_prefix_hit_resumes_from_the_blocks_state(engine):
    """The same prompt again: its full blocks come from the prefix cache,
    the prefill begins from the last one's state, and the answer is the
    first one's, token for token and logprob for logprob."""
    first = _generate(engine, _prompt(45), 6, "a")
    before = engine.stats()["state_restores_total"]
    cached_before = engine.cached_tokens_total
    again = _generate(engine, _prompt(45), 6, "b")
    assert engine.cached_tokens_total - cached_before == 40
    assert engine.stats()["state_restores_total"] == before + 1
    assert again[0] == first[0]
    np.testing.assert_allclose(again[1], first[1], atol=1e-4)


def test_a_prefix_hit_on_blocks_filled_during_decode(engine):
    """A turn's answer fills blocks while decoding; the next turn's prompt
    (prompt + answer + more) finds them cached, state and pages, and
    answers as an engine that never saw the first turn."""
    prompt = _prompt(21, salt=3)
    answer, _ = _generate(engine, prompt, 30, "turn1")
    follow = prompt + answer + _prompt(9, salt=5)
    cached_before = engine.cached_tokens_total
    got = _generate(engine, follow, 8, "turn2")
    # 21 + 30 = 51 tokens: six full blocks, of which four were filled by
    # decode steps (the last token's page is written when it is fed)
    assert engine.cached_tokens_total - cached_before >= 40
    fresh = _engine(enable_prefix_caching=False)
    fresh.start()
    try:
        want = _generate(fresh, follow, 8, "alone")
    finally:
        fresh.stop()
    assert got[0] == want[0]
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)


def test_preempt_and_resume_recomputes_the_state():
    """A pool too small for both sequences: one is preempted (its blocks
    freed) and resumed by recompute, and still answers as alone."""
    eng = _engine(num_blocks=20, enable_prefix_caching=False)
    eng.start()
    alone = _engine(enable_prefix_caching=False)
    alone.start()
    try:
        results = {}
        import threading
        threads = [threading.Thread(
            target=lambda i=i: results.__setitem__(
                i, _generate(eng, _prompt(50, salt=i), 60, f"p{i}")))
            for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert eng.scheduler.num_preempted_total > 0
        for i in range(2):
            want = _generate(alone, _prompt(50, salt=i), 60, f"w{i}")
            assert results[i][0] == want[0]
    finally:
        eng.stop()
        alone.stop()


def test_step_records_count_restores_and_blocks_written(engine):
    _generate(engine, _prompt(33, salt=9), 5, "r1")
    _generate(engine, _prompt(33, salt=9), 5, "r2")
    records = engine.step_recorder.snapshot()
    prefill = [r for r in records if r["kind"].startswith("prefill")]
    assert any(r.get("state_restores") == 1 for r in prefill)
    # 33 tokens over blocks of 8: five entries written by the first
    assert any(r.get("state_blocks_written") == 5 for r in prefill)
    decode = [r for r in records if r["kind"] == "decode_burst"]
    assert all(r.get("state_blocks_written", 0) > 0 for r in decode[:1])
    assert engine.stats()["state_blocks_written_total"] > 0


def test_extract_and_inject_carry_the_state(engine):
    prompt = _prompt(43, salt=13)
    want = _generate(engine, prompt, 6, "src")
    got = engine.extract_kv(prompt)
    assert got["num_tokens"] == 40
    cfg = engine.model_config
    # logical pages, of the layers that hold them; the blocks' state
    assert got["k"].shape == (5, 2, BS, cfg.num_kv_heads, cfg.head_dim)
    assert got["state"].shape == (5, 4, 2, cfg.hidden_size)
    assert np.abs(got["state"]).max() > 0
    on_device = engine.extract_kv_device(prompt)
    np.testing.assert_array_equal(
        np.asarray(on_device["state"]).swapaxes(0, 1), got["state"])
    other = _engine()
    other.start()
    try:
        with pytest.raises(ValueError, match="state"):
            other.inject_kv(got["hashes"], got["k"], got["v"])
        assert other.inject_kv(got["hashes"], got["k"], got["v"],
                               got["state"]) == 5
        before = other.cached_tokens_total
        resumed = _generate(other, prompt, 6, "dst")
        assert other.cached_tokens_total - before == 40
        assert resumed[0] == want[0]
        np.testing.assert_allclose(resumed[1], want[1], atol=1e-4)
    finally:
        other.stop()


@pytest.mark.parametrize("flags, says", [
    ({"speculative_num_tokens": 4}, "--speculative-num-tokens"),
    ({"kv_offload_bytes": 1 << 20}, "--kv-offload-bytes"),
    ({"kv_remote_url": "http://127.0.0.1:1"}, "--kv-remote-url"),
])
def test_start_up_refuses_what_the_state_is_not_taught(flags, says):
    with pytest.raises(ValueError, match=says):
        _engine(**flags)


def test_a_mesh_of_several_devices_is_refused():
    with pytest.raises(ValueError, match="mesh of several devices"):
        EngineCore(EngineConfig(
            model="tiny-lfm2", max_model_len=128, max_num_seqs=2,
            block_size=BS, num_blocks=32, tensor_parallel_size=2),
            devices=jax.devices()[:2])


def test_int8_weights_are_refused():
    with pytest.raises(ValueError, match="int8 quantization"):
        _engine(quantization="int8")


def test_int8_pages_keep_one_head_a_row_and_serve():
    """``--kv-cache-dtype int8`` (no control of the configuration's check:
    its ``limit_notes``): the pool is not packed, the reference path
    serves, and the answer is of the length asked for."""
    eng = _engine(kv_cache_dtype="int8")
    eng.start()
    try:
        assert eng.page_dims == (2, 8, 64)
        tokens, _ = _generate(eng, _prompt(30), 5, "q")
        assert len(tokens) == 5
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Narrow heads side by side in the pages' rows (ops/attention.py)
# --------------------------------------------------------------------- #

def test_packed_queries_see_their_own_head():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(3, 32, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(3, 8, 64)), jnp.float32)
    packed = att._packed_queries(q, 4, 2)
    assert packed.shape == (3, 32, 128)
    scores = jnp.einsum("brgd,brd->brg", packed.reshape(3, 4, 8, 128),
                        k.reshape(3, 4, 128))
    want = jnp.einsum("bhgd,bhd->bhg", q.reshape(3, 8, 4, 64), k)
    np.testing.assert_allclose(scores.reshape(3, 32), want.reshape(3, 32),
                               rtol=1e-5)
    # and an output's lanes come back to the head that owns them
    out = jnp.asarray(rng.normal(size=(3, 32, 128)), jnp.float32)
    own = att._unpacked_outputs(out, 4, 2).reshape(3, 4, 2, 4, 64)
    rows = out.reshape(3, 4, 2, 4, 2, 64)
    for e in range(2):
        np.testing.assert_array_equal(own[:, :, e], rows[:, :, e, :, e])
