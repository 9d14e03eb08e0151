"""The longcat family (PR 41): latent (MLA) pages of two unequal sides,
two attention sublayers a layer, a shortcut-connected expert layer with
zero-compute experts.

The program (``models/longcat.py`` through ``decoder.attend_latent``) is
held to the plain reference (``chipbench/reference/longcat.py``: float32,
nothing absorbed, no cache) on seeded random weights at the tiny size,
in float32. Tolerances: 2e-4 absolute on log-probabilities and on both
cache sides, which float32 accumulation order accounts for (the readings
are 4e-6 to 7e-6) and which bf16 in place of the test dtype fails by two
orders of magnitude (``test_bf16_fails_the_tolerance``).
"""

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
    kv_page_sides,
)
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import build_model, get_model_config, moe
from production_stack_tpu.models.decoder import Batch, attend_latent
from production_stack_tpu.models.registry import (
    arch_of_model_type,
    get_family,
    page_layers,
    page_sides,
)
from production_stack_tpu.models.weights import load_checkpoint
from production_stack_tpu.ops import attention as att
from production_stack_tpu.ops.pallas_mla_decode import (
    decode_tile,
    pallas_mla_decode,
    tiles_ok,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench.reference import longcat as reference  # noqa: E402

BS = 8
SEED = 11
TOL = 2e-4

with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny_longcat_config.json")) as _f:
    HF = json.load(_f)


def _model_dir(tmp_path, **changes):
    body = {k: v for k, v in {**HF, **changes}.items() if v is not None}
    path = tmp_path / "model"
    path.mkdir(exist_ok=True)
    (path / "config.json").write_text(json.dumps(body))
    return str(path)


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("tiny-longcat").replace(dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return build_model(cfg)[0](cfg, jax.random.key(SEED))


@functools.lru_cache(maxsize=None)
def _jitted_apply(cfg):
    _, apply = build_model(cfg)
    return jax.jit(
        lambda params, *args, mode: apply(params, cfg, *args, mode=mode),
        static_argnames=("mode",))


def _pool(cfg, num_blocks, dtype=jnp.float32):
    layers, *sides = kv_page_sides(cfg)
    return tuple(jnp.zeros((layers, num_blocks, BS) + side, dtype)
                 for side in sides)


class _Rows:
    """Rows of one pool, each a sequence with a block table of its own,
    driven span by span as the engine drives them."""

    def __init__(self, cfg, params, sequences):
        self.cfg, self.params = cfg, params
        self.tokens = [np.asarray(s, np.int32) for s in sequences]
        per_row = max(len(s) for s in sequences) // BS + 1
        self.kv = _pool(cfg, 2 * per_row * len(sequences),
                        cfg.jnp_dtype)
        order = np.random.default_rng(0).permutation(
            2 * per_row * len(sequences))
        self.tables = order[:per_row * len(sequences)].reshape(
            len(sequences), per_row)
        self.logp = [{} for _ in sequences]

    def span(self, mode, spans, width=None, idle_rows=0):
        """One program call: ``spans`` = [(row, lo, hi)], padded to
        ``width`` positions and by ``idle_rows`` rows that hold nothing
        (slot -1); the log-probabilities after each span's positions are
        kept."""
        width = width or max(hi - lo for _, lo, hi in spans)
        R = len(spans) + idle_rows
        tokens = np.zeros((R, width), np.int32)
        positions = np.zeros((R, width), np.int32)
        slots = np.full((R, width), -1, np.int64)
        tables = np.zeros((R, self.tables.shape[1]), np.int32)
        ends, takes = np.ones(R, np.int32), np.zeros(R, np.int32)
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
                self.logp[row][t] = np.asarray(jax.nn.log_softmax(
                    logits[i, t - lo].astype(jnp.float32)))

    def pages(self, row):
        """(c [page layers, T, latent], k_r [page layers, T, rope]) of a
        row's tokens, and the lanes the pool keeps beyond the key."""
        T = len(self.tokens[row])
        pos = np.arange(T)
        slot = self.tables[row][pos // BS] * BS + pos % BS
        c, r = (np.asarray(side, np.float32) for side in self.kv)
        c = c.reshape(c.shape[0], -1, c.shape[-1])[:, slot]
        r = r.reshape(r.shape[0], -1, r.shape[-1])[:, slot]
        rope = self.cfg.qk_rope_head_dim
        return c, r[..., :rope], r[..., rope:]


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
        dtype="float32", kv_layers=(0, 1, 2, 3))


def _hold(rows, wanted, row, positions, tol=TOL):
    logp, kv = wanted
    for t in positions:
        np.testing.assert_allclose(rows.logp[row][t], logp[row, t],
                                   atol=tol, rtol=0)
    c, k_r, beyond = rows.pages(row)
    n = len(rows.tokens[row])
    assert not beyond.any()  # the pool's lanes past the key stay zero
    for layer in range(4):  # page layer 2l + i: sublayer i of layer l
        np.testing.assert_allclose(c[layer], kv[layer][0][row, :n, 0],
                                   atol=tol)
        np.testing.assert_allclose(k_r[layer], kv[layer][1][row, :n, 0],
                                   atol=tol)


# --------------------------------------------------------------------- #
# The config reader and the record
# --------------------------------------------------------------------- #

def test_config_json_reads_as_the_preset(tmp_path, cfg):
    """A ``config.json`` of this model's keys (``num_layers``, no
    ``num_hidden_layers``; the family's own keys through
    ``Family.config_fields``, which it has without per-layer lists)."""
    read = get_model_config(_model_dir(tmp_path))
    assert read.replace(name="tiny-longcat", dtype="float32") == cfg
    assert arch_of_model_type("longcat_flash") == "longcat"
    assert read.num_layers == 2 and not get_family("longcat").per_layer_keys
    assert read.published_experts + read.zero_experts == 12
    assert read.router_bias


@pytest.mark.parametrize("changes, says", [
    ({"attention_method": "MHA"}, "MLA"),
    ({"zero_expert_type": "constant"}, "zero_expert_type"),
    ({"attention_bias": True}, "biases"),
    ({"hidden_act": "gelu"}, "silu"),
    ({"norm_topk_prob": True}, "norm_topk_prob"),
])
def test_a_config_the_family_does_not_serve_is_refused(tmp_path, changes,
                                                       says):
    with pytest.raises(ValueError, match=says):
        get_model_config(_model_dir(tmp_path, **changes))


def test_the_record_says_what_a_page_is(cfg):
    assert page_sides(cfg) == ((1, 128), (1, 16))
    assert page_layers(cfg) == 4  # two attention sublayers a layer
    # the pool: each side at its own width in whole 128-lane tiles
    assert kv_page_sides(cfg) == (4, (1, 128), (1, 128))
    assert kv_page_dims(cfg) == (4, 1, 128)
    assert kv_bytes_per_block(cfg, 16) == 4 * 16 * (128 + 128) * 4
    full = cfg.replace(kv_lora_rank=512, qk_rope_head_dim=64,
                       num_layers=4, dtype="bfloat16")
    # 512 + 128 lanes a token and sublayer in bf16: 1,280 bytes where
    # the values alone are 1,152 and grouped keys and values of these
    # heads would be 40,960
    assert kv_bytes_per_block(full, 64) == 8 * 64 * 1280
    for arch in ("tiny-llama", "tiny-laguna", "tiny-lfm2", "tiny-mixtral"):
        other = get_model_config(arch)
        assert page_sides(other) is None
        layers, first, second = kv_page_sides(other)
        assert first == second and (layers,) + first == kv_page_dims(other)
    with pytest.raises(NotImplementedError, match="checkpoint"):
        load_checkpoint(cfg, "/nowhere")


def test_own_recipe_draws_the_programs_weights():
    """The reference's copy of the init recipe gives the program's
    weights bit for bit: sublayer i of layer l is entry 2l + i of a
    stacked leaf, expert e of layer l entry l x held + e, and a block of
    a dense MLP's columns is those columns."""
    from production_stack_tpu.models import longcat

    cfg = get_model_config("tiny-longcat")
    p = longcat.init_params(cfg, jax.random.key(SEED))["layers"]
    keys = reference.split(reference.seed_key(SEED), 16)
    bf16 = jnp.bfloat16

    def same(mine, theirs):
        return bool(jnp.array_equal(mine.astype(theirs.dtype), theirs))

    assert same(reference._stacked(keys[2], 3, (128, 64), 128, bf16),
                p["wq_a"][1, 1])
    assert same(reference._stacked(keys[5], 2, (8, 128, 64), 128, bf16),
                p["wkv_b"][1, 0])
    assert same(reference._stacked(keys[3], 1, (8 * 48, 64), 64, bf16),
                p["wq_b"][0, 1])
    assert same(reference._stacked(keys[6], 1, (256, 128), 256, bf16),
                p["wo"][0, 1])
    assert same(reference._columns(keys[7], 3, (128, 256), 64, 32, 128,
                                   bf16), p["w_gate"][1, 1][:, 64:96])
    assert same(reference._stacked(keys[10], 1, (128, 12), 128, bf16),
                p["router"][1])
    assert same(reference._stacked(keys[13], 1 * 4 + 2, (64, 128), 64,
                                   bf16), p["e_down"][1, 2])
    assert not p["router_bias"].any()


# --------------------------------------------------------------------- #
# The three modes against the reference
# --------------------------------------------------------------------- #

def test_prefill_holds_the_reference(cfg, params, sequences, wanted):
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 61), (1, 0, 45), (2, 0, 37)], width=64)
    for row, n in enumerate((61, 45, 37)):
        _hold(rows, wanted, row, range(n))


def test_cached_prefill_in_two_chunks_holds_the_reference(
        cfg, params, sequences, wanted):
    """A prompt past one chunk: the first chunk plain, the rest through
    the latent pages (gathered and up-projected), here with a padded
    second chunk and a row beside it that begins elsewhere."""
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 32)], width=32)
    rows.span("prefill", [(1, 0, 24)], width=32)
    rows.span("prefill_cached", [(0, 32, 61), (1, 24, 45)], width=32)
    _hold(rows, wanted, 0, range(61))
    _hold(rows, wanted, 1, range(45))


def test_chunked_context_path_is_the_one_shot_one(cfg, params, sequences,
                                                  wanted, monkeypatch):
    """Past its score-size bound the cached path streams the context in
    chunks with an online softmax: the same numbers."""
    monkeypatch.setattr(att, "_CHUNKED_SCORE_BYTES", 1)
    monkeypatch.setattr(att, "_CHUNKED_SCORE_SPAN", 16)
    rows = _Rows(cfg.replace(name="chunked"), params, sequences)
    rows.span("prefill", [(0, 0, 32)], width=32)
    rows.span("prefill_cached", [(0, 32, 61)], width=32)
    _hold(rows, wanted, 0, range(61))


def test_decode_through_the_latent_pages_holds_the_reference(
        cfg, params, sequences, wanted):
    """Absorbed decode over the pages, two rows of ragged contexts
    stepping together beside a row that holds nothing (slot -1)."""
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 50), (2, 0, 30)], width=64)
    for step in range(7):
        rows.span("decode", [(0, 50 + step, 51 + step),
                             (2, 30 + step, 31 + step)], idle_rows=1)
    _hold(rows, wanted, 2, range(37))
    for t in range(57):
        np.testing.assert_allclose(rows.logp[0][t], wanted[0][0, t],
                                   atol=TOL, rtol=0)


def test_bf16_fails_the_tolerance(params, sequences, wanted):
    """The tolerance is tight: the same program in bf16 misses it."""
    cfg16 = get_model_config("tiny-longcat")
    p16 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32
        and x.ndim > 1 else x, params)
    rows = _Rows(cfg16, p16, sequences)
    rows.span("prefill", [(0, 0, 61)], width=64)
    worst = max(np.abs(rows.logp[0][t] - wanted[0][0, t]).max()
                for t in range(61))
    assert worst > 20 * TOL
    c, _, _ = rows.pages(0)
    assert np.abs(c[1] - wanted[1][1][0][0, :61, 0]).max() > 20 * TOL


# --------------------------------------------------------------------- #
# attend_latent: the absorbed form is the up-projected one
# --------------------------------------------------------------------- #

def _latent_case(rng, B, H=8, N=32, R=16, C=128, V=32, blocks=6):
    NB = B * blocks + 3
    kv = (jnp.asarray(rng.normal(size=(2, NB, BS, 1, C)), jnp.float32),
          jnp.zeros((2, NB, BS, 1, 128), jnp.float32).at[..., :R].set(
              rng.normal(size=(2, NB, BS, 1, R))))
    tables = jnp.asarray(rng.permutation(NB)[:B * blocks].reshape(B, blocks),
                         jnp.int32)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(q_nope=draw(B, 1, H, N), q_rope=draw(B, 1, H, R),
                c=draw(B, 1, C), k_rope=draw(B, 1, R),
                w_up=draw(H, C, N + V) / 11.0, kv=kv, tables=tables)


def test_absorbed_decode_equals_the_up_projected_form():
    """One new token a row: ``decode`` (absorb, attend in the latent
    space over the pages, un-absorb) against ``prefill_cached`` with a
    chunk of one (gather, up-project to per-head keys and values,
    attend): the same product in two orders."""
    case = _latent_case(np.random.default_rng(3), B=3)
    ctx = jnp.asarray([37, 9, 24], jnp.int32)  # with the new token
    pos = (ctx - 1)[:, None]
    slot = (jnp.take_along_axis(case["tables"], pos // BS, axis=1) * BS
            + pos % BS)
    batch = Batch(pos, slot, case["tables"], ctx, jnp.ones((3,), jnp.int32))
    outs = {}
    for mode in ("decode", "prefill_cached"):
        outs[mode], pages = attend_latent(
            mode, case["q_nope"], case["q_rope"], case["c"], case["k_rope"],
            case["w_up"], case["kv"], jnp.int32(1), batch, scale=48 ** -0.5,
            latent_scale=3.0 ** 0.5)
    assert outs["decode"].shape == (3, 1, 8, 32)
    np.testing.assert_allclose(outs["decode"], outs["prefill_cached"],
                               atol=2e-5)
    # the token was written: its latent on the first side, its key and
    # zeros on the second
    flat = np.asarray(pages[1]).reshape(2, -1, 128)
    np.testing.assert_array_equal(flat[1, np.asarray(slot[:, 0]), :16],
                                  np.asarray(case["k_rope"][:, 0]))
    assert not flat[..., 16:].any()
    np.testing.assert_array_equal(
        np.asarray(pages[0]).reshape(2, -1, 128)[1, np.asarray(slot[:, 0])],
        np.asarray(case["c"][:, 0]))


def test_a_decode_row_that_holds_nothing_writes_and_attends_nothing():
    case = _latent_case(np.random.default_rng(4), B=2)
    batch = Batch(jnp.asarray([[20], [5]]), jnp.asarray([[-1], [-1]]),
                  case["tables"], jnp.asarray([21, 6], jnp.int32),
                  jnp.ones((2,), jnp.int32))
    out, pages = attend_latent(
        "decode", case["q_nope"][:2], case["q_rope"][:2], case["c"][:2],
        case["k_rope"][:2], case["w_up"], case["kv"], jnp.int32(0), batch,
        scale=0.1)
    assert not np.asarray(out).any()
    for mine, was in zip(pages, case["kv"]):
        np.testing.assert_array_equal(mine, was)


# --------------------------------------------------------------------- #
# The Pallas kernel (interpret mode) against the XLA path
# --------------------------------------------------------------------- #

# name: (block size, pages a chunk, ring, the rows' contexts). A chunk of
# ``pages x block size`` tokens is computed in sub-blocks of 128 where it
# holds several (the last three: the cell's 64-token pages), a full one
# as two spans of them and a row's last over those that hold a live
# token; 0 and -1 are rows that hold nothing, between live ones so that
# the walk of the copies crosses rows inside a trip.
KERNEL_CASES = {
    "own_tile": (16, 0, 0, [37, 0, 96, 1, -1]),
    "ring_wraps_across_rows": (16, 2, 3, [37, 0, 96, 1, -1]),
    "one_page_chunks_nothing_ahead": (16, 1, 2, [37, 0, 96, 1, -1]),
    "exactly_k_chunks_odd_and_even": (
        16, 4, 3, [64, 0, 128, -1, 192, 0, 256, 320]),
    "k_chunks_and_one_token": (16, 4, 3, [65, 0, 129, -1, 193, 0, 257]),
    "k_chunks_less_one_token": (16, 4, 4, [63, 0, 127, -1, 191, 0, 255, 319]),
    "a_ring_of_two": (16, 4, 2, [320, 0, 1, 129, -1, 256]),
    "tail_in_the_first_and_the_last_sub_block": (
        64, 8, 3, [517, 0, 1012, 1, -1, 5, 500, 1541]),
    "tail_at_a_sub_block_edge": (
        64, 8, 6, [640, 0, 641, 639, -1, 128, 129, 1024 + 384]),
    "two_sub_blocks_a_chunk": (64, 4, 4, [255, 0, 256, 257, -1, 130, 771]),
}


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernel_is_the_xla_path(dtype, tol, case):
    """Every edge the kernel's loop has: contexts of exactly k chunks,
    one token more and one less, odd and even counts, a last chunk whose
    tail lies in its first or its last sub-block or on an edge (one,
    two, three sub-blocks live: one span, two even ones, two uneven
    ones), one token, rows that hold nothing (context 0, and -1: no
    slot) between live ones: nothing copied or computed for those, zeros
    out; chunks narrower than the table, and rings that wrap across
    rows."""
    bs, pages_per_block, ring, contexts = KERNEL_CASES[case]
    rng = np.random.default_rng(0)
    L, C, lanes, R, H, B = 3, 128, 128, 16, 8, len(contexts)
    MAXB = -(-max(contexts) // bs) + 1
    NB = B * MAXB
    c = jnp.asarray(rng.normal(size=(L, NB, bs, 1, C)), dtype)
    r = jnp.zeros((L, NB, bs, 1, lanes), dtype).at[..., :R].set(
        jnp.asarray(rng.normal(size=(L, NB, bs, 1, R)), dtype))
    q_abs = jnp.asarray(rng.normal(size=(B, H, C)), dtype)
    q_rope = jnp.asarray(rng.normal(size=(B, H, R)), dtype)
    tables = jnp.asarray(rng.permutation(NB).reshape(B, MAXB), jnp.int32)
    ctx = jnp.asarray(contexts, jnp.int32)
    got = pallas_mla_decode(q_abs, q_rope, c, r, tables, ctx, 1, scale=0.2,
                            pages_per_block=pages_per_block, ring=ring,
                            interpret=True)
    want = att.latent_decode_reference(q_abs, q_rope, c, r, tables, ctx, 1,
                                       scale=0.2)
    assert got.shape == (B, H, C) and got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol)
    for row, n in enumerate(contexts):
        assert (n > 0) == bool(np.asarray(got[row], np.float32).any())


def test_stale_nans_past_the_context_do_not_reach_the_output():
    """What lies past a row's live tokens in its last page (an earlier
    owner's) is zeroed where ``p @ c`` reads it: 0 x NaN is NaN."""
    rng = np.random.default_rng(1)
    c = jnp.asarray(rng.normal(size=(1, 4, 16, 1, 128)), jnp.float32)
    c = c.at[0, 2, 5:].set(jnp.nan)
    r = jnp.zeros((1, 4, 16, 1, 128), jnp.float32)
    q_abs = jnp.asarray(rng.normal(size=(1, 8, 128)), jnp.float32)
    got = pallas_mla_decode(q_abs, jnp.zeros((1, 8, 16)), c, r,
                            jnp.asarray([[1, 2]], jnp.int32),
                            jnp.asarray([21], jnp.int32), 0, scale=0.3,
                            interpret=True)
    assert np.isfinite(np.asarray(got)).all()


def test_the_path_is_chosen_from_shapes_and_counted(monkeypatch):
    assert tiles_ok(64, 64, 512, 128, 2) and tiles_ok(16, 8, 128, 128, 4)
    assert not tiles_ok(8, 64, 512, 128, 2)  # half a bf16 tile of tokens
    assert not tiles_ok(64, 64, 512, 64, 2)  # a side off the lanes
    assert decode_tile(64, 64, 512, 128, 2, 128) == (16, 4)
    assert decode_tile(64, 64, 512, 128, 2, 4) == (4, 4)
    assert att.latent_decode_path(64, 64, 512, 128, "bfloat16") == "xla"
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    assert att.latent_decode_path(64, 64, 512, 128, "bfloat16") == "pallas"
    assert att.latent_decode_path(8, 64, 512, 128, "bfloat16") == "xla"
    monkeypatch.undo()
    before = att.TRACED_PATHS["latent_decode", "xla"]
    case = _latent_case(np.random.default_rng(5), B=1)
    jax.make_jaxpr(lambda q: att.latent_decode_attention(
        q, case["q_rope"][:, 0], *case["kv"], case["tables"],
        jnp.asarray([9]), 0, scale=1.0))(jnp.zeros((1, 8, 128)))
    assert att.TRACED_PATHS["latent_decode", "xla"] == before + 1


# --------------------------------------------------------------------- #
# The expert layer: no renormalisation, zero-compute experts, shares
# --------------------------------------------------------------------- #

def _expert_case(rng, N=24, Hd=32, I=16, E=8, Z=4):
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(h=draw(1, N, Hd), router=draw(Hd, E + Z),
                bias=0.05 * draw(E + Z), w_gate=draw(1, E, Hd, I) / 4,
                w_up=draw(1, E, Hd, I) / 4, w_down=draw(1, E, I, Hd) / 4)


def test_route_without_renormalisation_against_a_loop():
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(9, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 12)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(12,)), jnp.float32)
    weights, experts = moe.route(h, router, 3, scaling=6.0, bias=bias,
                                 renormalise=False)
    for n in range(9):
        z = np.asarray(h[n] @ router, np.float64)
        score = np.exp(z - z.max()) / np.exp(z - z.max()).sum()
        picked = np.argsort(-(score + np.asarray(bias)))[:3]
        assert sorted(picked) == sorted(np.asarray(experts[n]))
        for w, e in zip(np.asarray(weights[n]), np.asarray(experts[n])):
            assert w == pytest.approx(6.0 * score[e], rel=1e-5)
    # what the three other families call is what it was
    again = moe.route(h, router, 3, scaling=6.0, bias=bias)
    same = moe.route(h, router, 3, scaling=6.0, bias=bias, renormalise=True)
    np.testing.assert_array_equal(again[0], same[0])
    np.testing.assert_allclose(again[0].sum(-1), 6.0, rtol=1e-5)


def test_zero_experts_against_a_loop():
    """Router outputs [E, E + Z) give the layer's input back: ten lines
    of numpy over every token and every pick."""
    case = _expert_case(np.random.default_rng(8))
    valid = jnp.arange(24)[None, :] < 20  # padding routes nowhere
    y, stats = moe.expert_layer(
        case["h"], {k: case[k] for k in ("router", "w_gate", "w_up",
                                         "w_down")},
        k=3, at=0, scaling=6.0, valid=valid, zero_experts=4,
        routing={"bias": case["bias"], "renormalise": False})
    weights, experts = moe.route(case["h"][0], case["router"], 3,
                                 scaling=6.0, bias=case["bias"],
                                 renormalise=False)
    want = np.zeros((24, 32))
    zero = loads = 0
    for n in range(20):
        for w, e in zip(np.asarray(weights[n]), np.asarray(experts[n])):
            x = np.asarray(case["h"][0, n], np.float64)
            if e >= 8:
                want[n] += w * x
                zero += 1
            else:
                g = x @ np.asarray(case["w_gate"][0, e])
                out = (g / (1 + np.exp(-g))
                       * (x @ np.asarray(case["w_up"][0, e]))
                       ) @ np.asarray(case["w_down"][0, e])
                want[n] += w * out
                loads += 1
    np.testing.assert_allclose(y[0], want, atol=2e-4)
    assert stats.shape == (5,) and int(stats[-1]) == zero > 0
    assert int(stats[moe.STATS.index("moe_idle_layers")]) == 0
    assert int(stats[0]) == loads
    assert moe.STATS + moe.ZERO_STATS == get_family("longcat").stats


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_the_shares_add_up_to_the_whole_layer(chips):
    """Over all ``chips`` shares, the shares' routed parts plus the
    zero-compute part counted once are the uncut reference's whole
    expert layer (every chip applies the identities to its own tokens, so
    a sum over chips of one token's layer counts them ``chips`` times)."""
    case = _expert_case(np.random.default_rng(9))
    held = 8 // chips
    experts = [tuple(case[k][0, e] for k in ("w_gate", "w_up", "w_down"))
               for e in range(8)]
    with jax.default_matmul_precision("highest"):
        routed, identity = reference.moe_layer(
            case["h"][0], case["router"], case["bias"], experts, first=0,
            zero=4, top_k=3, scaling=6.0)
    total = np.zeros((24, 32))
    hits = 0
    _, picks = moe.route(case["h"][0], case["router"], 3, bias=case["bias"],
                         renormalise=False)
    zero_picks = int((np.asarray(picks) >= 8).sum())
    for share in range(chips):
        mine = slice(share * held, (share + 1) * held)
        y, stats = moe.expert_layer(
            case["h"], {"router": case["router"],
                        **{k: case[k][:, mine]
                           for k in ("w_gate", "w_up", "w_down")}},
            k=3, at=0, share=share, scaling=6.0, zero_experts=4,
            routing={"bias": case["bias"], "renormalise": False})
        total += np.asarray(y[0]) - np.asarray(identity)
        hits += int(stats[0])
        assert int(stats[-1]) == zero_picks  # the same on every chip
        # the reference's own share is the program's
        mine_ref, _ = reference.moe_layer(
            case["h"][0], case["router"], case["bias"],
            experts[mine], first=share * held, zero=4, top_k=3, scaling=6.0)
        np.testing.assert_allclose(np.asarray(y[0]) - np.asarray(identity),
                                   mine_ref, atol=2e-4)
    np.testing.assert_allclose(total + np.asarray(identity),
                               np.asarray(routed) + np.asarray(identity),
                               atol=3e-4)
    assert hits + zero_picks == 24 * 3  # every assignment counted once


# Golden log-probabilities of the three MoE families that were here
# before (seeded tiny presets, float32, one prefill): their programs call
# ``route`` and ``expert_layer`` as they did, so these stand. The exact
# proof is scripts/hlo_digest.py on the parent and on the change
# (CHANGES.md, PR 41): 12 of 12 digests equal.
@pytest.mark.parametrize("preset", ["tiny-mixtral", "tiny-laguna",
                                    "tiny-lfm2"])
def test_the_other_moe_families_count_what_they_counted(preset):
    mc = get_model_config(preset).replace(dtype="float32")
    family = get_family(mc.arch)
    init, apply = build_model(mc)
    params = init(mc, jax.random.key(3))
    from production_stack_tpu.models.registry import block_state_shape

    layers, *sides = kv_page_sides(mc)
    kv = tuple(jnp.zeros((layers, 8, BS) + side) for side in sides)
    state = block_state_shape(mc)
    if state is not None:
        kv += (jnp.zeros((state[0], 8) + state[1:]),)
    T = 20
    tokens = (np.arange(T)[None] * 7 + 3) % 500
    pos = np.arange(T)[None]
    slots = BS + pos
    out = apply(params, mc, tokens, pos, kv, slots,
                np.asarray([[1, 2, 3, 4]]), np.asarray([T]),
                np.asarray([T]), mode="prefill", with_stats=True)
    if family.stats:
        assert family.stats == moe.STATS and out[2].shape == (4,)
        sparse = mc.num_layers - mc.dense_layers
        held_share = mc.num_experts / mc.published_experts
        assert 0 < int(out[2][0]) <= T * mc.experts_per_token * sparse
        if held_share == 1:
            assert int(out[2][0]) == T * mc.experts_per_token * sparse
    else:
        assert out[2] is None  # mixtral scans its layers: no counts
    assert np.isfinite(np.asarray(out[0])).all()


# --------------------------------------------------------------------- #
# The engine: scheduler, prefix cache, chunks, surfaces
# --------------------------------------------------------------------- #

def _engine(**overrides):
    config = dict(
        model="tiny-longcat", max_model_len=256, max_num_seqs=4,
        block_size=16, num_blocks=96, decode_steps=4, prefill_batch=1,
        prefill_chunk_size=32, dtype="float32")
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


def test_the_engine_serves_it_like_the_others(engine):
    """Chunked prefill (75 tokens in chunks of 32: cached latent path),
    burst decode, and the same prompt again from the prefix cache: token
    for token and logprob for logprob; the reference agrees on the
    tokens' log-probabilities."""
    first = _generate(engine, _prompt(75), 6, "a")
    cached_before = engine.cached_tokens_total
    again = _generate(engine, _prompt(75), 6, "b")
    assert engine.cached_tokens_total - cached_before == 64
    assert again[0] == first[0]
    np.testing.assert_allclose(again[1], first[1], atol=1e-5)
    tokens = np.asarray([_prompt(75) + first[0]], np.int32)
    # the engine's weights are drawn from its own seed (0)
    logp, _ = reference.forward(HF, engine.config.seed, tokens, [81],
                                keep_from=74, dtype="float32")
    for j, (tok, lp) in enumerate(zip(*first)):
        assert lp == pytest.approx(float(logp[0, j, tok]), abs=TOL)
    stats = engine.stats()
    assert stats["latent_decode_dispatch_total"]["xla"] >= 2
    assert stats["latent_decode_dispatch_total"]["pallas"] == 0
    assert engine.family_stats_total["moe_zero_assignments"] > 0
    records = engine.step_recorder.snapshot()
    pairs = [s["attn_pairs"] for s in records if s.get("attn_pairs")]
    # 75 tokens uncached, then the 11 behind the 64 cached
    assert {75 * 76 // 2, (75 * 76 - 64 * 65) // 2} <= set(pairs)
    assert any("moe_zero_assignments" in s for s in records)


def test_extract_gives_each_side_its_own_shape_and_inject_takes_it(engine):
    prompt = _prompt(50, salt=3)
    _generate(engine, prompt, 2, "c")
    got = engine.extract_kv(prompt)
    assert got["num_tokens"] == 48
    k, v = np.asarray(got["k"]), np.asarray(got["v"])
    assert k.shape == (3, 4, 16, 1, 128) and v.shape == (3, 4, 16, 1, 16)
    tokens = np.asarray([prompt], np.int32)
    _, kv = reference.forward(HF, engine.config.seed, tokens, [50],
                              keep_from=49, dtype="float32",
                              kv_layers=(0, 3))
    for layer in (0, 3):
        np.testing.assert_allclose(
            k[:, layer].reshape(48, 1, 128), kv[layer][0][0, :48], atol=TOL)
        np.testing.assert_allclose(
            v[:, layer].reshape(48, 1, 16), kv[layer][1][0, :48], atol=TOL)
    other = _engine()
    try:
        assert other.inject_kv_blocks(
            got["hashes"], k.swapaxes(0, 1), v.swapaxes(0, 1)) == 3
        back = other.extract_kv(prompt)
        np.testing.assert_array_equal(np.asarray(back["k"]), k)
        np.testing.assert_array_equal(np.asarray(back["v"]), v)
        # the lanes past the key are zeros in the pool
        assert not np.asarray(other.kv[1])[..., 16:].any()
    finally:
        other.stop()


def test_preemption_by_recompute_gives_the_same_answer():
    """A pool too small for three long answers at once: a sequence is
    preempted and recomputed, and answers as it does alone."""
    eng = _engine(num_blocks=14, max_num_seqs=3)
    eng.start()
    try:
        alone = _generate(eng, _prompt(40, 1), 40, "alone")
        results = {}

        def submit(rid, prompt):
            q: "queue.Queue" = queue.Queue()
            eng.add_request(
                rid, prompt, SamplingParams(temperature=0.0, max_tokens=40,
                                            ignore_eos=True, logprobs=1),
                lambda token, finish: q.put((token, finish)))
            return q

        queues = {rid: submit(rid, _prompt(40, salt))
                  for rid, salt in (("x", 1), ("y", 2), ("z", 5))}
        for rid, q in queues.items():
            tokens = []
            while True:
                token, finish = q.get(timeout=300)
                if token is not None:
                    tokens.append(int(token[0]))
                if finish is not None:
                    break
            results[rid] = tokens
        assert results["x"] == alone[0]
        assert eng.stats()["num_preempted_total"] >= 1
    finally:
        eng.stop()


REFUSED = [
    ({"speculative_num_tokens": 2}, "--speculative-num-tokens"),
    ({"speculative_draft_model": "tiny-llama", "speculative_num_tokens": 2},
     "--speculative-draft-model"),
    ({"kv_offload_bytes": 1 << 20}, "--kv-offload-bytes"),
    ({"kv_remote_url": "http://127.0.0.1:1"}, "--kv-remote-url"),
    ({"kv_cache_dtype": "int8"}, "--kv-cache-dtype int8"),
    ({"quantization": "int8"}, "int8 quantization is supported"),
    ({"pipeline_parallel_size": 2}, "pipeline_parallel_size"),
]


@pytest.mark.parametrize("flags, says", REFUSED,
                         ids=[says for _, says in REFUSED])
def test_start_up_refuses_what_the_page_sides_are_not_taught(flags, says):
    """One list: every surface that moves page bytes, or reads a page as
    grouped keys and values, and is not taught two shapes."""
    devices = jax.devices()[:2 if "pipeline_parallel_size" in flags else 1]
    with pytest.raises(ValueError, match=says):
        EngineCore(EngineConfig(
            model="tiny-longcat", max_model_len=128, block_size=16,
            num_blocks=32, dtype="float32", **flags), devices=devices)


def test_a_mesh_of_several_devices_is_refused():
    with pytest.raises(ValueError, match="mesh of several devices"):
        EngineCore(EngineConfig(
            model="tiny-longcat", max_model_len=128, block_size=16,
            num_blocks=32, dtype="float32", tensor_parallel_size=2),
            devices=jax.devices()[:2])


def test_the_server_streams_and_its_kv_transfer_routes_answer_501():
    """The normal server on this family: a streamed ``/v1/completions``,
    the new dispatch counter on ``/metrics``, and the KV-transfer routes
    (whose wire formats carry one shape) refused."""
    import asyncio

    import aiohttp

    from production_stack_tpu.engine.server import (
        EngineServer,
        run_engine_server,
    )

    server = EngineServer(EngineConfig(
        model="tiny-longcat", max_model_len=128, max_num_seqs=2,
        block_size=16, num_blocks=48, dtype="float32", max_loras=0))

    async def run():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                body = {"model": "tiny-longcat", "prompt": _prompt(40),
                        "max_tokens": 5, "ignore_eos": True, "stream": True,
                        "temperature": 0.0}
                async with s.post(f"{base}/v1/completions",
                                  json=body) as resp:
                    assert resp.status == 200
                    text = (await resp.read()).decode()
                    assert text.count("data: ") >= 2 and "[DONE]" in text
                for path in ("/kv/extract", "/kv/inject", "/kv/pull"):
                    async with s.post(f"{base}{path}",
                                      json={"prompt": [1, 2, 3]}) as resp:
                        assert resp.status == 501, path
                        assert "unequal width" in (
                            await resp.json())["error"]
                async with s.get(f"{base}/metrics") as resp:
                    metrics = await resp.text()
                assert "tpu:latent_decode_dispatch_total{" in metrics
        finally:
            await runner.cleanup()
            server.core.stop()

    asyncio.run(run())
