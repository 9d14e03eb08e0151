"""The expert layer's Pallas grouped matmul (ops/pallas_grouped_matmul.py)
in interpret mode on the CPU, against ``jax.lax.ragged_dot`` at tiny
widths; ``expert_layer`` with the path forced each way; and the path and
tile functions over the shapes the benchmark's configurations run."""

import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import moe
from production_stack_tpu.ops import pallas_grouped_matmul as gmm

BF16, F32 = jnp.bfloat16, jnp.float32

# name: (m, k, n, sizes of the layer's groups, layers, at, (tm, tk, tn))
CASES = {
    "empty_groups_between_full_ones":
        (64, 256, 256, [16, 0, 0, 32, 0, 16], 1, 0, (16, 256, 128)),
    "group_straddles_a_row_tile":
        (64, 256, 256, [5, 30, 3, 26], 1, 0, (16, 256, 128)),
    "rows_past_the_last_group":
        (96, 256, 128, [7, 0, 9, 4], 1, 0, (32, 256, 128)),
    "no_rows_at_all":
        (32, 256, 256, [0, 0, 0, 0], 2, 1, (16, 256, 128)),
    "stack_first_layer":
        (64, 256, 128, [20, 1, 0, 43], 3, 0, (16, 256, 128)),
    "stack_middle_layer":
        (64, 256, 128, [20, 1, 0, 43], 3, 1, (16, 256, 128)),
    "stack_last_layer":
        (64, 256, 128, [20, 1, 0, 43], 3, 2, (16, 256, 128)),
    "one_live_row_of_128_slots":  # LFM2's decode: 32 rows x top 4
        (128, 256, 256, [0, 1, 0, 0, 1, 0, 1, 1], 2, 1, (128, 256, 128)),
    "one_group_with_rows":
        (64, 256, 384, [0, 0, 40, 0], 2, 1, (16, 256, 128)),
    "m_not_a_multiple_of_128":  # 160 = 5 x 32 row slots
        (160, 256, 128, [50, 60, 0, 45], 1, 0, (32, 256, 128)),
    "k_greater_than_n":
        (64, 512, 128, [10, 22, 32], 2, 1, (32, 512, 128)),
    "n_greater_than_k":  # three n tiles: the fetch order crosses them
        (64, 128, 768, [10, 22, 32], 2, 1, (32, 128, 256)),
}


# The gate of a gated unit: none (one matmul), the kernel's default
# ``silu``, and ``relu`` (ReGLU: models/smallthinker.py, PR 51).
GATED, GATED_IDS = [None, "silu", "relu"], ["plain", "gated", "relu"]
ACT = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _activation(gated):
    """The kernel's keyword: left out for ``silu``, which is its default
    (the accepted families' programs name none)."""
    return {"activation": gated} if gated == "relu" else {}


@pytest.mark.parametrize("gated", GATED, ids=GATED_IDS)
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_ragged_dot(case, dtype, gated):
    """The kernel against ``ragged_dot`` on the live rows; ``gated``: the
    two matmuls of a gated unit in one pass under that activation,
    against ``silu`` (or ``relu``: PR 51) of one ``ragged_dot`` times
    another."""
    m, k, n, sizes, layers, at, tiles = CASES[case]
    held = len(sizes)
    keys = jax.random.split(jax.random.key(len(case)), 3)
    lhs = jax.random.normal(keys[0], (m, k), F32).astype(dtype)
    stack, gate = ((jax.random.normal(key, (layers, held, k, n), F32)
                    / np.sqrt(k)).astype(dtype) for key in keys[1:])
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())

    visits = gmm.group_visits(sizes, m, tiles[0])
    got = gmm.pallas_grouped_matmul(
        lhs, stack.reshape(layers * held, k, n), visits,
        jnp.int32(at * held), tiles=tiles, interpret=True,
        gate=gate.reshape(layers * held, k, n) if gated else None,
        **_activation(gated))

    def ragged(w):
        return jax.lax.ragged_dot(lhs, w[at], sizes,
                                  preferred_element_type=F32).astype(dtype)

    want = ragged(stack)
    if gated:
        want = ACT[gated](ragged(gate).astype(F32)).astype(dtype) * want
    assert got.shape == (m, n) and got.dtype == dtype
    # Float32 accumulation on both sides: the same values but for the
    # order of the sums.
    np.testing.assert_allclose(
        np.asarray(got[:live], np.float32), np.asarray(want[:live], np.float32),
        rtol=2e-2 if dtype == BF16 else 1e-5, atol=1e-5)


@pytest.mark.parametrize("sizes,m,tm", [
    ([5, 30, 3, 26], 64, 16), ([0, 0, 0, 0], 32, 16), ([64], 64, 16),
    ([0, 1, 0, 0, 1, 0, 1, 1], 128, 128), ([16, 16, 16, 16], 128, 16),
    ([1] * 7, 64, 32)])
def test_group_visits_are_the_pairs_that_hold_rows(sizes, m, tm):
    visits = gmm.group_visits(jnp.asarray(sizes, jnp.int32), m, tm)
    want, row = [], 0
    for g, size in enumerate(sizes):
        want += [(g, t) for t in range(row // tm, (row + size - 1) // tm + 1)
                 if size]
        row += size
    count = int(visits.visit_ends[-1])
    assert count == len(want) <= m // tm + len(sizes) - 1
    assert list(zip(np.asarray(visits.groups)[:count].tolist(),
                    np.asarray(visits.row_tiles)[:count].tolist())) == want
    assert np.asarray(visits.ends).tolist() == list(np.cumsum(sizes))
    # The groups with rows in the order their weights are fetched.
    ranked = [g for g, size in enumerate(sizes) if size]
    assert np.asarray(visits.by_rank)[:len(ranked)].tolist() == ranked
    assert [int(visits.ranks[g]) for g in ranked] == list(range(len(ranked)))
    assert int(visits.ranks[-1]) + (sizes[-1] > 0) == len(ranked)
    # What is past the count is never run, and names something valid.
    assert 0 <= int(visits.groups.min()) and int(visits.groups.max()) < len(sizes)
    assert 0 <= int(visits.row_tiles.min())
    assert int(visits.row_tiles.max()) < m // tm
    # The part of them that no row tile enters is what one tile takes.
    one = gmm.one_tile_visits(jnp.asarray(sizes, jnp.int32),
                              jnp.zeros((m,), jnp.int32))
    assert np.asarray(one.count).tolist() == [len(ranked)]
    assert np.asarray(one.by_rank).tolist() == np.asarray(
        visits.by_rank).tolist()
    assert one.group_of_row.shape == (m, 1)


# One row tile of 32 rows in the tokens' order over 4 held groups: each
# row's group, ``4`` for a row that is nobody's here (another chip's
# expert, a padding row).
ONE_TILE_ROWS = {
    "no_row_at_all": [4] * 32,
    "one_group": [2 if row in (3, 9, 20) else 4 for row in range(32)],
    "every_group": [row % 4 for row in range(32)],  # more than the ring
    "two_rows_on_one_expert": [1 if row in (5, 6) else 4 for row in range(32)],
    "other_chips_and_padding_mixed_in":
        [(0, 4, 3, 4, 4, 1, 4)[row % 7] for row in range(24)] + [4] * 8,
    "the_first_and_the_last_group":
        [(0, 3, 4)[row % 3] for row in range(32)],
}


def _one_tile_case(case, dtype, gated):
    """(kernel arguments, the rows' groups, live rows) of a case of
    :data:`ONE_TILE_ROWS`: 2 layers of 4 groups ``[128, 256]``, layer 1,
    two n tiles."""
    m, k, n, held, layers, at = 32, 128, 256, 4, 2, 1
    keys = jax.random.split(jax.random.key(len(case)), 3)
    lhs = jax.random.normal(keys[0], (m, k), F32).astype(dtype)
    stack, gate = ((jax.random.normal(key, (layers * held, k, n), F32)
                    / np.sqrt(k)).astype(dtype) for key in keys[1:])
    group = jnp.asarray(ONE_TILE_ROWS[case], jnp.int32)
    sizes = gmm.group_sizes(group, held)
    kwargs = dict(first_group=jnp.int32(at * held), tiles=(m, k, 128),
                  interpret=True, gate=gate if gated else None,
                  **_activation(gated))
    return lhs, stack, group, sizes, kwargs


@pytest.mark.parametrize("gated", GATED, ids=GATED_IDS)
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", ONE_TILE_ROWS)
def test_one_tile_in_the_tokens_order_is_the_sorted_path_bit_for_bit(
        case, dtype, gated):
    """Where the rows are one row tile the kernel takes them as they
    come, with each row's group beside them: on every row that has a
    group the same bits as the sorted path permuted back, and
    ``ragged_dot``'s values."""
    lhs, stack, group, sizes, kwargs = _one_tile_case(case, dtype, gated)
    m, held = lhs.shape[0], sizes.shape[0]
    live = np.asarray(group) < held
    got = gmm.pallas_grouped_matmul(
        lhs, stack, gmm.one_tile_visits(sizes, group), **kwargs)
    assert got.shape == (m, stack.shape[2]) and got.dtype == dtype

    order = jnp.argsort(group, stable=True)
    back = jnp.argsort(order)
    by_group = gmm.pallas_grouped_matmul(
        lhs[order], stack, gmm.group_visits(sizes, m, m), **kwargs)[back]
    assert np.array_equal(np.asarray(got, np.float32)[live],
                          np.asarray(by_group, np.float32)[live])

    def ragged(w):
        return jax.lax.ragged_dot(
            lhs[order], w[held:], sizes, preferred_element_type=F32
        ).astype(dtype)[back]

    want = ragged(stack)
    if gated:
        want = ACT[gated](ragged(kwargs["gate"]).astype(F32)
                          ).astype(dtype) * want
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        rtol=2e-2 if dtype == BF16 else 1e-5, atol=1e-5)


@pytest.mark.parametrize("gated", GATED, ids=GATED_IDS)
@pytest.mark.parametrize("case", ONE_TILE_ROWS)
def test_one_tile_copies_the_groups_that_have_rows_and_no_other(
        monkeypatch, case, gated):
    """The copies the kernel starts, counted as they run: one a stack, n
    tile and group that has rows, so none at all in an idle layer, where
    the sorted path reads a whole expert (``rhs[first_group]``) for
    nothing. Every group without rows is poisoned, that one among them,
    and no row that has a group sees it."""
    lhs, stack, group, sizes, kwargs = _one_tile_case(case, BF16, gated)
    held = sizes.shape[0]
    hit = int((sizes > 0).sum())
    poison = jnp.concatenate([jnp.ones((held,), bool), sizes == 0])
    stack = jnp.where(poison[:, None, None], jnp.nan, stack)
    if gated:
        kwargs["gate"] = jnp.where(poison[:, None, None], jnp.nan,
                                   kwargs["gate"])
    started = []
    make_copy = gmm.pltpu.make_async_copy

    class Counted:
        def __init__(self, *args):
            self.copy = make_copy(*args)
            self.wait = self.copy.wait

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

    monkeypatch.setattr(gmm.pltpu, "make_async_copy", Counted)

    def copies(visits, lhs):
        started.clear()
        # not the jitted entry point: its cache would hold the real copies
        out = jax.block_until_ready(gmm.pallas_grouped_matmul.__wrapped__(
            lhs, stack, visits, **kwargs))
        jax.effects_barrier()
        return len(started), out

    tiles_n, stacks = stack.shape[2] // 128, 1 + bool(gated)
    count, out = copies(gmm.one_tile_visits(sizes, group), lhs)
    assert count == tiles_n * stacks * hit
    live = np.asarray(group) < held
    assert not np.isnan(np.asarray(out, np.float32)[live]).any()
    order = jnp.argsort(group, stable=True)
    count, _ = copies(gmm.group_visits(sizes, lhs.shape[0], lhs.shape[0]),
                      lhs[order])
    assert count == tiles_n * stacks * max(hit, 1)


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("share,tokens", [(0, 16), (1, 16), (0, 3), (0, 24),
                                          (1, 24)])
def test_expert_layer_is_the_same_on_either_path(monkeypatch, share, tokens,
                                                 activation):
    """``expert_layer`` with the kernel forced (interpreted here) and with
    ``ragged_dot``, under either gate activation (``relu``: PR 51; all
    three paths, ``pallas``, ``pallas_one_tile`` and ``xla``, against the
    experts applied densely in ``jax.numpy``, with the routing handed in
    by the caller as models/smallthinker.py hands it): the same ``y`` and
    the same ``STATS``, for a chip that
    holds every expert and for one that holds a block of them, with
    padding rows; 16 tokens' 32 row slots are one row tile and stay in
    the tokens' order, 24 tokens' 48 are three tiles of 16 and are
    sorted; three tokens' six row slots do not tile and take
    ``ragged_dot`` whatever the platform."""
    hidden, width, held, layers, top = 128, 256, 4, 3, 2
    published = held * (2 if share else 1)
    keys = jax.random.split(jax.random.key(7), 5)
    p = {"router": jax.random.normal(keys[0], (hidden, published), F32
                                     ).astype(BF16),
         "w_gate": (jax.random.normal(keys[1], (layers, held, hidden, width))
                    / np.sqrt(hidden)).astype(BF16),
         "w_up": (jax.random.normal(keys[2], (layers, held, hidden, width))
                  / np.sqrt(hidden)).astype(BF16),
         "w_down": (jax.random.normal(keys[3], (layers, held, width, hidden))
                    / np.sqrt(width)).astype(BF16)}
    h = jax.random.normal(keys[4], (1, tokens, hidden), F32).astype(BF16)
    valid = jnp.arange(tokens)[None, :] < tokens - 2

    def run(pallas):
        monkeypatch.setattr(gmm, "_use_pallas", lambda: pallas)
        gmm.TRACED_PATHS.clear()
        y, stats = jax.jit(lambda h: moe.expert_layer(
            h, p, k=top, at=jnp.int32(1), share=share, scaling=2.5,
            valid=valid, **({} if activation == "silu" else
                            {"activation": activation})))(h)
        return y, stats, dict(gmm.TRACED_PATHS)

    y_xla, stats_xla, traced = run(False)
    assert traced == {("grouped_matmul", "xla"): 1}
    y, stats, traced = run(True)
    assert traced == {("grouped_matmul", {
        16: "pallas_one_tile", 24: "pallas", 3: "xla"}[tokens]): 1}
    assert np.asarray(stats).tolist() == np.asarray(stats_xla).tolist()
    assert int(stats[0]) > 0
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_xla, np.float32),
                               rtol=2e-2, atol=2e-2)
    assert not np.asarray(jnp.isnan(y.astype(F32))).any()
    # against jnp: every held expert applied densely to every token under
    # the routing, handed to ``expert_layer`` by its caller this time
    x = h.reshape(tokens, hidden)
    weights, experts = moe.route(x, p["router"], top, scaling=2.5)
    handed, handed_stats = jax.jit(lambda h: moe.expert_layer(
        h, {k: v for k, v in p.items() if k != "router"}, k=top,
        at=jnp.int32(1), share=share, valid=valid, activation=activation,
        routed=(weights, experts)))(h)
    assert np.array_equal(np.asarray(handed, np.float32),
                          np.asarray(y, np.float32))
    assert np.asarray(handed_stats).tolist() == np.asarray(stats).tolist()
    dense = jnp.zeros((tokens, hidden), F32)
    for e in range(held):
        unit = (ACT[activation]((x @ p["w_gate"][1, e]).astype(F32)
                                ).astype(BF16) * (x @ p["w_up"][1, e])
                ) @ p["w_down"][1, e]
        w = jnp.sum(jnp.where(experts == share * held + e, weights, 0.0), -1)
        dense = dense + (w * valid[0])[:, None] * unit.astype(F32)
    np.testing.assert_allclose(np.asarray(y, np.float32)[0],
                               np.asarray(dense), rtol=3e-2, atol=3e-2)
    if activation == "relu":  # and it is not the other function
        other, _ = jax.jit(lambda h: moe.expert_layer(
            h, p, k=top, at=jnp.int32(1), share=share, scaling=2.5,
            valid=valid))(h)
        assert np.abs(np.asarray(other, np.float32)
                      - np.asarray(y, np.float32)).max() > 0.1


# (hidden, expert width, held experts, top k) and the tokens of the step
# programs each configuration's cell compiles: decode rows, the prefill
# ladder's rungs, the plain-prefill groups.
LAGUNA = (3072, 1024, 64, 10)
LFM2 = (2048, 1536, 64, 4)
MIXTRAL = (4096, 14336, 8, 2)
TOKENS = (32, 64, 128, 256, 384, 512, 640, 768, 896, 1024, 2 * 640, 2 * 768,
          4 * 384, 4 * 512, 2048)


@pytest.mark.parametrize("widths", [LAGUNA, LFM2, MIXTRAL],
                         ids=["laguna", "lfm2", "mixtral"])
def test_every_shape_of_the_families_tiles(monkeypatch, widths):
    """On the TPU every step program of the three families takes the
    kernel, in both orientations, with tiles that divide, a weights block
    within the budget and no smaller than a quarter of it, and one row
    tile for the layer."""
    hidden, width, held, top = widths
    monkeypatch.setattr(gmm, "_use_pallas", lambda: True)
    for tokens in TOKENS:
        m = tokens * top
        row_tiles = set()
        for k, n in ((hidden, width), (width, hidden)):
            tm, tk, tn = gmm.grouped_matmul_tiles(m, k, n, BF16, held)
            assert gmm.grouped_matmul_path(m, k, n, BF16, held) == (
                "pallas_one_tile" if m == tm else "pallas")
            assert m % tm == 0 and tk == k and n % tn == 0
            assert tm % 16 == 0 and tn % 128 == 0
            assert tk * tn * 2 <= gmm.RHS_TILE_BYTES
            assert tk * tn * 2 >= gmm.RHS_TILE_BYTES // 4  # large tiles
            row_tiles.add(tm)
        assert len(row_tiles) == 1
    assert gmm.grouped_matmul_tiles(1280, 3072, 1024, BF16, 64) == (
        128, 3072, 512)
    assert gmm.grouped_matmul_tiles(1280, 1024, 3072, BF16, 64) == (
        128, 1024, 1536)
    assert gmm.grouped_matmul_tiles(128, 2048, 1536, BF16, 64) == (
        128, 2048, 768)
    assert gmm.grouped_matmul_tiles(128, 1536, 2048, BF16, 64) == (
        128, 1536, 1024)
    # One row tile: the sessions cells' decode programs (32 rows x top 4),
    # and nothing of Laguna's (its 32 rows x top 10 are five tiles of 64).
    one_tile = [tokens for tokens in TOKENS if gmm.grouped_matmul_path(
        tokens * top, hidden, width, BF16, held) == "pallas_one_tile"]
    assert one_tile == {LAGUNA: [], LFM2: [32], MIXTRAL: [32, 64]}[widths]


@pytest.mark.parametrize("m,k,n,dtype,why", [
    (1280, 3072, 1024, BF16, "off the TPU"),
    (20, 128, 256, BF16, "rows that are no multiple of 16"),
    (1280, 64, 128, BF16, "a contraction narrower than the lanes"),
    (1280, 128, 192, BF16, "a width that is no multiple of 128"),
    (1280, 3072, 1024, jnp.int8, "a dtype the kernel does not take"),
])
def test_what_does_not_tile_takes_ragged_dot(monkeypatch, m, k, n, dtype, why):
    monkeypatch.setattr(gmm, "_use_pallas", lambda: why != "off the TPU")
    assert gmm.grouped_matmul_path(m, k, n, dtype, 64) == "xla", why


@pytest.mark.parametrize("model,counts", [
    ("tiny-laguna", True), ("tiny-mixtral", True), ("tiny-llama", False)])
def test_the_engine_counts_step_programs_by_their_path(model, counts):
    """``expert_matmul_dispatch_total{path}``: one count per dispatched
    prefill and decode program of a model with an expert layer, under the
    path the layer's trace chose (``xla`` on the CPU); nothing for a
    dense model; both labels always there."""
    eng = EngineCore(EngineConfig(
        model=model, max_model_len=256, max_num_seqs=4, block_size=8,
        num_blocks=64, decode_steps=4, prefill_batch=1,
        enable_prefix_caching=False), devices=jax.devices()[:1])
    gmm.TRACED_PATHS.clear()
    eng.start()
    try:
        q: "queue.Queue" = queue.Queue()
        eng.add_request(
            "one", [(7 * i) % 200 + 1 for i in range(30)],
            SamplingParams(temperature=0.0, max_tokens=9, ignore_eos=True),
            lambda token, finish: q.put((token, finish)))
        while q.get(timeout=120)[1] is None:
            pass
    finally:
        eng.stop()
    stats = eng.stats()
    programs = sum(
        1 for r in eng.step_recorder.snapshot()
        if r.get("program", "").startswith(("prefill", "decode")))
    assert programs >= 3
    assert stats["expert_matmul_dispatch_total"] == {
        "pallas": 0, "pallas_one_tile": 0, "xla": programs if counts else 0}
    assert (gmm.TRACED_PATHS["grouped_matmul", "xla"] > 0) == counts
    assert gmm.TRACED_PATHS["grouped_matmul", "pallas"] == 0
    assert gmm.TRACED_PATHS["grouped_matmul", "pallas_one_tile"] == 0


def test_a_program_that_spans_devices_keeps_ragged_dot(monkeypatch):
    """The compiler cannot partition a ``pallas_call``: under
    ``spanning_devices`` (the engine wraps a mesh's forward in it) the
    trace-time choice is ``xla`` whatever the platform and the shape."""
    monkeypatch.setattr(gmm, "_use_pallas", lambda: True)
    shape = (1280, 3072, 1024, BF16, 64)
    assert gmm.grouped_matmul_path(*shape) == "pallas"
    assert gmm.grouped_matmul_path(*shape, devices=4) == "xla"
    gmm.TRACED_PATHS.clear()
    assert gmm.on_devices(gmm.traced_path, 4)(*shape) == "xla"
    assert gmm.traced_path(*shape) == "pallas"
    assert dict(gmm.TRACED_PATHS) == {("grouped_matmul", "xla"): 1,
                                      ("grouped_matmul", "pallas"): 1}
