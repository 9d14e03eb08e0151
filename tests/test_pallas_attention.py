"""Pallas paged-attention kernel parity (interpret mode, runs on the CPU
test mesh): the kernel must match the XLA reference bit-for-tolerance on
ragged contexts, GQA head groups, multi-chunk tables, and layer
indexing — the decode hot path's correctness pin (the real-TPU numbers
come from benchmarks/dispatch_accounting.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops.attention import paged_attention_reference
from production_stack_tpu.ops.pallas_paged_attention import (
    pallas_paged_attention,
)


def _setup(B, H, KVH, D, L, NB, bs, MAXB, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k_pages = jnp.asarray(
        rng.normal(size=(L, NB, bs, KVH, D)), jnp.float32)
    v_pages = jnp.asarray(
        rng.normal(size=(L, NB, bs, KVH, D)), jnp.float32)
    # Distinct pages per sequence, shuffled (scattered like real tables).
    tables = np.zeros((B, MAXB), np.int32)
    perm = rng.permutation(NB)[: B * MAXB].reshape(B, MAXB)
    tables[:, :] = perm
    ctx = rng.integers(1, MAXB * bs + 1, size=(B,)).astype(np.int32)
    return q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(ctx)


@pytest.mark.parametrize("H,KVH", [(16, 8), (24, 8), (8, 8)])
@pytest.mark.parametrize("MAXB", [4, 16])
def test_kernel_matches_reference(H, KVH, MAXB):
    B, D, L, bs = 4, 128, 3, 16
    NB = B * MAXB + 2
    q, k_pages, v_pages, tables, ctx = _setup(B, H, KVH, D, L, NB, bs, MAXB)
    for layer in (0, L - 1):
        ref = paged_attention_reference(
            q, k_pages, v_pages, tables, ctx, jnp.int32(layer), scale=0.1)
        got = pallas_paged_attention(
            q, k_pages, v_pages, tables, ctx, jnp.int32(layer),
            scale=0.1, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_kernel_single_token_context():
    """ctx=1 per sequence (first decode step after a 1-token prompt)."""
    B, H, KVH, D, L, bs, MAXB = 2, 16, 8, 128, 2, 16, 4
    NB = 16
    q, k_pages, v_pages, tables, _ = _setup(B, H, KVH, D, L, NB, bs, MAXB)
    ctx = jnp.ones((B,), jnp.int32)
    ref = paged_attention_reference(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.08)
    got = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1),
        scale=0.08, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_kernel_ragged_contexts_ignore_padded_pages():
    """Garbage in pages beyond each sequence's context must not leak."""
    B, H, KVH, D, L, bs, MAXB = 3, 16, 8, 128, 1, 16, 8
    NB = 40
    q, k_pages, v_pages, tables, _ = _setup(B, H, KVH, D, L, NB, bs, MAXB)
    k_pages = k_pages.at[:, 0].set(1e9)  # poison page 0
    v_pages = v_pages.at[:, 0].set(1e9)
    tables = tables.at[:, 2:].set(0)  # padded entries point at poison
    ctx = jnp.asarray([bs * 2, bs, 5], jnp.int32)  # all within 2 pages
    ref = paged_attention_reference(
        q, k_pages, v_pages, tables, ctx, jnp.int32(0), scale=0.1)
    got = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(0),
        scale=0.1, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)
    assert np.isfinite(np.asarray(got)).all()


def test_chunked_context_prefill_matches_einsum(monkeypatch):
    """The online-softmax (flash-structure) cached-prefill path must
    match the one-shot einsum path bit-for-tolerance (it engages
    automatically when the scores temp would exceed ~1 GB; forced here
    at toy shapes)."""
    import production_stack_tpu.ops.attention as att

    B, T, H, KVH, D, L, bs, MAXB = 3, 16, 12, 4, 32, 2, 16, 8
    NB = B * MAXB + 2
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k_pages = jnp.asarray(rng.normal(size=(L, NB, bs, KVH, D)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(L, NB, bs, KVH, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(NB)[: B * MAXB].reshape(B, MAXB).astype(np.int32))
    # Suffix queries at absolute positions near the context end.
    total = jnp.asarray([100, 77, 128], jnp.int32)
    positions = jnp.stack([t - T + jnp.arange(T) for t in total])

    ref = att.context_prefill_attention(
        q, k_pages, v_pages, tables, positions, total, jnp.int32(1),
        scale=0.11)
    monkeypatch.setattr(att, "_CHUNKED_SCORE_BYTES", 0)
    monkeypatch.setattr(att, "_CHUNKED_SCORE_SPAN", 32)
    got = att.context_prefill_attention(
        q, k_pages, v_pages, tables, positions, total, jnp.int32(1),
        scale=0.11)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # Ragged tail: a span that does NOT divide S pads with masked zero
    # pages and must still match.
    monkeypatch.setattr(att, "_CHUNKED_SCORE_SPAN", 48)
    got_ragged = att.context_prefill_attention(
        q, k_pages, v_pages, tables, positions, total, jnp.int32(1),
        scale=0.11)
    np.testing.assert_allclose(
        np.asarray(got_ragged), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_kernel_ring_crosses_many_short_sequences():
    """v3's prefetch window is indexed by a GLOBAL grid step, so with
    single-chunk sequences the depth-6 ring spans six DIFFERENT
    sequences at once — mixed tiny/ragged contexts must still match the
    reference exactly (exercises ring wraparound + the same-predicate
    issue/wait pairing at every boundary)."""
    B, H, KVH, D, L, bs, MAXB = 8, 16, 8, 128, 2, 16, 8
    NB = B * MAXB + 2
    q, k_pages, v_pages, tables, _ = _setup(B, H, KVH, D, L, NB, bs, MAXB)
    ctx = jnp.asarray([1, 16, 5, 128, 64, 2, 33, 100], jnp.int32)
    for layer in (0, L - 1):
        ref = paged_attention_reference(
            q, k_pages, v_pages, tables, ctx, jnp.int32(layer), scale=0.1)
        got = pallas_paged_attention(
            q, k_pages, v_pages, tables, ctx, jnp.int32(layer),
            scale=0.1, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)


# -- work follows the live tokens of the live rows (PR 32) -----------------
#
# bs 16, so a chunk is 8 pages: the widest the tile chooser takes, and at
# this page size the narrowest too (whole 128-lane tiles of tokens).
BS, CHUNK = 16, 128


def live_tables(ctx, maxb, bs, rng):
    """(tables [B, maxb], NB): each row's live pages distinct and
    shuffled over a pool of NB, the table zero past them (as the engine
    leaves it); page 0 belongs to no row."""
    pages = [-(-max(int(c), 0) // bs) for c in ctx]
    NB = sum(pages) + 3
    free = rng.permutation(np.arange(1, NB))
    tables = np.zeros((len(ctx), maxb), np.int32)
    at = 0
    for b, n in enumerate(pages):
        tables[b, :n] = free[at:at + n]
        at += n
    return tables, NB


def dead_slots(tables, ctx, NB, bs):
    """[NB, bs] bool: the token slots no live context holds."""
    dead = np.ones((NB, bs), bool)
    for row, c in zip(np.asarray(tables), np.asarray(ctx)):
        for i in range(max(int(c), 0)):
            dead[row[i // bs], i % bs] = False
    return jnp.asarray(dead)


def _live_setup(ctx, MAXB, seed=0, H=16, KVH=8, D=128, L=2, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    tables, NB = live_tables(ctx, MAXB, BS, rng)
    q = jnp.asarray(rng.normal(size=(len(ctx), H, D)), dtype)
    k_pages = jnp.asarray(rng.normal(size=(L, NB, BS, KVH, D)), dtype)
    v_pages = jnp.asarray(rng.normal(size=(L, NB, BS, KVH, D)), dtype)
    return (q, k_pages, v_pages, jnp.asarray(tables),
            jnp.asarray(np.asarray(ctx, np.int32)))


def _edges(MAXB):
    full = MAXB * BS
    short = [1, BS - 1, BS, BS + 1, CHUNK - 1, CHUNK, CHUNK + 1]
    return [min(c, full) for c in short]


@pytest.mark.parametrize("empty", [0, -3], ids=["zero", "negative"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_rows_that_hold_nothing_give_zeros(empty, dtype):
    """A context of 0 or less starts no copy and computes nothing: its
    output is zeros (the reference's too), and the live rows read bit for
    bit what they read with no such row among them."""
    ctx = [empty, 40, empty, empty, CHUNK + 5, 3 * CHUNK, empty, 1, empty]
    q, k_pages, v_pages, tables, ctx = _live_setup(ctx, 32, dtype=dtype)
    live = np.asarray(ctx) > 0
    got = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1,
        interpret=True)
    ref = paged_attention_reference(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1)
    assert not np.asarray(got, np.float32)[~live].any()
    assert not np.asarray(ref, np.float32)[~live].any()
    tol = 2e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)
    alone = pallas_paged_attention(
        q[live], k_pages, v_pages, tables[live], ctx[live], jnp.int32(1),
        scale=0.1, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32)[live], np.asarray(alone, np.float32))


@pytest.mark.parametrize("MAXB", [4, 8, 16, 32, 64])
def test_context_edges_with_one_long_row_among_short_ones(MAXB):
    """Contexts at every edge of a page and of a chunk, and one row that
    fills the table, for each table bucket: the table's width decides
    nothing but the longest context it can hold."""
    ctx = _edges(MAXB)
    ctx.insert(3, MAXB * BS)
    q, k_pages, v_pages, tables, ctx = _live_setup(ctx, MAXB, seed=MAXB)
    ref = paged_attention_reference(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1)
    got = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1,
        interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)
    if MAXB < 64:
        # the same rows under a wider table read the same, bit for bit
        short = np.asarray(ctx) < MAXB * BS
        wide = jnp.pad(tables, ((0, 0), (0, 64 - MAXB)))
        again = pallas_paged_attention(
            q, k_pages, v_pages, wide, ctx, jnp.int32(1), scale=0.1,
            interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got)[short], np.asarray(again)[short])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_nothing_past_a_context_reaches_the_result(dtype):
    """Every page no live context holds and every token slot past a
    context filled with NaN (keys) and Inf (values): the output is the
    same, bit for bit, and finite. A probability of exactly 0 does not
    silence a NaN in the p @ v dot, and a page that was not copied leaves
    in its ring slot what an earlier chunk brought: the short rows here
    land in slots the long row's poisoned tail has been through."""
    ctx = [2 * CHUNK + BS + 3, 5, 0, BS, CHUNK + 1, 1, BS + 1, 2 * BS - 1,
           CHUNK - 1, -1, 7]
    q, k_pages, v_pages, tables, ctx = _live_setup(ctx, 32, seed=5,
                                                   dtype=dtype)
    clean = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1,
        interpret=True)
    dead = dead_slots(tables, ctx, k_pages.shape[1], BS)[None, :, :, None,
                                                         None]
    k_bad = jnp.where(dead, jnp.nan, k_pages)
    v_bad = jnp.where(dead, jnp.inf, v_pages)
    got = pallas_paged_attention(
        q, k_bad, v_bad, tables, ctx, jnp.int32(1), scale=0.1,
        interpret=True)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(clean, np.float32))


def test_heads_that_fill_no_word_are_refused_at_trace_time():
    """The kernel reads a page's heads 32 bits wide, two bf16 heads a
    word: a page with an odd count of them never reaches Mosaic (the
    dispatcher's tile gate sends it to the reference; a direct caller is
    told why)."""
    q, k_pages, v_pages, tables, ctx = _live_setup(
        [40, 5], 4, H=3, KVH=3, dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="kv_heads=3"):
        pallas_paged_attention(
            q, k_pages, v_pages, tables, ctx, jnp.int32(0), scale=0.1,
            interpret=True)


@pytest.mark.parametrize("MAXB", [3, 8, 20])
def test_a_context_past_the_table_is_cut_to_it(MAXB):
    """The copies and the trip count follow the context, so the kernel
    bounds the context by the table itself: a row whose context claims
    more tokens than its table has pages for reads the table's tokens,
    as the reference does (its mask ends where the table ends), and
    never a table entry past the width. MAXB 3 and 20: a table that is
    no whole number of chunks."""
    full = MAXB * BS
    held = [full, 5, full, 0, full - 1]
    claimed = np.asarray([full + 1, 5, full + 9 * CHUNK, 0, full - 1],
                         np.int32)
    q, k_pages, v_pages, tables, held = _live_setup(held, MAXB, seed=MAXB)
    got = pallas_paged_attention(
        q, k_pages, v_pages, tables, jnp.asarray(claimed), jnp.int32(1),
        scale=0.1, interpret=True)
    ref = paged_attention_reference(
        q, k_pages, v_pages, tables, jnp.asarray(claimed), jnp.int32(1),
        scale=0.1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)
    cut = pallas_paged_attention(
        q, k_pages, v_pages, tables, held, jnp.int32(1), scale=0.1,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(cut))


@pytest.mark.parametrize("block_size", [16, 64])
def test_fetch_tokens_follows_the_live_pages(block_size):
    """The count the step records carry: each row's live pages, whole.
    At most one page a row over the live tokens (so under live + rows x
    chunk for any chunk), nothing for a row that holds nothing, the same
    under any table that holds the contexts, and cut to the table like
    the kernel's own contexts under one that does not."""
    from production_stack_tpu.ops.pallas_paged_attention import (
        fetch_tokens,
        live_pages,
    )

    rng = np.random.default_rng(block_size)
    ctx = rng.integers(1, 4096, size=32)
    width = 4096 // block_size
    fetched = fetch_tokens(ctx, block_size, width)
    assert ctx.sum() <= fetched < ctx.sum() + 32 * block_size
    assert fetched % block_size == 0
    assert fetch_tokens(ctx, block_size, 4 * width) == fetched
    assert fetch_tokens(np.array([0, -1, -64, -4096]), block_size, 64) == 0
    assert fetch_tokens(np.array([0, 1, -5]), block_size, 64) == block_size
    assert fetch_tokens(np.array([9000, 1, 0]), block_size, 8) == (
        9 * block_size)
    edges = np.array([1, block_size - 1, block_size, block_size + 1])
    assert list(live_pages(edges, block_size)) == [1, 1, 1, 2]
    # per scan step of a burst ([B, K]) as the engine counts it
    burst = ctx[:, None] + np.arange(8)
    assert fetch_tokens(burst, block_size, width + 1) == sum(
        fetch_tokens(burst[:, s], block_size, width + 1) for s in range(8))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("H", [48, 72])  # query groups 6 and 9
@pytest.mark.parametrize("MAXB,window", [(16, 40), (16, 64), (8, 100),
                                         (32, 64)])
def test_window_front_bound_matches_reference(MAXB, window, H, dtype):
    """With ``window`` a row's first live token is ``context - window``:
    the kernel starts its walk at that token's chunk, copies from its
    page on, and masks what lies before it in that page. One row holds
    nothing, one fills its table, the rest are ragged; a chunk is 128
    tokens, so the windows end inside a first chunk whose front pages
    were not copied."""
    B, KVH, D, L, bs = 5, 8, 128, 2, 16
    NB = B * MAXB + 2
    q, k_pages, v_pages, tables, ctx = _setup(
        B, H, KVH, D, L, NB, bs, MAXB, seed=H + MAXB)
    ctx = ctx.at[0].set(0).at[1].set(MAXB * bs)
    k_pages, v_pages = k_pages.astype(dtype), v_pages.astype(dtype)
    want = paged_attention_reference(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1,
        window=window)
    got = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1,
        interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    full = paged_attention_reference(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1)
    longer = np.asarray(ctx) > window
    assert np.abs(np.asarray(full) - np.asarray(want))[longer].max() > 1e-2
    np.testing.assert_array_equal(np.asarray(full)[~longer],
                                  np.asarray(want)[~longer])


def test_nothing_before_a_window_reaches_the_result():
    """NaN keys and Inf values in every page before the one that holds a
    row's first live token (and in every slot past its context): the
    same finite output, bit for bit. (The tokens before the window in
    that first page are the row's own, which it attended to a step
    earlier: they are masked, not zeroed.)"""
    B, H, KVH, D, L, bs, MAXB, window = 3, 48, 8, 128, 1, 16, 16, 40
    q, k_pages, v_pages, tables, _ = _setup(B, H, KVH, D, L, B * MAXB, bs,
                                            MAXB, seed=9)
    ctx = jnp.asarray([200, 77, 256], jnp.int32)
    clean = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(0), scale=0.1,
        interpret=True, window=window)
    token = np.arange(MAXB * bs)
    k_np, v_np = np.array(k_pages), np.array(v_pages)
    for b in range(B):
        first_page = max(int(ctx[b]) - window, 0) // bs
        dead = (token < first_page * bs) | (token >= int(ctx[b]))
        for t in token[dead]:
            k_np[0, int(tables[b, t // bs]), t % bs] = np.nan
            v_np[0, int(tables[b, t // bs]), t % bs] = np.inf
    dirty = pallas_paged_attention(
        q, jnp.asarray(k_np), jnp.asarray(v_np), tables, ctx, jnp.int32(0),
        scale=0.1, interpret=True, window=window)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


@pytest.mark.parametrize("block_size", [16, 64])
def test_fetch_tokens_under_a_window(block_size):
    from production_stack_tpu.ops.pallas_paged_attention import fetch_tokens

    bs, window = block_size, 512
    contexts = np.array([[0, 1, 512, 513, 700, 4096]])
    pages = [0, 1, 512 // bs, -(-513 // bs), -(-700 // bs) - 188 // bs,
             4096 // bs - 3584 // bs]
    assert fetch_tokens(contexts, bs, 64 * 64 // bs, window) == sum(pages) * bs
    assert fetch_tokens(contexts, bs, 64 * 64 // bs) == sum(
        -(-c // bs) for c in contexts[0]) * bs
