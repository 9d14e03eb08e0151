"""Pallas TPU kernel: paged attention for the decode hot loop.

One query token per sequence attends over that sequence's KV pages
scattered in HBM — the TPU counterpart of vLLM's CUDA PagedAttention
kernel, which the reference stack consumes via engine images
(ref helm/templates/deployment-vllm-multi.yaml:108-199).

v3 (round 5) gave the kernel its shape: a ring of page chunks copied
``RING - 1`` chunks ahead of the one being computed, along a walk that
crosses sequence boundaries (while one row's last chunks compute, the
next row's first pages are in flight); one scores scratch
``[KVH * g_pad, span]`` filled by per-head QK dots, so masking, running
max, exp and the l/acc updates run once over all heads' rows; q scaled
outside the kernel.

v4 (PR 32): the kernel's work follows the live tokens of the live rows.
On a v5e at the serving shapes (32 rows, 32 query / 8 KV heads of 128,
64-token pages; times of one call, one layer) v3 took 238 us for 32 rows
of context 1 and nothing useful, 131 us for 20 such rows beside 12 live
ones (411 against 280 us), 81 us more under a 64-page table than under a
32-page one (352 against 272), and 272 us for 24k live tokens whose bytes
need 121 us at 819 GB/s. Four things changed:

- **A row that holds nothing does nothing.** A context of 0 or less
  starts no copy, waits on none, computes nothing and writes zeros. The
  model's decode step hands such a context for every row that writes no
  token (``models/decoder.py::attend``).
- **The grid is the rows; a row's chunks are a loop** whose trip count is
  ``cdiv(live pages, P)``. A chunk past the context costs no step and the
  table's width decides nothing. The walk (which chunk to copy next, how
  many were started and consumed) lives in SMEM across the grid's rows,
  so the prefetch window still crosses from one live row into the next.
- **Only live pages are copied** (:func:`live_pages`; start and wait
  under one predicate). What the p @ v dot reads past the context is
  zeroed in VMEM in the row's last chunk: a probability of exactly 0
  does not silence a NaN.
- **A head's rows are read by strided loads.** A page keeps its heads on
  the sublanes, and v3's ``buf[slot, :, :, h, :]`` made Mosaic gather
  every token's row one by one and convert it: the kernel was bound by
  that, not by its copies (a 512-token chunk took 4.4 us to compute and
  2.6 us to copy). Read 32 bits wide with a sublane stride, one load
  brings two bf16 heads (four int8) and a shift makes each its f32
  (:func:`_decode_kernel`'s ``head_loads``; after
  ``jax.experimental.pallas.ops.tpu.ragged_paged_attention``).

With all four the 24k-token call takes 141 us (86% of the HBM roofline),
16 rows of 3,000 tokens 264 us (416; 91%), 12 live rows beside 20 that
hold nothing 153 us (411), and 2-, 4- and 8-page chunks read within 1%
of each other: the copies bound the kernel now, so the tile stays the
widest that fits (8 pages, ring 6, as v3's; a row's tail computes at most
512 tokens for nothing, hidden under the copies). PERF.md section 6,
PR 32, has the table; ``benchmarks/kernel_bs_sweep.py --cells`` times
these cases.

Structure credit: the scalar-prefetch / manual-DMA shape follows
``jax.experimental.pallas.ops.tpu.paged_attention`` (which cannot be
used directly: it wants per-layer page arrays, and slicing our
layer-stacked pool [L, NB, bs, KVH, D] per layer would copy the whole
layer every scan step — the layer index must reach the kernel as a
prefetched scalar).

Correctness is pinned by tests/test_pallas_attention.py (interpret-mode
parity vs the XLA reference on CPU; the bench drives it on real TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Deepest DMA ring (chunks prefetched ahead of compute) and widest chunk
# the tile chooser will pick. The round-5 sweep measured depth 6 with
# 8-page chunks fastest — deep enough to cover DMA issue->complete
# latency across sequence boundaries. The decode kernel, whose copies
# bound it since v4, reads the same at 2, 4 and 8 pages and at rings of
# 6 to 12 (PR 32's sweep).
RING = 6
MAX_PAGES_PER_BLOCK = 8

# What one kernel may plan to keep in VMEM. Mosaic's scoped limit on the
# v5e is 16 MiB and is enforced when the ENCLOSING jit compiles, where
# no fallback is possible, so tiles are sized from the shapes up front.
# The slack under the limit is the compiler's own (spills, relayouts),
# which the estimates below cannot see; tests/test_chip_compile.py holds
# the estimates to the real compiler at the serving shapes.
VMEM_BUDGET = 13 << 20


def ring_bytes(ring: int, pages: int, block_size: int, kvh: int,
               head_dim: int, itemsize: int) -> int:
    """VMEM bytes of the K and V page rings [ring, pages, bs, KVH, D]."""
    return 2 * ring * pages * block_size * kvh * head_dim * itemsize


def choose_tile(fits, tables_width: int, block_size: int,
                ring_floor: int = 3, max_pages: int = MAX_PAGES_PER_BLOCK,
                max_ring: int = RING):
    """(pages_per_block, ring) for a paged kernel: the widest chunk and
    then the deepest ring that ``fits(pages, ring)``; None when nothing
    does. A chunk spans whole 128-lane tiles of tokens (the scores' and
    the int8 scale rows' last dim) and no more pages than ``max_pages``
    (16 at most) or the table, rounded up to a power of two. Rings under
    ``ring_floor`` are tried only after every width failed at the floor."""
    cap = 1
    while cap < min(tables_width, max_pages):
        cap *= 2
    widths = [p for p in (16, 8, 4, 2, 1)
              if p <= cap and (p * block_size) % 128 == 0]
    widths = widths or [-(-128 // block_size)]
    for rings in (range(max_ring, ring_floor - 1, -1),
                  range(ring_floor - 1, 1, -1)):
        for p in widths:
            for r in rings:
                if fits(p, r):
                    return p, r
    return None


def pad_tables(block_tables: jax.Array, pages_per_block: int) -> jax.Array:
    """Pad the table width to a multiple of the chunk width (page 0; a
    padded entry lies past every context length, so it is never copied
    or attended)."""
    pad = -block_tables.shape[1] % pages_per_block
    if pad:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    return block_tables.astype(jnp.int32)


def gather_scale_rows(scales: jax.Array, block_tables: jax.Array, layer,
                      block_size: int, kvh: int) -> jax.Array:
    """Per-sequence int8 scales as head-major lane rows [B, KVH, S].

    The pool keeps scales token-major ([L, NB, bs*KVH], the layout the
    page scatter writes); the kernels need one lane vector per kv head.
    Mosaic cannot make that lane->sublane move in VMEM (it refuses the
    shape cast), so the table's scale rows — ~3% of the int8 bytes they
    describe — are gathered and transposed here, in XLA, and reach the
    kernel as an ordinary pipelined block."""
    L, NB, _ = scales.shape
    B, MAXB = block_tables.shape
    rows = scales.reshape(L * NB, block_size, kvh)[layer * NB + block_tables]
    return rows.transpose(0, 3, 1, 2).reshape(B, kvh, MAXB * block_size)


def live_pages(context_len, block_size: int):
    """Pages of a row that hold a live token: what the decode kernel
    copies for it, and nothing else. Plain arithmetic, so it takes the
    kernel's traced scalars and the host's numpy arrays alike; a
    context of 0 or less is a row that holds nothing."""
    return (context_len + block_size - 1) // block_size * (context_len > 0)


def first_live_page(context_len, block_size: int, window: int):
    """The page that holds a row's first live token under a window:
    token ``max(context - window, 0)``. Pages before it are not copied.
    Plain arithmetic, like :func:`live_pages`."""
    return (context_len - window) * (context_len > window) // block_size


def fetch_tokens(context_lens, block_size: int, tables_width: int,
                 window: int | None = None) -> int:
    """Token slots one decode call copies out of HBM for these contexts
    (a host array) under a table ``tables_width`` pages wide: each row's
    live pages, whole; with ``window`` those from the page of its first
    live token on. The chunk's width and the rows that hold nothing
    do not enter, nor does the table's width while it holds every
    context: a context past it is cut to it, as the kernel cuts it."""
    cut = np.minimum(context_lens, tables_width * block_size)
    pages = live_pages(cut, block_size)
    if window is not None:
        pages = pages - first_live_page(cut, block_size, window)
    return int(pages.sum()) * block_size


def _for_chunk_copies(fn, k_hbm, v_hbm, k_buf, v_buf, sems, bt_ref, layer,
                      b, chunk, slot, pages_per_block, row_pages=None,
                      first_page=None):
    """``fn`` on the async-copy descriptors of one chunk's pages into ring
    slot ``slot``; with ``row_pages`` (the row's :func:`live_pages`) only
    on the pages under it, and with ``first_page`` (the row's
    :func:`first_live_page`) only from that page on. Start and wait walk
    the same descriptors under the same predicate, so every started copy
    is waited exactly once."""
    for p in range(pages_per_block):
        def one(p=p):
            page = bt_ref[b, chunk * pages_per_block + p]
            fn(pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[slot, p], sems.at[slot, 0, p]))
            fn(pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[slot, p], sems.at[slot, 1, p]))

        if row_pages is None:
            one()
        elif first_page is None:
            pl.when(chunk * pages_per_block + p < row_pages)(one)
        else:
            page = chunk * pages_per_block + p
            pl.when(jnp.logical_and(page >= first_page,
                                    page < row_pages))(one)


def _start_chunk_copy(*args, **kwargs):
    _for_chunk_copies(lambda c: c.start(), *args, **kwargs)


def _wait_chunk_copy(*args, **kwargs):
    _for_chunk_copies(lambda c: c.wait(), *args, **kwargs)


def _decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, MAXB]
    context_lens_ref,  # [B]; <= 0: the row holds nothing
    layer_ref,  # [1]
    # inputs
    q_ref,  # [1, KVH * g_pad, D] (VMEM block for sequence b; pre-scaled)
    k_hbm_ref,  # [L, NB, bs, KVH, D] in ANY/HBM (int8 when quantized)
    v_hbm_ref,
    # quantized only: ks_hbm / vs_hbm [B, KVH, S] f32 in ANY/HBM (the
    # table's per-head scale rows); then output o_ref [1, KVH*g_pad, D];
    # then scratch: k_buf/v_buf VMEM [RING, P, bs, KVH, D], sems DMA
    # [RING, 2, P], (quantized: ks_buf/vs_buf VMEM [RING, KVH, span] f32,
    # scale_sems DMA [RING, 2],) s_ref [KVH*g_pad, span] f32, acc_ref
    # [KVH*g_pad, D] f32, m_ref/l_ref [KVH*g_pad, 128] f32, walk_ref SMEM
    # [4] int32.
    *refs,
    block_size: int,
    kvh: int,
    g_pad: int,
    pages_per_block: int,
    ring: int,
    quantized: bool,
    window: int | None = None,
):
    if quantized:
        ks_hbm_ref, vs_hbm_ref, *refs = refs
        (o_ref, k_buf, v_buf, sems, ks_buf, vs_buf, scale_sems,
         s_ref, acc_ref, m_ref, l_ref, walk_ref) = refs
    else:
        (o_ref, k_buf, v_buf, sems,
         s_ref, acc_ref, m_ref, l_ref, walk_ref) = refs
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    P = pages_per_block
    span_tokens = P * block_size

    # The walk over live chunks, in SMEM across the grid's rows:
    # [0], [1]: (row, chunk) of the next chunk to copy, row == nb when
    # none is left; [2]: chunks started; [3]: chunks consumed. Chunk g
    # lands in ring slot g % ring, RING-1 chunks ahead of the one being
    # computed, whichever live row it belongs to.
    NEXT_ROW, NEXT_CHUNK, STARTED, CONSUMED = range(4)

    def row_pages(row):
        return live_pages(context_lens_ref[row], block_size)

    # Under a window a row's walk starts at the chunk that holds its
    # first live token, and copies from that token's page on: every
    # statement that knows of it is behind ``window is not None``, so a
    # model without one traces the kernel it always traced.
    def row_first_page(row):
        return first_live_page(context_lens_ref[row], block_size, window)

    def row_first_chunk(row):
        return 0 if window is None else row_first_page(row) // P

    def window_copies(row):
        return {} if window is None else {
            "first_page": row_first_page(row)}

    def head_loads(buf, slot):
        """The chunk in ring slot ``slot`` as one f32 [span, D] per kv
        head. A page keeps its heads on the sublanes ([bs, KVH, D]), so a
        head is every KVH-th row of the slot's [span * KVH, D] view: read
        32 bits wide, one strided load brings the rows of as many heads
        as a word packs (two bf16, four int8), and a shift turns each
        into its f32 (a bf16 is the high half of its f32)."""
        packing = 4 // buf.dtype.itemsize
        words = buf.at[slot].reshape(
            span_tokens * kvh, buf.shape[-1]).bitcast(jnp.uint32)
        heads = []
        for first in range(0, kvh, packing):
            w = words[first // packing::kvh // packing, :]
            for i in range(packing):
                if packing == 1:
                    x = pltpu.bitcast(w, jnp.float32)
                elif packing == 2:
                    x = pltpu.bitcast(
                        w << 16 if i == 0 else w & jnp.uint32(0xFFFF0000),
                        jnp.float32)
                else:
                    x = (pltpu.bitcast(w << (24 - 8 * i), jnp.int32)
                         >> 24).astype(jnp.float32)
                heads.append(x)
        return heads

    def first_live_row(row):
        """The first row at or after ``row`` that holds a token."""
        def ctx_at(r):
            return context_lens_ref[jnp.minimum(r, nb - 1)]

        row, _ = jax.lax.while_loop(
            lambda rc: jnp.logical_and(rc[0] < nb, rc[1] <= 0),
            lambda rc: (rc[0] + 1, ctx_at(rc[0] + 1)),
            (row, ctx_at(row)))
        return row

    def scale_copies(row, chunk, slot):
        at = pl.multiple_of(chunk * span_tokens, 128)
        return [
            pltpu.make_async_copy(
                hbm.at[row, :, pl.ds(at, span_tokens)], buf.at[slot],
                scale_sems.at[slot, side])
            for side, (hbm, buf) in enumerate(
                ((ks_hbm_ref, ks_buf), (vs_hbm_ref, vs_buf)))]

    def start_next():
        row = walk_ref[NEXT_ROW]

        @pl.when(row < nb)
        def _():
            chunk = walk_ref[NEXT_CHUNK]
            slot = jax.lax.rem(walk_ref[STARTED], ring)
            pages = row_pages(row)
            _start_chunk_copy(
                k_hbm_ref, v_hbm_ref, k_buf, v_buf, sems, block_tables_ref,
                layer, row, chunk, slot, P, row_pages=pages,
                **window_copies(row))
            if quantized:
                for c in scale_copies(row, chunk, slot):
                    c.start()
            walk_ref[STARTED] = walk_ref[STARTED] + 1
            more = (chunk + 1) * P < pages

            @pl.when(more)
            def _():
                walk_ref[NEXT_CHUNK] = chunk + 1

            @pl.when(jnp.logical_not(more))
            def _():
                nxt = first_live_row(row + 1)
                walk_ref[NEXT_ROW] = nxt
                walk_ref[NEXT_CHUNK] = (
                    0 if window is None
                    else row_first_chunk(jnp.minimum(nxt, nb - 1)))

    @pl.when(b == 0)
    def _fill():
        row0 = first_live_row(jnp.int32(0))
        walk_ref[NEXT_ROW] = row0
        walk_ref[NEXT_CHUNK] = (
            0 if window is None
            else row_first_chunk(jnp.minimum(row0, nb - 1)))
        walk_ref[STARTED] = 0
        walk_ref[CONSUMED] = 0
        for _ in range(ring - 1):
            start_next()

    ctx = context_lens_ref[b]
    pages = row_pages(b)

    @pl.when(pages <= 0)
    def _empty():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    def chunk_step(c, carry):
        start_next()  # into the slot the previous step finished reading
        slot = jax.lax.rem(walk_ref[CONSUMED], ring)
        walk_ref[CONSUMED] = walk_ref[CONSUMED] + 1
        chunk_start = c * span_tokens
        _wait_chunk_copy(
            k_hbm_ref, v_hbm_ref, k_buf, v_buf, sems, block_tables_ref,
            layer, b, c, slot, P, row_pages=pages, **window_copies(b))
        if quantized:
            for cp in scale_copies(b, c, slot):
                cp.wait()

        if window is not None:
            # The row's front: the pages of its first chunk that lie
            # before its first live token were not copied, and hold what
            # an earlier chunk left in the slot. Zeroed where the p @ v
            # dot reads them, as the tail's are (0 x NaN is NaN).
            first_tok = jnp.maximum(ctx - window, 0)
            front = row_first_page(b) * block_size

            @pl.when(chunk_start < front)
            def _front():
                if quantized:
                    lane = chunk_start + jax.lax.broadcasted_iota(
                        jnp.int32, (1, span_tokens), 1)
                    vs_buf[slot] = jnp.where(lane >= front, vs_buf[slot],
                                             0.0)
                else:
                    for p in range(P):
                        @pl.when(chunk_start + (p + 1) * block_size <= front)
                        def _(p=p):
                            v_buf[slot, p] = jnp.zeros_like(v_buf[slot, p])

        # The row's tail. A probability of exactly 0 does not silence
        # what it multiplies (0 x NaN is NaN in the p @ v dot), so the
        # part of the chunk past the context is zeroed where that dot
        # reads it: pages that were not copied hold whatever an earlier
        # chunk left in the slot, and the last page's own tail holds what
        # an earlier owner of the page wrote. K needs none of this: its
        # scores are replaced, not multiplied.
        @pl.when(chunk_start + span_tokens > ctx)
        def _tail():
            if quantized:
                # int8 values are finite whatever they are; their scales
                # carry the zero.
                lane = chunk_start + jax.lax.broadcasted_iota(
                    jnp.int32, (1, span_tokens), 1)
                vs_buf[slot] = jnp.where(lane < ctx, vs_buf[slot], 0.0)
            else:
                for p in range(P):
                    page_start = chunk_start + p * block_size

                    @pl.when(page_start >= ctx)
                    def _(p=p):
                        v_buf[slot, p] = jnp.zeros_like(v_buf[slot, p])

                    @pl.when(jnp.logical_and(
                        page_start < ctx, page_start + block_size > ctx))
                    def _(p=p, page_start=page_start):
                        tok = page_start + jax.lax.broadcasted_iota(
                            jnp.int32, v_buf.shape[2:], 0)
                        v_buf[slot, p] = jnp.where(
                            tok < ctx, v_buf[slot, p],
                            jnp.zeros_like(v_buf[slot, p]))

        # Per-head QK dots into ONE scores scratch, then every VPU stage
        # (mask, max, exp, l/acc updates) runs once over all heads' rows.
        # Operands are cast to f32 first — measured FASTER than feeding
        # bf16 straight to the MXU at these tiny tile shapes (ring sweep,
        # round 5: bf16 operands cost +66%; Mosaic's repacking of skinny
        # bf16 tiles outweighs the cast traffic).
        for h, k in enumerate(head_loads(k_buf, slot)):  # static unroll
            rows = slice(h * g_pad, (h + 1) * g_pad)
            q = q_ref[0, rows, :].astype(jnp.float32)  # [g_pad, D]
            s_h = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if quantized:
                # Dequantize on-chip: the HBM stream stayed int8. A
                # token's scale is constant along D, so it factors out
                # of the dot and multiplies the scores ([1, span] lane
                # row, broadcast over the head's query rows).
                s_h = s_h * ks_buf[slot, h:h + 1, :]
            s_ref[rows, :] = s_h
        span = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, span_tokens), 1
        )
        valid = span < ctx  # [1, span]
        if window is not None:
            valid = jnp.logical_and(valid, span >= first_tok)
        s = jnp.where(valid, s_ref[...], NEG_INF)  # [KVH*g_pad, span]
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # [KVH*g_pad, 1]
        p_ = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p_, axis=1, keepdims=True),
            l_ref.shape,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha  # one batched rescale
        for h, v in enumerate(head_loads(v_buf, slot)):
            rows = slice(h * g_pad, (h + 1) * g_pad)
            p_h = p_[rows, :]
            if quantized:
                p_h = p_h * vs_buf[slot, h:h + 1, :]
            acc_ref[rows, :] = acc_ref[rows, :] + jax.lax.dot(
                p_h, v, preferred_element_type=jnp.float32)
        return carry

    @pl.when(pages > 0)
    def _row():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # A trip count that follows the context: a chunk past it costs
        # no step, whatever the table's width.
        jax.lax.fori_loop(row_first_chunk(b), (pages + P - 1) // P,
                          chunk_step, None)
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_tile(block_size: int, kvh: int, head_dim: int, g_pad: int,
                itemsize: int, tables_width: int, quantized: bool):
    """(pages_per_block, ring) the decode kernel runs with at these
    shapes, chosen to fit :data:`VMEM_BUDGET`; None when nothing fits."""
    rows = kvh * g_pad

    def fits(pages: int, ring: int) -> bool:
        span = pages * block_size
        total = ring_bytes(ring, pages, block_size, kvh, head_dim, itemsize)
        total += 4 * rows * (span + head_dim + 256)  # s/acc/m/l scratch
        total += 2 * 4 * rows * span  # masked scores and probabilities
        total += 2 * 2 * rows * head_dim * 2  # q and o blocks, 2 buffers
        if quantized:
            total += 2 * ring * 4 * max(kvh, 8) * span  # scale rings
        return total <= VMEM_BUDGET

    return choose_tile(fits, tables_width, block_size)


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_block", "ring", "interpret",
                              "window"))
def pallas_paged_attention(
    q: jax.Array,  # [B, H, D]
    k_pages,  # [L, NB, bs, KVH, D] stacked pages (or (data, scales))
    v_pages,  # [L, NB, bs, KVH, D] (or (data, scales))
    block_tables: jax.Array,  # [B, MAXB] int32
    context_lens: jax.Array,  # [B] int32; <= 0: the row holds nothing
    layer,  # scalar layer index (traced)
    *,
    scale: float,
    pages_per_block: int = 0,  # 0 -> from the VMEM budget (decode_tile)
    ring: int = 0,  # DMA ring depth; 0 -> from the VMEM budget
    interpret: bool = False,
    window: int | None = None,  # the row's last ``window`` tokens only
) -> jax.Array:
    """[B, H, D]; zeros for a row whose context is 0 or less. With
    ``window`` a row's first live token is ``max(context - window, 0)``:
    no page before that token's is copied and no chunk before its chunk
    is computed."""
    quantized = isinstance(k_pages, tuple)
    if quantized:
        k_pages, k_scales = k_pages
        v_pages, v_scales = v_pages
    B, H, D = q.shape
    L, NB, bs, KVH, _ = k_pages.shape
    if KVH % (4 // k_pages.dtype.itemsize):
        raise ValueError(
            f"kv_heads={KVH} of {k_pages.dtype} do not fill the 32-bit "
            "words the kernel reads a page's heads by")
    group = H // KVH
    # Pad each query-head group to the float32 sublane tile (8 rows).
    g_pad = max(group, 8)
    # A context past the table is cut to it: the kernel's copies and its
    # trip count follow the context, and the table bounds neither.
    context_lens = jnp.minimum(
        context_lens.astype(jnp.int32), block_tables.shape[1] * bs)
    tile = decode_tile(bs, KVH, D, g_pad, k_pages.dtype.itemsize,
                       block_tables.shape[1], quantized)
    if tile is None and not (pages_per_block and ring):
        raise ValueError(
            f"no decode tile fits VMEM at block_size={bs} kv_heads={KVH} "
            f"head_dim={D}")
    P = pages_per_block or tile[0]
    R = ring or tile[1]
    # The table is padded to whole chunks, so the chunk width need not
    # divide the engine's table bucket (its top bucket is clamped at
    # max_blocks_per_seq, which need not be a power of two).
    block_tables = pad_tables(block_tables, P)
    qg = (q * scale).astype(q.dtype).reshape(B, KVH, group, D)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    qg = qg.reshape(B, KVH * g_pad, D)

    kernel = functools.partial(
        _decode_kernel, block_size=bs, kvh=KVH, g_pad=g_pad,
        pages_per_block=P, ring=R, quantized=quantized,
        **({} if window is None else {"window": window}),
    )
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    row_block = pl.BlockSpec(
        (1, KVH * g_pad, D), lambda b, bt, cl, lr: (b, 0, 0))
    in_specs = [
        row_block,
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [qg, k_pages, v_pages]
    scratch_shapes = [
        pltpu.VMEM((R, P, bs, KVH, D), k_pages.dtype),
        pltpu.VMEM((R, P, bs, KVH, D), v_pages.dtype),
        pltpu.SemaphoreType.DMA((R, 2, P)),
    ]
    if quantized:
        # The table's scale rows stay in HBM; a live chunk's [KVH, span]
        # per side is copied beside its pages.
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands += [
            gather_scale_rows(k_scales, block_tables, layer, bs, KVH),
            gather_scale_rows(v_scales, block_tables, layer, bs, KVH)]
        scratch_shapes += [
            pltpu.VMEM((R, KVH, P * bs), jnp.float32),
            pltpu.VMEM((R, KVH, P * bs), jnp.float32),
            pltpu.SemaphoreType.DMA((R, 2)),
        ]
    scratch_shapes += [
        pltpu.VMEM((KVH * g_pad, P * bs), jnp.float32),
        pltpu.VMEM((KVH * g_pad, D), jnp.float32),
        pltpu.VMEM((KVH * g_pad, 128), jnp.float32),
        pltpu.VMEM((KVH * g_pad, 128), jnp.float32),
        pltpu.SMEM((4,), jnp.int32),
    ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=in_specs,
            out_specs=row_block,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH * g_pad, D), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, layer_arr, *operands)
    out = out.reshape(B, KVH, g_pad, D)[:, :, :group, :]
    return out.reshape(B, H, D)
