"""Pallas TPU kernel: absorbed latent (MLA) attention for the decode loop.

A latent cache keeps, per token and attention sublayer, one normed latent
``c`` (512 wide) and one rotated key ``k_r`` (64 wide) that every head
shares. In decode the up-projection is absorbed into the query
(``models/decoder.py::attend_latent``), so a head's score over a token
is ``q_abs . c + q_rope . k_r`` and its output is ``p c``, still in the
latent space: **the page is the key and the value**, each byte of it is
read once, and all ``H`` heads work on the one copy. That is ``H x (576
+ 512) x 2`` FLOPs over 1,152 bytes a token: at 64 heads (LongCat-Flash)
121 FLOPs a byte where the v5e's ridge is ~240, and 64 heads fill half
of the 128-row MXU, so the dots cost it about as long as the copies cost
the DMA engines; at 20 heads (GLM-4.7-Flash) 38 FLOPs a byte on a sixth
of the MXU's rows, the same number of passes over a chunk's tokens with
fewer rows in each, so the core has more slack and the copies bound it
the more. The operands stay bf16 (an f32 matmul is several passes of the
MXU). **The heads need no alignment**: the query block, the output block
and the accumulator take all of them as the whole of their dimension,
and the compiler rounds the rows up to whole sublane tiles in VMEM (20
heads lie in 24 rows of float32 and in 32 of bf16) whose padding no dot
reads and no store writes; :func:`decode_tile` counts those rows. Read
alone on the chip at 20 heads (PR 44, ``kernel_bs_sweep.py --cells
--latent --cases=agent --heads=20``: 32, 16 and 8 rows over 4,096-7,168
tokens): 305.2, 152.4 and 75.8 us a call, **82.4, 81.8 and 80.7% of the
live tokens' floor**, within 4e-4 of the XLA path, and the same to 0.2
us with the queries padded to 24 or to 32 heads outside the kernel: at
the same tile (16 pages, ring 4) the copies bound it as they do at 64.

The shape of the kernel is ``pallas_paged_attention``'s (PR 32), with
its rule kept: **a row that holds nothing does nothing, and nothing is
copied or computed past a row's live tokens.** The grid is the rows; a
row's chunks of ``P`` pages are a loop over its full chunks and then its
last one; the chunks are copied ``RING - 1`` ahead along a walk kept in
SMEM that crosses from one live row into the next; only live pages are
copied, and what the ``p @ c`` dot reads past the context is zeroed in
VMEM in the row's last chunk (a probability of exactly 0 does not
silence a NaN).

What differs: no heads on the sublanes. The pool's sides are ``[L, NB,
bs, 1, lanes]``; a dimension of 1 costs nothing in the device's layout
(the tiles lie over ``bs x lanes``), so the kernel takes the same bytes
as ``[L, NB, bs, lanes]`` and a span of pages is a plain ``[tokens,
512]`` matrix: two MXU dots for the scores (latent and rotary part), one
for ``p @ c``, no strided loads. The rotary side's lanes are the pool's
(128: the 64 of ``k_r`` and zeros; ``q_rope`` is padded with zeros to
match).

**The loop is built so that a chunk costs the core less than its copies
cost the DMA engines** (PR 42; each step read alone on the chip by
``benchmarks/kernel_bs_sweep.py --cells --latent``: 128 rows, the cell's
lognormal contexts at a mean of 2.3k, us a call beside the floor of the
live tokens' 1,152 B at 819 GB/s, 412 us). The kernel as PR 41 had it
(8-page chunks, ring 6, one chain dot -> max -> exp -> dot a chunk, a
predicate on every page's start and wait, mask and zeroing looked for in
every chunk) read **828 us, 49.8%**, and 221 us for 128 rows of one
token. Then, each on top of the one before:

- ``m`` and ``l`` values of the trip (one lane wide, no 128-lane rows
  stored and reloaded), a span read from VMEM where each dot uses it:
  730 us (56.4%).
- No fixed cost on a full chunk: one predicate over all its starts, one
  wait a side of a slot (a DMA semaphore counts bytes: every page of a
  side signals the same one), mask and zeroing in the row's last chunk
  only: 716, then 670 us (61.4%).
- The last chunk computes what lives in it: its dots and softmax over
  the sub-blocks of 128 tokens that hold a live token (a static count
  picks the body), not over the whole chunk: one-token rows 167 -> 129
  us, the cell's 696 (both before the shared semaphores). Taken away
  again under the final tile it costs 100 us of 516.
- Two chunks in flight on the core (a paired trip over two ring slots:
  both chunks' score dots, then each one's softmax and ``p @ c``, the
  second's dots under the first's softmax): 602 us (68.4%).
- A branch not taken costs the scalar core ~30 cycles (read from the
  one-token rows: 8 pages more a chunk cost 0.35 us a row), and a row
  had ~24 of them: the row's last chunk starts its live pages as the
  binary digits of their count (4 or 5 predicates for 8 or 16), a full
  chunk that is not its row's last is one predicate, the last chunk
  always exists (a context of exactly k chunks ends in a full one), the
  ring is filled by a loop: 560 us (73.5%), one-token rows 104.
- The tile: a 16-page chunk computed as two spans (:data:`SPANS`) in
  one body does what the paired trip did with half the walk's
  bookkeeping: **512 us, 80.5%** (16 pages as one span 537; the pair at
  16 pages 523; 32 pages 512; rings of 3 to 8 within 2%: ring 4). The
  paired trip went.

With the copies taken out the final body needs 327 us of the core, with
the dots taken out the copies need 511: **the copies bound it**, at 743
GB/s of the 1,280 bytes a token that the two 128-lane-aligned sides hold
(91% of the peak; the floor counts the 1,152 live ones, so the share
cannot pass 90). Rows a grid step (2, 4, 8) changed nothing.

Correctness: tests/test_longcat.py (interpret mode against the XLA path
on the CPU at every edge the loop has, a slot of -1 and ragged
contexts), tests/test_glm4_moe_lite.py (5 and 20 heads) and
tests/test_chip_compile.py (the v5e compiler at both cells' shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.pallas_paged_attention import (
    NEG_INF,
    VMEM_BUDGET,
    choose_tile,
    live_pages,
    pad_tables,
)


def tiles_ok(block_size: int, heads: int, latent: int, rope_lanes: int,
             itemsize: int) -> bool:
    """Trace-time gate: what the page copies and the dots need aligned.
    A page ``[bs, lanes]`` is sliced out of HBM whole, so ``bs`` has to
    fill the sublane tile of the dtype (16 rows of bf16, 8 of float32)
    and both sides whole 128-lane tiles. The heads are the rows of the
    query block and of the accumulator, and any number of them will do:
    a block takes all of them (the whole of its array's dimension, which
    needs no alignment), and the compiler rounds the rows up to whole
    sublane tiles in VMEM (20 heads lie in 24 rows of float32 and 32 of
    bf16), whose padding no dot reads and no store writes."""
    return (block_size % (32 // itemsize) == 0 and latent % 128 == 0
            and rope_lanes % 128 == 0 and heads >= 1
            and itemsize in (2, 4))


# The widest chunk and the deepest ring the tile chooser may pick for this
# kernel (PR 42's sweep, ``benchmarks/kernel_bs_sweep.py --cells
# --latent``): 16 pages are 1.3 MB of copies a trip over the trip's fixed
# cost, and three chunks ahead keep the DMA engines fed across a row's
# end; rings of 3 to 8 read the same within 2%.
MAX_PAGES_PER_BLOCK = 16
RING = 4

# A full chunk is computed as this many spans of pages that share nothing
# before the accumulator but the running maximum, so that one span's dots
# run while another's softmax has the VPU (4 reads as 2 does).
SPANS = 2


def decode_tile(block_size: int, heads: int, latent: int, rope_lanes: int,
                itemsize: int, tables_width: int):
    """(pages_per_block, ring) at these shapes within
    :data:`VMEM_BUDGET`; None when nothing fits."""

    # The heads as VMEM lays them out: whole tiles of 8 float32 rows
    # (the same number at a multiple of 8: 64 heads count as before).
    rows = -(-heads // 8) * 8

    def fits(pages: int, ring: int) -> bool:
        span = pages * block_size
        total = ring * span * (latent + rope_lanes) * itemsize  # rings
        total += 4 * rows * latent  # acc
        total += 3 * 4 * rows * span  # scores, probabilities, their bf16
        total += 2 * 2 * rows * (2 * latent + rope_lanes) * itemsize
        return total <= VMEM_BUDGET

    return choose_tile(fits, tables_width, block_size,
                       max_pages=MAX_PAGES_PER_BLOCK, max_ring=RING)


def _kernel(
    # scalar prefetch
    block_tables_ref,  # [B, MAXB]
    context_lens_ref,  # [B]; <= 0: the row holds nothing
    layer_ref,  # [1]
    # inputs
    q_abs_ref,  # [1, H, latent] (pre-scaled)
    q_rope_ref,  # [1, H, rope_lanes] (pre-scaled, zeros past the key)
    c_hbm_ref,  # [L, NB, bs, latent] in ANY/HBM
    r_hbm_ref,  # [L, NB, bs, rope_lanes]
    # output, then scratch
    o_ref,  # [1, H, latent]
    c_buf,  # VMEM [RING, P, bs, latent]
    r_buf,  # VMEM [RING, P, bs, rope_lanes]
    sems,  # DMA [RING, 2]: every page of a side of a slot signals one
    acc_ref,  # [H, latent] f32
    walk_ref,  # SMEM [4] int32
    *,
    block_size: int,
    pages_per_block: int,
    ring: int,
):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    P = pages_per_block
    span_tokens = P * block_size
    # A sub-block: the pages of one 128-lane tile of scores, the unit in
    # which a row's last chunk is computed.
    sub_pages = max(1, 128 // block_size)
    if P % sub_pages:
        sub_pages = P
    sub_tokens = sub_pages * block_size

    # The walk over live chunks, as pallas_paged_attention keeps it:
    # (row, chunk) of the next chunk to copy (row >= nb: none left),
    # chunks started, chunks read. Chunk g lands in slot g % ring.
    NEXT_ROW, NEXT_CHUNK, STARTED, READ = range(4)

    def take_slot(count):
        slot = jax.lax.rem(walk_ref[count], ring)
        walk_ref[count] = walk_ref[count] + 1
        return slot

    def first_live_row(row):
        def ctx_at(r):
            return context_lens_ref[jnp.minimum(r, nb - 1)]

        row, _ = jax.lax.while_loop(
            lambda rc: jnp.logical_and(rc[0] < nb, rc[1] <= 0),
            lambda rc: (rc[0] + 1, ctx_at(rc[0] + 1)),
            (row, ctx_at(row)))
        return row

    def start_next(*_):
        """Start the copies of the walk's next chunk and step the walk.
        A branch not taken costs the scalar core ~30 cycles, so a full
        chunk that is not its row's last (the common one) is one
        predicate over all its copies, and a row's last chunk starts its
        live pages as the binary digits of their count."""
        row = walk_ref[NEXT_ROW]
        chunk = walk_ref[NEXT_CHUNK]
        slot = take_slot(STARTED)
        live = jnp.where(  # pages of this chunk and after it in the row
            row < nb,
            live_pages(context_lens_ref[jnp.minimum(row, nb - 1)],
                       block_size) - chunk * P, 0)

        def start(p):
            page = block_tables_ref[row, chunk * P + p]
            pltpu.make_async_copy(c_hbm_ref.at[layer, page],
                                  c_buf.at[slot, p], sems.at[slot, 0]).start()
            pltpu.make_async_copy(r_hbm_ref.at[layer, page],
                                  r_buf.at[slot, p], sems.at[slot, 1]).start()

        @pl.when(live > P)
        def _():
            for p in range(P):
                start(p)
            walk_ref[NEXT_CHUNK] = chunk + 1

        @pl.when(live <= P)
        def _():
            size = 1 << (P.bit_length() - 1)
            while size:
                @pl.when(live & size != 0)
                def _(size=size):
                    first = live & ~(2 * size - 1)
                    for p in range(size):
                        start(first + p)
                size //= 2
            walk_ref[NEXT_ROW] = first_live_row(row + 1)
            walk_ref[NEXT_CHUNK] = 0

    def wait_pages(slot, first: int, pages: int):
        """Wait for pages ``first .. first + pages`` of a slot: a DMA
        semaphore counts bytes, so one wait a side takes all of them."""
        at = pl.ds(first, pages)
        for side, buf in enumerate((c_buf, r_buf)):
            pltpu.make_async_copy(buf.at[slot, at], buf.at[slot, at],
                                  sems.at[slot, side]).wait()

    contract_last = (((1,), (1,)), ((), ()))

    def tokens_of(buf, slot, first: int, pages: int):
        return buf[slot, first:first + pages].reshape(pages * block_size,
                                                      buf.shape[-1])

    def steps(m, l, slot, pages: int, ctx=None, chunk_start=0):
        """The online-softmax updates of a slot's first ``pages``, as at most
        :data:`SPANS` spans of whole sub-blocks that share nothing before
        the accumulator but the running maximum: a span's score dots run
        under the softmax of the one before, whose ``p @ c`` runs under
        this one's. Each span is read from VMEM where it is used, for the
        scores and again for ``p @ c``. ``ctx``: the row's context, in
        its last chunk, where the last span is masked (what lies past the
        context is all in it). Returns (m, l, the accumulator's new
        value)."""
        subs = pages // sub_pages
        n = min(SPANS, subs)
        sizes = [(subs // n + (i < subs % n)) * sub_pages for i in range(n)]
        spans = [(sum(sizes[:i]), sizes[i]) for i in range(n)]
        scores = [
            jax.lax.dot_general(
                q_abs_ref[0], tokens_of(c_buf, slot, *span), contract_last,
                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                q_rope_ref[0], tokens_of(r_buf, slot, *span), contract_last,
                preferred_element_type=jnp.float32)
            for span in spans]  # each [H, its tokens]
        if ctx is not None:
            at, size = spans[-1]
            tok = chunk_start + at * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, size * block_size), 1)
            scores[-1] = jnp.where(tok < ctx, scores[-1], NEG_INF)
        updates = []
        for s, span in zip(scores, spans):
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p_ = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p_, axis=1, keepdims=True)
            m = m_new
            latents = tokens_of(c_buf, slot, *span)
            updates.append((alpha, jax.lax.dot(
                p_.astype(latents.dtype), latents,
                preferred_element_type=jnp.float32)))
        acc = acc_ref[...]
        for alpha, pv in updates:
            acc = acc * alpha + pv
        return m, l, acc

    def full_step(_, carry):
        """A chunk whose every token is live: no mask, nothing to zero,
        one wait a side."""
        start_next()  # into the slot the step before this one read
        slot = take_slot(READ)
        wait_pages(slot, 0, P)
        m, l, acc_ref[...] = steps(*carry, slot, P)
        return m, l

    def last_step(m, l, slot, chunk_start, ctx, subs: int):
        """A row's last chunk when ``subs`` of its sub-blocks hold a
        live token: the dots and the softmax over those alone, and the
        row's output."""
        pages = subs * sub_pages
        live = live_pages(ctx - chunk_start, block_size)
        wait_pages(slot, 0, pages - sub_pages + 1)
        for p in range(pages - sub_pages + 1, pages):
            pl.when(p < live)(functools.partial(wait_pages, slot, p, 1))
        # The latent is the value too, and 0 x NaN is NaN in the p @ c
        # dot, so what lies past the context (pages not copied, the last
        # page's own tail) is zeroed where it is read: all of it is in
        # the last live sub-block.
        at = pl.ds(pages - sub_pages, sub_pages)
        shape = (sub_pages,) + c_buf.shape[2:]
        tok = chunk_start + (pages - sub_pages) * block_size + (
            jax.lax.broadcasted_iota(jnp.int32, shape, 0) * block_size
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        c_buf[slot, at] = jnp.where(tok < ctx, c_buf[slot, at],
                                    jnp.zeros_like(c_buf[slot, at]))
        _, l, acc = steps(m, l, slot, pages, ctx, chunk_start)
        o_ref[0] = (acc / l).astype(o_ref.dtype)

    @pl.when(b == 0)
    def _fill():
        walk_ref[NEXT_ROW] = first_live_row(jnp.int32(0))
        walk_ref[NEXT_CHUNK] = 0
        walk_ref[STARTED] = 0
        walk_ref[READ] = 0
        jax.lax.fori_loop(0, ring - 1, start_next, None)

    ctx = context_lens_ref[b]

    @pl.when(ctx <= 0)
    def _empty():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(ctx > 0)
    def _row():
        heads = acc_ref.shape[0]
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # The row's last chunk holds 1 to span_tokens live tokens; every
        # chunk before it is full. m and l are values of the trip, one
        # lane wide.
        full = (ctx - 1) // span_tokens
        m, l = jax.lax.fori_loop(
            0, full, full_step,
            (jnp.full((heads, 1), NEG_INF, jnp.float32),
             jnp.zeros((heads, 1), jnp.float32)))
        chunk_start = full * span_tokens
        live_subs = (ctx - chunk_start + sub_tokens - 1) // sub_tokens
        start_next()
        slot = take_slot(READ)
        for subs in range(1, P // sub_pages + 1):
            pl.when(live_subs == subs)(functools.partial(
                last_step, m, l, slot, chunk_start, ctx, subs))


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_block", "ring",
                              "interpret"))
def pallas_mla_decode(
    q_abs: jax.Array,  # [B, H, latent]
    q_rope: jax.Array,  # [B, H, R], R <= the rotary side's lanes
    c_pages: jax.Array,  # [L, NB, bs, 1, latent]
    r_pages: jax.Array,  # [L, NB, bs, 1, lanes]: k_r, zeros past it
    block_tables: jax.Array,  # [B, MAXB] int32
    context_lens: jax.Array,  # [B] int32; <= 0: the row holds nothing
    layer,  # scalar page-layer index (traced)
    *,
    scale: float,
    pages_per_block: int = 0,  # 0 -> from the VMEM budget (decode_tile)
    ring: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """``o_lat [B, H, latent]``: softmax over the row's live tokens of
    ``(q_abs . c + q_rope . k_r) * scale``, times ``c``; zeros for a row
    whose context is 0 or less."""
    B, H, latent = q_abs.shape
    L, NB, bs, _, lanes = r_pages.shape
    # A dimension of 1 is not laid out: the same bytes, four dims.
    c_pages = c_pages.reshape(L, NB, bs, latent)
    r_pages = r_pages.reshape(L, NB, bs, lanes)
    context_lens = jnp.minimum(
        context_lens.astype(jnp.int32), block_tables.shape[1] * bs)
    tile = decode_tile(bs, H, latent, lanes, c_pages.dtype.itemsize,
                       block_tables.shape[1])
    if tile is None and not (pages_per_block and ring):
        raise ValueError(
            f"no latent decode tile fits VMEM at block_size={bs} "
            f"heads={H} latent={latent}")
    P = pages_per_block or tile[0]
    R = ring or tile[1]
    block_tables = pad_tables(block_tables, P)
    dtype = c_pages.dtype
    q_abs = (q_abs * scale).astype(dtype)
    q_rope = jnp.pad((q_rope * scale).astype(dtype),
                     ((0, 0), (0, 0), (0, lanes - q_rope.shape[-1])))
    kernel = functools.partial(_kernel, block_size=bs, pages_per_block=P,
                               ring=R)

    def row_block(width):
        return pl.BlockSpec((1, H, width), lambda b, bt, cl, lr: (b, 0, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row_block(latent), row_block(lanes),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_block(latent),
            scratch_shapes=[
                pltpu.VMEM((R, P, bs, latent), dtype),
                pltpu.VMEM((R, P, bs, lanes), dtype),
                pltpu.SemaphoreType.DMA((R, 2)),
                pltpu.VMEM((H, latent), jnp.float32),
                pltpu.SMEM((4,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q_abs.dtype),
        interpret=interpret,
        name="pallas_mla_decode",
    )(block_tables, context_lens, jnp.asarray(layer, jnp.int32).reshape(1),
      q_abs, q_rope, c_pages, r_pages)
