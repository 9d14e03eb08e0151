"""Pallas TPU kernel: absorbed latent (MLA) attention for the decode loop.

A latent cache keeps, per token and attention sublayer, one normed latent
``c`` (512 wide) and one rotated key ``k_r`` (64 wide) that every head
shares. In decode the up-projection is absorbed into the query
(``models/decoder.py::attend_latent``), so a head's score over a token
is ``q_abs . c + q_rope . k_r`` and its output is ``p c``, still in the
latent space: **the page is the key and the value**, each byte of it is
read once, and all 64 heads work on the one copy. That is 64 x (576 +
512) x 2 FLOPs over 1,152 bytes a token, 121 FLOPs a byte where the
v5e's ridge is ~240: neither the copies nor the MXU alone bound it,
which is why the operands stay bf16 (an f32 matmul is several passes of
the MXU and would outlast the copies).

The shape of the kernel is ``pallas_paged_attention``'s (PR 32), with
its rule kept: **a row that holds nothing does nothing, and nothing is
copied or computed past a row's live tokens.** The grid is the rows; a
row's chunks of ``P`` pages are a loop whose trip count is
``cdiv(live pages, P)``; the chunks are copied ``RING - 1`` ahead along
a walk kept in SMEM that crosses from one live row into the next; only
live pages are copied (start and wait under one predicate), and what
the ``p @ c`` dot reads past the context is zeroed in VMEM in the row's
last chunk (a probability of exactly 0 does not silence a NaN).

What differs: no heads on the sublanes. The pool's sides are ``[L, NB,
bs, 1, lanes]``; a dimension of 1 costs nothing in the device's layout
(the tiles lie over ``bs x lanes``), so the kernel takes the same bytes
as ``[L, NB, bs, lanes]`` and a chunk is a plain ``[span, 512]`` matrix:
two MXU dots for the scores (latent and rotary part), one for ``p @ c``,
no strided loads. The rotary side's lanes are the pool's (128: the 64 of
``k_r`` and zeros; ``q_rope`` is padded with zeros to match).

Correctness: tests/test_longcat.py (interpret mode against the XLA path
on the CPU, a slot of -1 and ragged contexts) and
tests/test_chip_compile.py (the v5e compiler at the cell's shapes).
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
    and both sides whole 128-lane tiles; the heads are the rows of the
    query block."""
    return (block_size % (32 // itemsize) == 0 and latent % 128 == 0
            and rope_lanes % 128 == 0 and heads % 8 == 0
            and itemsize in (2, 4))


def decode_tile(block_size: int, heads: int, latent: int, rope_lanes: int,
                itemsize: int, tables_width: int):
    """(pages_per_block, ring) at these shapes within
    :data:`VMEM_BUDGET`; None when nothing fits."""

    def fits(pages: int, ring: int) -> bool:
        span = pages * block_size
        total = ring * span * (latent + rope_lanes) * itemsize  # rings
        total += 4 * heads * (latent + 256)  # acc, m, l
        total += 3 * 4 * heads * span  # scores, probabilities, their bf16
        total += 2 * 2 * heads * (2 * latent + rope_lanes) * itemsize
        return total <= VMEM_BUDGET

    return choose_tile(fits, tables_width, block_size)


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
    sems,  # DMA [RING, 2, P]
    acc_ref,  # [H, latent] f32
    m_ref,  # [H, 128] f32
    l_ref,  # [H, 128] f32
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

    # The walk over live chunks, as pallas_paged_attention keeps it:
    # (row, chunk) of the next chunk to copy (row == nb: none left),
    # chunks started, chunks consumed. Chunk g lands in slot g % ring.
    NEXT_ROW, NEXT_CHUNK, STARTED, CONSUMED = range(4)

    def row_pages(row):
        return live_pages(context_lens_ref[row], block_size)

    def for_copies(fn, row, chunk, slot, pages):
        """``fn`` on the copy descriptors of the chunk's live pages:
        start and wait walk the same ones under the same predicate."""
        for p in range(P):
            @pl.when(chunk * P + p < pages)
            def _(p=p):
                page = block_tables_ref[row, chunk * P + p]
                fn(pltpu.make_async_copy(
                    c_hbm_ref.at[layer, page], c_buf.at[slot, p],
                    sems.at[slot, 0, p]))
                fn(pltpu.make_async_copy(
                    r_hbm_ref.at[layer, page], r_buf.at[slot, p],
                    sems.at[slot, 1, p]))

    def first_live_row(row):
        def ctx_at(r):
            return context_lens_ref[jnp.minimum(r, nb - 1)]

        row, _ = jax.lax.while_loop(
            lambda rc: jnp.logical_and(rc[0] < nb, rc[1] <= 0),
            lambda rc: (rc[0] + 1, ctx_at(rc[0] + 1)),
            (row, ctx_at(row)))
        return row

    def start_next():
        row = walk_ref[NEXT_ROW]

        @pl.when(row < nb)
        def _():
            chunk = walk_ref[NEXT_CHUNK]
            slot = jax.lax.rem(walk_ref[STARTED], ring)
            pages = row_pages(row)
            for_copies(lambda c: c.start(), row, chunk, slot, pages)
            walk_ref[STARTED] = walk_ref[STARTED] + 1
            more = (chunk + 1) * P < pages

            @pl.when(more)
            def _():
                walk_ref[NEXT_CHUNK] = chunk + 1

            @pl.when(jnp.logical_not(more))
            def _():
                walk_ref[NEXT_ROW] = first_live_row(row + 1)
                walk_ref[NEXT_CHUNK] = 0

    @pl.when(b == 0)
    def _fill():
        walk_ref[NEXT_ROW] = first_live_row(jnp.int32(0))
        walk_ref[NEXT_CHUNK] = 0
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
        for_copies(lambda cp: cp.wait(), b, c, slot, pages)

        # The row's tail: the latent is the value too, and 0 x NaN is
        # NaN in the p @ c dot, so what lies past the context (pages not
        # copied, the last page's own tail) is zeroed where it is read.
        @pl.when(chunk_start + span_tokens > ctx)
        def _tail():
            for p in range(P):
                page_start = chunk_start + p * block_size

                @pl.when(page_start >= ctx)
                def _(p=p):
                    c_buf[slot, p] = jnp.zeros_like(c_buf[slot, p])

                @pl.when(jnp.logical_and(
                    page_start < ctx, page_start + block_size > ctx))
                def _(p=p, page_start=page_start):
                    tok = page_start + jax.lax.broadcasted_iota(
                        jnp.int32, c_buf.shape[2:], 0)
                    c_buf[slot, p] = jnp.where(
                        tok < ctx, c_buf[slot, p],
                        jnp.zeros_like(c_buf[slot, p]))

        latents = c_buf[slot].reshape(span_tokens, c_buf.shape[-1])
        rotary = r_buf[slot].reshape(span_tokens, r_buf.shape[-1])
        contract_last = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            q_abs_ref[0], latents, contract_last,
            preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(
            q_rope_ref[0], rotary, contract_last,
            preferred_element_type=jnp.float32)  # [H, span]
        span = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, span_tokens), 1)
        s = jnp.where(span < ctx, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p_ = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p_, axis=1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p_.astype(latents.dtype), latents,
            preferred_element_type=jnp.float32)
        return carry

    @pl.when(pages > 0)
    def _row():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        jax.lax.fori_loop(0, (pages + P - 1) // P, chunk_step, None)
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


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
                pltpu.SemaphoreType.DMA((R, 2, P)),
                pltpu.VMEM((H, latent), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.SMEM((4,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q_abs.dtype),
        interpret=interpret,
        name="pallas_mla_decode",
    )(block_tables, context_lens, jnp.asarray(layer, jnp.int32).reshape(1),
      q_abs, q_rope, c_pages, r_pages)
