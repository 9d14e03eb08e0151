"""Pallas TPU kernel: flash cached-prefill attention over paged KV.

The chunked-prefill hot path (``prefill_cached``) attends a bucket of
fresh query tokens to (a) the request's cached prefix, living in paged
HBM, and (b) the chunk's own just-computed K/V. The XLA reference path
(``ops/attention.py::context_prefill_attention``) services both from
HBM: ``_gather_ctx`` materializes and dequantizes the ENTIRE
``[B, MAXB*bs, KVH, D]`` context per layer — including the suffix span
it scattered to the pages one op earlier. At int8 that is a gather +
f32 upcast of every byte of context per chunk per layer.

This kernel restructures the read path the same way the decode kernel
(``pallas_paged_attention.py``) did for the decode loop:

- **Only live prefix pages stream from HBM**, chunk by chunk through
  the same ring-buffered manual DMAs (``_chunk_copies`` is imported,
  not copied) — no full-table materialization, and rows whose prefix
  is short stop streaming at their own boundary.
- **int8 pages dequantize on-chip**: the HBM stream stays int8 plus
  the tiny f32 scale rows, halving prefill KV read traffic exactly as
  PR 5 did for decode.
- **The suffix never makes the HBM round trip**: the kernel emits the
  prefix's online-softmax partials (acc, m, l); the chunk's own fresh
  K/V attends in-register via plain XLA, and the two are merged with
  the standard flash recombination. The write-then-regather of the
  suffix span disappears.

Grid ``(B, nq, nc)``: query tiles are an outer loop, prefix-page
chunks the innermost (serial) reduction, so the DMA ring's global step
``g = (b*nq + qi)*nc + c`` crosses both tile and sequence boundaries.
Each (b, qi) owns ``KVH * group * TQ`` head-batched score rows — the
decode kernel's layout with the query-tile axis folded in.

Correctness is pinned by tests/test_prefill_kernel.py (interpret-mode
parity vs the XLA reference on CPU, bf16 and int8, ragged lengths).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.pallas_paged_attention import (
    VMEM_BUDGET,
    _start_chunk_copy,
    _wait_chunk_copy,
    choose_tile,
    gather_scale_rows,
    pad_tables,
    ring_bytes,
)

NEG_INF = -1e30


def _prefill_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, MAXB]
    prefix_lens_ref,  # [B] cached-prefix tokens (pages to stream)
    layer_ref,  # [1]
    # inputs
    q_ref,  # [1, 1, KVH*gq, D] query tile for (b, qi); pre-scaled
    k_hbm_ref,  # [L, NB, bs, KVH, D] in ANY/HBM (int8 when quantized)
    v_hbm_ref,
    # quantized only: ks_ref / vs_ref [1, KVH, span] f32 VMEM blocks (this
    # chunk's per-head scale rows); then outputs o_acc [1, 1, KVH*gq, D]
    # f32 (unnormalized), o_m / o_l [1, 1, KVH*gq, 128] f32; then
    # scratch: k_buf/v_buf VMEM [RING, P, bs, KVH, D], sems DMA
    # [RING, 2, P], s_ref [KVH*gq, span] f32, acc_ref [KVH*gq, D] f32,
    # m_ref/l_ref [KVH*gq, 128] f32.
    *refs,
    block_size: int,
    kvh: int,
    gq: int,  # group * TQ rows per kv head
    pages_per_block: int,
    ring: int,
    quantized: bool,
    window: int | None = None,
    q_tile: int = 0,  # with ``window``: TQ, to give each row its position
):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    (o_acc_ref, o_m_ref, o_l_ref, k_buf, v_buf, sems,
     s_ref, acc_ref, m_ref, l_ref) = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    c = pl.program_id(2)
    nb = pl.num_programs(0)
    nq = pl.num_programs(1)
    nc = pl.num_programs(2)
    layer = layer_ref[0]
    prefix = prefix_lens_ref[b]
    P = pages_per_block
    span_tokens = P * block_size
    chunk_start = c * span_tokens
    # Global step: the prefetch window crosses query-tile AND sequence
    # boundaries (each tile re-streams its row's prefix pages).
    g = (b * nq + qi) * nc + c
    slot = jax.lax.rem(g, ring)

    @pl.when(g == 0)
    def _fill():
        # Cold start: fill the ring for the first live chunks
        # (liveness-guarded with the same predicate the consumer uses,
        # so every started copy is waited exactly once).
        for k in range(min(ring - 1, nb * nq * nc)):
            gb = k // (nq * nc)
            gc = k % nc

            @pl.when(gc * span_tokens < prefix_lens_ref[gb])
            def _(gb=gb, gc=gc, k=k):
                _start_chunk_copy(
                    k_hbm_ref, v_hbm_ref, k_buf, v_buf, sems,
                    block_tables_ref, layer, gb, gc, k % ring, P)

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Issue the chunk RING-1 global steps ahead (lands in the slot just
    # consumed, which the serial grid has already finished reading).
    g_pre = g + ring - 1
    b_pre = g_pre // (nq * nc)
    c_pre = jax.lax.rem(g_pre, nc)

    @pl.when(jnp.logical_and(
        b_pre < nb,
        c_pre * span_tokens < prefix_lens_ref[jnp.minimum(b_pre, nb - 1)]))
    def _prefetch():
        _start_chunk_copy(k_hbm_ref, v_hbm_ref, k_buf, v_buf, sems,
                          block_tables_ref, layer, b_pre, c_pre,
                          jax.lax.rem(g_pre, ring), P)

    def attend_chunk():
        for h in range(kvh):  # static unroll over kv heads
            rows = slice(h * gq, (h + 1) * gq)
            q = q_ref[0, 0, rows, :].astype(jnp.float32)  # [gq, D]
            k = (k_buf[slot, :, :, h, :]
                 .reshape(span_tokens, -1).astype(jnp.float32))
            s_h = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if quantized:
                # Dequantize on-chip: the HBM stream stayed int8; the
                # per-token scale factors out of the dot (see the
                # decode kernel).
                s_h = s_h * ks_ref[0, h:h + 1, :]
            s_ref[rows, :] = s_h
        # Every query row in the chunk sits at an absolute position
        # >= prefix, so the prefix side needs NO per-row causal mask —
        # only the prefix-length bound. (The causal structure lives
        # entirely in the fresh-suffix merge on the host side.)
        span = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, span_tokens), 1
        )
        valid = span < prefix  # [1, span]
        if window is not None:
            # Row (h * group + g) * TQ + t of the tile is the query at
            # position prefix + qi * TQ + t, which sees the keys after
            # position - window.
            t = jax.lax.rem(jax.lax.broadcasted_iota(
                jnp.int32, (kvh * gq, 1), 0), q_tile)
            valid = jnp.logical_and(
                valid, span > prefix + qi * q_tile + t - window)
        s = jnp.where(valid, s_ref[...], NEG_INF)  # [KVH*gq, span]
        m_prev = m_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # [KVH*gq, 1]
        p_ = jnp.exp(s - m_new)
        if window is not None:
            # A row that sees nothing of this chunk still has NEG_INF as
            # its running max: exp(0) would count each masked key as one.
            p_ = jnp.where(valid, p_, 0.0)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p_, axis=1, keepdims=True),
            l_ref.shape,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha  # one batched rescale
        for h in range(kvh):
            rows = slice(h * gq, (h + 1) * gq)
            v = (v_buf[slot, :, :, h, :]
                 .reshape(span_tokens, -1).astype(jnp.float32))
            p_h = p_[rows, :]
            if quantized:
                p_h = p_h * vs_ref[0, h:h + 1, :]
            acc_ref[rows, :] = acc_ref[rows, :] + jax.lax.dot(
                p_h, v, preferred_element_type=jnp.float32)

    @pl.when(chunk_start < prefix)
    def _compute():
        _wait_chunk_copy(k_hbm_ref, v_hbm_ref, k_buf, v_buf, sems,
                         block_tables_ref, layer, b, c, slot, P)
        if window is None:
            attend_chunk()
        else:
            # The copy was started, so it is waited for; a chunk that
            # ends before the tile's first query's window is not
            # computed.
            pl.when(chunk_start + span_tokens
                    > prefix + qi * q_tile - window + 1)(attend_chunk)

    @pl.when(c == nc - 1)
    def _finalize():
        # Emit the UN-normalized partials: the caller merges them with
        # the fresh-suffix partials (flash recombination), so dividing
        # by l here would just be undone. Rows with an empty prefix
        # leave (acc=0, m=NEG_INF, l=0), which the merge handles.
        o_acc_ref[0, 0] = acc_ref[...]
        o_m_ref[0, 0] = m_ref[...]
        o_l_ref[0, 0] = l_ref[...]


def prefill_tile(T: int, H: int, block_size: int, kvh: int, head_dim: int,
                 itemsize: int, tables_width: int, quantized: bool):
    """(q_tile, pages_per_block, ring) the kernel runs with at these
    shapes, chosen to fit :data:`VMEM_BUDGET`; None when nothing fits.

    The widest query tile first: every tile re-streams its row's whole
    prefix, and the f32 partial outputs [H * q_tile, D + 256] (double
    buffered) count as much as the page ring. The kernel reuses each
    chunk across H * q_tile query rows, so it is bound by the MXU long
    before the page stream: a ring of 3 already hides the copies, and
    2 is taken before a narrower tile."""
    t_pad = (T + 7) // 8 * 8
    for tq in (128, 64, 32, 16, 8):
        if tq > t_pad and tq > 8:
            continue
        rows = H * tq

        def fits(pages: int, ring: int) -> bool:
            span = pages * block_size
            total = ring_bytes(ring, pages, block_size, kvh, head_dim,
                               itemsize)
            total += 4 * rows * (span + head_dim + 256)  # s/acc/m/l
            total += 2 * 4 * rows * span  # masked scores, probabilities
            total += 2 * 4 * rows * (head_dim + 256)  # partial outputs
            total += 2 * 2 * rows * head_dim  # bf16 query block
            if quantized:
                total += 2 * 2 * 4 * max(kvh, 8) * span  # scale blocks
            return total <= VMEM_BUDGET

        tile = choose_tile(fits, tables_width, block_size, ring_floor=2)
        if tile is not None:
            return (tq,) + tile
    return None


@functools.partial(
    jax.jit,
    static_argnames=("scale", "pages_per_block", "ring", "q_tile",
                     "interpret", "window"))
def pallas_prefill_attention(
    q: jax.Array,  # [B, T, H, D] the chunk's query tokens
    k_pages,  # [L, NB, bs, KVH, D] stacked pages (or (data, scales))
    v_pages,
    block_tables: jax.Array,  # [B, MAXB] int32
    positions: jax.Array,  # [B, T] absolute, contiguous ascending
    total_lens: jax.Array,  # [B] context length incl. this chunk
    layer,  # scalar layer index (traced)
    k_new: jax.Array,  # [B, T, KVH, D] the chunk's own fresh K
    v_new: jax.Array,  # [B, T, KVH, D]
    suffix_lens: jax.Array,  # [B] valid fresh tokens (= seq_lens)
    *,
    scale: float,
    pages_per_block: int = 0,  # 0 -> from the VMEM budget (prefill_tile)
    ring: int = 0,  # DMA ring depth; 0 -> from the VMEM budget
    q_tile: int = 0,  # query-tile width; 0 -> from the VMEM budget
    interpret: bool = False,
    window: int | None = None,  # position p sees p - window < j <= p
) -> jax.Array:
    quantized = isinstance(k_pages, tuple)
    if quantized:
        k_pages, k_scales = k_pages
        v_pages, v_scales = v_pages
    B, T, H, D = q.shape
    L, NB, bs, KVH, _ = k_pages.shape
    group = H // KVH
    tile = prefill_tile(T, H, bs, KVH, D, k_pages.dtype.itemsize,
                        block_tables.shape[1], quantized)
    if tile is None and not (pages_per_block and ring and q_tile):
        raise ValueError(
            f"no prefill tile fits VMEM at heads={H} block_size={bs} "
            f"kv_heads={KVH} head_dim={D}")
    TQ = q_tile or tile[0]
    P = pages_per_block or tile[1]
    R = ring or tile[2]
    block_tables = pad_tables(block_tables, P)
    nc = block_tables.shape[1] // P
    T_pad = (T + TQ - 1) // TQ * TQ
    nq = T_pad // TQ
    gq = group * TQ

    # The contract with the engine's chunk layout: positions are
    # contiguous ascending per row, so the cached prefix the pages must
    # serve is everything before the row's first query position.
    prefix_lens = jnp.clip(
        jnp.minimum(positions[:, 0], total_lens), 0, None
    ).astype(jnp.int32)

    qs = (q * scale).astype(q.dtype)
    qg = qs.reshape(B, T, KVH, group, D)
    # Row layout per (b, qi) tile: (h * group + g) * TQ + t — the
    # decode kernel's head-major layout with the tile axis innermost.
    qt = qg.transpose(0, 2, 3, 1, 4)  # [B, KVH, group, T, D]
    if T_pad != T:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    qt = qt.reshape(B, KVH, group, nq, TQ, D).transpose(0, 3, 1, 2, 4, 5)
    qt = qt.reshape(B, nq, KVH * gq, D)

    kernel = functools.partial(
        _prefill_kernel, block_size=bs, kvh=KVH, gq=gq,
        pages_per_block=P, ring=R, quantized=quantized,
        **({} if window is None else {"window": window, "q_tile": TQ}),
    )
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    in_specs = [
        pl.BlockSpec(
            (1, 1, KVH * gq, D), lambda b, qi, c, bt, pfx, lr: (b, qi, 0, 0)
        ),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [qt, k_pages, v_pages]
    if quantized:
        # This chunk's scale rows [KVH, span] per side; the block index
        # stops at the row's last live chunk (see the decode kernel).
        def scale_block(b, qi, c, bt, pfx, lr):
            last = jnp.maximum(pfx[b] - 1, 0) // (P * bs)
            return (b, 0, jnp.minimum(c, last))

        in_specs += [pl.BlockSpec((1, KVH, P * bs), scale_block)] * 2
        operands += [
            gather_scale_rows(k_scales, block_tables, layer, bs, KVH),
            gather_scale_rows(v_scales, block_tables, layer, bs, KVH)]
    scratch_shapes = [
        pltpu.VMEM((R, P, bs, KVH, D), k_pages.dtype),
        pltpu.VMEM((R, P, bs, KVH, D), v_pages.dtype),
        pltpu.SemaphoreType.DMA((R, 2, P)),
        pltpu.VMEM((KVH * gq, P * bs), jnp.float32),
        pltpu.VMEM((KVH * gq, D), jnp.float32),
        pltpu.VMEM((KVH * gq, 128), jnp.float32),
        pltpu.VMEM((KVH * gq, 128), jnp.float32),
    ]
    out_block = lambda b, qi, c, bt, pfx, lr: (b, qi, 0, 0)  # noqa: E731
    acc_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nq, nc),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, KVH * gq, D), out_block),
                pl.BlockSpec((1, 1, KVH * gq, 128), out_block),
                pl.BlockSpec((1, 1, KVH * gq, 128), out_block),
            ],
            scratch_shapes=scratch_shapes,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, nq, KVH * gq, D), jnp.float32),
            jax.ShapeDtypeStruct((B, nq, KVH * gq, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, nq, KVH * gq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(block_tables, prefix_lens, layer_arr, *operands)

    def _untile(x):
        # [B, nq, KVH*gq, ...] -> [B, KVH, group, T, ...]
        x = x.reshape((B, nq, KVH, group, TQ) + x.shape[3:])
        x = jnp.moveaxis(x, 1, 3)  # [B, KVH, group, nq, TQ, ...]
        x = x.reshape((B, KVH, group, T_pad) + x.shape[5:])
        return x[:, :, :, :T]

    acc_p = _untile(acc_p)  # [B, KVH, group, T, D] f32
    m_p = _untile(m_p)[..., 0]  # [B, KVH, group, T]
    l_p = _untile(l_p)[..., 0]

    # Fresh-suffix attention straight from the chunk's own K/V — the
    # one part of the context that never needs to round-trip HBM.
    qf = qs.reshape(B, T, KVH, group, D).astype(jnp.float32)
    s = jnp.einsum("btkgd,bskd->bkgts", qf, k_new.astype(jnp.float32))
    causal = positions[:, None, :] <= positions[:, :, None]  # [B, t, s]
    fresh = (jnp.arange(T, dtype=jnp.int32)[None, :]
             < suffix_lens[:, None])  # [B, s]
    mask = jnp.logical_and(causal, fresh[:, None, :])
    if window is not None:
        mask = jnp.logical_and(
            mask, positions[:, None, :] > positions[:, :, None] - window)
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    m_s = jnp.max(s, axis=-1)  # [B, KVH, group, T]
    p = jnp.exp(s - m_s[..., None])
    l_s = jnp.sum(p, axis=-1)
    acc_s = jnp.einsum("bkgts,bskd->bkgtd", p, v_new.astype(jnp.float32))

    # Flash recombination of the two partial softmaxes.
    m_tot = jnp.maximum(m_p, m_s)
    a_p = jnp.exp(m_p - m_tot)
    a_s = jnp.exp(m_s - m_tot)
    l_tot = jnp.maximum(l_p * a_p + l_s * a_s, 1e-30)
    out = (acc_p * a_p[..., None] + acc_s * a_s[..., None]) / l_tot[..., None]
    out = out.swapaxes(2, 3).swapaxes(1, 2).reshape(B, T, H, D)
    return out.astype(q.dtype)
