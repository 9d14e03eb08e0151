"""Pallas TPU kernel: paged attention for the decode hot loop.

One query token per sequence attends over that sequence's KV pages
scattered in HBM — the TPU counterpart of vLLM's CUDA PagedAttention
kernel, which the reference stack consumes via engine images
(ref helm/templates/deployment-vllm-multi.yaml:108-199).

v3 (round 5). Round-5 profiling (benchmarks/kernel_dma_only.py) showed
the v2 kernel's double-buffered page DMAs already stream at ~705 GB/s —
1.16x the HBM floor — while the full kernel ran at 2.3x: the per-chunk
softmax compute was NOT overlapping the DMA stream (total ~= DMA +
compute instead of max(DMA, compute)). v3 restructures for overlap and
for fewer vector-op issues:

- **Ring buffer, depth R=4** (was 2): page copies are issued ``R-1``
  chunks ahead along a GLOBAL step index ``g = b * nc + c``, so the
  prefetch window crosses sequence boundaries — while sequence ``b``'s
  last chunks compute, sequence ``b+1``'s first pages are already in
  flight (the v2 kernel paid a cold refill at every ``c == 0``).
- **Head-batched softmax**: one scores scratch ``[KVH * g_pad, span]``
  is filled by per-head QK dots, then masking, running max, exp, and
  the l/acc updates run ONCE over all heads' rows (v2 issued every
  VPU stage 8x per chunk, once per kv head).
- q is pre-scaled by ``scale`` outside the kernel (one [B, H, D]
  multiply) instead of scaling every [g_pad, span] score tile.

Structure credit: the grid/BlockSpec shape follows
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Deepest DMA ring (chunks prefetched ahead of compute) and widest chunk
# the tile chooser will pick. The round-5 sweep measured depth 6 with
# 8-page chunks fastest — deep enough to cover DMA issue->complete
# latency across sequence boundaries.
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
                ring_floor: int = 3):
    """(pages_per_block, ring) for a paged kernel: the widest chunk and
    then the deepest ring that ``fits(pages, ring)``. A chunk spans
    whole 128-lane tiles of tokens (the scores' and the int8 scale
    rows' last dim) and no more pages than the table, rounded up to a
    power of two. Rings shallower than ``ring_floor`` are tried only
    after every chunk width failed at the floor. Returns None when
    nothing fits."""
    cap = 1
    while cap < min(tables_width, MAX_PAGES_PER_BLOCK):
        cap *= 2
    widths = [p for p in (8, 4, 2, 1)
              if p <= cap and (p * block_size) % 128 == 0]
    widths = widths or [-(-128 // block_size)]
    for rings in (range(RING, ring_floor - 1, -1),
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


def _chunk_copies(k_hbm, v_hbm, k_buf, v_buf, sems, bt_ref, layer,
                  b, chunk, slot, pages_per_block):
    """Async-copy descriptors for one chunk's pages into ring slot `slot`."""
    copies = []
    for p in range(pages_per_block):
        page = bt_ref[b, chunk * pages_per_block + p]
        copies.append(pltpu.make_async_copy(
            k_hbm.at[layer, page], k_buf.at[slot, p], sems.at[slot, 0, p]))
        copies.append(pltpu.make_async_copy(
            v_hbm.at[layer, page], v_buf.at[slot, p], sems.at[slot, 1, p]))
    return copies


def _start_chunk_copy(*args, **kwargs):
    for c in _chunk_copies(*args, **kwargs):
        c.start()


def _wait_chunk_copy(*args, **kwargs):
    for c in _chunk_copies(*args, **kwargs):
        c.wait()


def _decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, MAXB]
    context_lens_ref,  # [B]
    layer_ref,  # [1]
    # inputs
    q_ref,  # [1, KVH * g_pad, D] (VMEM block for sequence b; pre-scaled)
    k_hbm_ref,  # [L, NB, bs, KVH, D] in ANY/HBM (int8 when quantized)
    v_hbm_ref,
    # quantized only: ks_ref / vs_ref [1, KVH, span] f32 VMEM blocks (this
    # chunk's per-head scale rows), then output o_ref [1, KVH*g_pad, D],
    # then scratch: k_buf/v_buf VMEM [RING, P, bs, KVH, D], sems DMA
    # [RING, 2, P], s_ref [KVH*g_pad, span] f32, acc_ref [KVH*g_pad, D]
    # f32, m_ref/l_ref [KVH*g_pad, 128] f32.
    *refs,
    block_size: int,
    kvh: int,
    g_pad: int,
    pages_per_block: int,
    ring: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    (o_ref, k_buf, v_buf, sems, s_ref, acc_ref, m_ref, l_ref) = refs
    b = pl.program_id(0)
    c = pl.program_id(1)
    nc = pl.num_programs(1)
    nb = pl.num_programs(0)
    layer = layer_ref[0]
    ctx = context_lens_ref[b]
    P = pages_per_block
    span_tokens = P * block_size
    chunk_start = c * span_tokens
    g = b * nc + c  # global step: the prefetch window crosses sequences
    slot = jax.lax.rem(g, ring)

    @pl.when(g == 0)
    def _fill():
        # Cold start: fill the ring for the first live chunks of the
        # leading sequences (liveness-guarded per chunk; the guard is
        # the same predicate the consumer uses, so every started copy
        # is waited exactly once).
        for k in range(min(ring - 1, nb * nc)):
            gb, gc = divmod(k, nc)

            @pl.when(gc * span_tokens < context_lens_ref[gb])
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
    b_pre = g_pre // nc
    c_pre = jax.lax.rem(g_pre, nc)

    @pl.when(jnp.logical_and(
        b_pre < nb,
        c_pre * span_tokens < context_lens_ref[jnp.minimum(b_pre, nb - 1)]))
    def _prefetch():
        _start_chunk_copy(k_hbm_ref, v_hbm_ref, k_buf, v_buf, sems,
                          block_tables_ref, layer, b_pre, c_pre,
                          jax.lax.rem(g_pre, ring), P)

    @pl.when(chunk_start < ctx)
    def _compute():
        _wait_chunk_copy(k_hbm_ref, v_hbm_ref, k_buf, v_buf, sems,
                         block_tables_ref, layer, b, c, slot, P)
        # Per-head QK dots into ONE scores scratch, then every VPU stage
        # (mask, max, exp, l/acc updates) runs once over all heads' rows.
        # Operands are cast to f32 first — measured FASTER than feeding
        # bf16 straight to the MXU at these tiny tile shapes (ring sweep,
        # round 5: bf16 operands cost +66%; Mosaic's repacking of skinny
        # bf16 tiles outweighs the cast traffic).
        for h in range(kvh):  # static unroll over kv heads
            rows = slice(h * g_pad, (h + 1) * g_pad)
            q = q_ref[0, rows, :].astype(jnp.float32)  # [g_pad, D]
            k = (k_buf[slot, :, :, h, :]
                 .reshape(span_tokens, -1).astype(jnp.float32))
            s_h = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if quantized:
                # Dequantize on-chip: the HBM stream stayed int8. A
                # token's scale is constant along D, so it factors out
                # of the dot and multiplies the scores ([1, span] lane
                # row, broadcast over the head's query rows).
                s_h = s_h * ks_ref[0, h:h + 1, :]
            s_ref[rows, :] = s_h
        span = chunk_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, span_tokens), 1
        )
        valid = span < ctx  # [1, span]
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
        for h in range(kvh):
            rows = slice(h * g_pad, (h + 1) * g_pad)
            v = (v_buf[slot, :, :, h, :]
                 .reshape(span_tokens, -1).astype(jnp.float32))
            p_h = p_[rows, :]
            if quantized:
                p_h = p_h * vs_ref[0, h:h + 1, :]
            acc_ref[rows, :] = acc_ref[rows, :] + jax.lax.dot(
                p_h, v, preferred_element_type=jnp.float32)

    @pl.when(c == nc - 1)
    def _finalize():
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
            total += 2 * 2 * 4 * max(kvh, 8) * span  # scale blocks
        return total <= VMEM_BUDGET

    return choose_tile(fits, tables_width, block_size)


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_block", "ring", "interpret"))
def pallas_paged_attention(
    q: jax.Array,  # [B, H, D]
    k_pages,  # [L, NB, bs, KVH, D] stacked pages (or (data, scales))
    v_pages,  # [L, NB, bs, KVH, D] (or (data, scales))
    block_tables: jax.Array,  # [B, MAXB] int32
    context_lens: jax.Array,  # [B] int32
    layer,  # scalar layer index (traced)
    *,
    scale: float,
    pages_per_block: int = 0,  # 0 -> from the VMEM budget (decode_tile)
    ring: int = 0,  # DMA ring depth; 0 -> from the VMEM budget
    interpret: bool = False,
) -> jax.Array:
    quantized = isinstance(k_pages, tuple)
    if quantized:
        k_pages, k_scales = k_pages
        v_pages, v_scales = v_pages
    B, H, D = q.shape
    L, NB, bs, KVH, _ = k_pages.shape
    group = H // KVH
    # Pad each query-head group to the float32 sublane tile (8 rows).
    g_pad = max(group, 8)
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
    nc = block_tables.shape[1] // P
    qg = (q * scale).astype(q.dtype).reshape(B, KVH, group, D)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))
    qg = qg.reshape(B, KVH * g_pad, D)

    kernel = functools.partial(
        _decode_kernel, block_size=bs, kvh=KVH, g_pad=g_pad,
        pages_per_block=P, ring=R, quantized=quantized,
    )
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    in_specs = [
        pl.BlockSpec(
            (1, KVH * g_pad, D), lambda b, c, bt, cl, lr: (b, 0, 0)
        ),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        # This chunk's scale rows [KVH, span] per side. Past a
        # sequence's last live chunk the block index stops moving, so
        # dead grid steps fetch nothing.
        def scale_block(b, c, bt, cl, lr):
            last = jnp.maximum(cl[b] - 1, 0) // (P * bs)
            return (b, 0, jnp.minimum(c, last))

        in_specs += [pl.BlockSpec((1, KVH, P * bs), scale_block)] * 2
        operands += [
            gather_scale_rows(k_scales, block_tables, layer, bs, KVH),
            gather_scale_rows(v_scales, block_tables, layer, bs, KVH)]
    scratch_shapes = [
        pltpu.VMEM((R, P, bs, KVH, D), k_pages.dtype),
        pltpu.VMEM((R, P, bs, KVH, D), v_pages.dtype),
        pltpu.SemaphoreType.DMA((R, 2, P)),
        pltpu.VMEM((KVH * g_pad, P * bs), jnp.float32),
        pltpu.VMEM((KVH * g_pad, D), jnp.float32),
        pltpu.VMEM((KVH * g_pad, 128), jnp.float32),
        pltpu.VMEM((KVH * g_pad, 128), jnp.float32),
    ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, KVH * g_pad, D), lambda b, c, bt, cl, lr: (b, 0, 0)
            ),
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH * g_pad, D), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens.astype(jnp.int32), layer_arr, *operands)
    out = out.reshape(B, KVH, g_pad, D)[:, :, :group, :]
    return out.reshape(B, H, D)
