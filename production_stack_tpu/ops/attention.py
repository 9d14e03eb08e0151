"""Attention ops: XLA reference implementations + pallas dispatch.

The serving engine replaces vLLM's CUDA PagedAttention (which the reference
stack consumes via container images) with TPU-native equivalents:

- prefill: causal self-attention over the prompt, computed from fresh K/V —
  XLA fuses this into MXU-friendly batched matmuls.
- decode: query length 1 per sequence against KV pages scattered in HBM.
  The pallas kernel (:mod:`production_stack_tpu.ops.pallas_paged_attention`)
  walks only the live pages of the rows that hold a token; the XLA fallback
  gathers the padded context (correct everywhere, used on CPU test meshes).

All softmax accumulation is float32 regardless of compute dtype.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30

# context_prefill_attention switches to the chunked online-softmax path
# when its f32 scores tensor would exceed this (tests lower it to force
# the chunked path at toy shapes).
_CHUNKED_SCORE_BYTES = 1 << 30
_CHUNKED_SCORE_SPAN = 1024


def kv_page_data(pages):
    """The array leaf of a KV page operand.

    Pages are either a bare ``[L, NB, bs, KVH, D]`` array (bf16 cache) or
    a ``(data, scales)`` 2-tuple (int8 cache): ``data`` is the int8 pages
    array and ``scales`` is a float32 ``[L, NB, bs * KVH]`` per-slot,
    per-kv-head symmetric scale (flat token-major: row-major it bitcasts
    to ``(L * NB * bs, KVH)``, the same flat-slot view the scatter uses).
    The last dim is kept flat so it rides the 128-lane tile instead of
    padding a tiny KVH axis."""
    return pages[0] if isinstance(pages, tuple) else pages


def quantize_kv(x: jax.Array):
    """Symmetric per-(token, kv-head) int8 quantization of [..., KVH, D]
    values: scale = amax/127 over D (1.0 where the row is all-zero, so
    empty slots stay exactly zero and nothing divides by zero)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)  # [..., KVH]
    scale = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _use_pallas() -> bool:
    if os.environ.get("TPU_STACK_FORCE_XLA_ATTENTION"):
        return False
    return jax.devices()[0].platform == "tpu"


def _page_tile_ok(block_size: int, kvh: int, head_dim: int,
                  packed: bool = False) -> bool:
    """Trace-time tile-alignment gate shared by the paged kernels. The
    manual page DMAs slice [bs, KVH, D] out of HBM: Mosaic requires the
    sliced dims tile-aligned (KVH to the 8-row sublane, D to the 128
    lanes, bs to 8). Four rows of bf16 or float32 are a tile of their own
    (``T(4,128)(2,1)`` for bf16: the v5e compiler takes both kernels
    there, tests/test_chip_compile.py), admitted (``packed``) for a pool
    whose own rows are four: the rows :func:`packed_page_dims` made of
    eight 64-wide heads, or a model of four kv heads of 128
    (:func:`attention_path`); eight heads sharded two ways take the
    reference as they did. Misaligned models (e.g. OPT: 12 kv-heads, head_dim 64)
    take the XLA reference — and this MUST be decided at trace time: a
    Mosaic failure surfaces when the enclosing jit compiles, where no
    fallback is possible."""
    rows_ok = kvh % 8 == 0 or (packed and kvh == 4)
    return block_size % 8 == 0 and rows_ok and head_dim % 128 == 0


def packed_page_dims(kvh: int, head_dim: int, quantized: bool = False):
    """``(rows, lanes)`` a page keeps of one token: ``(kvh, head_dim)``,
    or, for heads narrower than the 128 lanes, ``128 // head_dim`` heads
    side by side in each 128-lane row where that is a layout the paged
    kernels take (eight heads of 64: ``(4, 128)``). A row-major view of
    the same values, so a page's bytes are what they were without a
    lane's padding (2 KiB a token and layer at 8 x 64 in bf16, not the 8
    of ``(16, 128)`` tiles), and the kernels run as they are on
    ``rows`` heads of 128 (:func:`_packed_queries`). Decided from shapes
    alone: the pool has one layout on every platform. int8 pages keep
    ``(kvh, head_dim)`` (their scales are per head) and the reference."""
    per_row = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    if (per_row > 1 and not quantized and kvh % per_row == 0
            and _page_tile_ok(8, kvh // per_row, 128, packed=True)):
        return kvh // per_row, 128
    return kvh, head_dim


def _packed_queries(q: jax.Array, rows: int, per_row: int) -> jax.Array:
    """``q [..., H, D]`` as queries over ``rows`` heads of ``per_row * D``
    lanes: a query head of KV head ``e`` of a row keeps its values in
    lanes ``[e * D, (e + 1) * D)`` and zeros elsewhere, so its dot with
    the row is its dot with its own head. ``[..., rows * per_row * G,
    per_row * D]``, a row's ``per_row * G`` queries together."""
    *lead, H, D = q.shape
    G = H // (rows * per_row)
    eye = jnp.eye(per_row, dtype=q.dtype)
    spread = (q.reshape(*lead, rows, per_row, G, 1, D)
              * eye[:, None, :, None])  # [..., rows, e, G, e', D]
    return spread.reshape(*lead, H, per_row * D)


def _unpacked_outputs(out: jax.Array, rows: int, per_row: int) -> jax.Array:
    """The inverse on the attention output ``[..., H, per_row * D]``: a
    query keeps the lanes of its own head (the others hold the row's
    other heads' values under this head's probabilities)."""
    *lead, H, lanes = out.shape
    D, G = lanes // per_row, H // (rows * per_row)
    out = out.reshape(*lead, rows, per_row, G, per_row, D)
    own = jnp.stack([out[..., e, :, e, :] for e in range(per_row)], axis=-3)
    return own.reshape(*lead, H, D)


def _lanes_per_head(q: jax.Array, k_pages) -> int:
    """How many of ``q``'s heads share a row of the pages (1: none)."""
    return kv_page_data(k_pages).shape[-1] // q.shape[-1]


# (mesh, axis) while a model whose KV pool is sharded over kv heads is
# being traced; set by the engine around its forward (kv_head_sharding).
_KV_SHARD: contextvars.ContextVar = contextvars.ContextVar(
    "kv_head_sharding", default=None)

# Trace-time dispatch decisions, keyed (op, path): what each compiled
# program will run. Counts traces, not calls.
TRACED_PATHS: collections.Counter = collections.Counter()


@contextlib.contextmanager
def kv_head_sharding(mesh, axis: str):
    """Tell the attention dispatchers, for the traces made inside, that
    the KV pool is sharded over its kv-head axis on ``mesh``'s ``axis``.
    The compiler cannot partition a pallas_call (under a sharded jit it
    refuses: "Mosaic kernels cannot be automatically partitioned"), so
    the kernels then run per shard inside an explicit shard_map."""
    token = _KV_SHARD.set((mesh, axis))
    try:
        yield
    finally:
        _KV_SHARD.reset(token)


def shard_paged_kernels(apply, pages_sharding):
    """``(apply, kv_shards)`` for a model whose pool has this sharding:
    where its kv-head axis is sharded over ``tp``, ``apply`` is wrapped
    so its traces run under :func:`kv_head_sharding`."""
    if "tp" not in pages_sharding.spec or "pp" in pages_sharding.spec:
        return apply, 1
    mesh = pages_sharding.mesh

    def sharded_apply(*args, **kwargs):
        with kv_head_sharding(mesh, "tp"):
            return apply(*args, **kwargs)

    return sharded_apply, mesh.shape["tp"]


def attention_path(block_size: int, kvh: int, head_dim: int,
                   quantized: bool, kv_shards: int = 1,
                   packed: bool = False) -> str:
    """Which backend a paged-attention dispatch with these (static) page
    shapes (the pool's own; ``packed``: its rows are narrow heads side
    by side, :func:`packed_page_dims`) takes: ``"pallas"`` or ``"xla"``.
    THE decision — both
    dispatchers and the engine's dispatch counter evaluate it, from
    shapes, platform and the env override only. ``kv_shards`` is the
    number of ways the pool's kv-head axis is sharded: the kernel then
    sees ``kvh / kv_shards`` heads per chip. A sharded int8 pool takes
    the reference: its token-major scales are replicated and interleave
    the heads, so a shard cannot address its own."""
    if kv_shards > 1 and quantized:
        return "xla"
    # Four rows are a tile where the pool itself holds four: packed of
    # eight narrow heads, or a model's own four kv heads (smallthinker:
    # 4 x 128, the same ``[bs, 4, 128]`` pages); never a shard's four of a
    # wider pool, never int8 (whose tile is 32 rows).
    four_rows = (packed or kvh == 4) and not quantized and kv_shards == 1
    if (_page_tile_ok(block_size, kvh // kv_shards, head_dim, four_rows)
            and _use_pallas()):
        return "pallas"
    return "xla"


def _traced_path(op: str, k_pages, packed: bool = False) -> str:
    """The dispatch decision for the trace in progress, counted."""
    _, _, bs, kvh, head_dim = kv_page_data(k_pages).shape
    shard = _KV_SHARD.get()
    path = attention_path(
        bs, kvh, head_dim, isinstance(k_pages, tuple),
        shard[0].shape[shard[1]] if shard else 1, packed)
    TRACED_PATHS[op, path] += 1
    return path


def _per_kv_shard(kernel, head_ranks, n_replicated: int):
    """``kernel(*head_operands, k_pages, v_pages, *replicated)`` as it is,
    or — while a pool sharded over kv heads is traced — run per shard
    inside a ``shard_map``. Head operands (q, a chunk's fresh K/V; their
    ranks in ``head_ranks``) and the output (shaped like the first) carry
    heads on their second-to-last axis, kv-head-major, so one split
    serves query and kv heads alike; pages carry them on axis 3."""
    shard = _KV_SHARD.get()
    if shard is None:
        return kernel
    mesh, axis = shard
    heads = [P(*[None] * (rank - 2), axis, None) for rank in head_ranks]
    pages = P(None, None, None, axis, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(*heads, pages, pages) + (P(),) * n_replicated,
        out_specs=heads[0], check_vma=False)


def prefill_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, T, KVH, D]
    v: jax.Array,  # [B, T, KVH, D]
    *,
    scale: float,
    seq_lens: jax.Array | None = None,  # [B] valid lengths (padding masked)
    window: int | None = None,  # static: query i sees key j iff i - j < window
) -> jax.Array:
    """Causal attention over a prompt chunk. Returns [B, T, H, Dv]: the
    values may be of another width than queries and keys (a latent
    cache's up-projected heads: 192-wide keys, 128-wide values)."""
    B, T, H, D = q.shape
    KVH = k.shape[2]
    group = H // KVH
    qg = q.reshape(B, T, KVH, group, D)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(T)
    causal = pos[None, :, None] >= pos[None, None, :]  # [1, T, S]
    mask = causal
    if seq_lens is not None:
        valid = pos[None, None, :] < seq_lens[:, None, None]  # [B,1,S]
        mask = causal & valid
    if window is not None:
        mask = mask & (pos[None, :, None] - pos[None, None, :] < window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bskd->btkgd", probs.astype(v.dtype), v,
    )
    return out.reshape(B, T, H, v.shape[-1])


def _gather_ctx(pages, block_tables: jax.Array, layer: jax.Array,
                out_dtype=None, head_dim: int | None = None):
    """Gather a batch's context from stacked pages [L, NB, bs, KVH, D]
    without materializing a whole layer: page-level indices into the
    (L*NB)-page flat view; with ``head_dim`` the heads come back one
    each, ``[B, S, KVH, head_dim]``, whatever the pool's rows. Quantized (data, scales) pages are gathered
    page-wise too — int8 bytes over the wire — then dequantized (f32
    multiply, always) right before use.

    The returned dtype is ``out_dtype`` when given, float32 otherwise —
    for BOTH page encodings. (Historically the bf16 branch returned the
    raw page dtype under the default while the int8 branch returned
    f32; parity tolerances against the pallas kernels, which accumulate
    in f32 unconditionally, depend on this being explicit.)"""
    data = kv_page_data(pages)
    L, NB, bs, KVH, D = data.shape
    B, MAXB = block_tables.shape
    flat = data.reshape(L * NB, bs, KVH, D)
    idx = layer * NB + block_tables  # [B, MAXB]
    ctx = flat[idx].reshape(B, MAXB * bs, KVH, D)
    if isinstance(pages, tuple):
        flat_s = pages[1].reshape(L * NB, bs, KVH)
        ctx_s = flat_s[idx].reshape(B, MAXB * bs, KVH)
        ctx = ctx.astype(jnp.float32) * ctx_s[..., None]
    if head_dim is not None and head_dim != D:
        # Heads side by side in a row (packed_page_dims): one each again.
        ctx = ctx.reshape(B, MAXB * bs, -1, head_dim)
    return ctx.astype(out_dtype if out_dtype is not None else jnp.float32)


def context_prefill_attention(
    q: jax.Array,  # [B, T, H, D] suffix queries
    k_pages: jax.Array,  # [L, NB, bs, KVH, D] stacked pages
    v_pages: jax.Array,  # [L, NB, bs, KVH, D]
    block_tables: jax.Array,  # [B, MAXB]
    positions: jax.Array,  # [B, T] absolute positions of the queries
    total_lens: jax.Array,  # [B] full context length (cached + suffix)
    layer: jax.Array,  # scalar layer index
    *,
    scale: float,
    k_new: jax.Array | None = None,  # [B, T, KVH, D] the chunk's fresh K
    v_new: jax.Array | None = None,  # [B, T, KVH, D]
    suffix_lens: jax.Array | None = None,  # [B] valid fresh tokens
    window: int | None = None,  # static: position p sees p - window < j <= p
) -> jax.Array:
    """Prefill attention for a suffix whose K/V (and the cached prefix's)
    already live in HBM pages: query at absolute position p attends to page
    positions 0..p. This is what makes prefix-cache hits skip recompute —
    only the suffix runs through the model, attending to reused pages
    (reference buys this from vLLM ``--enable-prefix-caching`` +
    LMCache offload; here it is native). Returns [B, T, H, D].

    When the caller also passes the chunk's own fresh ``k_new``/``v_new``
    (+ ``suffix_lens``, their per-row valid counts) AND the page shapes
    are tile-aligned, the flash pallas kernel serves the cached prefix
    straight from its live pages (int8 dequant on-chip) while the suffix
    attends from the fresh values — no full-context materialization, no
    write-then-regather round trip. The contract is the engine's chunk
    layout: ``positions`` contiguous ascending per row and
    ``total_lens = positions[:, 0] + suffix_lens`` for live rows.
    Elsewhere (misaligned shapes, CPU, fresh values not provided) the
    XLA gather reference below runs — identical math, so the dispatch
    choice never changes results beyond accumulation order. The choice
    is made once, at trace time (:func:`attention_path`); a kernel that
    then fails to compile or run raises."""
    per_row = _lanes_per_head(q, k_pages)
    if (k_new is not None and v_new is not None and suffix_lens is not None
            and _traced_path("prefill", k_pages, per_row > 1) == "pallas"):
        from production_stack_tpu.ops.pallas_prefill_attention import (
            pallas_prefill_attention,
        )

        def kernel(q, k_new, v_new, k_pages, v_pages, block_tables,
                   positions, total_lens, layer, suffix_lens):
            # ``window`` only where there is one: a program without it
            # traces the call it always traced.
            bound = {} if window is None else {"window": window}
            return pallas_prefill_attention(
                q, k_pages, v_pages, block_tables, positions, total_lens,
                layer, k_new, v_new, suffix_lens, scale=scale, **bound)

        if per_row > 1:
            # Narrow heads side by side in the pages' rows: the kernel
            # runs on the rows as heads of 128, the chunk's fresh k/v
            # viewed the same way.
            rows = k_pages.shape[3]
            as_rows = k_new.shape[:2] + k_pages.shape[3:]
            out = kernel(
                _packed_queries(q, rows, per_row), k_new.reshape(as_rows),
                v_new.reshape(as_rows), k_pages, v_pages, block_tables,
                positions, total_lens, layer, suffix_lens)
            return _unpacked_outputs(out, rows, per_row)
        return _per_kv_shard(kernel, (4, 4, 4), 5)(
            q, k_new, v_new, k_pages, v_pages, block_tables, positions,
            total_lens, layer, suffix_lens)
    return _context_prefill_reference(
        q, k_pages, v_pages, block_tables, positions, total_lens, layer,
        scale=scale, window=window,
    )


def _context_prefill_reference(
    q: jax.Array,  # [B, T, H, D] suffix queries
    k_pages: jax.Array,  # [L, NB, bs, KVH, D] stacked pages
    v_pages: jax.Array,  # [L, NB, bs, KVH, D]
    block_tables: jax.Array,  # [B, MAXB]
    positions: jax.Array,  # [B, T] absolute positions of the queries
    total_lens: jax.Array,  # [B] full context length (cached + suffix)
    layer: jax.Array,  # scalar layer index
    *,
    scale: float,
    window: int | None = None,
) -> jax.Array:
    """XLA reference: gather the whole padded context (suffix included —
    it was scattered to the pages by write_kv_pages one op earlier),
    mask causally against ``positions``, softmax."""
    D = q.shape[-1]
    k_ctx = _gather_ctx(k_pages, block_tables, layer, q.dtype, D)
    v_ctx = _gather_ctx(v_pages, block_tables, layer, q.dtype, D)
    return dense_context_attention(q, k_ctx, v_ctx, positions, total_lens,
                                   scale=scale, window=window)


def dense_context_attention(
    q: jax.Array,  # [B, T, H, D]
    k_ctx: jax.Array,  # [B, S, KVH, D] the whole padded context
    v_ctx: jax.Array,  # [B, S, KVH, Dv]
    positions: jax.Array,  # [B, T] absolute positions of the queries
    total_lens: jax.Array,  # [B] full context length (cached + suffix)
    *,
    scale: float,
    window: int | None = None,
) -> jax.Array:
    """Attention of a chunk's queries over a context laid out densely
    (gathered from pages, or up-projected from gathered latents): query
    at position p sees the context's positions ``<= p`` under
    ``total_lens``. Returns [B, T, H, Dv]."""
    B, T, H, D = q.shape
    S, KVH, Dv = v_ctx.shape[1:]
    group = H // KVH
    qg = q.reshape(B, T, KVH, group, D)
    # The one-shot einsum materializes f32 scores [B, KVH, g, T, S] —
    # fine for single-row prefills, but multi-GB for batched-prefill
    # shapes ([4, 2048] rows over 4k contexts). Past ~1 GB, stream the
    # context in chunks with an online softmax instead (flash-attention
    # structure in plain lax.scan; same math, bounded temps).
    scores_bytes = 4 * B * KVH * group * T * S
    chunk = _CHUNKED_SCORE_SPAN
    if scores_bytes > _CHUNKED_SCORE_BYTES and S > chunk:
        # Ragged tails pad with zero pages (their span indices exceed
        # every total_len, so the mask drops them) — the bounded-memory
        # path must engage for ANY S, not only multiples of the chunk.
        nc = -(-S // chunk)
        if nc * chunk != S:
            pad = nc * chunk - S
            k_ctx = jnp.pad(k_ctx, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v_ctx = jnp.pad(v_ctx, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_chunks = k_ctx.reshape(B, nc, chunk, KVH, D).swapaxes(0, 1)
        v_chunks = v_ctx.reshape(B, nc, chunk, KVH, Dv).swapaxes(0, 1)

        def body(carry, inputs):
            m, l, acc, ci = carry
            k_c, v_c = inputs  # [B, chunk, KVH, D]
            s = jnp.einsum(
                "btkgd,bskd->bkgts", qg, k_c,
                preferred_element_type=jnp.float32) * scale
            span_c = ci * chunk + jnp.arange(chunk)
            causal = span_c[None, None, :] <= positions[:, :, None]
            valid = span_c[None, None, :] < total_lens[:, None, None]
            seen = causal & valid
            if window is not None:
                seen = seen & (
                    span_c[None, None, :] > positions[:, :, None] - window)
            s = jnp.where(seen[:, None, None, :, :], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_cur)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            if window is not None:
                # A query whose window starts past this chunk sees none
                # of it: its running max is still NEG_INF and exp(0)
                # would count every masked key as one.
                p = jnp.where(seen[:, None, None, :, :], p, 0.0)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            upd = jnp.einsum("bkgts,bskd->bkgtd", p.astype(v_c.dtype),
                             v_c).astype(jnp.float32)
            acc_new = acc * alpha + upd
            return (m_new, l_new, acc_new, ci + 1), None

        m0 = jnp.full((B, KVH, group, T, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, group, T, 1), jnp.float32)
        a0 = jnp.zeros((B, KVH, group, T, Dv), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            body, (m0, l0, a0, jnp.int32(0)), (k_chunks, v_chunks),
            length=nc)
        out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
        return out.swapaxes(2, 3).swapaxes(1, 2).reshape(B, T, H, Dv)

    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k_ctx, preferred_element_type=jnp.float32
    ) * scale
    span = jnp.arange(S)
    causal = span[None, None, :] <= positions[:, :, None]  # [B, T, S]
    valid = span[None, None, :] < total_lens[:, None, None]
    mask = causal & valid
    if window is not None:
        mask = mask & (span[None, None, :] > positions[:, :, None] - window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(v_ctx.dtype), v_ctx)
    return out.reshape(B, T, H, Dv)


@jax.named_scope("kv_write")
def write_kv_pages(
    k_pages,  # [L, NB, bs, KVH, D] stacked pages (or (data, scales))
    v_pages,  # [L, NB, bs, KVH, D] (or (data, scales))
    k_new: jax.Array,  # [B, T, KVH, D]
    v_new: jax.Array,  # [B, T, KVH, D]
    slot_mapping: jax.Array,  # [B, T] flat slot ids (layer 0); negative = skip
    layer: jax.Array,  # scalar layer index
):
    """Scatter fresh K/V into their HBM page slots.

    Operates on the FULL stacked array through a flat reshape (a bitcast):
    when the stacked pages are threaded as a loop carry, XLA performs this
    scatter in place — slicing out a per-layer view first would copy the
    layer every step. Quantized (data, scales) pages quantize here, on
    the scatter: pages only ever hold int8 + scales, so every downstream
    reader (reference, pallas, offload) sees one canonical encoding.
    The fresh values take the pages' own trailing dims: where narrow
    heads lie side by side in a row (:func:`packed_page_dims`) that is a
    row-major view of ``[KVH, D]``. Each side has its own (a latent
    cache's two sides are of unequal widths)."""
    L, NB, bs = kv_page_data(k_pages).shape[:3]
    slots = slot_mapping.reshape(-1)
    # Layer offset; out-of-range slots are dropped by scatter mode="drop".
    slots = jnp.where(slots < 0, L * NB * bs, slots + layer * NB * bs)

    def scatter(pages, new):
        KVH, D = kv_page_data(pages).shape[3:]
        if isinstance(pages, tuple):
            data, scales = pages
            q, s = quantize_kv(new)
            flat = data.reshape(L * NB * bs, KVH, D)
            flat = flat.at[slots].set(q.reshape(-1, KVH, D), mode="drop")
            # The [L, NB, bs*KVH] scale array is row-major identical to
            # (L*NB*bs, KVH): the same flat slot indexes both scatters.
            flat_s = scales.reshape(L * NB * bs, KVH)
            flat_s = flat_s.at[slots].set(s.reshape(-1, KVH), mode="drop")
            return (flat.reshape(L, NB, bs, KVH, D),
                    flat_s.reshape(L, NB, bs * KVH))
        flat = pages.reshape(L * NB * bs, KVH, D)
        flat = flat.at[slots].set(
            new.reshape(-1, KVH, D).astype(pages.dtype), mode="drop")
        return flat.reshape(L, NB, bs, KVH, D)

    return scatter(k_pages, k_new), scatter(v_pages, v_new)


def paged_attention_reference(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [L, NB, bs, KVH, D]
    v_pages: jax.Array,  # [L, NB, bs, KVH, D]
    block_tables: jax.Array,  # [B, MAXB] page ids
    context_lens: jax.Array,  # [B]
    layer: jax.Array,  # scalar layer index
    *,
    scale: float,
    window: int | None = None,  # static: the last ``window`` tokens only
) -> jax.Array:
    """XLA fallback: gather the padded context, mask, soft-max. [B, H, D];
    zeros for a row whose context is 0 or less (it holds nothing), as the
    kernel gives."""
    B, H, D = q.shape
    bs = kv_page_data(k_pages).shape[2]
    MAXB = block_tables.shape[1]
    k_ctx = _gather_ctx(k_pages, block_tables, layer, q.dtype, D)
    v_ctx = _gather_ctx(v_pages, block_tables, layer, q.dtype, D)
    KVH = k_ctx.shape[2]
    group = H // KVH
    qg = q.reshape(B, KVH, group, D)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k_ctx, preferred_element_type=jnp.float32
    ) * scale
    span = jnp.arange(MAXB * bs)
    mask = span[None, :] < context_lens[:, None]  # [B, S]
    if window is not None:
        mask = mask & (span[None, :] >= context_lens[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v_ctx.dtype), v_ctx)
    out = jnp.where(context_lens[:, None, None, None] > 0, out, 0)
    return out.reshape(B, H, D)


def paged_decode_attention(
    q: jax.Array,  # [B, H, D]
    k_pages: jax.Array,  # [L, NB, bs, KVH, D]
    v_pages: jax.Array,  # [L, NB, bs, KVH, D]
    block_tables: jax.Array,
    context_lens: jax.Array,
    layer: jax.Array,  # scalar layer index
    *,
    scale: float,
    window: int | None = None,  # static: the last ``window`` tokens only
) -> jax.Array:
    """Dispatch to the pallas kernel on TPU, XLA reference elsewhere."""
    per_row = _lanes_per_head(q, k_pages)
    if _traced_path("decode", k_pages, per_row > 1) == "pallas":
        from production_stack_tpu.ops.pallas_paged_attention import (
            pallas_paged_attention,
        )

        def kernel(q, k_pages, v_pages, block_tables, context_lens, layer):
            bound = {} if window is None else {"window": window}
            return pallas_paged_attention(
                q, k_pages, v_pages, block_tables, context_lens, layer,
                scale=scale, **bound)

        if per_row > 1:
            rows = k_pages.shape[3]
            return _unpacked_outputs(
                kernel(_packed_queries(q, rows, per_row), k_pages, v_pages,
                       block_tables, context_lens, layer), rows, per_row)
        return _per_kv_shard(kernel, (3,), 3)(
            q, k_pages, v_pages, block_tables, context_lens, layer)
    return paged_attention_reference(
        q, k_pages, v_pages, block_tables, context_lens, layer, scale=scale,
        window=window,
    )


# -- a latent cache (MLA): one normed latent and one rotated key a token --

def latent_decode_path(block_size: int, heads: int, latent: int,
                       rope_lanes: int, dtype) -> str:
    """Which backend the absorbed decode attention over latent pages
    takes at these (static) shapes: ``"pallas"``
    (ops/pallas_mla_decode.py) or ``"xla"`` (gather and einsum). THE
    decision, like :func:`attention_path`: the dispatcher and the
    engine's dispatch counter evaluate it, from shapes, platform and the
    env override only."""
    from production_stack_tpu.ops.pallas_mla_decode import tiles_ok

    if (tiles_ok(block_size, heads, latent, rope_lanes,
                 jnp.dtype(dtype).itemsize) and _use_pallas()):
        return "pallas"
    return "xla"


def gather_latents(c_pages, r_pages, block_tables, layer, rope: int):
    """(c [B, S, latent], k_r [B, S, rope]) of a batch's padded context,
    page-wise out of the stacked sides ``[L, NB, bs, 1, lanes]``, in the
    pages' dtype."""
    L, NB, bs = c_pages.shape[:3]
    B, MAXB = block_tables.shape
    idx = layer * NB + block_tables

    def side(pages, width):
        flat = pages.reshape(L * NB, bs, pages.shape[-1])
        return flat[idx].reshape(B, MAXB * bs, -1)[..., :width]

    return side(c_pages, c_pages.shape[-1]), side(r_pages, rope)


def latent_decode_reference(q_abs, q_rope, c_pages, r_pages, block_tables,
                            context_lens, layer, *, scale: float):
    """XLA path of :func:`latent_decode_attention`: gather the padded
    context, mask, softmax in float32. Zeros for a row whose context is
    0 or less, as the kernel gives."""
    c_ctx, r_ctx = gather_latents(c_pages, r_pages, block_tables, layer,
                                   q_rope.shape[-1])
    scores = (jnp.einsum("bhc,bsc->bhs", q_abs, c_ctx,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,bsr->bhs", q_rope, r_ctx,
                           preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(c_ctx.shape[1])[None, :] < context_lens[:, None]
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bsc->bhc", probs.astype(c_ctx.dtype), c_ctx)
    return jnp.where(context_lens[:, None, None] > 0, out, 0)


def latent_decode_attention(
    q_abs: jax.Array,  # [B, H, latent]: the query with Wuk absorbed
    q_rope: jax.Array,  # [B, H, rope], rotated
    c_pages: jax.Array,  # [L, NB, bs, 1, latent]
    r_pages: jax.Array,  # [L, NB, bs, 1, lanes >= rope]
    block_tables: jax.Array,
    context_lens: jax.Array,  # [B]; <= 0: the row holds nothing
    layer: jax.Array,
    *,
    scale: float,
) -> jax.Array:
    """``o_lat [B, H, latent]``: every head's attention over the row's
    live tokens in the latent space, where the page is key and value.
    The Pallas kernel on the TPU, gather and einsum elsewhere; chosen at
    trace time and counted in :data:`TRACED_PATHS` under ``latent_decode``."""
    _, _, bs, _, lanes = r_pages.shape
    path = latent_decode_path(bs, q_abs.shape[1], q_abs.shape[2], lanes,
                              c_pages.dtype)
    TRACED_PATHS["latent_decode", path] += 1
    if path == "pallas":
        from production_stack_tpu.ops.pallas_mla_decode import (
            pallas_mla_decode,
        )

        return pallas_mla_decode(q_abs, q_rope, c_pages, r_pages,
                                 block_tables, context_lens, layer,
                                 scale=scale)
    return latent_decode_reference(q_abs, q_rope, c_pages, r_pages,
                                   block_tables, context_lens, layer,
                                   scale=scale)
