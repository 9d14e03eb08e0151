"""The decoder skeleton every model family shares.

A family (models/registry.py::Family) brings ``embed``, ``layer`` and
``head``; what is the same for all of them is written here once:

- :func:`attend`: every forward writes fresh K/V into HBM pages
  (``ops.write_kv_pages``) and attends either causally within the prompt
  (prefill), over the pages plus the fresh suffix (cached prefill) or over
  the pages alone (decode). The only code under ``models/`` that imports
  ``ops.attention`` and the only code that spells the three mode names:
  a window mask has this one call site to change, and a latent cache
  (MLA) has its sibling :func:`attend_latent`, the only other. A state
  per cache block beside the pages (a short convolution's last inputs)
  goes through :func:`read_block_state` and :func:`write_block_state`;
- :func:`scan_layers`: THE layer loop, one ``lax.scan`` over a stretch
  of consecutive layers (single-layer trace, fast compiles even at 80
  layers) with the carry convention of the paged pool. A family whose
  layers are of several kinds says what they are (``Family.loop``) and
  hands them to it: :func:`by_layer` chooses between two kinds by the
  layer's number, :func:`take` reads a layer's leaves of a stack,
  :func:`index_in_kind` numbers the layers of each kind, and the rules
  the chip taught about each are written beside them, once;
- :func:`latent_attention`: the layer part both latent families share;
- :func:`apply`: embed -> layers (that scan, stepped by ``Family.layer``
  or by the family's own ``loop``) -> the ``last_token`` slice -> head.
  ``models.<family>.apply`` is this function bound to the family, and
  ``parallel/pp_serving.py`` runs the same three parts as pipeline stages.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops import attention as paged
from production_stack_tpu.ops.attention import (
    context_prefill_attention,
    dense_context_attention,
    gather_latents,
    latent_decode_attention,
    paged_decode_attention,
    prefill_attention,
    write_kv_pages,
)


class Batch(NamedTuple):
    """What a layer reads of one forward beside its weights. Every field
    but ``lora_scaling`` (per slot ``[S]``) has the batch axis first, so a
    pipeline stage can cut them into microbatches."""

    positions: jax.Array  # [B, T]
    slot_mapping: jax.Array  # [B, T]
    block_tables: jax.Array  # [B, MAXB]
    context_lens: jax.Array  # [B]
    seq_lens: jax.Array  # [B] valid prompt lengths (prefill padding mask)
    adapter_ids: jax.Array | None = None  # [B] LoRA slot per sequence
    lora_scaling: jax.Array | None = None


def attend(
    mode: str,  # "prefill" | "prefill_cached" | "decode"  (static)
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, T, KVH, D]
    v: jax.Array,  # [B, T, KVH, D]
    kv: Tuple,  # STACKED pages ([L, NB, bs, KVH, D], same)
    layer: jax.Array,  # scalar layer index
    batch: Batch,
    *,
    scale: float,
    window: int | None = None,  # static, per layer kind
):
    """Write this layer's fresh k/v into its pages, then attend. Returns
    (attention output [B, T, H, D], updated pages). With ``window`` a
    query at position p sees the keys at ``p - window < j <= p`` in all
    three modes: a band mask in plain prefill, a lower bound in the
    cached-prefill kernel, and in the decode kernel a first live token
    ``context - window`` before which it copies and computes nothing.
    The pages stay per token (the window saves a layer's bandwidth and
    compute, not its memory)."""
    k_pages, v_pages = write_kv_pages(
        *kv, k, v, batch.slot_mapping, layer)
    bound = {} if window is None else {"window": window}
    # The named scopes are what a profiler trace files the device's time
    # under (docs/profiling.md): metadata only, no operation changes.
    with jax.named_scope("attention"):
        if mode == "prefill":
            attn = prefill_attention(
                q, k, v, scale=scale, seq_lens=batch.seq_lens,
                **bound)
        elif mode == "prefill_cached":
            # Suffix prefill after a prefix-cache hit: attend over HBM
            # pages (cached prefix + just-written suffix). The chunk's own
            # fresh k/v ride along so the flash kernel can serve the
            # suffix from VMEM and stream only the cached prefix pages.
            attn = context_prefill_attention(
                q, k_pages, v_pages, batch.block_tables, batch.positions,
                batch.context_lens, layer, scale=scale, k_new=k, v_new=v,
                suffix_lens=batch.seq_lens, **bound,
            )
        else:
            # A row that writes no token this step (slot -1: a row no
            # sequence holds, or a burst step past what its sequence may
            # use) has its sampled token discarded, so it attends over
            # nothing: the kernel copies no page for a context of 0.
            live = jnp.where(
                batch.slot_mapping[:, 0] >= 0, batch.context_lens, 0)
            attn = paged_decode_attention(
                q[:, 0], k_pages, v_pages, batch.block_tables, live,
                layer, scale=scale, **bound,
            )[:, None]
    return attn, (k_pages, v_pages)


# Multiply-adds a byte of HBM traffic at a v5e's ridge: 197e12 / 2 / 819e9
# (Google Cloud "TPU v5e"; ``obs/steps.py::DEVICE_PEAKS``). A constant of
# :func:`latent_prefill_form`, never the attached device's: the CPU names
# the form the chip compiles.
MACS_PER_HBM_BYTE = 120
# An intermediate up to this size stays on the chip between the steps that
# write and read it: 84 MB of scores read as staying, 134 MB as not (PR 45).
ON_CHIP_BYTES = 96 << 20


def attend_latent(
    mode: str,  # "prefill" | "prefill_cached" | "decode"  (static)
    q_nope: jax.Array,  # [B, T, H, N]
    q_rope: jax.Array,  # [B, T, H, R], rotated
    c: jax.Array,  # [B, T, C] the chunk's normed latents
    k_rope: jax.Array,  # [B, T, R] the one rotated key of all heads
    w_up: jax.Array,  # [H, C, N + V]: latent -> a head's key and value
    kv: Tuple,  # STACKED pages ([L, NB, bs, 1, C], [L, NB, bs, 1, lanes])
    layer: jax.Array,  # scalar page-layer index
    batch: Batch,
    *,
    scale: float,
    latent_scale: float = 1.0,  # on the latent before ``w_up``
):
    """:func:`attend` for a latent cache (multi-head latent attention):
    a token's page keeps ``c`` and ``k_rope`` (zeros in the lanes beyond
    it) and no head's keys or values. Writes them, then attends; returns
    (outputs [B, T, H, V], updated pages). Head ``n``'s key is ``[(s c)
    Wuk[n], k_rope]``, its value ``(s c) Wuv[n]`` (``s = latent_scale``;
    ``Wuk``, ``Wuv`` the halves of ``w_up``): one product, two orders.

    - ``prefill`` **up-projects** the chunk's latents into per-head keys
      (``N + R``) and values (``V``) and attends causally within itself.
    - ``prefill_cached`` gathers the context's S latents from the pages and
      takes **the form the chip runs faster** (:func:`latent_prefill_form`,
      from T, S, H, C, N, R, V alone). A step costs the larger of its
      multiply-adds and its HBM bytes x :data:`MACS_PER_HBM_BYTE`; with P =
      T S H, *up-projected* (scope ``mla_up_context``): ``S H C (N + V)``
      writing ``up``; keys and values cut from it, ``2 S H (2N + R + 2V)``
      bytes; ``P (N + R)`` reading the keys; ``P V`` reading the values.
      *Absorbed* (multi-query attention over the latents, ``mla_absorb``
      around it): ``T H C (N + V)``, ``P (C + R)``, ``P C``. Either form's
      two attention matmuls also move float32 scores, 4 bytes a pair. Bytes
      count past :data:`ON_CHIP_BYTES` (the scores; ``up`` with its keys and
      values); a streamed context carries a float32 accumulator a span.
    - ``decode`` **absorbs** around ``ops/pallas_mla_decode.py``: no key or
      value of a head is built, a page is read once for all heads; the two
      matmuls beside the kernel lie under ``mla_absorb``."""
    N = q_nope.shape[-1]
    R = k_rope.shape[-1]
    lanes = kv[1].shape[-1]
    c_pages, r_pages = write_kv_pages(
        *kv, c[:, :, None, :],
        jnp.pad(k_rope, ((0, 0), (0, 0), (0, lanes - R)))[:, :, None, :],
        batch.slot_mapping, layer)

    def scaled(x):  # the latent's factor, applied in float32
        return (x.astype(jnp.float32) * latent_scale).astype(x.dtype)

    def up_project(latents, rotary):
        """([.., S, H, N + R] keys, [.., S, H, V] values) of latents
        ``[B, S, C]`` and their rotated keys ``[B, S, R]``."""
        with jax.named_scope("mla_proj"):
            up = jnp.einsum("bsc,hcd->bshd", scaled(latents), w_up)
            keys = jnp.concatenate(
                [up[..., :N], jnp.broadcast_to(
                    rotary[:, :, None, :], up.shape[:3] + (R,))], axis=-1)
            return keys, up[..., N:]

    if mode == "decode":
        live = jnp.where(
            batch.slot_mapping[:, 0] >= 0, batch.context_lens, 0)
        with jax.named_scope("mla_absorb"):
            q_abs = jnp.einsum("bhn,hcn->bhc", scaled(q_nope[:, 0]),
                               w_up[..., :N])
        with jax.named_scope("attention"):
            o_lat = latent_decode_attention(
                q_abs, q_rope[:, 0], c_pages, r_pages, batch.block_tables,
                live, layer, scale=scale)
        with jax.named_scope("mla_absorb"):
            attn = jnp.einsum("bhc,hcv->bhv", scaled(o_lat),
                              w_up[..., N:])[:, None]
        return attn, (c_pages, r_pages)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if mode == "prefill":
        k, v = up_project(c, k_rope)
        with jax.named_scope("attention"):
            attn = prefill_attention(q, k, v, scale=scale,
                                     seq_lens=batch.seq_lens)
        return attn, (c_pages, r_pages)
    with jax.named_scope("attention"):  # the pages' bytes are its
        context = gather_latents(c_pages, r_pages, batch.block_tables,
                                 layer, R)
    form = latent_prefill_form(
        q.shape[1], context[0].shape[1], *w_up.shape[:2], N, R,
        w_up.shape[-1] - N)
    if form == "up_projected":
        with jax.named_scope("mla_up_context"):
            k, v = up_project(*context)
        with jax.named_scope("attention"):
            attn = dense_context_attention(
                q, k, v, batch.positions, batch.context_lens, scale=scale)
        return attn, (c_pages, r_pages)
    with jax.named_scope("mla_absorb"):
        q_abs = jnp.einsum("bthn,hcn->bthc", scaled(q_nope), w_up[..., :N])
    with jax.named_scope("attention"):
        # The latents are every head's key (beside the rotated key) and
        # every head's value: multi-query attention over one kv head.
        o_lat = dense_context_attention(
            jnp.concatenate([q_abs, q_rope], axis=-1),
            jnp.concatenate(context, axis=-1)[:, :, None, :],
            context[0][:, :, None, :], batch.positions, batch.context_lens,
            scale=scale)
    with jax.named_scope("mla_absorb"):
        attn = jnp.einsum("bthc,hcv->bthv", scaled(o_lat), w_up[..., N:])
    return attn, (c_pages, r_pages)


def latent_prefill_form(new_tokens: int, context: int, heads: int,
                        latent: int, nope: int, rope: int, value: int) -> str:
    """``"absorbed"`` or ``"up_projected"``: the form of a cached prefill
    over a latent cache that the chip runs faster, for ``new_tokens``
    queries (the bucket) over ``context`` gathered tokens (the table).
    THE decision, from static shapes alone: :func:`attend_latent` takes it
    at trace time and states its terms, the engine counts it a dispatch;
    read on the chip by ``benchmarks/latent_prefill_forms.py`` (PERF.md)."""
    pairs, kv = new_tokens * context * heads, 2 * context * heads  # B a lane
    built = kv * (2 * nope + rope + 2 * value)  # ``up``, its keys and values
    far = MACS_PER_HBM_BYTE * (built > ON_CHIP_BYTES)  # a byte of those
    scores = MACS_PER_HBM_BYTE * 4 * pairs * (4 * pairs > ON_CHIP_BYTES)
    projection = heads * latent * (nope + value)  # multiply-adds a token
    up_projected = (
        max(context * projection, far * kv * (nope + value)) + far * built
        + max(pairs * (nope + rope), far * kv * (nope + rope) + scores)
        + max(pairs * value, far * kv * value + scores))
    absorbed = (new_tokens * projection + max(pairs * latent, scores)
                + max(pairs * (latent + rope), scores))
    spans = -(-context // paged._CHUNKED_SCORE_SPAN)
    if 4 * pairs > paged._CHUNKED_SCORE_BYTES and spans > 1:  # streamed
        carried = MACS_PER_HBM_BYTE * 8 * spans * new_tokens * heads
        up_projected += carried * value  # the accumulator's lanes
        absorbed += carried * latent
    return "absorbed" if absorbed < up_projected else "up_projected"


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def rope_pairs(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over all of the last axis, adjacent lanes
    ``(2j, 2j + 1)`` rotating together and staying where they are.
    ``x [B, T, ..., R]``, ``positions [B, T]``."""
    rot = x.shape[-1]
    inv_freq = (theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
                ).astype(np.float32)
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3)
                            + angles.shape[-1:])
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (rot // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def latent_page_sides(cfg: ModelConfig):
    """``Family.page_sides`` of a latent cache: one normed latent and one
    rotated key a token and page layer."""
    return (1, cfg.kv_lora_rank), (1, cfg.qk_rope_head_dim)


@functools.partial(jax.jit, static_argnums=(0, 1))
def latent_attention(cfg: ModelConfig, mode: str, x, p: Dict, kv, page_layer,
                     batch: Batch):
    """``x + MLA(RMS(x))`` of one (sub)layer on its own leaves ``p``,
    through :func:`attend_latent`; ``cfg.mla_scale_*`` put LongCat's
    factors on the two latents. Jitted so that a step program traces and
    lowers it once though a family calls it from two stretches (the
    compiler inlines the calls): a warm start lowers each of a server's
    ~50 step programs, cache hit or not, and a second copy of the
    attention cost GLM's cell 25 s of ``setup_s`` (PR 46)."""
    B, T, Hd = x.shape
    H, C = cfg.num_heads, cfg.kv_lora_rank
    N, R = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla_proj"):
        h = rms_norm(x, p["in_norm"], cfg.rms_norm_eps)
        cq = rms_norm(h @ p["wq_a"], p["q_norm"], cfg.rms_norm_eps)
        q = jnp.einsum("btq,oq->bto", cq, p["wq_b"]).reshape(
            B, T, H, N + R)
        if cfg.mla_scale_q_lora:
            q = (q.astype(jnp.float32)
                 * math.sqrt(Hd / cfg.q_lora_rank)).astype(x.dtype)
        t = h @ p["wkv_a"]
        c = rms_norm(t[..., :C], p["kv_norm"], cfg.rms_norm_eps)
        # assumed (both families): adjacent lanes rotate, in place.
        q_rope = rope_pairs(q[..., N:], batch.positions, cfg.rope_theta)
        k_rope = rope_pairs(t[..., C:], batch.positions, cfg.rope_theta)
    attn, kv = attend_latent(
        mode, q[..., :N], q_rope, c, k_rope, p["wkv_b"], kv, page_layer,
        batch, scale=(N + R) ** -0.5,
        latent_scale=(math.sqrt(Hd / C) if cfg.mla_scale_kv_lora else 1.0))
    with jax.named_scope("mla_proj"):
        x = x + attn.reshape(B, T, -1) @ p["wo"]
    return x, kv


def read_block_state(state: jax.Array, at, batch: Batch, block_size: int):
    """The state each row's chunk begins from: ``[B, rows, width]`` of
    entry ``at`` (a layer of the pool's third side ``[layers, NB, rows,
    width]``) of the block that holds the row's position ``p0 - 1``,
    found through the block table; zeros where the row begins at 0.

    A block's entry is the state after the last token written into it.
    A sequence only ever appends behind its own last token (a chunk
    follows the chunk before it, a decode step the token before it) or
    behind a prefix hit, which ends on a block's boundary, where the
    cached block is full and its entry is the state at that boundary: so
    the entry of the block of ``p0 - 1`` is the state after ``p0 - 1``,
    with no copy and no bookkeeping beyond the block table."""
    layers, NB = state.shape[:2]
    p0 = batch.positions[:, 0]
    block = jnp.take_along_axis(
        batch.block_tables,
        (jnp.maximum(p0 - 1, 0) // block_size)[:, None], axis=1)[:, 0]
    held = state.reshape((layers * NB,) + state.shape[2:])[at * NB + block]
    return jnp.where((p0 > 0)[:, None, None], held, 0)


def write_block_state(state: jax.Array, at, batch: Batch, block_size: int,
                      inputs: jax.Array):
    """Entry ``at`` of every block the chunk writes a token into: the
    ``rows`` inputs up to and including the last position the chunk
    writes there. ``inputs [B, rows + T, width]`` is what
    :func:`read_block_state` gave followed by the chunk's own, so a block
    the chunk enters with one token still gets the token before it. The
    last written position of a block is found from ``seq_lens`` (a padded
    row of a group writes its true tail) and its block from the slot of
    that position (a slot of -1 writes nothing: padding, or a decode row
    that holds no sequence). Scattered through the flat view, in place
    on the scan's carry, as ``write_kv_pages`` writes the pages."""
    layers, NB, rows, width = state.shape
    B, T = batch.positions.shape
    p0 = batch.positions[:, :1]
    last = p0 + batch.seq_lens[:, None] - 1  # [B, 1] last position written
    touched = 1 if T == 1 else -(-T // block_size) + 1  # static
    block = p0 // block_size + jnp.arange(touched)[None, :]  # [B, touched]
    tail = jnp.minimum((block + 1) * block_size - 1, last)
    i = jnp.clip(tail - p0, 0, T - 1)  # the tail's index in the chunk
    slot = jnp.take_along_axis(batch.slot_mapping, i, axis=1)
    live = (block * block_size <= last) & (slot >= 0)
    # inputs[:, rows + i] is position ``tail``'s.
    take = (i + 1)[..., None] + jnp.arange(rows)  # [B, touched, rows]
    values = jnp.take_along_axis(
        inputs, take.reshape(B, touched * rows, 1), axis=1)
    flat = state.reshape(layers * NB, rows, width)
    flat = flat.at[
        jnp.where(live, at * NB + slot // block_size, layers * NB).reshape(-1)
    ].set(values.reshape(B * touched, rows, width).astype(state.dtype),
          mode="drop")
    return flat.reshape(state.shape)


def first_carry(x: jax.Array, sides: Tuple, counted: Sequence[str] = (),
                *extra):
    """The carry :func:`scan_layers` starts a forward from, ``(x, sides,
    *extra, counts, layer)``: layer 0, and a zero for each name of
    ``counted`` (``Family.stats``; None where nothing is counted)."""
    counts = jnp.zeros((len(counted),), jnp.int32) if counted else None
    return (x, tuple(sides), *extra, counts, jnp.int32(0))


def scan_layers(step, carry: Tuple, layers: int | None = None, xs=None):
    """One *stretch* of the layer loop: ``layers`` consecutive layers on
    from the carry's (:func:`first_carry`), ONE ``lax.scan`` whose body is
    ``step(x, sides, layer, per_layer, *extra) -> (x, sides, counts,
    *extra)``. Returns the carry, for the next stretch to go on from; a
    stretch of no layers returns it as it is.

    - The layer's number is counted in the carry; ``counts`` (an int32
      entry a name of ``Family.stats``, or None) are summed over the
      stretch; ``extra`` is what one layer hands the next beside ``x``.
    - ``sides``: the STACKED KV pages ride the carry whole; every op
      addresses them through the scalar layer index (flat scatter /
      page-level gather). Loop carries alias in place under XLA, so only
      the touched pages move: per-layer slices (or pages in the scan's
      ys) would copy the entire pool every forward step. An int8 side is
      a (data, scales) tuple and a third side (``Family.block_state``)
      one more entry, riding the same way.
    - ``xs``: WHOLE ``[L, ...]`` stacks the scan slices a layer at a time
      into ``per_layer`` (Llama's leaves and LoRA slots, LongCat's ``[2L,
      ...]`` sublayers). A part of a stack, ``leaf[d:]``, is a COPY of the
      leaves there (2 GB of temporaries at GLM's 47 layers, PR 46): read
      it with :func:`take` at the layer's number.

    **Stretches.** Kinds of layer that do not interleave can each be a
    stretch with no ``lax.cond`` between them (GLM: a dense prefix, then
    the sparse layers). Cut where a kind few layers take has small
    operands: the compiler prefetches a ``conditional``'s operands into
    VMEM in EVERY layer, read or not (GLM's dense MLP, 84 MB a layer, a
    quarter of the device's time, PR 46), seen only in the program nested
    as the engine's burst nests it (``scripts/hlo_digest.py``'s
    ``<name>.decode_k8``: ``copy-start`` with ``S(1)`` in the loop's
    body). Every stretch is one more body in every step program, and the
    compile cache holds 192 MiB (PR 33); a part two stretches share goes
    under ``jax.jit`` (:func:`latent_attention`). **A stack applied
    several times** is this scan inside :func:`scan_passes`, not a cut."""
    if layers == 0:
        return carry

    def body(carry, per_layer):
        x, sides, *extra, counts, layer = carry
        x, sides, counted, *extra = step(x, sides, layer, per_layer, *extra)
        if counts is not None:
            counts = counts + counted
        return (x, tuple(sides), *extra, counts, layer + 1), None

    return jax.lax.scan(body, carry, xs, length=layers)[0]


def take(stack: Dict, index) -> Dict:
    """One layer's leaves of a stack ``{name: [n, ...]}`` at a traced
    index: a dynamic slice of the whole stack, read in place."""
    return {k: jax.lax.dynamic_index_in_dim(v, index, 0, keepdims=False)
            for k, v in stack.items()}


def index_in_kind(kinds: Sequence) -> jax.Array:
    """``at [L]``: layer ``l`` is the ``at[l]``-th of its kind, the entry
    it reads of its kind's stack, pages or state."""
    return jnp.asarray([list(kinds[:l]).count(kind)
                        for l, kind in enumerate(kinds)], jnp.int32)


def by_layer(flags: np.ndarray, layer, if_true, if_false, *operands):
    """``if_true(*operands)`` for the layers ``flags [L]`` marks,
    ``if_false`` for the others: the one branch itself where only one
    kind occurs, ``lax.cond`` on the traced ``layer`` where both do.

    An operand that a branch of the ``cond`` hands back as it got it (a
    side of the pool its kind does not use) is COPIED there, whole, in
    every layer: the conditional's result is a buffer of its own unless
    every branch updates the operand in place, and ``memory_analysis()``
    shows nothing of it (1.5 s of a 2 s trace in LFM2's first run on the
    chip, PR 36). So what a branch returns as the very object it received
    goes through a scatter whose one index is dropped, which updates in
    place and moves nothing; the branch need not know."""
    if flags.all() or not flags.any():
        return (if_true if flags.all() else if_false)(*operands)

    def written_nowhere(side):
        flat = side.reshape((-1,) + side.shape[2:])
        return flat.at[jnp.full((1,), flat.shape[0])].set(
            jnp.zeros((1,) + flat.shape[1:], flat.dtype),
            mode="drop").reshape(side.shape)

    def in_place(branch):
        def run(*operands):
            received = {id(leaf)
                        for leaf in jax.tree_util.tree_leaves(operands)}
            return jax.tree_util.tree_map(
                lambda leaf: (written_nowhere(leaf)
                              if id(leaf) in received else leaf),
                branch(*operands))
        return run

    return jax.lax.cond(jnp.asarray(flags)[layer], in_place(if_true),
                        in_place(if_false), *operands)


def take_last_token(x: jax.Array, last_token: jax.Array | None):
    """Prefill sampling reads ONE position's logits: slice the hidden
    states to it BEFORE the norm + head (positionwise ops commute with
    the slice), so the vocab projection runs on [B, 1, Hd] instead of the
    whole chunk. For a 128k-vocab model that removes a multi-GB f32
    logits temp and ~0.8 TFLOP per 2048-token chunk, with bit-identical
    results."""
    if last_token is None:
        return x
    with jax.named_scope("head"):
        return jnp.take_along_axis(x, last_token[:, None, None], axis=1)


def apply(
    family,
    params,
    cfg: ModelConfig,
    token_ids: jax.Array,  # [B, T]
    positions: jax.Array,  # [B, T]
    kv_pages: Tuple,  # ([L,NB,bs,KVH,D], [L,NB,bs,KVH,D])
    slot_mapping: jax.Array,  # [B, T]
    block_tables: jax.Array,  # [B, MAXB]
    context_lens: jax.Array,  # [B]
    seq_lens: jax.Array,  # [B]
    *,
    mode: str,  # "prefill" | "prefill_cached" | "decode"  (static)
    adapter_ids: jax.Array | None = None,  # [B] LoRA slot per sequence
    output_hidden: bool = False,  # return final hidden states, not logits
    last_token: jax.Array | None = None,  # [B] position whose logits to keep
    with_stats: bool = False,  # also what the family's loop counts
):
    """Full forward. Returns (logits [B, T, V], updated kv_pages), or the
    post-norm hidden states [B, T, Hd] instead of logits when
    ``output_hidden`` (the /v1/embeddings pass); with ``last_token``, of
    that one position (:func:`take_last_token`); with ``with_stats`` a
    third value, the counts of a family that brings its own layer loop
    (``Family.loop`` / ``Family.stats``)."""
    x, lora_layers, lora_scaling, adapter_ids = family.embed(
        params, cfg, token_ids, positions, adapter_ids)
    batch = Batch(positions, slot_mapping, block_tables, context_lens,
                  seq_lens, adapter_ids, lora_scaling)
    if family.loop is not None:
        x, kv_pages, stats = family.loop(cfg, mode, x, params, kv_pages,
                                         batch)
    else:
        # Leaves the layer takes as whole stacks (``Family.whole_leaves``)
        # stay out of the scan's slices and reach every layer as they are.
        layers = params["layers"]
        whole = {k: layers[k] for k in family.whole_leaves}
        sliced = {k: v for k, v in layers.items() if k not in whole}

        def step(x, kv, l, per_layer):
            p, lora = per_layer
            return *family.layer(cfg, mode, x, ({**p, **whole}, lora), kv, l,
                                 batch), None

        x, kv_pages, stats, _ = scan_layers(
            step, first_carry(x, kv_pages), xs=(sliced, lora_layers))
    x = take_last_token(x, last_token)
    out = family.head(params, cfg, x, output_hidden)
    return (out, kv_pages, stats) if with_stats else (out, kv_pages)


def scan_passes(step, x: jax.Array, sides: Tuple, passes: int, xs, close):
    """A stack of layers applied ``passes`` times over the same weights
    (models/ouro.py): ONE ``lax.scan`` over the passes around ONE
    :func:`scan_layers` over ``xs``, ``step(x, sides, layer, per_layer,
    u) -> (x, sides)`` with ``layer`` counted from 0 in every pass and
    ``u`` the pass, ``close(x)`` after a pass's last layer. Returns (x,
    sides, passes run as an int32 scalar, counted on the carry).

    The rule (PR 48, read in ``scripts/hlo_digest.py``'s ``ouro.*``
    programs): nest, do not unroll and do not flatten. A Python loop of
    ``passes`` stretches is ``passes`` bodies a step program. One stretch
    of ``passes x L`` steps reading layer ``n % L`` with :func:`take`
    needs a ``cond`` or a select for ``close`` in every step and gives up
    the scan's own slices. Nested, a program holds one body of the
    layer, the inner scan slices the stack in place as Llama's does
    (the weights are loop constants of the outer scan: no copy, the
    temporaries of ``memory_analysis()`` are a single pass's), and the
    pool rides both carries in place, as it rides the engine's burst
    around them: a family numbers its page layers by ``(layer, u)``."""
    def one_pass(carry, u):
        x, sides, done = carry

        def layer_step(x, sides, layer, per_layer):
            return *step(x, sides, layer, per_layer, u), None

        x, sides, _, _ = scan_layers(layer_step, first_carry(x, sides),
                                     xs=xs)
        return (close(x), sides, done + 1), None

    return jax.lax.scan(one_pass, (x, tuple(sides), jnp.int32(0)),
                        jnp.arange(passes, dtype=jnp.int32))[0]
