"""The expert layer's grouped matmul as a Pallas TPU kernel.

``out[rows of group g] = lhs[rows of group g] @ rhs[first_group + g]`` for
the rows ``lhs [m, k]`` sorted by group, after megablox ``gmm``
(``jax.experimental.pallas.ops.tpu.megablox``): a grid over (n tiles, row
tile *visits*) with the group boundaries as scalar prefetch. A visit is
one (group, row tile) pair that holds rows, so a group of five rows costs
one visit and a group that straddles a row tile two; the grid's second
axis is as long as there are visits, a number only the device knows.
**Each group's weights stream once per n tile, in blocks of megabytes**
(``[k, tn]``: the whole contraction), and the copies are what bounds the
kernel at decode shapes (PERF.md section 6, PR 37, step 0).

What differs from ``gmm``:

- ``rhs`` is a whole layer-stacked leaf ``[layers x held, k, n]`` and
  ``first_group`` (``layer x held``, traced) is added where its blocks
  are addressed, so the group arithmetic runs over the ``held`` groups of
  one layer and no slice of the stack is ever made (``models/moe.py``).
- The weights' blocks are copied by the kernel itself, *groups* ahead:
  a group's first visit starts the copy of the block two groups on
  (past the last group, the first groups' of the next n tile) into the
  buffer the group before has done with, and waits for its own: two
  copies are always in flight over a ring of three buffers. The
  pipeline of block specs fetches one *step* ahead, so a group of two
  visits left the copy engine idle through the first (step 0).
- The visits come from :func:`group_visits`: compare-and-reduce over
  ``[groups, groups]`` and ``[visits, groups]``, where ``gmm`` repeats
  and histograms; one call serves the matmuls of a layer.
- With ``gate`` the kernel makes both matmuls of a gated unit in one pass
  over the rows, ``act(lhs @ gate[g]) * (lhs @ rhs[g])``, each factor
  rounded as a matmul of its own would round it: an expert layer is two
  kernels, not three (a third less to trace in every step program's
  warm-up, and no ``[m, n]`` gate and up written and read back). ``act``
  is the static argument ``activation`` (:data:`ACTIVATIONS`: ``silu``
  for SwiGLU, ``relu`` for ReGLU), the family's to choose.
- Rows past the last group are not visited and their output is whatever
  the buffer held: the caller replaces them (``expert_layer`` does).
- The whole contraction is one block, so there is no accumulator: the
  product is stored under the group's row mask as it is made.
- The tiles are :func:`grouped_matmul_tiles` of the shapes alone, and the
  scoped VMEM limit follows from them.
- **Rows that are one row tile need no order** (``m == tm``: a decode
  step of 32 rows x top 4). Every visit would read the same block of
  rows, so the rows stay in the tokens' order, a visit is a group that
  has rows (:func:`one_tile_visits`) and its mask is "this row's group
  is mine" from a ``[m, 1]`` block beside the rows; with no row at all
  the one step the grid runs starts no copy and makes no product
  (PERF.md section 6, PR 50). :func:`grouped_matmul_path` names it
  ``pallas_one_tile``.

bf16 (or float32) operands, float32 accumulation, the operands' dtype
out. Correctness is pinned by tests/test_grouped_matmul.py (interpret
mode against ``jax.lax.ragged_dot``); tests/test_chip_compile.py compiles
the MoE families' programs with it for a v5e.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.attention import TRACED_PATHS

# The largest ``[k, tn]`` block of weights one copy brings in. At 819 GB/s
# it is a copy of ~5 us, against the ~0.35 us a grid step costs whatever
# it does.
RHS_TILE_BYTES = 4 << 20
# Buffers of such a block: the one worked on and the copies in flight.
# With one copy in flight the copy engine idled between a block's arrival
# and the next group's first step (step 0, PERF.md section 6, PR 37).
RING = 3
# Row tiles: the MXU's 128 rows, or the largest smaller tile the dtype's
# sublane packing allows (16 rows of bf16) that divides the rows.
ROW_TILES = (128, 64, 32, 16, 8)
LANES = 128
VMEM_LIMIT_CAP = 96 << 20  # of a v5e core's 128 MiB
# The gate's function in a gated unit ``act(x Wgate) * (x Wup)``, by the
# name a family passes (static: a kernel a name): on float32, in the
# kernel's epilogue and in ``models/moe.py``'s other paths alike.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


# How many devices the program being traced spans; set by the engine
# around a forward that runs on a mesh (:func:`spanning_devices`).
_DEVICES: contextvars.ContextVar = contextvars.ContextVar(
    "grouped_matmul_devices", default=1)


@contextlib.contextmanager
def spanning_devices(devices: int):
    """Tell :func:`traced_path`, for the traces made inside, that the
    program spans ``devices`` devices. The compiler cannot partition a
    ``pallas_call`` ("Mosaic kernels cannot be automatically
    partitioned"), and the experts' stacks of such a program may be
    sharded, so its grouped matmuls stay ``ragged_dot``."""
    token = _DEVICES.set(devices)
    try:
        yield
    finally:
        _DEVICES.reset(token)


def on_devices(apply, devices: int):
    """``apply`` with its traces made under :func:`spanning_devices`."""
    def spanning_apply(*args, **kwargs):
        with spanning_devices(devices):
            return apply(*args, **kwargs)

    return spanning_apply


def _platform() -> str:
    return jax.devices()[0].platform


def _use_pallas() -> bool:
    return _platform() == "tpu"


def _row_tile(m: int, dtype) -> Optional[int]:
    sublanes = 32 // jnp.dtype(dtype).itemsize  # 16 rows of bf16, 8 of f32
    return next((t for t in ROW_TILES if t >= sublanes and m % t == 0), None)


def _largest_tile(extent: int, limit: int) -> Optional[int]:
    """The largest multiple of the 128 lanes that divides ``extent`` and
    does not pass ``limit``."""
    if extent % LANES:
        return None
    units = extent // LANES
    return next((u * LANES for u in range(units, 0, -1)
                 if units % u == 0 and u * LANES <= limit), None)


def grouped_matmul_tiles(m: int, k: int, n: int, dtype,
                         groups: int) -> Optional[Tuple[int, int, int]]:
    """``(tm, tk, tn)`` for ``[m, k] x [groups, k, n]``, or None where the
    shape does not tile. A pure function of what a trace can see.

    ``tm`` follows from ``m`` and the dtype alone (one set of visits then
    serves a layer's three matmuls, whose ``k`` and ``n`` differ): the
    largest of :data:`ROW_TILES` that divides the rows. ``tk`` is the
    whole contraction (a multiple of the 128 lanes), and ``tn`` as many
    lanes of ``n`` as fit :data:`RHS_TILE_BYTES` beside it and divide
    ``n``: 3072 x 512 and 1024 x 1536 at Laguna's widths, 2048 x 768 and
    1536 x 1024 at LFM2's, 3 MiB each; 4096 x 512 and 14336 x 128 at
    Mixtral's. No tiling for a contraction of which 128 lanes pass the
    budget (over 16,384 in bf16)."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)) or groups < 1:
        return None
    size = jnp.dtype(dtype).itemsize
    tm = _row_tile(m, dtype)
    tn = None if k % LANES else _largest_tile(
        n, RHS_TILE_BYTES // (k * size))
    if not (tm and tn):
        return None
    return tm, k, tn


def grouped_matmul_path(m: int, k: int, n: int, dtype, groups: int,
                        devices: int = 1) -> str:
    """Which backend the grouped matmuls of an expert layer take whose
    experts are ``[k, n]`` up and ``[n, k]`` down, at these (static)
    shapes: ``"pallas"`` (this kernel), ``"pallas_one_tile"`` (this
    kernel where the rows are one row tile: no sort,
    :func:`one_tile_visits`) or ``"xla"``
    (``jax.lax.ragged_dot``). THE decision, from shapes, dtype and
    platform only: ``models/moe.py`` and the engine's
    ``expert_matmul_dispatch_total`` both evaluate it. ``"xla"`` off the
    TPU, in a program that spans several ``devices``
    (:func:`spanning_devices`), and where :func:`grouped_matmul_tiles`
    finds no tiling for either orientation: a width that is not a
    multiple of 128 (the tiny test models), rows that are not a multiple
    of 16 (fewer than 16 row slots in bf16)."""
    if not (devices == 1 and _use_pallas()
            and grouped_matmul_tiles(m, n, k, dtype, groups)):
        return "xla"
    tiles = grouped_matmul_tiles(m, k, n, dtype, groups)
    if not tiles:
        return "xla"
    return "pallas_one_tile" if tiles[0] == m else "pallas"


def traced_path(m: int, k: int, n: int, dtype, groups: int) -> str:
    """:func:`grouped_matmul_path` for the trace in progress, counted."""
    path = grouped_matmul_path(m, k, n, dtype, groups, _DEVICES.get())
    TRACED_PATHS["grouped_matmul", path] += 1
    return path


class GroupVisits(NamedTuple):
    """The kernel's scalar prefetch: where each group's rows end, for each
    step of the grid's visit axis its group and its row tile, and the
    groups that have rows in the order their weights are fetched."""
    ends: jax.Array  # [groups] int32: group g is rows [ends[g - 1], ends[g])
    groups: jax.Array  # [tiles_m + groups - 1] int32
    row_tiles: jax.Array  # [tiles_m + groups - 1] int32
    ranks: jax.Array  # [groups] int32: how many groups with rows precede
    by_rank: jax.Array  # [groups] int32: the group of that rank
    visit_ends: jax.Array  # [groups] int32: visits of the groups up to g


def _running_sums(x: jax.Array) -> jax.Array:
    """``cumsum`` along the last axis of short vectors ``[..., n]`` as one
    compare-and-reduce (one fusion on the chip, where ``jnp.cumsum`` is a
    reduce-window and its pads)."""
    i = jnp.arange(x.shape[-1], dtype=jnp.int32)
    return jnp.sum(jnp.where(i[None, :] <= i[:, None], x[..., None, :], 0),
                   axis=-1, dtype=jnp.int32)


def _groups_by_rank(index: jax.Array, with_rows: jax.Array,
                    ranks: jax.Array) -> jax.Array:
    """``[groups]`` int32: the groups (``index``: 0, 1, ...) that have
    rows, in order (the entries past them are 0), from each group's
    ``ranks`` among them."""
    return jnp.sum(
        jnp.where(with_rows[None, :] & (ranks[None, :] == index[:, None]),
                  index[None, :], 0), axis=1, dtype=jnp.int32)


def group_visits(sizes: jax.Array, m: int, tm: int) -> GroupVisits:
    """The (group, row tile) pairs that hold rows, in row order, of groups
    of ``sizes`` rows laid end to end from row 0 of ``m`` (``tm`` divides
    it). At most ``m / tm + groups - 1`` pairs (every row tile once, and
    once more for each group that starts inside one), ``visit_ends[-1]``
    of them; the entries past those name a valid group and tile and are
    never run. A dozen small fusions on the chip, 13-14 us a call (step 0)."""
    groups = sizes.shape[0]
    tiles_m = m // tm
    sizes = sizes.astype(jnp.int32)
    index = jnp.arange(groups, dtype=jnp.int32)
    with_rows = (sizes > 0).astype(jnp.int32)
    ends, ranks = _running_sums(jnp.stack([sizes, with_rows]))
    ranks = ranks - with_rows
    first = (ends - sizes) // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = _running_sums(tiles)
    visit = jnp.arange(tiles_m + groups - 1, dtype=jnp.int32)
    # The group of a visit: how many groups' visits end at or before it.
    group = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= visit[:, None], axis=1,
                dtype=jnp.int32), groups - 1)
    # first[group] - (visits before the group) + visit, without a gather.
    row_tile = visit + jnp.sum(
        jnp.where(group[:, None] == index[None, :],
                  (first - (visit_ends - tiles))[None, :], 0),
        axis=1, dtype=jnp.int32)
    by_rank = _groups_by_rank(index, sizes > 0, ranks)
    return GroupVisits(ends, group, jnp.clip(row_tile, 0, tiles_m - 1),
                       ranks, by_rank, visit_ends)


class OneTileVisits(NamedTuple):
    """The kernel's visits where the rows are one row tile and stay in
    the tokens' order: a visit is a group that has rows."""
    by_rank: jax.Array  # [groups] int32: the groups that have rows, in order
    count: jax.Array  # [1] int32: how many there are; 0 in an idle layer
    group_of_row: jax.Array  # [m, 1] int32; ``groups`` or more: nobody's


def group_sizes(group_of_row: jax.Array, groups: int) -> jax.Array:
    """``[groups]`` int32: how many of the rows ``[m]`` each group has, as
    one compare-and-reduce (a scatter-add of 128 elements is a loop on
    the chip); a row of group ``groups`` or more counts nowhere."""
    return jnp.sum(group_of_row[:, None] == jnp.arange(groups)[None, :],
                   axis=0, dtype=jnp.int32)


def one_tile_visits(sizes: jax.Array, group_of_row: jax.Array
                    ) -> OneTileVisits:
    """The part of :func:`group_visits` that no row tile enters: which
    groups have rows (``sizes [groups]``), and each row's group as the
    kernel's mask reads it. Two small fusions on the chip."""
    with_rows = (sizes > 0).astype(jnp.int32)
    by_rank = _groups_by_rank(
        jnp.arange(sizes.shape[0], dtype=jnp.int32), sizes > 0,
        _running_sums(with_rows) - with_rows)
    return OneTileVisits(by_rank, jnp.sum(with_rows).reshape(1),
                         group_of_row.astype(jnp.int32).reshape(-1, 1))


def _await_block(fetch, rhs_hbm, rhs, sems, by_rank, first_group, with_rows,
                 *, tn: int, tiles_n: int):
    """What a group's first visit in an n tile does about the weights.
    Their blocks are fetched in one order: n tile by n tile, the
    ``with_rows`` groups that have rows by rank; block ``fetch`` (of each
    stack) lands in slot ``fetch % RING``. Start the copies ``RING - 1``
    blocks ahead of ``fetch`` and wait for its own."""
    def copies(fetch):
        of_n = fetch // with_rows
        at = first_group[0] + by_rank[fetch - of_n * with_rows]
        to = jax.lax.rem(fetch, RING)
        return [pltpu.make_async_copy(
            hbm.at[at, :, pl.ds(pl.multiple_of(of_n * tn, LANES), tn)],
            rhs.at[to, i], sems.at[to, i]) for i, hbm in enumerate(rhs_hbm)]

    def start(fetch):
        @pl.when(fetch < tiles_n * with_rows)
        def _():
            for copy in copies(fetch):
                copy.start()

    @pl.when(fetch == 0)
    def _():
        for ahead in range(RING - 1):
            start(ahead)

    # Into the slot the group before has just done with: the copies of
    # the next RING - 1 blocks run while this one is worked on.
    start(fetch + RING - 1)
    for copy in copies(fetch):
        copy.wait()


def _store_product(mine, lhs, rhs, slot, out, stacks: int, activation: str):
    """``out`` under the mask ``mine`` = the rows ``lhs`` times the
    block(s) in ``slot`` (two stacks: the gated unit under
    ``activation``): the tile's other rows are another visit's (a
    neighbouring group's) or nobody's."""
    rows = lhs[...]
    # Each product rounded to the operands' dtype, as a matmul of its own
    # would hand it on.
    product = jnp.dot(rows, rhs[slot, stacks - 1],
                      preferred_element_type=jnp.float32).astype(out.dtype)
    if stacks == 2:  # act(rows @ gate) * (rows @ up)
        gate = jnp.dot(rows, rhs[slot, 0], preferred_element_type=jnp.float32
                       ).astype(out.dtype).astype(jnp.float32)
        product = (ACTIVATIONS[activation](gate).astype(out.dtype).astype(
            jnp.float32) * product.astype(jnp.float32)).astype(out.dtype)
    out[...] = jnp.where(mine, product.astype(jnp.float32),
                         out[...].astype(jnp.float32)).astype(out.dtype)


def _kernel(ends, groups, row_tiles, ranks, by_rank, visit_ends, first_group,
            lhs, *refs, tm: int, tn: int, tiles_n: int, stacks: int,
            activation: str):
    del visit_ends  # the grid's
    rhs_hbm, (out, rhs, sems) = refs[:stacks], refs[stacks:]
    n_i, visit = pl.program_id(0), pl.program_id(1)
    group = groups[visit]
    last = ends.shape[0] - 1
    # With no row at all the one visit run is the last group's, which
    # fetches group 0's blocks and stores nothing under its empty mask
    # (no layer is idle where the rows are many tiles; where they are one,
    # :func:`_one_tile_kernel` runs and fetches nothing).
    with_rows = jnp.maximum(
        ranks[last] + (ends[last] > (ends[last - 1] if last else 0)
                       ).astype(jnp.int32), 1)
    fetch = n_i * with_rows + ranks[group]
    slot = jax.lax.rem(fetch, RING)

    @pl.when((visit == 0) | (groups[jnp.maximum(visit - 1, 0)] != group))
    def _():  # the group's first visit in this n tile
        _await_block(fetch, rhs_hbm, rhs, sems, by_rank, first_group,
                     with_rows, tn=tn, tiles_n=tiles_n)

    row = row_tiles[visit] * tm + jax.lax.broadcasted_iota(
        jnp.int32, out.shape, 0)
    begin = jnp.where(group > 0, ends[jnp.maximum(group - 1, 0)], 0)
    mine = (row >= begin) & (row < ends[group])
    _store_product(mine, lhs, rhs, slot, out, stacks, activation)


def _one_tile_kernel(by_rank, count, first_group, lhs, group_of_row, *refs,
                     tn: int, tiles_n: int, stacks: int, activation: str):
    rhs_hbm, (out, rhs, sems) = refs[:stacks], refs[stacks:]
    n_i, rank = pl.program_id(0), pl.program_id(1)
    with_rows = count[0]

    # No rows, no work: the one step an idle layer's grid runs starts no
    # copy and makes no product, and ``out`` is whatever the buffer held.
    @pl.when(with_rows > 0)
    def _():
        fetch = n_i * with_rows + rank
        # Every visit is its group's first (and only) in this n tile.
        _await_block(fetch, rhs_hbm, rhs, sems, by_rank, first_group,
                     with_rows, tn=tn, tiles_n=tiles_n)
        mine = jnp.broadcast_to(group_of_row[...], out.shape) == by_rank[rank]
        _store_product(mine, lhs, rhs, jax.lax.rem(fetch, RING), out, stacks,
                       activation)


@functools.partial(jax.jit,
                   static_argnames=("tiles", "interpret", "activation"))
def pallas_grouped_matmul(
    lhs: jax.Array,  # [m, k], rows sorted by group (one tile: as they come)
    rhs: jax.Array,  # [all groups, k, n]: a whole stack
    visits,  # GroupVisits of the groups' sizes at ``tiles[0]``, or
    #          OneTileVisits where ``m == tiles[0]``
    first_group: jax.Array,  # () int32: group 0 is ``rhs[first_group]``
    *,
    tiles: Tuple[int, int, int],
    gate: Optional[jax.Array] = None,  # a second stack, shaped like ``rhs``
    interpret: bool = False,
    activation: str = "silu",  # of the gate: a key of ACTIVATIONS
) -> jax.Array:
    """``[m, n]`` in ``lhs``'s dtype; rows past the last group (one tile:
    rows of no group) undefined. With ``gate`` the two matmuls of a gated
    unit in one pass over the rows: ``activation(lhs @ gate[g]) * (lhs @
    rhs[g])``, each factor rounded to the dtype as the matmuls alone
    would round it."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"gate activation {activation!r} is not one of "
                         f"{sorted(ACTIVATIONS)}")
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiles
    if m % tm or tk != k or n % tn:
        raise ValueError(f"tiles {tiles} do not fit ({m}, {k}, {n})")
    size = lhs.dtype.itemsize
    stacks = (rhs,) if gate is None else (gate, rhs)
    one_tile = isinstance(visits, OneTileVisits)
    if one_tile:
        if m != tm:
            raise ValueError(f"{m} rows are not one row tile of {tm}")
        groups = visits.by_rank.shape[0]
        kernel = functools.partial(_one_tile_kernel, tn=tn, tiles_n=n // tn,
                                   stacks=len(stacks), activation=activation)
        prefetch = (visits.by_rank, visits.count)
        rows = (lhs, visits.group_of_row)
        steps = jnp.maximum(visits.count[0], 1)

        def lhs_index(n_i, rank, *_):
            return 0, 0

        def out_index(n_i, rank, *_):
            return 0, n_i

        rows_specs = [pl.BlockSpec((tm, k), lhs_index),
                      pl.BlockSpec((tm, 1), lhs_index)]
    else:
        groups = visits.ranks.shape[0]
        kernel = functools.partial(_kernel, tm=tm, tn=tn, tiles_n=n // tn,
                                   stacks=len(stacks), activation=activation)
        prefetch, rows = tuple(visits), (lhs,)
        # An idle step (no row routed here) still runs one visit.
        steps = jnp.maximum(visits.visit_ends[-1], 1)

        def lhs_index(n_i, visit, ends, groups, row_tiles, *_):
            return row_tiles[visit], 0

        def out_index(n_i, visit, ends, groups, row_tiles, *_):
            return row_tiles[visit], n_i

        rows_specs = [pl.BlockSpec((tm, k), lhs_index)]

    vmem = ((RING * len(stacks) * k * tn + 2 * tm * k + 2 * tm * tn) * size
            + (1 + len(stacks)) * tm * tn * 4)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch) + 1,
            in_specs=rows_specs
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(stacks),
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(n // tn, steps),
            scratch_shapes=[
                pltpu.VMEM((RING, len(stacks), k, tn), rhs.dtype),
                pltpu.SemaphoreType.DMA((RING, len(stacks)))],
        ),
        compiler_params=pltpu.CompilerParams(
            # One order of steps: blocks are fetched groups ahead.
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(VMEM_LIMIT_CAP, vmem + (8 << 20))),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n * len(stacks),
            # silu's exponential; relu has none
            transcendentals=(m * n * (len(stacks) - 1)
                             if activation == "silu" else 0),
            bytes_accessed=(len(stacks) * groups * k * n
                            + m * k * (n // tn) + m * n) * size),
        interpret=interpret,
        name="pallas_grouped_matmul",
    )
    return call(*prefetch, jnp.asarray(first_group, jnp.int32).reshape(1),
                *rows, *stacks)


def grouped_matmul(lhs: jax.Array, stack: jax.Array, visits, at,
                   tiles: Tuple[int, int, int],
                   gate: Optional[jax.Array] = None,
                   activation: str = "silu") -> jax.Array:
    """The kernel on layer ``at`` of ``stack [layers, held, k, n]`` (and of
    ``gate``, shaped like it, under ``activation``) under ``visits``
    (:class:`GroupVisits` or :class:`OneTileVisits`), read as ``layers x
    held`` groups (a reshape of leading dims: no copy). Off the TPU (a
    test that forced the path) it runs interpreted."""
    layers, held = stack.shape[:2]

    def as_groups(w):
        return w.reshape((layers * held,) + w.shape[2:])

    return pallas_grouped_matmul(
        lhs, as_groups(stack), visits, jnp.asarray(at, jnp.int32) * held,
        tiles=tiles, gate=None if gate is None else as_groups(gate),
        interpret=_platform() != "tpu", activation=activation)
