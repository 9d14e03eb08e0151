"""The routed expert layer the MoE families share (Mixtral, Laguna, LFM2).

One chip of the ``chips`` that share a layer holds a block of the
experts: ``held = E / chips`` of them, block ``share``. The router keeps
its published width and scores all ``E``; every token takes its top
``k``; the assignments that land on this chip's block are sorted by
expert and go through ONE grouped matmul per projection: **the Pallas
grouped matmul on the chip** (``ops/pallas_grouped_matmul.py``: each
expert's weights stream once, in tiles of megabytes chosen from the
shapes, only the row tiles of groups that have rows are visited, and
the gate and up projections share one pass over the rows, under the
gate activation the family names: ``silu`` or ``relu``),
**``jax.lax.ragged_dot`` elsewhere** (off the TPU, in a program that
spans devices, at a shape that does not tile). **Where the assignments
are one row tile** (``tokens x k`` = the row tile: a decode step of 32
rows x top 4) **nothing is sorted**: the rows stay in the tokens' order,
the kernel visits the experts that have rows under the mask "this row's
expert", and a layer whose held experts received no row copies and
multiplies nothing (``pallas_one_tile``; PERF.md section 6, PR 50). One
trace-time choice a layer (``grouped_matmul_path``), counted per
dispatched step program in the engine's
``expert_matmul_dispatch_total{path}``; PERF.md section 6, PR 37 has
both on the chip. Then the outputs are weighted
and summed back per token. No capacity and no dropped token: the rows
are as many as there are assignments, ``tokens x k``, and what lands on
another chip's experts is sorted past the last group, where the grouped
matmul does no work and the combine reads zeros. What the other chips'
experts would add is theirs to add: nothing here stands in for them.

With every expert held (``chips = 1``: Mixtral) this is the whole layer,
and softmax over all scores renormalised over the top k equals Mixtral's
softmax over the top-k logits.

**The expert leaves reach the grouped matmul whole.** A grouped matmul is
a kernel, and a kernel's operand is a buffer: handed one layer's slice of
a layer-stacked leaf, the compiler copies the slice out first, every
expert of the layer in every forward (1.2 GB a layer at Laguna's widths:
the v5e compiler ran out of memory on the copies alone). So a family
passes its expert leaves as they are stacked over its sparse layers,
with the layer's index ``at``: the stack ``[layers, held, ...]`` is read
as ``layers x held`` groups (a reshape of leading dims,
no copy): the Pallas kernel adds ``at x held`` to the group in its index
map, ``ragged_dot`` gets sizes that are zero but for this layer's block.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.models import decoder
from production_stack_tpu.ops import pallas_grouped_matmul as gmm

# What :func:`expert_layer` counts of one call, in this order; the last is
# 1 where no held expert received a row (the layer had nothing to do).
STATS = ("moe_assignments", "moe_experts_hit", "moe_max_expert_load",
         "moe_idle_layers")
# What it counts beside them in a layer with zero-compute experts: the
# assignments that landed on one (a family with such a layer puts
# ``ZERO_STATS`` behind ``STATS`` in its ``Family.stats``).
ZERO_STATS = ("moe_zero_assignments",)
# The experts' leaves, ``[sparse layers, held, ...]`` each.
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def swiglu(h: jax.Array, w_gate, w_up, w_down, *,
           activation: str = "silu") -> jax.Array:
    """``(act(h Wgate) * (h Wup)) Wdown``, the gated unit: the dense MLP,
    the shared expert, and what each routed expert computes on its rows.
    ``activation`` names ``act`` (``gmm.ACTIVATIONS``): ``silu`` is
    SwiGLU, ``relu`` ReGLU."""
    gate = gmm.ACTIVATIONS[activation](
        (h @ w_gate).astype(jnp.float32)).astype(h.dtype)
    return (gate * (h @ w_up)) @ w_down


def dense_layer(h: jax.Array, stack: Dict, at, *, activation: str = "silu"
                ) -> Tuple[jax.Array, jax.Array]:
    """The other kind of MLP of a family with leading dense layers:
    :func:`swiglu` (under ``activation``) on entry ``at`` of the stack
    ``{w_gate, w_up, w_down: [dense layers, ...]}``, and the zeros its
    layer adds to the expert layers' :data:`STATS`."""
    w = decoder.take(stack, at)
    return (swiglu(h, w["w_gate"], w["w_up"], w["w_down"],
                   activation=activation),
            jnp.zeros((len(STATS),), jnp.int32))


def sparse_leaves(layers: Dict, at) -> Tuple[Dict, Dict]:
    """Of the leaves stacked over a family's sparse layers: (layer
    ``at``'s own small ones, read at its index: router, bias, shared
    expert; what :func:`expert_layer` takes as ``p``: that router and the
    experts' stacks WHOLE, for ``at`` to index: a slice of them would be
    copied out in every forward, the module's docstring)."""
    stacks = {k: layers[k] for k in EXPERT_STACKS}
    w = decoder.take(
        {k: v for k, v in layers.items() if k not in stacks}, at)
    return w, {"router": w["router"], **stacks}


def route(h: jax.Array, router: jax.Array, k: int, *, scaling: float = 1.0,
          scoring: str = "softmax", bias: jax.Array | None = None,
          eps: float = 0.0, renormalise: bool = True
          ) -> Tuple[jax.Array, jax.Array]:
    """(weights [N, k] float32, experts [N, k]) of the tokens ``h [N, Hd]``
    over all of ``router``'s outputs: float32 scores (``softmax`` over
    them all, or a ``sigmoid`` of each), top k, weights renormalised to
    sum 1 (``+ eps``; the scores as they are with ``renormalise`` off)
    and multiplied by ``scaling``. With ``bias`` (float32
    ``[E]``) the experts are *selected* by score plus bias and *weighted*
    by the score without it."""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"router scoring {scoring!r} is not implemented")
    if bias is None:
        weights, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(scores + bias, k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + eps if eps else total)
    return weights * scaling, experts


def expert_layer(
    h: jax.Array,  # [B, T, Hd], normed
    p: Dict,  # router [Hd, E]; w_gate, w_up [layers, held, Hd, I]; w_down
    #           [layers, held, I, Hd]: the stacks over the sparse layers
    *,
    k: int,
    at,  # which layer of the stacks this is (static or traced)
    share: int = 0,  # this chip's block of experts: [share * held, ...)
    scaling: float = 1.0,
    valid: jax.Array | None = None,  # [B, T] bool: padding routes nowhere
    routing: Dict | None = None,  # route()'s scoring / bias / eps / ...
    zero_experts: int = 0,  # the router's last outputs are identities
    activation: str = "silu",  # of each expert's gate: silu or relu
    routed: Tuple[jax.Array, jax.Array] | None = None,  # route()'s result
) -> Tuple[jax.Array, jax.Array]:
    """The routed experts held here on ``h``, each a gated unit
    ``(activation(h Wgate) * (h Wup)) Wdown``. Returns (their weighted sum
    per token [B, T, Hd], the :data:`STATS` of the call as int32 [4]: the
    assignments the held experts received, how many of them received one,
    the largest number one received, and 1 if none received any).

    ``routed``: the (weights [N, k] float32, experts [N, k]) of
    :func:`route` where the family computed them elsewhere, under its
    own ``moe_router`` scope (a router that reads another tensor than
    the experts compute on: the layer's input, an attention earlier).
    ``p`` then needs no ``router``, and ``k``, ``scaling`` and
    ``routing`` are the caller's to have applied.

    With ``zero_experts`` the router's outputs ``[E, E + zero_experts)``
    behind the ``E`` experts that have weights are identities
    (zero-compute experts): each gives the layer's input back, so their
    part of the sum is ``(sum of their weights) * h``, added under the
    scope ``moe_zero``; they have no weights to hold, so every chip of
    the layer has them all and a token's home chip applies them. In the
    grouped matmul they sort past the groups like another chip's
    experts. The call's stats are then :data:`STATS` + :data:`ZERO_STATS`
    (int32 [5]): the assignments that landed on one, last."""
    B, T, Hd = h.shape
    N = B * T
    held = p["w_gate"].shape[1]
    x = h.reshape(N, Hd)
    if routed is None:
        with jax.named_scope("moe_router"):
            weights, experts = route(x, p["router"], k, scaling=scaling,
                                     **(routing or {}))
    else:
        weights, experts = routed
    with jax.named_scope("moe_experts"):
        local = experts - share * held
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine = mine & valid.reshape(N, 1)
        # ``held`` is the group of everything that is not computed here:
        # it sorts last, past the rows the grouped matmul is given.
        group = jnp.where(mine, local, held).reshape(N * k)
        # One trace-time choice for the layer's three matmuls (their row
        # tile is the same, so one set of visits serves them).
        m = N * k
        path = gmm.traced_path(m, *p["w_up"].shape[2:], h.dtype, held)
        one_tile = path == "pallas_one_tile"
        if one_tile:
            # One row tile needs no order: the tokens' rows, each ``k``
            # times, and the kernel's mask is the row's own group.
            sizes = gmm.group_sizes(group, held)
            rows = jnp.repeat(x, k, axis=0)  # [N * k, Hd], by token
        else:
            order = jnp.argsort(group, stable=True)
            sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
            rows = x[order // k]  # [N * k, Hd], by expert

        if path != "xla":
            up_tiles, down_tiles = (
                gmm.grouped_matmul_tiles(m, *p[name].shape[2:], h.dtype, held)
                for name in ("w_up", "w_down"))
            visits = (gmm.one_tile_visits(sizes, group) if one_tile else
                      gmm.group_visits(sizes, m, up_tiles[0]))
            # act(rows Wgate) * (rows Wup) in one pass over the rows.
            act = gmm.grouped_matmul(rows, p["w_up"], visits, at, up_tiles,
                                     gate=p["w_gate"], activation=activation)
            out = gmm.grouped_matmul(act, p["w_down"], visits, at,
                                     down_tiles)
        else:
            layers = p["w_up"].shape[0]
            in_stack = jax.lax.dynamic_update_slice(
                jnp.zeros((layers * held,), jnp.int32), sizes,
                (jnp.asarray(at, jnp.int32) * held,))

            def grouped(lhs, name):
                w = p[name]
                return jax.lax.ragged_dot(
                    lhs, w.reshape((layers * held,) + w.shape[2:]), in_stack)

            gate = grouped(rows, "w_gate")
            up = grouped(rows, "w_up")
            act = gmm.ACTIVATIONS[activation](
                gate.astype(jnp.float32)).astype(h.dtype) * up
            out = grouped(act, "w_down")  # [N * k, Hd]
        # Back to the token's order; a row past the groups is not the
        # grouped matmul's to define, so it is replaced, not multiplied.
        if not one_tile:
            out = out[jnp.argsort(order)]
        out = jnp.where(mine[..., None],
                        out.reshape(N, k, Hd).astype(jnp.float32), 0.0)
        y = jnp.einsum("nkh,nk->nh", out, weights).astype(h.dtype)
    assignments = jnp.sum(sizes)
    stats = [assignments, jnp.sum(sizes > 0), jnp.max(sizes),
             (assignments == 0).astype(jnp.int32)]
    if zero_experts:
        with jax.named_scope("moe_zero"):
            identity = experts >= p["router"].shape[-1] - zero_experts
            if valid is not None:
                identity = identity & valid.reshape(N, 1)
            y = y + (jnp.sum(jnp.where(identity, weights, 0.0), axis=-1,
                             keepdims=True)
                     * x.astype(jnp.float32)).astype(h.dtype)
        stats.append(jnp.sum(identity, dtype=jnp.int32))
    return y.reshape(B, T, Hd), jnp.stack(stats)
