"""Ring attention: causal attention over a sequence sharded across a mesh
axis (sequence/context parallelism for long prompts).

The reference stack has **no** SP/CP anywhere (SURVEY §2.3 row 5): it buys
long context with ``maxModelLen`` pass-through and LMCache CPU offload.
Here long context is an engine-layer capability: the sequence is sharded
over an ``sp`` mesh axis, each device holds one contiguous chunk of
Q/K/V, and K/V chunks rotate around the ring via ``jax.lax.ppermute``
while a flash-style online softmax accumulates — peak memory per device is
O(T/sp · T/sp) for scores instead of O(T·T), and the K/V traffic rides
ICI neighbor-to-neighbor links (the all-to-all-free formulation of
Liu et al., "Ring Attention with Blockwise Transformers", 2023).

Layout contract: the global sequence is split into ``sp`` contiguous
chunks; device ``i`` holds chunk ``i`` (positions ``[i*C, (i+1)*C)``).
Causality is enforced chunk-to-chunk: a query chunk attends fully to
earlier chunks, causally within its own chunk, and not at all to later
chunks (those steps contribute -inf and wash out of the online softmax).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _chunk_scores(q, k, *, scale):
    """q [B,C,KVH,G,D] x k [B,C,KVH,D] -> scores [B,KVH,G,Cq,Ck] (f32)."""
    return jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32
    ) * scale


def ring_attention_fwd(
    q: jax.Array,  # [B, C, H, D] local query chunk
    k: jax.Array,  # [B, C, KVH, D] local key chunk
    v: jax.Array,  # [B, C, KVH, D] local value chunk
    *,
    axis_name: str,
    scale: float,
) -> jax.Array:
    """Causal ring attention body. Call inside shard_map over ``axis_name``.

    Returns the attention output for the local query chunk [B, C, H, D].
    """
    B, C, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    sp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)

    qg = q.reshape(B, C, KVH, G, D)
    pos_q = my_idx * C + jnp.arange(C)  # global positions of local queries

    # Online-softmax accumulators (float32). pcast marks them as varying
    # over the ring axis so the fori_loop carry types line up with the
    # per-device outputs.
    m = jax.lax.pcast(
        jnp.full((B, KVH, G, C), -jnp.inf, jnp.float32), (axis_name,),
        to="varying")
    l = jax.lax.pcast(
        jnp.zeros((B, KVH, G, C), jnp.float32), (axis_name,), to="varying")
    o = jax.lax.pcast(
        jnp.zeros((B, KVH, G, C, D), jnp.float32), (axis_name,),
        to="varying")

    def step(s, carry):
        m, l, o, k_cur, v_cur = carry
        # After s rotations each device holds the chunk of the device s
        # hops *behind* it on the ring.
        k_idx = (my_idx - s) % sp
        pos_k = k_idx * C + jnp.arange(C)
        scores = _chunk_scores(qg, k_cur, scale=scale)  # [B,KVH,G,C,Ck]
        mask = pos_k[None, :] <= pos_q[:, None]  # [Cq, Ck] causal (global)
        scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)

        chunk_max = jnp.max(scores, axis=-1)  # [B,KVH,G,C]
        new_m = jnp.maximum(m, chunk_max)
        # Guard fully-masked rows: keep exp() finite.
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        correction = jnp.where(
            jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(mask[None, None, None, :, :], p, 0.0)
        l_new = l * correction + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd",
                        p.astype(v_cur.dtype), v_cur).astype(jnp.float32)
        o_new = o * correction[..., None] + pv

        # Rotate K/V one hop around the ring (i -> i+1), so the next step
        # sees the chunk previously held by i-1.
        perm = [(j, (j + 1) % sp) for j in range(sp)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return new_m, l_new, o_new, k_nxt, v_nxt

    m, l, o, _, _ = jax.lax.fori_loop(0, sp, step, (m, l, o, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]  # [B,KVH,G,C,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, D).astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    axis_name: str = "sp",
    *,
    scale: float,
):
    """Build a jitted ring-attention over full (unsharded-view) arrays.

    Takes global q [B, T, H, D], k/v [B, T, KVH, D] with T divisible by the
    ``axis_name`` mesh size; shards the T axis, runs the ring, returns the
    global output [B, T, H, D].
    """
    seq_spec = P(None, axis_name, None, None)
    seq_sharding = NamedSharding(mesh, seq_spec)

    @jax.jit
    def run(q, k, v):
        body = functools.partial(
            ring_attention_fwd, axis_name=axis_name, scale=scale)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(seq_spec, seq_spec, seq_spec),
            out_specs=seq_spec,
        )(
            jax.lax.with_sharding_constraint(q, seq_sharding),
            jax.lax.with_sharding_constraint(k, seq_sharding),
            jax.lax.with_sharding_constraint(v, seq_sharding),
        )

    return run


def reference_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float
) -> jax.Array:
    """Single-device causal attention (for numerics comparison)."""
    B, T, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, T, KVH, G, D)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(T)
    mask = pos[None, :] <= pos[:, None]
    scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bkgqd", probs.astype(v.dtype), v)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, D)
