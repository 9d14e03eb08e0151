"""Pipeline-parallel SERVING forward: the model's layer stack staged over a
``pp`` mesh axis, drop-in compatible with the model's ``apply``.

The reference deploys pipeline-parallel engines by orchestrating multi-node
vLLM with KubeRay (``helm/templates/ray-cluster.yaml``,
``docs/source/use_cases/pipeline-parallelism-kuberay.rst``); on TPU the same
capability is a mesh axis inside one program. ``make_pp_apply`` wraps the
Llama-family per-layer function in a GPipe schedule:

- layer-stacked parameters AND the paged KV pool shard their leading (layer)
  axis over ``pp`` — each stage's HBM holds only its layers' weights and
  pages (the memory point of PP);
- the batch splits into microbatches that ride the pipeline; activations
  hand over stage-to-stage via ``ppermute`` (ICI/DCN);
- ``shard_map`` is manual over ``pp`` only (``axis_names={"pp"}``), so the
  Megatron tp shardings inside each stage still compile to GSPMD
  all-reduces — tp × pp compose in one jitted program;
- inactive (bubble) ticks run the same SPMD computation on garbage data;
  their KV-page writes are masked to slot ``-1`` (page scatter drops
  negative slots), so the cache stays exact.

Because the wrapper has the model ``apply`` signature, the whole engine —
bucketed prefill, cached prefill, fused multi-step decode bursts, pooled
embeddings — runs unchanged on top of it.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.models.config import ModelConfig


def _microbatch_count(batch: int, requested: int) -> int:
    """Largest divisor of ``batch`` that is <= requested (>=1)."""
    m = max(min(requested, batch), 1)
    while batch % m:
        m -= 1
    return m


def make_pp_apply(mesh: Mesh, microbatches: int = 1):
    """Build a pipeline-parallel ``apply`` for the Llama family.

    ``microbatches`` bounds the GPipe microbatch count per forward (the
    actual count is the largest divisor of the batch size, so any batch
    shape works). Returns a function with the exact signature of
    :func:`production_stack_tpu.models.llama.apply`.
    """
    from production_stack_tpu.models.llama import (
        _layer,
        embed_tokens,
        project_out,
    )

    pp = mesh.shape["pp"]
    ring = [(i, (i + 1) % pp) for i in range(pp)]

    def pp_apply(
        params,
        cfg: ModelConfig,
        token_ids: jax.Array,      # [B, T]
        positions: jax.Array,      # [B, T]
        kv_pages: Tuple[jax.Array, jax.Array],  # [L, NB, bs, KVH, D] x2
        slot_mapping: jax.Array,   # [B, T]
        block_tables: jax.Array,   # [B, MAXB]
        context_lens: jax.Array,   # [B]
        seq_lens: jax.Array,       # [B]
        *,
        mode: str,
        adapter_ids: jax.Array | None = None,
        output_hidden: bool = False,
        last_token: jax.Array | None = None,
    ):
        B, T = token_ids.shape
        M = _microbatch_count(B, microbatches)
        Bm = B // M
        n_ticks = M + pp - 1

        x, lora_layers, lora_scaling, adapter_ids = embed_tokens(
            params, cfg, token_ids, adapter_ids)  # x: [B, T, Hd]

        def mb(a):
            return a.reshape((M, Bm) + a.shape[1:])

        x_mb = mb(x)
        pos_mb = mb(positions)
        slots_mb = mb(slot_mapping)
        tables_mb = mb(block_tables)
        ctx_mb = mb(context_lens)
        seq_mb = mb(seq_lens)
        aid_mb = (
            mb(adapter_ids) if adapter_ids is not None
            else jnp.zeros((M, Bm), jnp.int32)
        )

        k_all, v_all = kv_pages
        layer_spec = jax.tree_util.tree_map(lambda _: P("pp"), params["layers"])
        lora_spec = (
            jax.tree_util.tree_map(lambda _: P("pp"), lora_layers)
            if lora_layers is not None else None
        )

        def to_varying(a):
            return jax.lax.pcast(a, ("pp",), to="varying")

        def stage_body(layers_loc, lora_loc, scaling, k_loc, v_loc,
                       x_mb, pos_mb, slots_mb, tables_mb, ctx_mb, seq_mb,
                       aid_mb):
            idx = jax.lax.axis_index("pp")

            def run_local(x, k_loc, v_loc, pos, slots, tables, ctx, seq,
                          aid):
                layer_fn = functools.partial(
                    _layer, cfg, mode,
                    positions=pos, slot_mapping=slots, block_tables=tables,
                    context_lens=ctx, seq_lens=seq,
                    lora_scaling=scaling, adapter_ids=aid,
                )

                def body(carry, per_layer):
                    x, k, v, l = carry
                    if lora_loc is not None:
                        lp, lo = per_layer
                    else:
                        lp, lo = per_layer, None
                    x, (k, v) = layer_fn(x, lp, lo, (k, v), l)
                    return (x, k, v, l + 1), None

                xs = (
                    (layers_loc, lora_loc) if lora_loc is not None
                    else layers_loc
                )
                (x, k_loc, v_loc, _), _ = jax.lax.scan(
                    body, (x, k_loc, v_loc, jnp.int32(0)), xs,
                )
                return x, k_loc, v_loc

            # Microbatch metadata indexed by this stage's CURRENT microbatch
            # (varying index -> pcast the operand to varying first).
            def pick(a, m):
                return jax.lax.dynamic_index_in_dim(
                    to_varying(a), m, 0, keepdims=False)

            zero = to_varying(jnp.zeros_like(x_mb[0]))
            outputs = to_varying(jnp.zeros_like(x_mb))

            def tick(t, carry):
                inflow, outputs, k_loc, v_loc = carry
                m_raw = t - idx
                m = jnp.clip(m_raw, 0, M - 1)
                active = jnp.logical_and(m_raw >= 0, m_raw < M)
                x_in = jnp.where(idx == 0, pick(x_mb, m), inflow)
                pos = pick(pos_mb, m)
                tables = pick(tables_mb, m)
                ctx = pick(ctx_mb, m)
                seq = pick(seq_mb, m)
                aid = pick(aid_mb, m)
                # Bubble ticks compute on garbage; masking their page writes
                # to slot -1 (dropped by the scatter) keeps the cache exact.
                picked_slots = pick(slots_mb, m)
                slots = jnp.where(
                    active, picked_slots,
                    jnp.asarray(-1, picked_slots.dtype))
                y, k_loc, v_loc = run_local(
                    x_in, k_loc, v_loc, pos, slots, tables, ctx, seq, aid)
                commit = jnp.logical_and(idx == pp - 1, active)
                outputs = jax.lax.cond(
                    commit,
                    lambda o: jax.lax.dynamic_update_index_in_dim(o, y, m, 0),
                    lambda o: o,
                    outputs,
                )
                inflow = jax.lax.ppermute(y, "pp", ring)
                return (inflow, outputs, k_loc, v_loc)

            _, outputs, k_loc, v_loc = jax.lax.fori_loop(
                0, n_ticks, tick, (zero, outputs, k_loc, v_loc),
            )
            # Only the last stage holds real outputs; share them. The psum
            # runs in float32: XLA's CPU AllReducePromotion pass crashes on
            # bf16 all-reduce (and f32 also keeps the broadcast exact).
            has = (idx == pp - 1).astype(jnp.float32)
            outputs = jax.lax.psum(
                outputs.astype(jnp.float32) * has, "pp"
            ).astype(outputs.dtype)
            return outputs, k_loc, v_loc

        hidden_mb, k_all, v_all = jax.shard_map(
            stage_body,
            mesh=mesh,
            in_specs=(layer_spec, lora_spec, P(), P("pp"), P("pp"),
                      P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P("pp"), P("pp")),
            axis_names={"pp"},
        )(params["layers"], lora_layers, lora_scaling, k_all, v_all,
          x_mb, pos_mb, slots_mb, tables_mb, ctx_mb, seq_mb, aid_mb)

        x = hidden_mb.reshape(B, T, -1)
        if last_token is not None:
            # Prefill sampling reads ONE position (see llama.apply).
            x = jnp.take_along_axis(x, last_token[:, None, None], axis=1)
        return project_out(params, cfg, x, output_hidden), (k_all, v_all)

    return pp_apply
