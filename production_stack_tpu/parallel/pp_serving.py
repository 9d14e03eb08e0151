"""Pipeline-parallel SERVING forward: the model's layer stack staged over a
``pp`` mesh axis, drop-in compatible with the model's ``apply``.

The reference deploys pipeline-parallel engines by orchestrating multi-node
vLLM with KubeRay (``helm/templates/ray-cluster.yaml``,
``docs/source/use_cases/pipeline-parallelism-kuberay.rst``); on TPU the same
capability is a mesh axis inside one program. ``make_pp_apply`` wraps a
family's per-layer function (models/registry.py::Family) in a GPipe
schedule:

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

from typing import Tuple

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.decoder import (
    Batch,
    first_carry,
    scan_layers,
    take_last_token,
)


def _microbatch_count(batch: int, requested: int) -> int:
    """Largest divisor of ``batch`` that is <= requested (>=1)."""
    m = max(min(requested, batch), 1)
    while batch % m:
        m -= 1
    return m


def make_pp_apply(mesh: Mesh, family, microbatches: int = 1):
    """Build a pipeline-parallel ``apply`` from a family's ``embed`` /
    ``layer`` / ``head`` (one whose record says ``pipeline``).

    ``microbatches`` bounds the GPipe microbatch count per forward (the
    actual count is the largest divisor of the batch size, so any batch
    shape works). Returns a function with the exact signature of
    ``models.<family>.apply``.
    """
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

        x, lora_layers, lora_scaling, adapter_ids = family.embed(
            params, cfg, token_ids, positions, adapter_ids)  # x: [B, T, Hd]

        def mb(a):
            return a.reshape((M, Bm) + a.shape[1:])

        # Every per-sequence input of a layer, cut into microbatches
        # (no adapter ids: an empty leaf, which every map passes over).
        batch_mb = jax.tree_util.tree_map(mb, Batch(
            positions, slot_mapping, block_tables, context_lens, seq_lens,
            adapter_ids))
        x_mb = mb(x)

        k_all, v_all = kv_pages
        layer_spec = jax.tree_util.tree_map(
            lambda _: P("pp"), (params["layers"], lora_layers))

        def to_varying(a):
            return jax.lax.pcast(a, ("pp",), to="varying")

        def stage_body(xs_loc, scaling, k_loc, v_loc, x_mb, batch_mb):
            idx = jax.lax.axis_index("pp")

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
                batch = jax.tree_util.tree_map(
                    lambda a: pick(a, m), batch_mb)
                # Bubble ticks compute on garbage; masking their page writes
                # to slot -1 (dropped by the scatter) keeps the cache exact.
                batch = batch._replace(
                    slot_mapping=jnp.where(
                        active, batch.slot_mapping,
                        jnp.asarray(-1, batch.slot_mapping.dtype)),
                    lora_scaling=scaling)

                def step(x, kv, l, per_layer):
                    return *family.layer(cfg, mode, x, per_layer, kv, l,
                                         batch), None

                # This stage's layers only: the local index addresses the
                # local shard of the pool.
                y, (k_loc, v_loc), _, _ = scan_layers(
                    step, first_carry(x_in, (k_loc, v_loc)), xs=xs_loc)
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
            in_specs=(layer_spec, P(), P("pp"), P("pp"), P(), P()),
            out_specs=(P(), P("pp"), P("pp")),
            axis_names={"pp"},
        )((params["layers"], lora_layers), lora_scaling, k_all, v_all,
          x_mb, batch_mb)

        x = take_last_token(hidden_mb.reshape(B, T, -1), last_token)
        return family.head(params, cfg, x, output_hidden), (k_all, v_all)

    return pp_apply
