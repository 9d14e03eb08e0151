"""Pipeline parallelism: layer stages sharded over a ``pp`` mesh axis.

The reference stack gets pipeline parallelism by orchestrating multi-node
vLLM with KubeRay (``helm/templates/ray-cluster.yaml``); on TPU the same
capability is a mesh axis — no Ray, no separate processes. Layer-stacked
parameters shard on the layer axis across ``pp`` stages; activations flow
stage-to-stage with ``ppermute`` over ICI/DCN; microbatches fill the
pipeline GPipe-style (T = n_micro + pp - 1 ticks, bubbles at the ends).

``pipeline_forward`` is the schedule around any per-layer function. It is
exercised standalone (tests, dryrun) and is the building block for
stage-sharded serving of models too large for one slice's HBM.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_forward(
    layer_fn: Callable,  # (x, one_layer_params) -> x
    mesh: Mesh,
    axis_name: str = "pp",
):
    """Build a jitted pipelined forward.

    Takes params whose leaves are layer-stacked on axis 0 (length L,
    divisible by the ``pp`` mesh size — each stage owns a contiguous
    [L/pp] shard) and ``x`` of shape [M, ...] (M microbatches, divisible
    by nothing in particular; each microbatch rides the pipeline whole).
    Returns the forward output [M, ...].
    """
    pp = mesh.shape[axis_name]

    def run(params, x):
        M = x.shape[0]
        T = M + pp - 1  # total pipeline ticks

        p_spec = jax.tree_util.tree_map(lambda _: P(axis_name), params)
        x_spec = P()  # microbatches replicated; each stage uses its turn's

        def stage_body(local_params, x_all):
            # local_params: leaves [L/pp, ...] (this stage's layers);
            # x_all: [M, ...] full microbatch set (replicated input).
            idx = jax.lax.axis_index(axis_name)

            def apply_local(x):
                def body(h, one_layer):
                    return layer_fn(h, one_layer), None

                h, _ = jax.lax.scan(body, x, local_params)
                return h

            # pcast-to-varying: carries mix with per-stage (varying) values
            # inside the loop, so their types must be varying over the pp
            # axis too.
            zero = jax.lax.pcast(
                jnp.zeros_like(x_all[0]), (axis_name,), to="varying")
            outputs = jax.lax.pcast(
                jnp.zeros_like(x_all), (axis_name,), to="varying")

            def tick(t, carry):
                inflow, outputs = carry
                # Stage 0 injects microbatch t (when in range); others take
                # the activation handed over from the previous stage.
                m_for_stage0 = jnp.clip(t, 0, M - 1)
                injected = jax.lax.pcast(
                    jax.lax.dynamic_index_in_dim(
                        x_all, m_for_stage0, 0, False),
                    (axis_name,), to="varying",
                )
                x_in = jnp.where(idx == 0, injected, inflow)
                y = apply_local(x_in)
                # Last stage commits microbatch (t - pp + 1) when in range.
                m_done = t - (pp - 1)
                commit = jnp.logical_and(idx == pp - 1,
                                         jnp.logical_and(m_done >= 0,
                                                         m_done < M))
                outputs = jax.lax.cond(
                    commit,
                    lambda o: jax.lax.dynamic_update_index_in_dim(
                        o, y, jnp.clip(m_done, 0, M - 1), 0),
                    lambda o: o,
                    outputs,
                )
                # Hand activations to the next stage (ring; the wraparound
                # value into stage 0 is ignored — it injects fresh input).
                nxt = jax.lax.ppermute(
                    y, axis_name, [(i, (i + 1) % pp) for i in range(pp)])
                return (nxt, outputs)

            _, outputs = jax.lax.fori_loop(0, T, tick, (zero, outputs))
            # Only the last stage holds real outputs; share them.
            stage_has = (idx == pp - 1).astype(outputs.dtype)
            return jax.lax.psum(outputs * stage_has, axis_name)

        out = jax.shard_map(
            stage_body, mesh=mesh,
            in_specs=(p_spec, x_spec), out_specs=x_spec,
        )(
            jax.lax.with_sharding_constraint(
                params,
                jax.tree_util.tree_map(
                    lambda _: NamedSharding(mesh, P(axis_name)), params),
            ),
            x,
        )
        return out

    return jax.jit(run)


def reference_forward(layer_fn: Callable):
    """Sequential single-device forward for parity checks."""

    @jax.jit
    def run(params, x):
        def body(h, one_layer):
            return layer_fn(h, one_layer), None

        def per_micro(xm):
            h, _ = jax.lax.scan(body, xm, params)
            return h

        return jax.vmap(per_micro)(x)

    return run
