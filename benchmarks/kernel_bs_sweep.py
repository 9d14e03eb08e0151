#!/usr/bin/env python3
"""Sweep KV page size (block_size) at fixed total context: fewer, bigger
DMAs per kernel invocation.

Timing methodology (benchmarks/timing.py): every timed sequence ends in
a real ``device_get`` readback, and per-iteration cost is recovered by
differencing two pipelined runs (N2 vs N1 enqueues, one readback each),
which cancels the constant dispatch + transfer cost."""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from production_stack_tpu.models.config import get_model_config  # noqa: E402
from production_stack_tpu.ops.pallas_paged_attention import (  # noqa: E402
    pallas_paged_attention,
)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from timing import timed_per_call  # noqa: E402

B = 16
CTX = int(os.environ.get("CHECK_CTX", "3000"))


def main():
    mc = get_model_config("tpu-llama-1b")
    L, KVH, D, H = mc.num_layers, mc.num_kv_heads, mc.head_dim, mc.num_heads
    rng = np.random.default_rng(0)
    scale = 1.0 / (D ** 0.5)

    for bs in (64, 128, 256, 512):
        maxb = max(4096 // bs, 1)  # table spans 4096 tokens
        nb = max(3000 * 18 // bs, maxb)  # same total pool bytes-ish
        shape = (L, nb, bs, KVH, D)

        @jax.jit
        def mk(key, shape=shape):
            k1, k2 = jax.random.split(key)
            return (jax.random.normal(k1, shape, jnp.bfloat16) * 0.1,
                    jax.random.normal(k2, shape, jnp.bfloat16) * 0.1)

        k_pages, v_pages = mk(jax.random.key(0))
        bt = jnp.asarray(rng.integers(0, nb, (B, maxb)), jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
        cl = jnp.full((B,), CTX, jnp.int32)
        # pages_per_block sized so one chunk spans 512 tokens.
        P = max(512 // bs, 1)
        while maxb % P:
            P //= 2

        @jax.jit
        def all_layers(q, k_pages, v_pages, bt, cl, P=P):
            def body(acc, l):
                o = pallas_paged_attention(
                    q, k_pages, v_pages, bt, cl, l, scale=scale,
                    pages_per_block=P)
                return acc + o.astype(jnp.float32), None
            out, _ = jax.lax.scan(
                body, jnp.zeros(q.shape, jnp.float32), jnp.arange(L))
            return out

        try:
            per_call = timed_per_call(all_layers, q, k_pages, v_pages,
                                      bt, cl)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"bs": bs, "error": str(e)[:160]}), flush=True)
            continue
        live = min(-(-CTX // bs), maxb)
        floor = (B * live * bs * KVH * D * 2 * 2 * L) / 819e9
        print(json.dumps({
            "bs": bs, "P": P, "maxb": maxb, "nb": nb,
            "all_L_per_call_s": round(per_call, 5),
            "floor_s": round(floor, 5),
            "x_floor": round(per_call / floor, 2),
            "dmas_per_invocation": B * live * 2,
        }), flush=True)
        del k_pages, v_pages


if __name__ == "__main__":
    main()
