#!/usr/bin/env python3
"""The expert layer alone on the chip, at laguna-s-2.1-l8e64's widths
(PR 33, step 0): ``models/moe.py::expert_layer`` on N tokens over the 64
held experts of 7 stacked sparse layers, router scoring 256, top 10.

One jitted ``lax.scan`` of 64 calls (``i % layers`` as the layer, the
output summed into the carry) per case, timed after a warm call; per call
the time, the held experts hit, the assignments, and the share of the
roofline (the larger of weight bytes of the experts hit over 819 GB/s and
``2 x 3 x 3072 x 1024`` operations an assignment over 197 TFLOP/s).

    chiprun -- python benchmarks/expert_layer_step0.py
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from production_stack_tpu.models import moe  # noqa: E402

HIDDEN, WIDTH, HELD, PUBLISHED, TOP_K, LAYERS = 3072, 1024, 64, 256, 10, 7
CALLS = 64
CASES = {"decode_128": 128, "decode_32": 32, "prefill_512": 512,
         "prefill_1024": 1024}


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not os.environ.get("STEP0_TINY"):
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    hidden, width, layers = ((128, 64, 2) if os.environ.get("STEP0_TINY")
                             else (HIDDEN, WIDTH, LAYERS))
    keys = jax.random.split(jax.random.key(0), 5)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, fan_in):  # jitted: no float32 copy of a stack
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(jnp.bfloat16)

    p = {"router": draw(keys[0], (layers, hidden, PUBLISHED), hidden),
         "w_gate": draw(keys[1], (layers, HELD, hidden, width), hidden),
         "w_up": draw(keys[2], (layers, HELD, hidden, width), hidden),
         "w_down": draw(keys[3], (layers, HELD, width, hidden), width)}

    @jax.jit
    def run(p, h):
        def body(carry, i):
            acc, stats = carry
            at = i % layers
            out, s = moe.expert_layer(
                h, {**p, "router": p["router"][at]}, k=TOP_K, at=at,
                scaling=2.5)
            return (acc + out.astype(jnp.float32), stats + s), None

        (acc, stats), _ = jax.lax.scan(
            body, (jnp.zeros(h.shape, jnp.float32),
                   jnp.zeros((3,), jnp.int32)),
            jnp.arange(CALLS, dtype=jnp.int32))
        return acc, stats

    for name, tokens in CASES.items():
        h = draw(keys[4], (1, tokens, hidden), 1.0)
        jax.block_until_ready(run(p, h))
        t0 = time.perf_counter()
        _, stats = jax.block_until_ready(run(p, h))
        seconds = (time.perf_counter() - t0) / CALLS
        assignments, hit, _ = (int(v) / CALLS for v in stats)
        floor = max(hit * 3 * hidden * width * 2 / 819e9,
                    2 * 3 * hidden * width * assignments / 197e12)
        print(json.dumps({
            "case": name, "tokens": tokens, "us_per_call": seconds * 1e6,
            "experts_hit": hit, "assignments": assignments,
            "roofline_pct": 100 * floor / seconds,
            "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
