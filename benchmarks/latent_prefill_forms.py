#!/usr/bin/env python3
"""A cached prefill's attention over a latent cache, alone, in each of its
two forms (``models/decoder.py::attend_latent``, ``prefill_cached``): what
:func:`decoder.latent_prefill_form` chooses between, read on the chip at
the shapes two configurations hand it (PRs 44 and 45; PERF.md section 6;
the readings the rule was fitted to are ``chiprun_out/pr45/`` and the cases
of ``tests/test_glm4_moe_lite.py``).

    chiprun -- python benchmarks/latent_prefill_forms.py   # ~6 chip-minutes

One JSON line a case (geometry, the chunk's bucket, the table's blocks):
ms a call of one layer, ``up_projected`` and ``absorbed`` (the rule set
aside for the reading), ``rule`` (what the rule picks there) and
``up_only`` (the gather and the context's up-projection without the
attention: what the up-projected form pays before its first score). A
call is one step of a jitted ``lax.scan`` of :data:`ITER` (the page layer
scanned, the pages and a sum of the outputs carried, queries and latents
scaled by the carry so that nothing leaves the loop), timed on the host
around ``block_until_ready``: the median of :data:`REPS` over ITER.
``--tiny``: two small cases, for the CPU."""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from production_stack_tpu.models import decoder  # noqa: E402

TINY = "--tiny" in sys.argv[1:]
ITER, REPS = (2, 2) if TINY else (24, 5)
BLOCK, LAYERS, LANES = 64, 2, 128

# name: heads, latent C, nope N, rope R, value V, latent scale, softmax scale
GEOMETRIES = {
    "glm-4.7-flash": (20, 512, 192, 64, 256, 1.0, 256 ** -0.5),
    "longcat-flash": (64, 512, 128, 64, 128, (6144 / 512) ** 0.5,
                      192 ** -0.5),
}
# geometry: {table blocks: the buckets read under it}. The agent cell's turns
# (~5.7k of context under the 128-block table) at each bucket they take,
# LongCat's tails around its crossover, and a whole chunk (1,024) of each
# (PR 44); then, for PR 45's rule, every (bucket, table) pair whose form it
# changes and the pairs beside them that must stay: LongCat's tails and
# whole chunks under the tables its prompts of 1-6k take (32, 64, 128
# blocks), both models' widest buckets under shorter tables, and short
# tables whose float32 scores stay on the chip.
BUCKETS = {
    "glm-4.7-flash": {128: (64, 128, 256, 512, 1024), 64: (512, 1024),
                      32: (512, 1024), 16: (512, 1024), 8: (256, 512),
                      4: (256,)},
    "longcat-flash": {128: (64, 128, 256, 512, 1024), 64: (256, 512, 1024),
                      32: (128, 256, 512, 1024), 16: (256, 512, 1024),
                      8: (128, 256, 512), 4: (128, 256)},
}
LIVE = {4: 256, 8: 512, 16: 800, 32: 1500, 64: 3000, 128: 5700}  # context
CASES = [(geometry, t, table, LIVE[table])
         for geometry, tables in BUCKETS.items()
         for table, buckets in tables.items() for t in buckets]
if TINY:
    CASES = [("glm-4.7-flash", 16, 4, 200), ("longcat-flash", 16, 4, 200)]


def scan_of(geometry: str, T: int, table: int, live: int, form: str):
    """(the jitted scan, its pages) of one case in one form."""
    H, C, N, R, V, latent_scale, scale = GEOMETRIES[geometry]
    keys = jax.random.split(jax.random.key(0), 7)
    dt = jnp.bfloat16
    q_nope = jax.random.normal(keys[0], (1, T, H, N), dt)
    q_rope = jax.random.normal(keys[1], (1, T, H, R), dt)
    c = jax.random.normal(keys[2], (1, T, C), dt)
    k_rope = jax.random.normal(keys[3], (1, T, R), dt)
    w_up = jax.random.normal(keys[4], (H, C, N + V), dt) * C ** -0.5
    pages = (jax.random.normal(keys[5], (LAYERS, table + 4, BLOCK, 1, C), dt),
             jax.random.normal(keys[6], (LAYERS, table + 4, BLOCK, 1, LANES),
                               dt))
    positions = (live - T + jnp.arange(T, dtype=jnp.int32))[None]
    batch = decoder.Batch(  # block i of the table is page i
        positions=positions, slot_mapping=positions,
        block_tables=jnp.arange(table, dtype=jnp.int32)[None],
        context_lens=jnp.array([live], jnp.int32),
        seq_lens=jnp.array([T], jnp.int32))

    def step(carry, layer):
        kv, acc = carry
        f = (1.0 + 1e-9 * acc).astype(dt)
        if form == "up_only":
            latents, _ = decoder.gather_latents(
                *kv, batch.block_tables, layer, R)
            out = jnp.einsum("bsc,hcd->bshd", latents * f, w_up)
        else:
            out, kv = decoder.attend_latent(
                "prefill_cached", q_nope * f, q_rope, c * f, k_rope, w_up,
                kv, layer, batch, scale=scale, latent_scale=latent_scale)
        return (kv, acc + 1e-9 * jnp.sum(out.astype(jnp.float32))), None

    @jax.jit
    def run(kv):
        (_, acc), _ = jax.lax.scan(
            step, (kv, jnp.float32(0)),
            jnp.arange(ITER, dtype=jnp.int32) % LAYERS)
        return acc

    return run, pages


def main():
    rule = decoder.latent_prefill_form
    for geometry, T, table, live in CASES:
        H, C, N, R, V = GEOMETRIES[geometry][:5]
        line = {"geometry": geometry, "bucket": T, "table_blocks": table,
                "context": live, "device": jax.devices()[0].device_kind,
                "rule": rule(T, table * BLOCK, H, C, N, R, V)}
        for form in ("up_projected", "absorbed", "up_only"):
            decoder.latent_prefill_form = lambda *shapes, _form=form: _form
            try:
                run, pages = scan_of(geometry, T, table, live, form)
                for _ in range(2):  # compile, then once warm
                    run(pages).block_until_ready()
                seconds = []
                for _ in range(REPS):
                    t0 = time.perf_counter()
                    run(pages).block_until_ready()
                    seconds.append(time.perf_counter() - t0)
            finally:
                decoder.latent_prefill_form = rule
            line[form + "_ms"] = round(
                1e3 * statistics.median(seconds) / ITER, 4)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
