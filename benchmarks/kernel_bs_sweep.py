#!/usr/bin/env python3
"""Sweep KV page size (block_size) at fixed total context: fewer, bigger
DMAs per kernel invocation. With ``--cells``: the decode kernel alone, at
the tile it chooses itself, on the contexts the benchmark's cells hand it
(:data:`CELL_CASES`; ``--cells --narrow``: at eight kv heads of 64 in the
pool's packed rows; the numbers of the kernel's docstring and of PERF.md
section 6, PR 32; ``--cells --latent``: the absorbed latent kernel at
``longcat-backlog-long``'s shapes, :func:`time_latent`, PR 42).

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

# The serving shapes of the benchmark's cells (Mistral-7B's heads, 64-token
# pages, the 16-layer pool of 1,559 blocks, 32 rows) and the contexts they
# hand the kernel: name -> (contexts, table width in pages).
CELL_L, CELL_NB, CELL_BS, CELL_KVH, CELL_D, CELL_H = 16, 1559, 64, 8, 128, 32
_SESSIONS = [3235, 1080, 3178, 2317, 1870, 1731, 1449, 1569, 2561, 2660,
             1667, 3517]  # 12 live rows, uniform in 600-3,600
_BACKLOG = [1636, 844, 208, 363, 462, 784, 1348, 769, 512, 840, 575, 718,
            450, 334, 1316, 545, 693, 467, 481, 535, 933, 1270, 876, 479,
            2500, 1031, 1092, 481, 568, 908, 249, 424]  # lognormal, median 634
CELL_CASES = {
    "a_32x1_w64": ([1] * 32, 64),
    "b_backlog_w32": ([min(c, 2048) for c in _BACKLOG], 32),
    "b_backlog_w64": (_BACKLOG, 64),
    "c_12live_20x1_w64": (_SESSIONS + [1] * 20, 64),
    "d_12live_20x0_w64": (_SESSIONS + [0] * 20, 64),
    "e_16x3000_w64": ([3000] * 16, 64),
    "f_32x700_w16": ([700] * 32, 16),
}


def cell_tables(contexts, width: int, bs: int, nb: int, rng) -> np.ndarray:
    """[B, width]: each row's live pages distinct and scattered over the
    pool, zero past them (as the engine leaves a table)."""
    tables = np.zeros((len(contexts), width), np.int32)
    free = rng.permutation(np.arange(1, nb))
    at = 0
    for b, c in enumerate(contexts):
        n = min(-(-c // bs), width)
        tables[b, :n] = free[at:at + n]
        at += n
    return tables


def scan_of(call, layers: int, reps: int):
    """``run(*args)``, jitted: one ``lax.scan`` of ``layers x reps`` calls
    of ``call(*args, layer)`` (the layer is the scanned value, the output
    is summed into the carry); ``call`` None: the same scan without the
    kernel, whose time is taken off."""
    @jax.jit
    def run(*args):
        def body(acc, l):
            o = (call(*args, l % layers) if call
                 else args[0] * (l % layers).astype(args[0].dtype))
            return acc + o.astype(jnp.float32), None
        out, _ = jax.lax.scan(
            body, jnp.zeros(args[0].shape, jnp.float32),
            jnp.arange(layers * reps))
        return out
    return run


def time_cells(narrow: bool = False):
    """One JSON line a case: us a call (one layer) of the kernel alone,
    the same scan without it taken off, beside the time its live tokens'
    bytes need at 819 GB/s. ``narrow``: eight kv heads of 64 in the
    pool's packed rows (``ops.attention.packed_page_dims``: pages
    ``[2, NB, 64, 4, 128]``, lfm2-24b-a2b-l10's), through the dispatcher
    that spreads the queries over their head's lanes and brings the
    outputs back, so that work is in the time."""
    from production_stack_tpu.ops import attention as att

    L, NB, bs, KVH, D, H = (CELL_L, CELL_NB, CELL_BS, CELL_KVH, CELL_D,
                            CELL_H)
    if narrow:
        L, NB, D = 2, 8192, 64
    rng = np.random.default_rng(32)
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    page = (L, NB, bs) + att.packed_page_dims(KVH, D)
    k_pages = jax.random.normal(k1, page, jnp.bfloat16)
    v_pages = jax.random.normal(k2, page, jnp.bfloat16)
    reps = 8 * CELL_L // L  # calls of each layer in one timed scan
    kernel = att.paged_decode_attention if narrow else pallas_paged_attention

    def call(q, k_pages, v_pages, bt, cl, layer):
        return kernel(q, k_pages, v_pages, bt, cl, layer, scale=D ** -0.5)

    for name, (contexts, width) in CELL_CASES.items():
        q = jax.random.normal(k3, (len(contexts), H, D), jnp.bfloat16)
        bt = jnp.asarray(cell_tables(contexts, width, bs, NB, rng))
        cl = jnp.asarray(contexts, jnp.int32)
        args = (q, k_pages, v_pages, bt, cl)
        per_scan = (timed_per_call(scan_of(call, L, reps), *args)
                    - timed_per_call(scan_of(None, L, reps), *args))
        live = sum(contexts)
        print(json.dumps({
            "case": name, "rows": len(contexts), "table_pages": width,
            "page": list(page[2:]),
            "live_tokens": live,
            "us_per_call": round(per_scan / (L * reps) * 1e6, 1),
            "floor_us": round(live * 2 * KVH * D * 2 / 819e9 * 1e6, 1),
        }), flush=True)


# ``longcat-backlog-long``: 64 heads over a 512-wide latent and a rotated
# key in a 128-lane row, 64-token pages, the 8-sublayer pool of 6,988
# blocks, 128 rows.
LATENT_L, LATENT_NB, LATENT_BS, LATENT_H = 8, 6988, 64, 64
LATENT_C, LATENT_LANES, LATENT_ROPE, LATENT_ROWS = 512, 128, 64, 128
LATENT_TOKEN_BYTES = (LATENT_C + LATENT_ROPE) * 2  # what the reader counts


def latent_contexts(rows: int = LATENT_ROWS, mean: int = 2304) -> list:
    """The cell's live contexts, fixed: a prompt (lognormal, median 1,536,
    sigma 0.5, 512-6,144) and a uniform part of its answer (median 512,
    sigma 0.6, 128-2,048), as ``backlog-long.json`` draws them, scaled to
    the mean the cell's decode forwards read (~2.3k: rows with long
    answers stay longest)."""
    rng = np.random.default_rng(42)
    prompt = np.clip(rng.lognormal(np.log(1536), 0.5, rows), 512, 6144)
    answer = np.clip(rng.lognormal(np.log(512), 0.6, rows), 128, 2048)
    ctx = prompt + rng.uniform(0, 1, rows) * answer
    return [int(c) for c in np.minimum(ctx * mean / ctx.mean(), 8192)]


# ``glm47flash-agent-sessions`` (``--cases=agent --heads=20``): 20 heads
# over the same page, up to 32 rows of 4-7k tokens (a 4,096-token system
# prompt and a history to 7,168).
AGENT_ROWS, AGENT_CONTEXTS = 32, (4096, 7168)


def time_latent(tile=(0, 0), heads=LATENT_H, cases="backlog"):
    """One JSON line a case: us a call (one attention sublayer of one
    decode forward, 128 rows) of ``pallas_mla_decode`` alone, the same
    scan without it taken off, beside the reader's floor (live tokens x
    1,152 B at 819 GB/s: ``chipbench/readers/mla_decode_roofline.py``)
    and the share of it; ``max_err`` is the largest distance from the
    XLA path on the device. ``tile``: (pages a chunk, ring), 0 = the
    kernel's own choice. ``cases``: ``"backlog"``, the rows and
    contexts of ``longcat-backlog-long``, or ``"agent"``, those of
    ``glm47flash-agent-sessions`` (32, 16 and 8 rows spread evenly over
    4,096-7,168 tokens, and 32 one-token rows); ``heads`` is the
    kernel's, whatever the cases."""
    from production_stack_tpu.ops.attention import latent_decode_reference
    from production_stack_tpu.ops.pallas_mla_decode import pallas_mla_decode

    L, NB, bs, H = LATENT_L, LATENT_NB, LATENT_BS, heads
    rng = np.random.default_rng(42)
    k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
    c_pages = jax.random.normal(k1, (L, NB, bs, 1, LATENT_C), jnp.bfloat16)
    r_pages = jnp.pad(
        jax.random.normal(k2, (L, NB, bs, 1, LATENT_ROPE), jnp.bfloat16),
        ((0, 0),) * 4 + ((0, LATENT_LANES - LATENT_ROPE),))
    scale = 192 ** -0.5 / 8  # scores of a few units, as a trained model's
    reps = 16  # calls of each page layer in one timed scan

    def kernel(q_abs, q_rope, c_pages, r_pages, bt, cl, layer):
        return pallas_mla_decode(
            q_abs, q_rope, c_pages, r_pages, bt, cl, layer, scale=scale,
            pages_per_block=tile[0], ring=tile[1])

    distance = jax.jit(lambda *args: jnp.max(jnp.abs(
        kernel(*args).astype(jnp.float32)
        - latent_decode_reference(*args, scale=scale).astype(jnp.float32))))
    if cases == "backlog":
        cases = {"cell_lognormal": latent_contexts(),
                 "all_full_128x2304": [2304] * LATENT_ROWS,
                 "fixed_cost_128x1": [1] * LATENT_ROWS}
        widths = (64, 128)
    else:
        low, high = AGENT_CONTEXTS
        cases = {f"agent_{rows}_rows": [
            low + (high - low) * i // rows for i in range(rows)]
            for rows in (AGENT_ROWS, 16, 8)}
        cases[f"fixed_cost_{AGENT_ROWS}x1"] = [1] * AGENT_ROWS
        widths = (128,)
    for name, contexts in cases.items():
        q_abs = jax.random.normal(k3, (len(contexts), H, LATENT_C),
                                  jnp.bfloat16)
        q_rope = jax.random.normal(k4, (len(contexts), H, LATENT_ROPE),
                                   jnp.bfloat16)
        for width in widths:
            contexts_w = [min(c, width * bs) for c in contexts]
            bt = jnp.asarray(cell_tables(contexts_w, width, bs, NB, rng))
            cl = jnp.asarray(contexts_w, jnp.int32)
            args = (q_abs, q_rope, c_pages, r_pages, bt, cl)
            err = float(distance(*args, L - 1))
            per_scan = (timed_per_call(scan_of(kernel, L, reps), *args)
                        - timed_per_call(scan_of(None, L, reps), *args))
            live = sum(contexts_w)
            us = per_scan / (L * reps) * 1e6
            floor = live * LATENT_TOKEN_BYTES / 819e9 * 1e6
            print(json.dumps({
                "case": f"{name}_w{width}", "rows": len(contexts_w),
                "table_pages": width, "tile": list(tile), "heads": H,
                "live_tokens": live, "us_per_call": round(us, 1),
                "floor_us": round(floor, 1),
                "share_pct": round(100 * floor / us, 1),
                "max_err": round(err, 5),
            }), flush=True)


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
    if "--latent" in sys.argv[1:]:
        given = dict(a[2:].split("=") for a in sys.argv[1:] if "=" in a)
        time_latent(  # --tile=16:4 (pages:ring) --heads=20 --cases=agent
            tuple(int(x) for x in given.get("tile", "0:0").split(":")),
            int(given.get("heads", LATENT_H)),
            given.get("cases", "backlog"))
    elif "--cells" in sys.argv[1:]:
        time_cells(narrow="--narrow" in sys.argv[1:])
    else:
        main()
