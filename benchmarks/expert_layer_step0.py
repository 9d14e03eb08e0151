#!/usr/bin/env python3
"""The expert layer alone on the chip, at the widths and row counts the
two MoE cells run (PR 33 step 0; PR 37 step 0): the whole of
``models/moe.py::expert_layer`` and its three grouped matmuls alone, as
``jax.lax.ragged_dot`` and as the Pallas kernel
(``ops/pallas_grouped_matmul.py``) over a grid of tiles.

Per case one jitted ``lax.scan`` of 64 calls (``i % layers`` as the
layer, the output summed into the carry), timed after a warm call. The
matmuls alone take the rows and group sizes the case's own routing made
and run gate, up, ``silu(gate) * up`` and down, nothing else (the kernel
makes the first three in one pass). One JSON
line per measurement: us a call, the experts hit, the assignments, and
the share of the floor (the larger of the hit experts' three matrices
over 819 GB/s and ``2 x 3 x hidden x width`` operations an assignment
over 197 TFLOP/s). ``what`` is ``layer`` (whole), ``matmuls`` (alone) or
``visits`` (the kernel's metadata alone); ``path`` ``xla``, ``pallas`` or,
where the case's row slots are one row tile and the tree has that path
(PR 50), ``pallas_one_tile`` (the rows in the tokens' order; the whole
layer then takes it under ``pallas``, and ``pallas_sorted`` is the layer
forced down the sorted path); ``err`` the kernel's largest difference
from ``ragged_dot`` on the live rows of the down projection.

    chiprun -- python benchmarks/expert_layer_step0.py [laguna lfm2 glm]
        [--tiles 128:512:1536,64:1024:3072]   # tm:tn of gate/up:tn of down
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from production_stack_tpu.models import moe  # noqa: E402
from production_stack_tpu.ops import pallas_grouped_matmul as gmm  # noqa: E402

CALLS = 64
# hidden, expert width, held, published, top k, stacked sparse layers; the
# cases' (rows, width, live rows or None for all).
CONFIGS = {
    "laguna": dict(
        hidden=3072, width=1024, held=64, published=256, top=10, layers=7,
        cases={"decode_128": (128, 1, None), "prefill_1x640": (1, 640, None),
               "prefill_2x768": (2, 768, None), "prefill_4x512": (4, 512, None),
               "cached_1x256": (1, 256, None), "decode_32": (32, 1, None)},
        # weight-block bytes 1.5, 3 (the tile function's) and 6 MiB
        sweep=[(tm, up, down) for tm in (256, 128, 64, 16)
               for up, down in ((256, 768), (512, 1536), (1024, 3072))]),
    "lfm2": dict(
        hidden=2048, width=1536, held=64, published=64, top=4, layers=8,
        cases={"decode_32_live_1": (32, 1, 1), "decode_32_live_8": (32, 1, 8),
               "decode_32_live_0": (32, 1, 0), "cached_1x128": (1, 128, None)},
        sweep=[(tm, up, down) for tm in (128, 64, 16)
               for up, down in ((384, 512), (768, 1024), (1536, 2048))]),
    # GLM-4.7-Flash on one chip of eight (PR 50 step 0): a decode forward
    # of ~1.4 live rows leaves about half of the 46 sparse layers idle.
    "glm": dict(
        hidden=2048, width=1536, held=8, published=64, top=4, layers=46,
        cases={"decode_32_live_1": (32, 1, 1), "decode_32_live_2": (32, 1, 2),
               "decode_32_live_0": (32, 1, 0)},
        sweep=[(128, 768, 1024), (128, 1536, 2048)]),
}
TINY = dict(hidden=128, width=256, held=4, published=8, top=2, layers=2,
            cases={"decode_16": (16, 1, None), "live_1": (16, 1, 1)},
            sweep=[(16, 128, 128), (32, 256, 128)])


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / CALLS, out


def scan_calls(step, init):
    """``step(at) -> value`` 64 times, summed into a carry shaped ``init``."""
    def body(acc, i):
        return jax.tree_util.tree_map(jnp.add, acc, step(i)), None

    return jax.lax.scan(body, init, jnp.arange(CALLS, dtype=jnp.int32))[0]


def measure(name: str, c: dict, tilings, dev) -> None:
    hidden, width, held, top, layers = (
        c["hidden"], c["width"], c["held"], c["top"], c["layers"])
    keys = jax.random.split(jax.random.key(0), 5)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, fan_in):  # jitted: no float32 copy of a stack
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(jnp.bfloat16)

    p = {"router": draw(keys[0], (layers, hidden, c["published"]), hidden),
         "w_gate": draw(keys[1], (layers, held, hidden, width), hidden),
         "w_up": draw(keys[2], (layers, held, hidden, width), hidden),
         "w_down": draw(keys[3], (layers, held, width, hidden), width)}
    stacks = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    on_tpu = dev.platform == "tpu"

    def layer_fn(forced):
        @jax.jit
        def run(p, h, valid):
            def step(i):
                at = i % layers
                out, s = moe.expert_layer(
                    h, {**p, "router": p["router"][at]}, k=top, at=at,
                    scaling=2.5, valid=valid)
                return out.astype(jnp.float32), s

            return scan_calls(step, (jnp.zeros(h.shape, jnp.float32),
                                     jnp.zeros((len(moe.STATS),), jnp.int32)))

        def call(*args):
            # The choice is made while tracing: hold it for the trace.
            real = gmm._use_pallas, gmm.grouped_matmul_path
            gmm._use_pallas = lambda: forced != "xla"
            if forced == "pallas_sorted":
                gmm.grouped_matmul_path = lambda *a, **k: "pallas"
            try:
                return run(*args)
            finally:
                gmm._use_pallas, gmm.grouped_matmul_path = real

        return call

    def silu_mul(gate, up):
        return jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up

    @jax.jit
    def routed(p, h, valid):  # the rows and sizes expert_layer makes
        x = h.reshape(-1, hidden)
        _, experts = moe.route(x, p["router"][0], top)
        group = jnp.where(valid.reshape(-1, 1), experts, held)
        group = jnp.where(group < held, group, held).reshape(-1)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        return x[order // top], sizes, jnp.repeat(x, top, axis=0), group

    def as_groups(stacks):  # inside a jit: a view, no copy
        return {k: w.reshape((layers * held,) + w.shape[2:])
                for k, w in stacks.items()}

    @jax.jit
    def matmuls_xla(stacks, rows, sizes):
        stacks = as_groups(stacks)

        def step(i):
            in_stack = jax.lax.dynamic_update_slice(
                jnp.zeros((layers * held,), jnp.int32), sizes,
                ((i % layers) * held,))
            g = functools.partial(jax.lax.ragged_dot, group_sizes=in_stack)
            act = silu_mul(g(rows, stacks["w_gate"]), g(rows, stacks["w_up"]))
            return g(act, stacks["w_down"]).astype(jnp.float32)

        return scan_calls(step, jnp.zeros(rows.shape, jnp.float32))

    @functools.partial(jax.jit, static_argnums=(3, 4, 5))
    def matmuls_pallas(stacks, rows, sizes, tm, tn_up, tn_down):
        stacks = as_groups(stacks)

        def step(i):
            visits = gmm.group_visits(sizes, rows.shape[0], tm)
            g = functools.partial(
                gmm.pallas_grouped_matmul, visits=visits,
                first_group=(i % layers) * held, interpret=not on_tpu)
            act = g(rows, stacks["w_up"], gate=stacks["w_gate"],
                    tiles=(tm, hidden, tn_up))  # silu(gate) * up, one pass
            return g(act, stacks["w_down"], tiles=(tm, width, tn_down)
                     ).astype(jnp.float32)

        return scan_calls(step, jnp.zeros(rows.shape, jnp.float32))

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def matmuls_one_tile(stacks, rows, group, tn_up, tn_down):
        stacks = as_groups(stacks)  # ``rows`` in the tokens' order

        def step(i):
            sizes = gmm.group_sizes(group, held)
            g = functools.partial(
                gmm.pallas_grouped_matmul,
                visits=gmm.one_tile_visits(sizes, group),
                first_group=(i % layers) * held, interpret=not on_tpu)
            act = g(rows, stacks["w_up"], gate=stacks["w_gate"],
                    tiles=(rows.shape[0], hidden, tn_up))
            return g(act, stacks["w_down"],
                     tiles=(rows.shape[0], width, tn_down)
                     ).astype(jnp.float32)

        return scan_calls(step, jnp.zeros(rows.shape, jnp.float32))

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def visits_alone(sizes, m, tm):
        def step(i):  # ``+ i`` keeps the scan from hoisting it
            v = gmm.group_visits(sizes + i, m, tm)
            return v.groups + v.row_tiles + (
                v.ends + v.ranks + v.by_rank + v.visit_ends).sum()

        return scan_calls(step, jnp.zeros((m // tm + held - 1,), jnp.int32))

    @jax.jit
    def visits_one_tile(sizes, group):
        def step(i):
            v = gmm.one_tile_visits(sizes + i, group)
            return v.by_rank + v.count + v.group_of_row.sum()

        return scan_calls(step, jnp.zeros((held,), jnp.int32))

    for case, (rows_n, span, live) in c["cases"].items():
        tokens = rows_n * span
        m = tokens * top
        h = draw(keys[4], (rows_n, span, hidden), 1.0)
        valid = (jnp.arange(rows_n)[:, None] < (rows_n if live is None
                                                else live)
                 ) & jnp.ones((rows_n, span), bool)
        rows, sizes, by_token, group = routed(p, h, valid)
        of_rows = (int(sizes.sum()), int((sizes > 0).sum()))

        def line(what, path, seconds, stats=None, **more):
            # A whole layer's counts are its own (each layer's router
            # sends other rows here); the matmuls' are the rows they got.
            assignments, hit = (of_rows if stats is None else
                                (float(v) / CALLS for v in stats[:2]))
            floor = max(hit * 3 * hidden * width * 2 / 819e9,
                        2 * 3 * hidden * width * assignments / 197e12)
            print(json.dumps({
                "config": name, "case": case, "what": what, "path": path,
                "row_slots": m, "us_per_call": round(seconds * 1e6, 1),
                "floor_us": round(floor * 1e6, 1),
                "floor_pct": round(100 * floor / seconds, 1) if floor else None,
                "experts_hit": hit, "assignments": assignments, **more,
                "device": dev.device_kind}), flush=True)

        seconds, (_, stats) = timed(layer_fn("xla"), p, h, valid)
        line("layer", "xla", seconds, stats)
        seconds, want = timed(matmuls_xla, stacks, rows, sizes)
        line("matmuls", "xla", seconds)
        chosen = gmm.grouped_matmul_tiles(m, hidden, width, rows.dtype, held)
        if chosen is None:
            line("layer", "pallas", float("nan"), tiles=None)
            continue
        seconds, (_, stats) = timed(layer_fn("pallas"), p, h, valid)
        line("layer", "pallas", seconds, stats)
        down = gmm.grouped_matmul_tiles(m, width, hidden, rows.dtype, held)
        default = (chosen[0], chosen[2], down[2])
        for tm, tn_up, tn_down in [default] + [
                t for t in tilings if t != default and m % t[0] == 0]:
            try:
                seconds, got = timed(matmuls_pallas, stacks, rows, sizes,
                                     tm, tn_up, tn_down)
            except Exception as e:  # noqa: BLE001 - a tiling Mosaic refuses
                line("matmuls", "pallas", float("nan"),
                     tiles=[tm, tn_up, tn_down], error=str(e)[:200])
                continue
            err = float(jnp.max(jnp.abs(got[:of_rows[0]]
                                        - want[:of_rows[0]]),
                                initial=0.0)) / CALLS
            line("matmuls", "pallas", seconds, tiles=[tm, tn_up, tn_down],
                 chosen=(tm, tn_up, tn_down) == default, err=round(err, 5))
        seconds, _ = timed(visits_alone, sizes, m, chosen[0])
        line("visits", "pallas", seconds, tiles=[chosen[0]])
        if chosen[0] != m or not hasattr(gmm, "one_tile_visits"):
            continue
        seconds, (_, stats) = timed(layer_fn("pallas_sorted"), p, h, valid)
        line("layer", "pallas_sorted", seconds, stats)
        seconds, got = timed(matmuls_one_tile, stacks, by_token, group,
                             default[1], default[2])
        live = (group < held)[:, None]
        err = float(jnp.max(jnp.abs(jnp.where(
            live, got - want[jnp.argsort(jnp.argsort(group, stable=True))],
            0.0)))) / CALLS
        line("matmuls", "pallas_one_tile", seconds, tiles=list(default),
             err=round(err, 5))
        seconds, _ = timed(visits_one_tile, sizes, group)
        line("visits", "pallas_one_tile", seconds, tiles=[chosen[0]])
    del p, stacks
    gc.collect()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="*", default=list(CONFIGS))
    ap.add_argument("--tiles", default=None,
                    help="tm:tn_gate_up:tn_down,... in place of the sweep")
    args = ap.parse_args()
    dev = jax.devices()[0]
    tiny = bool(os.environ.get("STEP0_TINY"))
    if dev.platform != "tpu" and not tiny:
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    for name in args.configs:
        c = TINY if tiny else CONFIGS[name]
        tilings = ([tuple(int(v) for v in t.split(":"))
                    for t in args.tiles.split(",")] if args.tiles
                   else c["sweep"])
        measure(name, c, tilings, dev)


if __name__ == "__main__":
    main()
