#!/usr/bin/env python3
"""The tail of a decode step alone, from ``[B, V]`` logits to a token and
its logprobs a row, over a burst of 8 steps: the engine's tail
(``engine/sampling.py``: ``burst_terms`` once, then ``shape_logits`` and
``sample_with_logprobs`` a step) beside the tail it replaced (kept as the
reference of ``tests/test_sampling_tail.py``), at the cells' rows and
vocabularies, and how ``lax.top_k`` orders exact ties on this device
(PR 49; PERF.md section 6).

    chiprun -- python benchmarks/sampling_tail_step0.py   # ~2 chip-minutes

One JSON line a shape: us a step of each tail (a burst's device time on
the host's clock around ``block_until_ready``, the median of :data:`REPS`,
over its 8 steps; no head and no forward, so a step is the tail's passes
over ``[B, V]`` and nothing else), whether the two gave the same tokens,
ids and logprob bits there, and the largest distance of the logprobs.
Then one line for the ties: rows whose maximum is tied 2 to 4,096 times,
``argmax`` against rank 0 of the selection. ``--tiny``: one small shape,
for the CPU."""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import test_sampling_tail as tail  # noqa: E402
from production_stack_tpu.engine import sampling  # noqa: E402

TINY = "--tiny" in sys.argv[1:]
REPS, STEPS = (2 if TINY else 7), tail.K
# rows (--max-num-seqs) x vocabulary of the seven cells' configurations
SHAPES = {"tiny": (4, 512)} if TINY else {
    "lfm2-24b-a2b-l10": (32, 65536),
    "mistral-7b-l16": (32, 32000),
    "laguna-s-2.1-l8e64": (128, 25088),
    "longcat-flash-l4e16": (128, 16384),
    "glm-4.7-flash-e8v8": (32, 19360),
    "ouro-2.6b": (8, 49152),
}


def _args(rows, vocab, seed):
    return {key: jnp.asarray(value) for key, value in tail._inputs(
        rows=rows, vocab=vocab, seed=seed).items()}


def _us_a_step(fn, args):
    jax.block_until_ready(fn(**args, max_top_k=64))
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(**args, max_top_k=64))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / STEPS * 1e6


def main():
    device = jax.devices()[0]
    for name, (rows, vocab) in SHAPES.items():
        args = _args(rows, vocab, 49)
        old, _ = tail._reference_burst(**args, max_top_k=64)
        new, _ = tail._burst(**args, max_top_k=64)
        same = {key: bool(np.array_equal(
            np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32)))
            for key, a, b in zip(
                ("sampled", "chosen_lp", "top_lp", "top_ids"), old, new)}
        print(json.dumps({
            "shape": name, "rows": rows, "vocab": vocab,
            "device": device.device_kind,
            "old_us_a_step": _us_a_step(tail._reference_burst, args),
            "new_us_a_step": _us_a_step(tail._burst, args),
            "same_bits": same,
            "top_lp_max_abs_diff": float(np.max(np.abs(
                np.asarray(old[2]) - np.asarray(new[2])))),
        }), flush=True)
    rows, vocab = SHAPES[next(iter(SHAPES))]
    rng = np.random.default_rng(50)
    logits = rng.standard_normal((rows, vocab)).astype(np.float32)
    tied = []
    for r in range(rows):
        n = min(2 ** (1 + r % 12), vocab)
        at = rng.choice(vocab, size=n, replace=False)
        logits[r, at] = 9.0
        tied.append(int(at.min()))
    zeros = jnp.zeros((rows,), jnp.float32)
    sampled, _, _, top_ids = sampling.sample_with_logprobs(
        jnp.asarray(logits), sampling.make_rng_keys(0, 0, jnp.arange(rows)),
        zeros, jnp.zeros((rows,), jnp.int32), zeros + 1.0)
    argmax = np.asarray(jnp.argmax(jnp.asarray(logits), axis=-1))
    print(json.dumps({
        "ties": "maximum tied 2 to 4,096 times a row",
        "device": device.device_kind,
        "rank0_is_argmax": bool(np.array_equal(np.asarray(sampled), argmax)),
        "argmax_is_lowest_id": bool(np.array_equal(argmax, tied)),
        "tied_ids_rise": bool((np.diff(np.asarray(top_ids)[:, :2]) > 0).all()),
    }), flush=True)


if __name__ == "__main__":
    main()
