#!/usr/bin/env python3
"""The plain-prefill step program alone on the chip at ``[R, rung]``
(PR 35, step 0): what a same-rung group of R uncached prompts costs
against R batch-1 calls, for one of the benchmark's configurations.

The engine is built from the configuration's own server flags without
warm-up; each case runs ``jit_prefill`` (``EngineCore._prefill_fn``) on R
rows of real lengths inside the rung (distinct pages, so the page writes
and the expert layer's ``valid`` mask see real rows), CALLS times after a
warm call, and prints ms a call and ms a prompt. A group's case also runs
its prompts one at a time onto other pages and holds the group to them:
each row's greedy token and its logprob, and the KV pages it wrote, layer
by layer (exit code 1 where a row is not its single's within the
tolerances below).

    chiprun -- python benchmarks/prefill_group_step0.py laguna-s-2.1-l8e64
    python benchmarks/prefill_group_step0.py tiny-laguna   # rehearsal, CPU
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))

import numpy as np  # noqa: E402

CALLS = 8
CASES = [(1, 384), (2, 384), (4, 384), (1, 512), (2, 512), (4, 512),
         (1, 768), (2, 768), (1, 1024), (2, 1024)]
# A row of a group against the same prompt alone: the same mathematics in
# a differently shaped program, so bf16 rounding apart. The logprob of the
# greedy token as chip_smoke.LOGPROB_TOL has it (a near-tie may flip the
# token itself). The pages of the first layer, which no routing decision
# precedes, by their relative RMS; deeper layers are reported and not
# held, because a router's near-tie sends a token to another expert in one
# program and not the other (the share of positions more than a tenth
# apart says how many).
LOGPROB_TOL = 0.15
FIRST_LAYER_TOL = 1e-2
APART = 0.1


def prefill_args(core, prompts: list, rung: int, first_block: int) -> list:
    """Host operands of one plain prefill over ``prompts`` (token arrays
    of the rung), each on pages of its own from ``first_block`` on."""
    from production_stack_tpu.engine.core import (
        MAX_LOGIT_BIAS,
        MAX_STOP_IDS,
    )

    rows = len(prompts)
    bs = core.config.block_size
    blocks = -(-rung // bs)
    table = core._table_width(rung)
    tokens = np.zeros((rows, rung), np.int32)
    slots = np.full((rows, rung), -1, np.int64)
    block_table = np.zeros((rows, table), np.int32)
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for i, prompt in enumerate(prompts):
        n = len(prompt)
        tokens[i, :n] = prompt
        ids = first_block + i * blocks + np.arange(blocks)
        block_table[i, :blocks] = ids
        pos = np.arange(n)
        slots[i, :n] = ids[pos // bs] * bs + pos % bs
    positions = np.tile(np.arange(rung, dtype=np.int32), (rows, 1))
    return [
        tokens, positions, slots, block_table, lens, lens.copy(),
        np.zeros((rows,), np.int32),
        np.zeros((rows,), np.float32), np.zeros((rows,), np.int32),
        np.ones((rows,), np.float32), np.zeros((rows,), np.int64),
        lens.astype(np.int64), np.zeros((rows,), bool),
        np.zeros((rows, MAX_LOGIT_BIAS), np.int32),
        np.zeros((rows, MAX_LOGIT_BIAS), np.float32),
        np.zeros((rows, MAX_STOP_IDS), np.int32),
        np.zeros((rows, MAX_STOP_IDS), np.float32),
        np.zeros((rows, core._mask_row_bytes), np.uint8),
        np.zeros((rows,), bool),
    ]


def main() -> None:
    import jax

    from chipbench.registry import Registry
    from chipbench.stack import write_model_dir
    from production_stack_tpu.engine.server import (
        build_arg_parser,
        engine_server_from_args,
    )

    name = sys.argv[1]
    tiny = name.startswith("tiny-")  # a CPU rehearsal of this script
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not tiny:
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    # These programs are not the cells': keep them out of the machine's
    # compile cache, which two configurations' programs already overflow.
    jax.config.update("jax_enable_compilation_cache", False)
    if tiny:
        flags = ["--max-model-len", "2048", "--num-blocks", "512"]
        model = name
    else:
        config = Registry().config(name)
        model = write_model_dir(config, tempfile.mkdtemp(), name)
        flags = config["server_flags"]
    args = build_arg_parser().parse_args(
        [model, "--served-model-name", name, *flags, "--no-warmup"])
    core = engine_server_from_args(args).core
    rng = np.random.default_rng(0)
    vocab = core.model_config.vocab_size
    bs = core.config.block_size
    single, ok = {}, True

    def call(operands):
        out, core.kv = core._prefill_fn(core.params, core.kv, *operands)
        return out

    def pages(first_block: int, prompts: list, rung: int) -> list:
        """What the prompts' prefill wrote: per prompt, every KV leaf's
        rows of its tokens, float32 on the host."""
        blocks = -(-rung // bs)
        got = []
        for i, prompt in enumerate(prompts):
            ids = first_block + i * blocks + np.arange(blocks)
            got.append([
                np.asarray(leaf[:, ids], np.float32).reshape(
                    leaf.shape[0], blocks * bs, -1)[:, :len(prompt)]
                for leaf in jax.tree_util.tree_leaves(core.kv)
                if leaf.ndim == 5])
        return got

    try:
        for rows, rung in CASES:
            prompts = [rng.integers(1, vocab, rung - int(
                rng.integers(0, min(128, rung // 2)))) for _ in range(rows)]
            operands = prefill_args(core, prompts, rung, 1)
            out = jax.block_until_ready(call(operands))
            t0 = time.perf_counter()
            for _ in range(CALLS):
                last = call(operands)
            jax.block_until_ready(last)
            ms = (time.perf_counter() - t0) / CALLS * 1e3
            if rows == 1:
                single[rung] = ms
            line = {
                "config": name, "device": dev.device_kind, "rows": rows,
                "rung": rung, "ms_per_call": round(ms, 3),
                "ms_per_prompt": round(ms / rows, 3),
                "over_singles": round(ms / (rows * single[rung]), 4),
            }
            if rows > 1:
                tokens, logprobs = (np.asarray(a) for a in out[:2])
                ours = pages(1, prompts, rung)
                alone = 1 + rows * -(-rung // bs)
                same, lp_diff = 0, 0.0
                err = ref = apart = 0.0  # by layer, over rows and leaves
                for i, prompt in enumerate(prompts):
                    one = call(prefill_args(core, [prompt], rung, alone))
                    same += int(np.asarray(one[0])[0] == tokens[i])
                    lp_diff = max(lp_diff, abs(float(
                        np.asarray(one[1])[0] - logprobs[i])))
                    for a, b in zip(ours[i], pages(alone, [prompt], rung)[0]):
                        d2, b2 = ((a - b) ** 2).sum(-1), (b ** 2).sum(-1)
                        err, ref = err + d2.sum(-1), ref + b2.sum(-1)
                        apart = apart + (d2 > APART ** 2 * b2).mean(-1) / (
                            rows * len(ours[i]))
                rms = np.sqrt(err / ref)  # nan: a layer nobody wrote
                line.update(
                    tokens_equal=same, logprob_diff=round(lp_diff, 5),
                    pages_rel_rms_by_layer=[round(float(x), 5) for x in rms],
                    positions_apart_by_layer=[
                        round(float(x), 4) for x in apart],
                    parity=bool(lp_diff <= LOGPROB_TOL
                                and rms[0] <= FIRST_LAYER_TOL))
                ok = ok and line["parity"]
            print(json.dumps(line), flush=True)
    finally:
        core.stop()
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
