"""Chip smoke: the main path, once, on the attached TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # only the four-chip path (tp=4 vs tp=1)

Serves ``tpu-llama-3b`` (Llama-3.2-3B widths and depth, random weights from
the server's ``--seed``) through the entry points a user calls — the engine
server built from its own flags (``--max-model-len 8192 --prefill-batch 4``,
every other one its default), the router in front with static discovery,
requests over HTTP to the router — in this one process, the only one that
touches JAX. It checks what comes out against the repo's own
references and prints one JSON object per phase; the last line is
``{"ok": true, "device": {...}}``. Any phase that fails raises: the exit
code is then non-zero and no result line is printed. There is no CPU leg.

What is printed besides the verdicts are counts and set-up times, for the
record. None of them is a speed.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import os
import random
import re
import time

# Two greedy runs of the same prompt through differently shaped programs
# (uncached vs cached prefill, tp=1 vs tp=4) agree until a near-tie under
# random weights flips an argmax. Up to there every token's logprob must
# agree within this many nats; at the flip the two chosen tokens' logprobs
# must also be this close (that is what makes it a near-tie).
LOGPROB_TOL = 0.15
# Max abs error of a Pallas kernel against its XLA reference on the same
# inputs: bf16 outputs of O(1) attention averages, f32 accumulation on
# both sides (the reference rounds its probabilities to bf16 first).
KERNEL_TOL = 3e-2


@dataclasses.dataclass
class Plan:
    """What one run drives. ``main`` uses the defaults; the CPU rehearsal
    in tests/test_chip_smoke.py steers a tiny model through the same code."""
    model: str = "tpu-llama-3b"
    platform: str = "tpu"
    # The server's flag default is max_model_len 2048; the model's full
    # context is asked for, and the prefill group size stated.
    engine_flags: tuple = ("--max-model-len", "8192", "--prefill-batch", "4")
    long_prompt_tokens: int = 1500
    # Four of these wait together: one rung with a group of four
    # (EngineConfig.prefill_group_rows), so one [4, 512] plain prefill.
    batch_prompt_tokens: int = 500
    max_tokens: int = 32
    interpret: bool = False  # Pallas kernels in interpret mode
    kernel_path: str = "pallas"  # the path the engine must have taken
    # --chips 4: the most of the parameters one of four devices may hold.
    # The input embedding is replicated (parallel/sharding.py), so it is
    # that table plus a quarter of the rest: a third at these widths.
    max_param_share: float = 0.4


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def make_prompt(n_tokens: int, tag: str) -> str:
    """``n_tokens`` byte-tokenizer tokens of incompressible ASCII."""
    rng = random.Random(tag)
    return "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ")
                   for _ in range(n_tokens))


# --------------------------------------------------------------------------
# Phase: each Pallas kernel against its XLA reference, on the device
# --------------------------------------------------------------------------

def kernel_parity(plan: Plan) -> None:
    """Both kernels, over bf16 pages (the default path) and int8 pages
    (``--kv-cache-dtype int8``), at the model's real page shape; for a
    model whose pages the dispatchers send to the reference (the
    rehearsal's tiny one), at the narrowest page they send to the kernels."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.engine.server import build_arg_parser
    from production_stack_tpu.models import get_model_config
    from production_stack_tpu.ops.attention import (
        _context_prefill_reference,
        _page_tile_ok,
        paged_attention_reference,
        quantize_kv,
    )
    from production_stack_tpu.ops.pallas_paged_attention import (
        pallas_paged_attention,
    )
    from production_stack_tpu.ops.pallas_prefill_attention import (
        pallas_prefill_attention,
    )

    mc = get_model_config(plan.model)
    bs = build_arg_parser().parse_args(
        [plan.model, *plan.engine_flags]).block_size
    H, KVH, D = mc.num_heads, mc.num_kv_heads, mc.head_dim
    if not _page_tile_ok(bs, KVH, D):
        H, KVH, D = 8 * (H // KVH), 8, 128  # same query group
    L, layer, scale = 2, jnp.int32(1), D ** -0.5
    B, MAXB, T = 4, 16, 4 * bs
    S, NB = MAXB * bs, 4 * 16 + 3
    keys = jax.random.split(jax.random.key(0), 6)
    tables = jax.random.permutation(keys[0], NB)[:B * MAXB].reshape(
        B, MAXB).astype(jnp.int32)
    q = jax.random.normal(keys[5], (B, H, D), jnp.bfloat16)
    qp = jax.random.normal(keys[5], (B, T, H, D), jnp.bfloat16)
    # decode: one query per sequence, ragged context lengths
    ctx_lens = jnp.asarray([S, S // 2 + 3, bs - 1, 1], jnp.int32)
    # cached prefill: a chunk of T fresh tokens behind ragged prefixes
    # (row 0 has none), the chunk's K/V already scattered to the pages
    prefix = jnp.asarray([0, S - T, 5 * bs + 7, bs], jnp.int32)
    take = jnp.asarray([T, T, T - 9, 3], jnp.int32)
    positions = prefix[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    live = (jnp.arange(T)[None, :] < take[:, None])[:, :, None, None]

    def max_err(got, ref, where=True):
        return float(jnp.max(jnp.abs(jnp.where(
            where, got.astype(jnp.float32) - ref.astype(jnp.float32), 0.0))))

    errors = {}
    for dtype in ("bf16", "int8"):
        def side(ctx_key, pool_key):
            """(pages holding a [B, S] context at ``tables``, the context
            as the pages encode it)."""
            ctx = jax.random.normal(ctx_key, (B, S, KVH, D), jnp.bfloat16)
            paged = (B, MAXB, bs, KVH, D)
            if dtype == "bf16":
                pool = jax.random.normal(pool_key, (L, NB) + paged[2:],
                                         jnp.bfloat16)
                return pool.at[layer, tables].set(ctx.reshape(paged)), ctx
            data, scales = quantize_kv(ctx)
            pool = jnp.zeros((L, NB) + paged[2:], jnp.int8)
            pool_s = jnp.ones((L, NB, bs * KVH), jnp.float32)
            pages = (pool.at[layer, tables].set(data.reshape(paged)),
                     pool_s.at[layer, tables].set(
                         scales.reshape(B, MAXB, bs * KVH)))
            return pages, (data * scales[..., None]).astype(jnp.bfloat16)

        (k_pages, ctx_k), (v_pages, ctx_v) = (side(keys[1], keys[3]),
                                              side(keys[2], keys[4]))
        errors[f"decode_{dtype}"] = max_err(
            pallas_paged_attention(
                q, k_pages, v_pages, tables, ctx_lens, layer, scale=scale,
                interpret=plan.interpret),
            paged_attention_reference(
                q, k_pages, v_pages, tables, ctx_lens, layer, scale=scale))
        k_new = jnp.take_along_axis(ctx_k, positions[:, :, None, None], 1)
        v_new = jnp.take_along_axis(ctx_v, positions[:, :, None, None], 1)
        errors[f"prefill_{dtype}"] = max_err(
            pallas_prefill_attention(
                qp, k_pages, v_pages, tables, positions, prefix + take,
                layer, k_new, v_new, take, scale=scale,
                interpret=plan.interpret),
            _context_prefill_reference(
                qp, k_pages, v_pages, tables, positions, prefix + take,
                layer, scale=scale),
            live)

    emit("kernel_parity", page_shape=[bs, KVH, D], heads=H,
         max_abs_err=errors, bound=KERNEL_TOL, interpret=plan.interpret)
    check(all(err <= KERNEL_TOL for err in errors.values()),
          f"kernel parity outside {KERNEL_TOL}: {errors}")


# --------------------------------------------------------------------------
# The stack: engine server from its own flags, router in front
# --------------------------------------------------------------------------

class Stack:
    """One engine (built as ``engine/server.py::main`` builds it) behind
    one router, both on loopback ports in this process."""

    def __init__(self, plan: Plan, extra_flags=(), devices=None):
        from production_stack_tpu.engine.server import (
            build_arg_parser,
            engine_server_from_args,
        )

        self.plan = plan
        self.args = build_arg_parser().parse_args(
            [plan.model, *plan.engine_flags, *extra_flags])
        t0 = time.time()
        self.server = engine_server_from_args(self.args, devices=devices)
        self.start_seconds = time.time() - t0
        self.core = self.server.core
        self._runners = []

    async def start(self) -> None:
        from aiohttp import web

        from production_stack_tpu.engine.server import run_engine_server
        from production_stack_tpu.router.app import build_app
        from production_stack_tpu.router.parser import build_parser

        runner = await run_engine_server(self.server, "127.0.0.1", 0)
        self._runners.append(runner)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        self.engine_url = f"http://127.0.0.1:{port}"
        rargs = build_parser().parse_args([])
        rargs.service_discovery = "static"
        rargs.static_backends = self.engine_url
        rargs.static_models = self.plan.model
        rargs.routing_logic = "roundrobin"
        router = web.AppRunner(build_app(rargs))
        await router.setup()
        site = web.TCPSite(router, "127.0.0.1", 0)
        await site.start()
        self._runners.append(router)
        self.url = "http://127.0.0.1:%d" % (
            site._server.sockets[0].getsockname()[1])

    async def stop(self) -> None:
        for runner in reversed(self._runners):
            await runner.cleanup()
        self.core.stop()

    def free_device_memory(self) -> None:
        """Give the engine's parameters and pages back to the device (a
        second engine in this process is sized from what is free)."""
        import jax

        for leaf in jax.tree_util.tree_leaves((self.core.params,
                                               self.core.kv)):
            leaf.delete()
        self.core.params = self.core.kv = None
        gc.collect()

    async def complete(self, session, prompt: str, **extra) -> dict:
        body = {"model": self.plan.model, "prompt": prompt,
                "max_tokens": self.plan.max_tokens, "temperature": 0.0,
                "logprobs": 1, **extra}
        async with session.post(self.url + "/v1/completions",
                                json=body) as resp:
            text = await resp.text()
            check(resp.status == 200, f"/v1/completions {resp.status}: "
                                      f"{text[:300]}")
        out = json.loads(text)
        choice, usage = out["choices"][0], out["usage"]
        check(bool(choice["text"]), "empty completion")
        check(abs(usage["prompt_tokens"] - len(prompt)) <= 2
              and 1 <= usage["completion_tokens"] <= self.plan.max_tokens
              and usage["total_tokens"] == usage["prompt_tokens"]
              + usage["completion_tokens"],
              f"usage {usage} does not fit a {len(prompt)}-token prompt")
        return {"tokens": choice["logprobs"]["tokens"],
                "logprobs": choice["logprobs"]["token_logprobs"],
                "usage": usage}


def compare_greedy(a: dict, b: dict, what: str) -> dict:
    """Hold two greedy runs of one prompt to LOGPROB_TOL (see there)."""
    n = min(len(a["tokens"]), len(b["tokens"]))
    same = next((i for i in range(n) if a["tokens"][i] != b["tokens"][i]), n)
    upto = min(same + 1, n)  # the flipped position is compared too
    diffs = [abs(a["logprobs"][i] - b["logprobs"][i]) for i in range(upto)]
    out = {"tokens_compared": n, "equal_prefix": same,
           "first_token_logprob_diff": diffs[0],
           "max_logprob_diff": max(diffs), "tolerance": LOGPROB_TOL}
    check(max(diffs) <= LOGPROB_TOL,
          f"{what}: logprobs differ by {max(diffs)} > {LOGPROB_TOL} "
          f"within the first {upto} tokens")
    return out


async def drive_requests(stack: Stack) -> None:
    import aiohttp

    plan = stack.plan
    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        async with session.get(stack.url + "/health") as resp:
            check(resp.status == 200, f"/health {resp.status}")
        async with session.get(stack.url + "/v1/models") as resp:
            check(resp.status == 200, f"/v1/models {resp.status}")
            served = [m["id"] for m in (await resp.json())["data"]]
            check(plan.model in served, f"{plan.model} not in {served}")
        emit("probes", health=200, models=served)

        prompt = make_prompt(plan.long_prompt_tokens, "long")
        first = await stack.complete(session, prompt)
        hits0 = stack.core.stats()["prefix_cache_hits"]
        again = await stack.complete(session, prompt)
        hits1 = stack.core.stats()["prefix_cache_hits"]
        emit("repeat_request", prompt_tokens=first["usage"]["prompt_tokens"],
             prefix_hits_by_repeat=hits1 - hits0,
             **compare_greedy(first, again, "repeated request"))
        check(hits1 > hits0, "the repeated request hit no cached prefix")

        body = {"model": plan.model, "stream": True, "temperature": 0.0,
                "max_tokens": plan.max_tokens,
                "messages": [{"role": "user",
                              "content": make_prompt(200, "chat")}]}
        pieces, done = [], False
        async with session.post(stack.url + "/v1/chat/completions",
                                json=body) as resp:
            check(resp.status == 200, f"chat stream {resp.status}")
            async for raw in resp.content:
                line = raw.decode().strip()
                if line == "data: [DONE]":
                    done = True
                elif line.startswith("data: "):
                    delta = json.loads(line[6:])["choices"][0]["delta"]
                    pieces.append(delta.get("content") or "")
        check(done and "".join(pieces) != "", "empty or unfinished stream")
        emit("chat_stream", chunks=len(pieces), done=done)

        # Four uncached prompts of one rung, held back until all of them
        # wait (the loop takes the first and stops at the step): they run
        # as one group. Each is then sent again alone, which reads the
        # pages the group wrote through the cached program.
        prompts = [make_prompt(plan.batch_prompt_tokens, f"b{i}")
                   for i in range(4)]
        core = stack.core
        rows0 = core.stats()["prefill_group_rows"]
        with core._step_lock:
            tasks = [asyncio.ensure_future(stack.complete(session, p))
                     for p in prompts]
            while core.scheduler.num_waiting < len(prompts) - 1:
                await asyncio.sleep(0.01)
        outs = await asyncio.gather(*tasks)
        rows = core.stats()["prefill_group_rows"] - rows0
        alone = [compare_greedy(out, await stack.complete(session, p),
                                "a group's row against its repeat")
                 for out, p in zip(outs, prompts)]
        emit("concurrent", requests=len(outs), prefill_group_rows=rows,
             completion_tokens=[o["usage"]["completion_tokens"]
                                for o in outs],
             equal_prefix=[c["equal_prefix"] for c in alone],
             max_logprob_diff=max(c["max_logprob_diff"] for c in alone))
        check(rows == len(prompts),
              f"{rows} prompts prefilled in a group, not {len(prompts)}")


async def engine_counters(stack: Stack, n_generations: int) -> None:
    """Proof of the path taken, read from the engine after the requests."""
    import aiohttp

    from production_stack_tpu.ops.attention import TRACED_PATHS

    async with aiohttp.ClientSession() as session:
        async with session.get(stack.engine_url + "/metrics") as resp:
            metrics = await resp.text()
    dispatch = {
        path: int(float(re.search(
            r'tpu:prefill_attention_dispatch_total\{[^}]*path="%s"[^}]*\} '
            r'(\S+)' % path, metrics).group(1)))
        for path in ("pallas", "xla")}
    s = stack.core.stats()
    other = "xla" if stack.plan.kernel_path == "pallas" else "pallas"
    emit("engine_counters",
         prefill_attention_dispatch_total=dispatch,
         traced_attention_paths={f"{op}:{path}": n for (op, path), n
                                 in sorted(TRACED_PATHS.items())},
         decode_forward_steps=s["decode_forward_steps_total"],
         prefix_cache_hits=s["prefix_cache_hits"],
         preemptions=s["num_preempted_total"],
         rejected=s["rejected_requests"],
         requests_finished=s["requests_finished_total"],
         prefill_group_rows=s["prefill_group_rows"])
    check(dispatch[stack.plan.kernel_path] > 0 and dispatch[other] == 0,
          f"cached prefill dispatches {dispatch}: expected only "
          f"{stack.plan.kernel_path}")
    check(not any(path == other for _, path in TRACED_PATHS),
          f"a program was traced onto the {other} path: "
          f"{dict(TRACED_PATHS)}")
    check(s["decode_forward_steps_total"] > 0, "no decode step ran")
    check(s["prefix_cache_hits"] > 0, "no prefix-cache hit")
    check(s["num_preempted_total"] == 0
          and not any(s["rejected_requests"].values()),
          "requests were preempted or rejected")
    check(s["requests_finished_total"] == n_generations,
          f"{s['requests_finished_total']} requests finished, "
          f"{n_generations} were sent")


def emit_engine_start(stack: Stack, cache_dir: str, cache_cold: bool) -> None:
    core = stack.core
    emit("engine_start", model=stack.plan.model,
         tensor_parallel_size=core.mesh.shape["tp"],
         start_seconds=round(stack.start_seconds, 1),
         warmup_seconds=round(core.warmup_seconds, 1),
         warmup_variants=core.warmup_variants,
         num_blocks=core.num_blocks,
         free_bytes_before_pool=core.free_hbm_before_pool,
         kv_cache_dtype=core.config.kv_cache_dtype,
         prefill_batch=core.config.prefill_batch,
         max_model_len=core.config.max_model_len,
         compile_cache_dir=cache_dir, compile_cache_cold=cache_cold)
    check(stack.args.warmup and sum(core.warmup_variants.values()) > 0,
          "the engine did not warm up")


async def one_chip(plan: Plan, cache_dir: str, cache_cold: bool) -> None:
    import jax

    kernel_parity(plan)
    stack = Stack(plan)
    try:
        await stack.start()
        emit_engine_start(stack, cache_dir, cache_cold)
        await drive_requests(stack)
        await engine_counters(stack, n_generations=11)
        stats = jax.devices()[0].memory_stats() or {}
        emit("device_memory",
             peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             bytes_limit=stats.get("bytes_limit"))
        check(plan.platform != "tpu" or stats.get("peak_bytes_in_use"),
              "the device reported no peak_bytes_in_use")
    finally:
        await stack.stop()


# --------------------------------------------------------------------------
# --chips 4: tensor parallelism over four devices against one device
# --------------------------------------------------------------------------

def shard_bytes(tree, devices) -> dict:
    """Bytes of ``tree`` resident on each device, beside its logical size
    (every array counted once, however it is sharded or replicated)."""
    import jax

    per_device = {d: 0 for d in devices}
    logical = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        logical += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device] += shard.data.nbytes
    return {"logical_bytes": logical,
            "per_device": [per_device[d] for d in devices]}


def decode_collectives(core) -> dict:
    """Collectives in the compiled decode step (narrowest table bucket)."""
    K = max(core.config.decode_steps, 1)
    maxb = min(4, core.config.max_blocks_per_seq)
    text = core._multi_decode_fn(K).lower(
        core.params, core.kv, core._token_counts,
        *core.decode_warmup_args(K, maxb)).compile().as_text()
    found = re.findall(
        r"= \S+ (all-reduce|all-gather|all-to-all|reduce-scatter|"
        r"collective-permute)(?:-start)?\(", text)
    return {name: found.count(name) for name in sorted(set(found))}


async def four_chips(plan: Plan, cache_dir: str, cache_cold: bool) -> None:
    import aiohttp
    import jax

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 needs four devices, found "
                             f"{len(jax.devices())}")
    prompts = [make_prompt(plan.long_prompt_tokens, "long"),
               make_prompt(plan.batch_prompt_tokens, "b0")]
    runs = {}
    for tp, devs in ((4, devices), (1, devices[:1])):
        stack = Stack(plan, ("--tensor-parallel-size", str(tp)), devs)
        try:
            await stack.start()
            emit_engine_start(stack, cache_dir, cache_cold)
            params = shard_bytes(stack.core.params, devs)
            pages = shard_bytes(stack.core.kv, devs)
            emit("resident_bytes", tensor_parallel_size=tp,
                 parameters=params, kv_pages=pages,
                 decode_step_collectives=decode_collectives(stack.core))
            if tp == 4:
                # About a quarter each, none holds all; the pages split
                # exactly.
                for name, held, most in (
                        ("parameter", params, plan.max_param_share),
                        ("KV page", pages, 0.26)):
                    share = [b / held["logical_bytes"]
                             for b in held["per_device"]]
                    check(all(0.2 <= x <= most for x in share),
                          f"{name} bytes per device are not about a "
                          f"quarter of {held['logical_bytes']}: "
                          f"{held['per_device']}")
            timeout = aiohttp.ClientTimeout(total=600)
            async with aiohttp.ClientSession(timeout=timeout) as session:
                runs[tp] = [await stack.complete(session, p)
                            for p in prompts]
        finally:
            await stack.stop()
            stack.free_device_memory()
    for i, (a, b) in enumerate(zip(runs[4], runs[1])):
        emit("tp4_vs_tp1", prompt=i,
             **compare_greedy(a, b, f"tp=4 against tp=1, prompt {i}"))


# --------------------------------------------------------------------------

def run(plan: Plan, chips: int) -> dict:
    """Run the plan on the attached device; returns the result object."""
    import jax

    from production_stack_tpu.ops.attention import TRACED_PATHS
    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    # The counter is the process's: ``engine_counters`` judges this run's
    # programs, not what something else in the process traced before it.
    TRACED_PATHS.clear()
    check(not os.environ.get("TPU_STACK_FORCE_XLA_ATTENTION"),
          "TPU_STACK_FORCE_XLA_ATTENTION is set: the kernels would be "
          "bypassed")
    device = jax.devices()[0]
    check(device.platform == plan.platform,
          f"needs a {plan.platform}, JAX found {device.platform}")
    cache_dir = configure_compile_cache()
    cache_cold = not (os.path.isdir(cache_dir) and os.listdir(cache_dir))
    asyncio.run((four_chips if chips == 4 else one_chip)(
        plan, cache_dir, cache_cold))
    return {"ok": True,
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "count": len(jax.devices())}}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    result = run(Plan(), args.chips)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
