"""Disaggregated-prefill KV handoff microbenchmark.

Two real engines in one process (prefill + decode) on the available
accelerator; a long prompt's prefix pages move across the /kv/pull path
and the end-to-end handoff rate is recorded — the measured counterpart of
the reference's NIXL-pipe transfer (helm deployment-vllm-multi.yaml:267-305).

Prints ONE JSON line:
  {"metric": "kv_handoff", "path": ..., "bytes": N, "seconds": s,
   "gigabytes_per_second": r, ...}

Env knobs: KVBENCH_MODEL (default tpu-llama-1b), KVBENCH_PROMPT_TOKENS
(default 8000), KVBENCH_PATH (auto|host|device).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys

# Runnable as a script from anywhere.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODEL = os.environ.get("KVBENCH_MODEL", "tpu-llama-1b")
PROMPT_TOKENS = int(os.environ.get("KVBENCH_PROMPT_TOKENS", 8000))
PATH = os.environ.get("KVBENCH_PATH", "auto")


async def _main() -> dict:
    import aiohttp

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.server import (
        EngineServer,
        run_engine_server,
    )

    cfg = EngineConfig(
        model=MODEL, max_model_len=PROMPT_TOKENS + 256, max_num_seqs=2,
        num_blocks=2 * (PROMPT_TOKENS // 64 + 8), max_loras=0,
    )
    prefill = EngineServer(cfg, warmup=False)
    decode = EngineServer(cfg, warmup=False)
    p_runner = await run_engine_server(prefill, "127.0.0.1", 0)
    d_runner = await run_engine_server(decode, "127.0.0.1", 0)
    p_port = list(p_runner.sites)[0]._server.sockets[0].getsockname()[1]
    d_port = list(d_runner.sites)[0]._server.sockets[0].getsockname()[1]

    # Two distinct prompts: the first pull pays one-time XLA compiles for
    # the move program; the second measures the steady-state handoff.
    prompts = [
        [(7 + 13 * i + 31 * r) % 30000 for i in range(PROMPT_TOKENS)]
        for r in (1, 2)
    ]
    bodies = []
    try:
        async with aiohttp.ClientSession() as s:
            for tokens in prompts:
                async with s.post(
                        f"http://127.0.0.1:{p_port}/v1/completions",
                        json={"prompt": tokens, "max_tokens": 2,
                              "temperature": 0.0},
                        timeout=aiohttp.ClientTimeout(total=900)) as resp:
                    assert resp.status == 200, await resp.text()
                async with s.post(
                        f"http://127.0.0.1:{d_port}/kv/pull",
                        json={"source_url": f"http://127.0.0.1:{p_port}",
                              "token_ids": tokens, "kv_path": PATH},
                        timeout=aiohttp.ClientTimeout(total=900)) as resp:
                    assert resp.status == 200, await resp.text()
                    bodies.append(await resp.json())
        body = bodies[-1]
    finally:
        await p_runner.cleanup()
        await d_runner.cleanup()
        prefill.core.stop()
        decode.core.stop()

    t = body["transfer"]
    t_cold = bodies[0]["transfer"]
    return {
        "metric": "kv_handoff",
        "model": MODEL,
        "prompt_tokens": PROMPT_TOKENS,
        "injected_blocks": body["injected_blocks"],
        "num_tokens": body["num_tokens"],
        "path": t["path"],
        "bytes": t["bytes"],
        "seconds": t["total_seconds"],
        "gigabytes_per_second": round(
            t["bytes"] / max(t["total_seconds"], 1e-9) / 1e9, 3),
        "cold_seconds": t_cold["total_seconds"],  # includes XLA compiles
    }


def main() -> None:
    import jax

    result = asyncio.run(_main())
    result["backend"] = jax.devices()[0].platform
    print(json.dumps(result))


if __name__ == "__main__":
    main()
