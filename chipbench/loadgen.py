"""The load generator: a process of its own that never imports JAX.

    python -m chipbench.loadgen <plan.json> <result.json>

``plan.json``: {"url", "model", "start_unix", "window": [start, end],
"max_outstanding", "stop_at" (seconds after start at which requests
still running are dropped; null: every request runs to its end),
"requests": [{id, due, prompt, max_tokens}]}. Each request is a streamed
``/v1/completions`` call to the router with the prompt as token ids,
``max_tokens`` the scheduled length and ``ignore_eos``, so the served
work is the scheduled work. It is sent at ``start_unix + due`` (open
loop) or, with ``max_outstanding``, as soon as a place is free. The
result holds one record per request (see ``timeline``), on this
process's clock relative to ``start_unix``.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

import aiohttp


async def _one(session, plan, req, t0, records):
    rec = {"id": req["id"], "due": req["due"], "sent": None, "chunks": [],
           "end": None, "ok": False, "out_tokens": req["max_tokens"],
           "finish": None, "error": None}
    records.append(rec)
    body = {"model": plan["model"], "prompt": req["prompt"],
            "max_tokens": req["max_tokens"], "ignore_eos": True,
            "temperature": 1.0, "seed": req.get("sampling_seed", 0),
            "stream": True}
    rec["sent"] = time.time() - t0
    try:
        async with session.post(plan["url"] + "/v1/completions", json=body,
                                headers={"X-Request-Id": req["id"]}) as resp:
            if resp.status != 200:
                rec["error"] = f"http {resp.status}"
                return
            async for raw in resp.content:
                if not raw.startswith(b"data: "):
                    continue
                now = time.time() - t0
                if raw.startswith(b"data: [DONE]"):
                    break
                choice = json.loads(raw[6:])["choices"][0]
                # the byte tokenizer renders a token as one character
                if choice.get("text"):
                    rec["chunks"].append([now, len(choice["text"])])
                if choice.get("finish_reason"):
                    rec["finish"] = choice["finish_reason"]
        rec["end"] = time.time() - t0
        rec["ok"] = rec["finish"] == "length" and bool(rec["chunks"])
    except asyncio.CancelledError:
        rec["error"] = "dropped at stop_at"
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"


async def run(plan: dict) -> dict:
    t0 = plan["start_unix"]
    records, tasks = [], set()
    cap = plan.get("max_outstanding") or 0
    slots = asyncio.Semaphore(cap) if cap else None
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None, sock_read=300)

    async def guarded(req):
        try:
            await _one(session, plan, req, t0, records)
        finally:
            if slots is not None:
                slots.release()

    async def feed():
        for req in plan["requests"]:
            delay = t0 + req["due"] - time.time()
            if delay > 0:
                await asyncio.sleep(delay)
            if slots is not None:
                await slots.acquire()
            task = asyncio.ensure_future(guarded(req))
            tasks.add(task)
            task.add_done_callback(tasks.discard)

    async with aiohttp.ClientSession(connector=conn,
                                     timeout=timeout) as session:
        feeder = asyncio.ensure_future(feed())
        stop_at = plan.get("stop_at")
        if stop_at is None:
            await feeder
        else:
            await asyncio.sleep(max(0.0, t0 + stop_at - time.time()))
            feeder.cancel()
        pending = list(tasks)
        if stop_at is not None:
            for task in pending:
                task.cancel()
        await asyncio.gather(feeder, *pending, return_exceptions=True)
    return {"records": records, "generator_modules_jax":
            any(m == "jax" or m.startswith("jax.") for m in sys.modules)}


def main(argv=None) -> None:
    plan_path, result_path = (argv or sys.argv[1:])[:2]
    with open(plan_path) as f:
        plan = json.load(f)
    result = asyncio.run(run(plan))
    with open(result_path, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
