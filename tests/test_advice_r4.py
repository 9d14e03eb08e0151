"""Regression tests for round-3 advisor findings: logprobs computed from
the shaped sampling distribution, logit_bias capacity rejection, device
pipe offer cap, per-core HBM table entries, and n>1 abort hygiene."""

import asyncio
import math

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import MAX_LOGIT_BIAS
from production_stack_tpu.engine.server import EngineServer, run_engine_server


def _server():
    return EngineServer(EngineConfig(
        model="tiny-llama", max_model_len=64, max_num_seqs=2,
        block_size=8, num_blocks=64, max_loras=0))


def test_logit_bias_over_capacity_rejected_and_logprobs_shaped():
    server = _server()

    async def run():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        import aiohttp

        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                # 1) logit_bias beyond the compiled capacity: explicit 400,
                #    not silent truncation (chat and completions).
                too_many = {str(i): 1.0 for i in range(MAX_LOGIT_BIAS + 1)}
                async with s.post(
                        f"{base}/v1/chat/completions",
                        json={"model": "tiny-llama",
                              "messages": [{"role": "user", "content": "x"}],
                              "max_tokens": 2,
                              "logit_bias": too_many}) as resp:
                    assert resp.status == 400
                    err = await resp.json()
                    assert "logit_bias" in err["error"]["message"]
                async with s.post(
                        f"{base}/v1/completions",
                        json={"model": "tiny-llama", "prompt": "abc",
                              "max_tokens": 2,
                              "logit_bias": too_many}) as resp:
                    assert resp.status == 400
                # At capacity: accepted.
                ok_bias = {str(i): 0.0 for i in range(MAX_LOGIT_BIAS)}
                async with s.post(
                        f"{base}/v1/completions",
                        json={"model": "tiny-llama", "prompt": "abc",
                              "max_tokens": 2, "ignore_eos": True,
                              "logit_bias": ok_bias}) as resp:
                    assert resp.status == 200, await resp.text()

                # 2) Logprobs reflect the shaped distribution: a +100 bias
                #    forces the token AND its reported logprob is ~0 (the
                #    raw distribution would report a huge negative value).
                forced = 61  # arbitrary valid byte-tokenizer id
                async with s.post(
                        f"{base}/v1/completions",
                        json={"model": "tiny-llama", "prompt": "abc",
                              "max_tokens": 3, "temperature": 0.0,
                              "ignore_eos": True, "logprobs": 2,
                              "logit_bias": {str(forced): 100.0}}) as resp:
                    assert resp.status == 200, await resp.text()
                    out = await resp.json()
                lp = out["choices"][0]["logprobs"]
                # Every sampled token is the forced one, reported at
                # probability ~1 under the biased distribution.
                for chosen_lp in lp["token_logprobs"]:
                    assert math.isclose(chosen_lp, 0.0, abs_tol=1e-3)
        finally:
            await runner.cleanup()

    asyncio.run(run())
    server.core.stop()


def test_n_oversize_prompt_400_aborts_choice0():
    """The n>1 oversize-prompt 400 must abort the already-enqueued
    choice-0 request instead of leaving it for async scheduler
    rejection."""
    server = _server()

    async def run():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        import aiohttp

        try:
            async with aiohttp.ClientSession() as s:
                async with s.post(
                        f"http://127.0.0.1:{port}/v1/completions",
                        json={"model": "tiny-llama",
                              "prompt": "x" * 500,  # > max_model_len=64
                              "max_tokens": 2, "n": 3}) as resp:
                    assert resp.status == 400
            # The choice-0 request was aborted synchronously with the 400.
            core = server.core
            assert not core.scheduler.waiting
            assert not core.scheduler.running()
        finally:
            await runner.cleanup()

    asyncio.run(run())
    server.core.stop()


def test_device_pipe_offer_cap():
    """offer() refuses once MAX_PENDING_OFFERS registrations are
    outstanding (await_pull cannot be cancelled, so expiry must not be
    treated as reclamation), and release() frees slots."""
    from production_stack_tpu.kv.device_pipe import KVDevicePipe

    class _StubServer:
        def __init__(self):
            self.registered = []

        def await_pull(self, uuid, arrays):
            self.registered.append(uuid)

        def address(self):
            return "127.0.0.1:0"

    import itertools
    import threading

    pipe = KVDevicePipe.__new__(KVDevicePipe)
    pipe._server = _StubServer()
    pipe._uuid = itertools.count(1)
    pipe._pending = {}
    pipe._registered = set()
    pipe._conns = {}
    pipe._lock = threading.Lock()

    uuids = [pipe.offer(["k", "v"]) for _ in range(KVDevicePipe.MAX_PENDING_OFFERS)]
    assert all(u is not None for u in uuids)
    assert pipe.offer(["k", "v"]) is None  # full

    # Bogus / duplicate release calls must NOT undercount pinned HBM.
    pipe.release(999999)  # never offered
    assert pipe.offer(["k", "v"]) is None

    pipe.release(uuids[0])
    fresh = pipe.offer(["k", "v"])
    assert fresh is not None  # slot freed
    pipe.release(uuids[0])  # duplicate of an already-freed uuid
    assert pipe.offer(["k", "v"]) is None  # still full

    # TTL pruning of the dict does NOT free registration slots: age out
    # every entry and the pipe must still refuse (pinned HBM is bounded by
    # registrations, not by our bookkeeping dict).
    with pipe._lock:
        pipe._pending = {u: (a, 0.0) for u, (a, _) in pipe._pending.items()}
    assert pipe.offer(["k", "v"]) is None

    # A failing await_pull rolls the slot back (no registration = no pin).
    pipe.release(fresh)

    class _Boom(_StubServer):
        def await_pull(self, uuid, arrays):
            raise RuntimeError("no transfer runtime")

    pipe._server = _Boom()
    try:
        pipe.offer(["k", "v"])
    except RuntimeError:
        pass
    pipe._server = _StubServer()
    assert pipe.offer(["k", "v"]) is not None  # slot was rolled back


def test_pool_is_sized_from_memory_stats_or_not_at_all():
    """The KV pool is sized from what the runtime says is free. A TPU
    that returns no memory_stats() is an error at start-up — no capacity
    table, no environment override, no workspace guess stands in for it;
    the CPU (no such figure) gets the minimal pool."""

    class _Dev:
        def __init__(self, platform, stats):
            self.platform, self._stats = platform, stats

        def memory_stats(self):
            return self._stats

    class _Core:
        _free_hbm_bytes = EngineCore._free_hbm_bytes

        def __init__(self, dev):
            self._local_device = lambda: dev

    assert _Core(_Dev("tpu", {"bytes_limit": 16, "bytes_in_use": 5})
                 )._free_hbm_bytes() == 11
    assert _Core(_Dev("cpu", None))._free_hbm_bytes() is None
    with pytest.raises(RuntimeError, match="memory_stats"):
        _Core(_Dev("tpu", None))._free_hbm_bytes()
    assert not hasattr(EngineCore, "_HBM_BY_KIND")
