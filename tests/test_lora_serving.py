"""LoRA serving correctness: the adapter a request names is the adapter
that shapes its tokens.

Two guarantees, each load-bearing for the multi-model plane:

1. **Offline-merge parity**: greedy output through a *served* adapter
   (``add_request(..., adapter_name=...)`` hitting the slot-scattered
   LoRA leaves) is token-identical to a second engine whose base weights
   were merged offline (``W' = W + scaling * A @ B``). This is the
   algebraic identity the LoRA path claims; float32 engines make the
   argmax stable enough to compare token-for-token.
2. **No silent base fallback**: a request naming an adapter that is not
   resident gets a clean 404 — at the engine's OpenAI server AND at the
   router's LoRA plane — never a quiet answer from the base model.
"""

import asyncio
import queue
import threading
import time

import aiohttp
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import get_model_config
from production_stack_tpu.models.llama import fuse_qkv

ADAPTER = "sql-expert"
RANK = 16  # must equal max_lora_rank: the slot scatter takes full-rank operands
ALPHA = 16.0


def _make_engine(**over) -> EngineCore:
    # float32 end to end: the served-vs-merged comparison is exact algebra,
    # and bf16 rounding would make greedy argmax ties platform luck.
    kwargs = dict(
        model="tiny-llama",
        max_model_len=128,
        max_num_seqs=4,
        block_size=4,
        num_blocks=96,
        min_prefill_bucket=16,
        max_loras=4,
        max_lora_rank=RANK,
        dtype="float32",
    )
    kwargs.update(over)
    eng = EngineCore(EngineConfig(**kwargs), devices=jax.devices()[:1])
    eng.start()
    return eng


def _collect(engine, prompt, sampling, rid, adapter_name=None, timeout=120):
    q: "queue.Queue" = queue.Queue()

    def on_token(token, finish):
        q.put((token, finish))

    engine.add_request(rid, prompt, sampling, on_token,
                      adapter_name=adapter_name)
    tokens = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            token, finish = q.get(timeout=5)
        except queue.Empty:
            continue
        if token is not None:
            tokens.append(token)
        if finish is not None:
            return tokens, finish
    raise TimeoutError("generation did not finish")


def _adapter_weights():
    """Seeded full-rank adapter deltas for tiny-llama's q/v projections."""
    cfg = get_model_config("tiny-llama")
    L, Hd = cfg.num_layers, cfg.hidden_size
    q_out = cfg.num_heads * cfg.head_dim
    v_out = cfg.num_kv_heads * cfg.head_dim
    rng = np.random.default_rng(7)

    def w(*shape):
        # Big enough that the q/v delta is O(base projection): the test
        # needs the adapter to actually flip greedy tokens.
        return (0.15 * rng.standard_normal(shape)).astype(np.float32)

    return {
        "wq_a": w(L, Hd, RANK), "wq_b": w(L, RANK, q_out),
        "wv_a": w(L, Hd, RANK), "wv_b": w(L, RANK, v_out),
    }


def test_served_adapter_matches_offline_merged_weights():
    prompt = [1, 2, 3, 4, 5, 6, 7]
    greedy = SamplingParams(temperature=0.0, max_tokens=8)
    weights = _adapter_weights()

    eng = _make_engine()
    try:
        assert eng.load_lora_adapter(
            ADAPTER, rank=RANK, weights=weights, alpha=ALPHA)
        base, base_fin = _collect(eng, prompt, greedy, rid="base-1")
        served, served_fin = _collect(
            eng, prompt, greedy, rid="served-1", adapter_name=ADAPTER)
    finally:
        eng.stop()
    assert base_fin == "length" and served_fin == "length"
    # The adapter must be a real delta, or the parity below proves nothing.
    assert served != base

    # Second engine: same init (seeded by model name), base weights merged
    # offline with the identical adapter. No adapter named at request time.
    eng2 = _make_engine()
    try:
        scaling = ALPHA / RANK
        dq = scaling * np.einsum("lhr,lro->lho",
                                 weights["wq_a"], weights["wq_b"])
        dv = scaling * np.einsum("lhr,lro->lho",
                                 weights["wv_a"], weights["wv_b"])
        with eng2._lock:
            layers = dict(eng2.params["layers"])
            # The deltas' columns in the fused leaf's order: q and v
            # move, k (a zero delta) does not.
            dk = np.zeros_like(dv)
            layers["wqkv"] = layers["wqkv"] + jnp.asarray(
                fuse_qkv(dq, dk, dv, eng2.model_config.num_kv_heads),
                layers["wqkv"].dtype)
            eng2.params = {**eng2.params, "layers": layers}
        merged, merged_fin = _collect(eng2, prompt, greedy, rid="merged-1")
    finally:
        eng2.stop()
    assert merged_fin == "length"
    assert merged == served


@pytest.fixture(scope="module")
def engine_server_url():
    from production_stack_tpu.engine.server import (
        EngineServer,
        run_engine_server,
    )

    config = EngineConfig(
        model="tiny-llama", max_model_len=128, max_num_seqs=4,
        num_blocks=96, max_loras=4, max_lora_rank=8,
    )
    server = EngineServer(config)
    loop = asyncio.new_event_loop()
    holder = {}

    async def _boot():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        holder["runner"] = runner
        return f"http://127.0.0.1:{port}"

    started = threading.Event()

    def _run():
        asyncio.set_event_loop(loop)
        holder["url"] = loop.run_until_complete(_boot())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    started.wait(timeout=60)
    yield holder["url"]
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=5)
    server.core.stop()


def test_unknown_adapter_404_at_engine(engine_server_url):
    """The engine's OpenAI server rejects a non-resident adapter with 404
    on both chat and completions — it never answers from the base model."""
    async def run():
        async with aiohttp.ClientSession() as s:
            for path, payload in (
                ("/v1/chat/completions",
                 {"model": "ghost-adapter", "max_tokens": 2,
                  "messages": [{"role": "user", "content": "hi"}]}),
                ("/v1/completions",
                 {"model": "ghost-adapter", "max_tokens": 2,
                  "prompt": "hi"}),
            ):
                async with s.post(engine_server_url + path,
                                  json=payload) as resp:
                    assert resp.status == 404
                    body = await resp.json()
                    assert body["error"]["type"] == "NotFoundError"
            # Load it, and the same request is served — proving the 404
            # was residency, not a broken route.
            async with s.post(
                engine_server_url + "/v1/load_lora_adapter",
                json={"lora_name": "ghost-adapter"},
            ) as resp:
                assert resp.status == 200
            async with s.post(
                engine_server_url + "/v1/chat/completions",
                json={"model": "ghost-adapter", "max_tokens": 2,
                      "messages": [{"role": "user", "content": "hi"}]},
            ) as resp:
                assert resp.status == 200
                body = await resp.json()
                assert body["model"] == "ghost-adapter"
    asyncio.run(run())


def test_unknown_adapter_404_at_router():
    """With the LoRA plane on, the router 404s an adapter nobody serves
    *before* forwarding — the backend never sees the request."""
    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.router.parser import build_parser
    from production_stack_tpu.testing.fake_engine import (
        FakeEngine,
        run_fake_engine,
    )
    from production_stack_tpu.testing.fleet_ab import _start
    from production_stack_tpu.testing.qos_ab import _reset_router_singletons

    async def run():
        _reset_router_singletons()
        eng = FakeEngine(model="lora-base", max_loras=3)
        runner = await run_fake_engine(eng, "127.0.0.1", 0)
        args = build_parser().parse_args([])
        args.static_backends = eng.self_url
        args.static_models = "lora-base"
        args.engine_stats_interval = 60
        args.lora_plane = True
        router_runner, url = await _start(build_app(args))
        try:
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    url + "/v1/chat/completions",
                    json={"model": "ghost-adapter", "max_tokens": 2,
                          "messages": [{"role": "user", "content": "hi"}]},
                ) as resp:
                    assert resp.status == 404
                    body = await resp.json()
                    assert "ghost-adapter" in str(body)
            assert not eng.requests_seen  # no silent base fallback
        finally:
            await router_runner.cleanup()
            await runner.cleanup()
            _reset_router_singletons()
    asyncio.run(run())
