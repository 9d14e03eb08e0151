"""End-to-end tests for the engine's OpenAI HTTP server: real HTTP against a
real EngineCore (tiny model, CPU mesh). Mirrors what the reference gets from
vLLM's own API server, which its stack only configures
(helm/templates/deployment-vllm-multi.yaml:108-199)."""

import asyncio
import json

import aiohttp
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.server import EngineServer, run_engine_server


@pytest.fixture(scope="module")
def server_url():
    config = EngineConfig(
        model="tiny-llama", max_model_len=256, max_num_seqs=4,
        num_blocks=128, max_loras=4, max_lora_rank=8,
    )
    server = EngineServer(config)
    loop = asyncio.new_event_loop()
    holder = {}

    async def _boot():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        holder["runner"] = runner
        return f"http://127.0.0.1:{port}"

    import threading

    started = threading.Event()

    def _run():
        asyncio.set_event_loop(loop)
        holder["url"] = loop.run_until_complete(_boot())
        started.set()
        loop.run_forever()

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    started.wait(timeout=30)
    yield holder["url"]
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=5)
    server.core.stop()


async def _get(url, path):
    async with aiohttp.ClientSession() as s:
        async with s.get(url + path) as r:
            return r.status, await r.json()


async def _post(url, path, payload):
    async with aiohttp.ClientSession() as s:
        async with s.post(url + path, json=payload) as r:
            if r.content_type == "application/json":
                return r.status, await r.json()
            return r.status, await r.text()


def test_models_and_health(server_url):
    async def run():
        status, body = await _get(server_url, "/v1/models")
        assert status == 200
        assert body["data"][0]["id"] == "tiny-llama"
        status, body = await _get(server_url, "/health")
        assert status == 200
        status, body = await _get(server_url, "/version")
        assert status == 200 and "version" in body
    asyncio.run(run())


def test_completion_nonstream(server_url):
    async def run():
        status, body = await _post(server_url, "/v1/completions", {
            "model": "tiny-llama", "prompt": "hello world",
            "max_tokens": 8, "temperature": 0.0, "ignore_eos": True,
        })
        assert status == 200
        assert body["choices"][0]["finish_reason"] == "length"
        assert body["usage"]["completion_tokens"] == 8
    asyncio.run(run())


def test_chat_streaming_sse(server_url):
    async def run():
        async with aiohttp.ClientSession() as s:
            async with s.post(server_url + "/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 6, "stream": True, "temperature": 0.0,
                "ignore_eos": True,
            }) as r:
                assert r.status == 200
                assert r.content_type == "text/event-stream"
                chunks = []
                async for line in r.content:
                    line = line.decode().strip()
                    if not line.startswith("data: "):
                        continue
                    data = line[len("data: "):]
                    if data == "[DONE]":
                        break
                    chunks.append(json.loads(data))
        assert chunks, "no SSE chunks received"
        assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    asyncio.run(run())


def test_deterministic_greedy(server_url):
    async def run():
        outs = []
        for _ in range(2):
            _, body = await _post(server_url, "/v1/completions", {
                "model": "tiny-llama", "prompt": "determinism",
                "max_tokens": 8, "temperature": 0.0, "ignore_eos": True,
            })
            outs.append(body["choices"][0]["text"])
        assert outs[0] == outs[1]
    asyncio.run(run())


def test_tokenize_detokenize_roundtrip(server_url):
    async def run():
        status, body = await _post(server_url, "/tokenize",
                                   {"prompt": "round trip"})
        assert status == 200 and body["count"] == len(body["tokens"])
        status, body2 = await _post(server_url, "/detokenize",
                                    {"tokens": body["tokens"]})
        assert status == 200
        assert body2["prompt"] == "round trip"
    asyncio.run(run())


def test_embeddings(server_url):
    async def run():
        status, body = await _post(server_url, "/v1/embeddings", {
            "model": "tiny-llama", "input": ["a", "b"],
        })
        assert status == 200
        assert len(body["data"]) == 2
        assert len(body["data"][0]["embedding"]) > 0
    asyncio.run(run())


def test_metrics_exposition(server_url):
    async def run():
        async with aiohttp.ClientSession() as s:
            async with s.get(server_url + "/metrics") as r:
                assert r.status == 200
                text = await r.text()
        assert "vllm:num_requests_running" in text
        assert "vllm:num_requests_waiting" in text
        assert "vllm:gpu_cache_usage_perc" in text
        assert "tpu:hbm_kv_usage_perc" in text
        assert "vllm:generation_tokens_total" in text
        # Flag-off exposition parity: the fused/dispatch-path series
        # export (at zero / with both label values) without --fused-step.
        assert "tpu:fused_steps_total" in text
        assert "tpu:prefill_attention_dispatch_total{" in text
        assert "tpu:expert_matmul_dispatch_total{" in text
        assert 'path="pallas"}' in text
        assert 'path="pallas_one_tile"}' in text
        assert "tpu:moe_idle_layers_total{" in text
        assert 'path="xla"}' in text
        assert 'tpu:first_token_feed_total{' in text
        assert 'path="device"}' in text and 'path="host"}' in text
    asyncio.run(run())


def test_unknown_model_404(server_url):
    async def run():
        status, _ = await _post(server_url, "/v1/completions", {
            "model": "nope", "prompt": "x", "max_tokens": 2,
        })
        assert status == 404
    asyncio.run(run())


def test_sleep_wake_cycle(server_url):
    async def run():
        status, _ = await _post(server_url, "/sleep", {})
        assert status == 200
        status, body = await _get(server_url, "/is_sleeping")
        assert body["is_sleeping"] is True
        status, _ = await _post(server_url, "/v1/completions", {
            "model": "tiny-llama", "prompt": "x", "max_tokens": 2,
        })
        assert status == 503
        status, _ = await _post(server_url, "/wake_up", {})
        assert status == 200
        status, body = await _get(server_url, "/is_sleeping")
        assert body["is_sleeping"] is False
        status, body = await _post(server_url, "/v1/completions", {
            "model": "tiny-llama", "prompt": "x", "max_tokens": 2,
            "temperature": 0.0, "ignore_eos": True,
        })
        assert status == 200
    asyncio.run(run())


def test_lora_load_unload_and_routing(server_url):
    async def run():
        status, body = await _post(server_url, "/v1/load_lora_adapter", {
            "lora_name": "my-adapter", "lora_rank": 4,
        })
        assert status == 200, body
        status, body = await _get(server_url, "/v1/lora_adapters")
        assert any(a["lora_name"] == "my-adapter" for a in body["adapters"])
        # /v1/models lists the adapter; requests for it are accepted.
        _, models = await _get(server_url, "/v1/models")
        assert any(m["id"] == "my-adapter" for m in models["data"])
        status, body = await _post(server_url, "/v1/completions", {
            "model": "my-adapter", "prompt": "adapter", "max_tokens": 4,
            "temperature": 0.0, "ignore_eos": True,
        })
        assert status == 200
        status, _ = await _post(server_url, "/v1/unload_lora_adapter",
                                {"lora_name": "my-adapter"})
        assert status == 200
        status, _ = await _post(server_url, "/v1/unload_lora_adapter",
                                {"lora_name": "my-adapter"})
        assert status == 400
    asyncio.run(run())


def test_stop_string(server_url):
    async def run():
        _, ref = await _post(server_url, "/v1/completions", {
            "model": "tiny-llama", "prompt": "stops", "max_tokens": 12,
            "temperature": 0.0, "ignore_eos": True,
        })
        full = ref["choices"][0]["text"]
        if len(full) < 3:
            return  # degenerate output; nothing to stop on
        stop = full[2]
        _, body = await _post(server_url, "/v1/completions", {
            "model": "tiny-llama", "prompt": "stops", "max_tokens": 12,
            "temperature": 0.0, "ignore_eos": True, "stop": [stop],
        })
        text = body["choices"][0]["text"]
        assert stop not in text
        assert body["choices"][0]["finish_reason"] == "stop"
    asyncio.run(run())


def test_overlong_prompt_rejected_400(server_url):
    async def run():
        status, body = await _post(server_url, "/v1/completions", {
            "model": "tiny-llama", "prompt": "x" * 400,  # > max_model_len 256
            "max_tokens": 4,
        })
        assert status == 400
        assert "max_model_len" in body["error"]["message"]
    asyncio.run(run())


def test_transcriptions_explicit_501(server_url):
    async def run():
        async with aiohttp.ClientSession() as s:
            form = aiohttp.FormData()
            form.add_field("file", b"RIFF....WAVE", filename="a.wav")
            form.add_field("model", "tiny-llama")
            async with s.post(server_url + "/v1/audio/transcriptions",
                              data=form) as r:
                assert r.status == 501
                body = await r.json()
        assert body["error"]["type"] == "NotImplementedError"
    asyncio.run(run())


def test_concurrent_requests(server_url):
    async def run():
        async def one(i):
            return await _post(server_url, "/v1/completions", {
                "model": "tiny-llama", "prompt": f"req {i}",
                "max_tokens": 6, "temperature": 0.0, "ignore_eos": True,
            })
        results = await asyncio.gather(*[one(i) for i in range(8)])
        for status, body in results:
            assert status == 200
            assert body["usage"]["completion_tokens"] == 6
    asyncio.run(run())


def test_request_trace_and_stage_metrics(server_url):
    """The real engine records queue/prefill/decode spans from the
    StageClock the core stamps, links them under the router's traceparent,
    and feeds the tpu:*_time_seconds exposition."""
    import re

    rid = "trace-engine-e2e"
    trace_id, parent_span = "ef" * 16, "12" * 8

    async def run():
        async with aiohttp.ClientSession() as s:
            async with s.post(server_url + "/v1/completions", json={
                "model": "tiny-llama", "prompt": "trace me",
                "max_tokens": 6, "temperature": 0.0, "ignore_eos": True,
            }, headers={
                "X-Request-Id": rid,
                "traceparent": f"00-{trace_id}-{parent_span}-01",
            }) as r:
                assert r.status == 200
            async with s.get(server_url + f"/debug/traces/{rid}") as r:
                assert r.status == 200
                trace = await r.json()
            async with s.get(server_url + "/metrics") as r:
                metrics = await r.text()
        return trace, metrics

    trace, metrics = asyncio.run(run())

    assert trace["trace_id"] == trace_id
    assert trace["remote_parent_span_id"] == parent_span
    spans = {sp["name"]: sp for sp in trace["spans"]}
    assert {"engine.request", "engine.queue", "engine.prefill",
            "engine.decode"} <= set(spans)
    root = spans["engine.request"]
    for name in ("engine.queue", "engine.prefill", "engine.decode"):
        assert spans[name]["parent_span_id"] == root["span_id"]
    # Stage ordering and a stage sum consistent with the root duration.
    assert (spans["engine.queue"]["start_unix"]
            <= spans["engine.prefill"]["start_unix"]
            <= spans["engine.decode"]["start_unix"])
    stage_sum = sum(spans[n]["duration_s"] for n in
                    ("engine.queue", "engine.prefill", "engine.decode"))
    assert stage_sum <= root["duration_s"] + 0.1
    assert spans["engine.decode"]["attributes"]["tokens"] == 6
    assert spans["engine.prefill"]["attributes"]["prompt_tokens"] > 0

    # The recorder's aggregates reach /metrics as sum/count pairs.
    for fam in ("tpu:queue_time_seconds", "tpu:prefill_time_seconds",
                "tpu:decode_time_seconds"):
        m = re.search(rf"{fam}_count{{[^}}]*}} (\d+)", metrics)
        assert m and int(m.group(1)) >= 1, fam
    assert "tpu:slow_requests_total" in metrics
    assert re.search(r"tpu:hbm_headroom_bytes{[^}]*} \d+", metrics)


def test_stream_gap_span_and_the_markers_of_the_hand_over(server_url):
    """A request that gets its tokens in several deliveries (a prefill's
    first, then bursts of 8) carries one ``engine.stream_gap``: the
    longest interval between two of them, under its decode span, with the
    steps that held the loop in it as causes; a request that got all in
    one delivery carries none. The records of the steps that flushed the
    bursts carry the two markers' times, written by the server's loop."""
    async def run():
        async with aiohttp.ClientSession() as s:
            for rid, n in (("gap-many", 30), ("gap-once", 1)):
                async with s.post(server_url + "/v1/completions", json={
                    "model": "tiny-llama", "prompt": "mind the gap",
                    "max_tokens": n, "temperature": 0.0,
                    "ignore_eos": True,
                }, headers={"X-Request-Id": rid}) as r:
                    assert r.status == 200
            traces = {}
            for rid in ("gap-many", "gap-once"):
                async with s.get(server_url + f"/debug/traces/{rid}") as r:
                    traces[rid] = await r.json()
            async with s.get(server_url + "/debug/steps?limit=12") as r:
                steps = (await r.json())["steps"]
        return traces, steps

    traces, steps = asyncio.run(run())
    spans = {sp["name"]: sp for sp in traces["gap-many"]["spans"]}
    gap, decode = spans["engine.stream_gap"], spans["engine.decode"]
    assert gap["parent_span_id"] == decode["span_id"]
    assert decode["start_unix"] <= gap["start_unix"] < gap["end_unix"] \
        <= decode["end_unix"] + 1e-6
    attrs = gap["attributes"]
    assert 1 <= attrs["at_token"] < 30
    assert attrs["behind_prefill_s"] >= 0 and attrs["behind_decode_s"] >= 0
    assert attrs["behind_prefill_s"] + attrs["behind_decode_s"] \
        <= gap["duration_s"] + 1e-6
    assert decode["attributes"]["tokens"] == 30
    assert "engine.stream_gap" not in {
        sp["name"] for sp in traces["gap-once"]["spans"]}

    flushed = [r for r in steps if r.get("emit_tokens")]
    assert flushed
    for r in flushed:
        assert r["deliver_wake_s"] > 0 and r["deliver_drain_s"] > 0
        assert set(r["phases_cpu"]) == set(r["phases"])


def test_drain_endpoint_must_stay_last(server_url):
    """Graceful drain (ISSUE 6): /drain stops admission, readiness
    flips to 503, inference answers 503 + Retry-After, the draining
    gauge rises — while ungated paths (/metrics) stay open.

    MUST remain the last test in this module: it permanently drains the
    module-scoped server.
    """
    async def run():
        async with aiohttp.ClientSession() as s:
            async with s.post(server_url + "/drain?timeout_s=10") as r:
                assert r.status == 200
                body = await r.json()
                assert body["status"] == "drained"
                assert body["in_flight"] == 0
            async with s.get(server_url + "/health") as r:
                assert r.status == 503
                assert (await r.json())["status"] == "draining"
                assert r.headers.get("Retry-After") == "1"
            async with s.post(server_url + "/v1/completions", json={
                "model": "tiny-llama", "prompt": "x", "max_tokens": 1,
            }) as r:
                assert r.status == 503
                assert r.headers.get("Retry-After") == "1"
            async with s.get(server_url + "/metrics") as r:
                assert r.status == 200
                text = await r.text()
        import re as _re

        assert _re.search(r"tpu:engine_draining{[^}]*} 1", text)
        assert "tpu:pool_shrink_retries_total" in text

    asyncio.run(run())
