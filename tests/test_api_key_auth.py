"""Serving-surface API-key auth (reference tutorial 11 "secure vLLM
serve", VLLM_API_KEY): engine and router reject unauthenticated
requests with 401, probes/scrapes stay open, and the router's header
forwarding lets one shared deployment key authenticate end to end."""

import asyncio
import time

import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.server import EngineServer, run_engine_server

KEY = "sk-test-123"


def _config():
    return EngineConfig(model="tiny-llama", max_model_len=128,
                        max_num_seqs=2, block_size=8, num_blocks=64,
                        max_loras=0)


def test_engine_requires_bearer_key():
    server = EngineServer(_config(), api_key=KEY)

    async def run():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        import aiohttp

        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                body = {"model": "tiny-llama", "prompt": "ab",
                        "max_tokens": 2, "ignore_eos": True}
                # No key / wrong key -> 401 with OpenAI error shape.
                async with s.post(f"{base}/v1/completions",
                                  json=body) as resp:
                    assert resp.status == 401
                    err = await resp.json()
                    assert err["error"]["type"] == "AuthenticationError"
                async with s.post(
                        f"{base}/v1/completions", json=body,
                        headers={"Authorization": "Bearer nope"}) as resp:
                    assert resp.status == 401
                # The whole /v1 surface is gated (vLLM semantics),
                # including LoRA admin.
                async with s.post(f"{base}/v1/load_lora_adapter",
                                  json={"lora_name": "x"}) as resp:
                    assert resp.status == 401
                async with s.get(f"{base}/v1/models") as resp:
                    assert resp.status == 401
                # Probes, scrapes, and the intra-stack control plane
                # stay open (kubelet/Prometheus/peer engines send no
                # client credentials; see utils/auth.py).
                async with s.get(f"{base}/health") as resp:
                    assert resp.status == 200
                async with s.get(f"{base}/metrics") as resp:
                    assert resp.status == 200
                async with s.get(f"{base}/is_sleeping") as resp:
                    assert resp.status == 200
                # The whole /debug tree is privileged: traces leak
                # request ids and backend URLs, steps leak workload
                # shape (utils/auth.py _PRIVILEGED_EXACT).
                async with s.get(f"{base}/debug/traces") as resp:
                    assert resp.status == 401
                async with s.get(f"{base}/debug/traces/rid") as resp:
                    assert resp.status == 401
                async with s.get(f"{base}/debug/steps") as resp:
                    assert resp.status == 401
                async with s.get(
                        f"{base}/debug/traces",
                        headers={"Authorization":
                                 f"Bearer {KEY}"}) as resp:
                    assert resp.status == 200
                async with s.get(
                        f"{base}/debug/steps",
                        headers={"Authorization":
                                 f"Bearer {KEY}"}) as resp:
                    assert resp.status == 200
                # Correct key -> served.
                async with s.post(
                        f"{base}/v1/completions", json=body,
                        headers={"Authorization": f"Bearer {KEY}"}) as resp:
                    assert resp.status == 200, await resp.text()
        finally:
            await runner.cleanup()

    asyncio.run(run())
    server.core.stop()


def test_router_edge_auth_and_shared_key_passthrough():
    """Router 401s unauthenticated clients; with the shared deployment
    key the request flows router -> engine (the router forwards the
    Authorization header) and completes."""
    from aiohttp import web

    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.router.parser import build_parser

    engine = EngineServer(_config(), api_key=KEY)

    async def run():
        e_runner = await run_engine_server(engine, "127.0.0.1", 0)
        e_port = list(e_runner.sites)[0]._server.sockets[0].getsockname()[1]

        args = build_parser().parse_args([])
        args.service_discovery = "static"
        args.static_backends = f"http://127.0.0.1:{e_port}"
        args.static_models = "tiny-llama"
        args.routing_logic = "roundrobin"
        args.api_key = KEY
        app = build_app(args)
        r_runner = web.AppRunner(app)
        await r_runner.setup()
        site = web.TCPSite(r_runner, "127.0.0.1", 0)
        await site.start()
        r_port = site._server.sockets[0].getsockname()[1]
        import aiohttp

        base = f"http://127.0.0.1:{r_port}"
        try:
            async with aiohttp.ClientSession() as s:
                body = {"model": "tiny-llama",
                        "messages": [{"role": "user", "content": "hi"}],
                        "max_tokens": 2}
                async with s.post(f"{base}/v1/chat/completions",
                                  json=body) as resp:
                    assert resp.status == 401
                async with s.get(f"{base}/health") as resp:
                    assert resp.status == 200
                # Privileged control-plane endpoints are gated: an
                # unauthenticated scale_in auto-picks a victim and
                # drains it (one-request outage), and /kv/deregister
                # sweeps a replica's routing claims.
                async with s.post(f"{base}/autoscale/scale_in",
                                  json={}) as resp:
                    assert resp.status == 401
                async with s.get(
                        f"{base}/autoscale/recommendation") as resp:
                    assert resp.status == 401
                async with s.post(f"{base}/kv/deregister",
                                  json={"instance_id": "x"}) as resp:
                    assert resp.status == 401
                # Read-only /debug surfaces are gated too: traces
                # carry request ids, endpoint URLs, and slow-request
                # timelines.
                async with s.get(f"{base}/debug/traces") as resp:
                    assert resp.status == 401
                async with s.get(f"{base}/debug/traces/rid") as resp:
                    assert resp.status == 401
                async with s.get(f"{base}/debug/steps") as resp:
                    assert resp.status == 401
                async with s.get(f"{base}/debug/loop") as resp:
                    assert resp.status == 401
                auth_hdr = {"Authorization": f"Bearer {KEY}"}
                async with s.get(f"{base}/debug/traces",
                                 headers=auth_hdr) as resp:
                    assert resp.status == 200
                # /debug/steps is engine-only and --loop-monitor is off
                # here: authenticated callers see 404, never 401.
                async with s.get(f"{base}/debug/steps",
                                 headers=auth_hdr) as resp:
                    assert resp.status == 404
                async with s.get(f"{base}/debug/loop",
                                 headers=auth_hdr) as resp:
                    assert resp.status == 404
                # With the deployment key they pass the gate: the
                # autoscaler is not enabled here (404, not 401), the
                # deregister succeeds, and the non-destructive /kv
                # reporting channel stays open to keyless engines.
                async with s.post(f"{base}/autoscale/scale_in",
                                  json={}, headers=auth_hdr) as resp:
                    assert resp.status == 404
                async with s.post(f"{base}/kv/deregister",
                                  json={"instance_id": "x"},
                                  headers=auth_hdr) as resp:
                    assert resp.status == 200
                async with s.post(f"{base}/kv/lookup",
                                  json={"text": "ab"}) as resp:
                    assert resp.status == 200
                async with s.post(
                        f"{base}/v1/chat/completions", json=body,
                        headers=auth_hdr) as resp:
                    assert resp.status == 200, await resp.text()
                    out = await resp.json()
                    assert out["choices"][0]["message"]["role"] == "assistant"
        finally:
            await r_runner.cleanup()
            await e_runner.cleanup()

    asyncio.run(run())
    engine.core.stop()


def test_second_router_app_in_one_process_is_healthy():
    """A router app closes its engine-stats scraper on cleanup; the next
    app built in the same process must get a live one (the scraper class
    is a singleton, and handing back the dead instance made /health 503
    for whichever test file a worker happened to run second)."""
    from aiohttp import web

    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.router.parser import build_parser

    async def health_of_new_app():
        import aiohttp

        args = build_parser().parse_args([])
        args.service_discovery = "static"
        args.static_backends = "http://127.0.0.1:9"  # never contacted
        args.static_models = "tiny-llama"
        args.routing_logic = "roundrobin"
        runner = web.AppRunner(build_app(args))
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://127.0.0.1:{port}/health") as resp:
                    return resp.status
        finally:
            await runner.cleanup()

    assert asyncio.run(health_of_new_app()) == 200
    time.sleep(0.3)  # the closed scraper's thread notices and exits
    assert asyncio.run(health_of_new_app()) == 200


def _debug_routes(app):
    """Every registered (method, path) under /debug/, with path params
    filled in — auto-discovered so a future debug route can't ship
    unauthenticated by being forgotten here."""
    import re

    seen = set()
    for route in app.router.routes():
        method = route.method.upper()
        if method in ("HEAD", "OPTIONS", "*"):
            continue
        canonical = route.resource.canonical
        if not canonical.startswith("/debug/"):
            continue
        seen.add((method, re.sub(r"{[^}]+}", "x", canonical)))
    return sorted(seen)


def test_every_debug_route_requires_key():
    """Auth coverage by construction: enumerate every registered router
    and engine route under /debug/ and assert each one 401s without the
    deployment key. The per-endpoint tests above check semantics; this
    one makes the privileged set closed under addition."""
    from aiohttp import web

    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.router.parser import build_parser

    engine = EngineServer(_config(), api_key=KEY)

    async def run():
        e_runner = await run_engine_server(engine, "127.0.0.1", 0)
        e_port = list(e_runner.sites)[0]._server.sockets[0].getsockname()[1]

        args = build_parser().parse_args([])
        args.service_discovery = "static"
        args.static_backends = f"http://127.0.0.1:{e_port}"
        args.static_models = "tiny-llama"
        args.routing_logic = "roundrobin"
        args.api_key = KEY
        # Turn on the optional subsystems so their debug routes are
        # registered and therefore enumerated.
        args.fleet_cache = True
        args.loop_monitor = True
        app = build_app(args)
        r_runner = web.AppRunner(app)
        await r_runner.setup()
        site = web.TCPSite(r_runner, "127.0.0.1", 0)
        await site.start()
        r_port = site._server.sockets[0].getsockname()[1]
        import aiohttp

        router_routes = _debug_routes(app)
        engine_routes = _debug_routes(engine.make_app())
        # The discovery itself must be working: the known surfaces
        # appear (an empty enumeration would vacuously pass).
        router_paths = {p for _, p in router_routes}
        for expected in ("/debug/traces", "/debug/kv/economics",
                         "/debug/kv/trie", "/debug/loop",
                         # The worker-federation plane (PR 16): the
                         # snapshot feed would leak every telemetry
                         # store at once if it ever shipped open.
                         "/debug/snapshot", "/debug/workers"):
            assert expected in router_paths, router_paths
        engine_paths = {p for _, p in engine_routes}
        assert "/debug/steps" in engine_paths, engine_paths

        try:
            async with aiohttp.ClientSession() as s:
                for base, routes in (
                        (f"http://127.0.0.1:{r_port}", router_routes),
                        (f"http://127.0.0.1:{e_port}", engine_routes)):
                    for method, path in routes:
                        async with s.request(
                                method, base + path,
                                json={} if method != "GET"
                                else None) as resp:
                            assert resp.status == 401, (
                                f"{method} {path}: {resp.status}")
                        async with s.request(
                                method, base + path,
                                json={} if method != "GET" else None,
                                headers={"Authorization":
                                         "Bearer nope"}) as resp:
                            assert resp.status == 401, (
                                f"{method} {path} (bad key): "
                                f"{resp.status}")
        finally:
            await r_runner.cleanup()
            await e_runner.cleanup()

    asyncio.run(run())
    engine.core.stop()


def test_multi_key_resolution_and_constant_time_check(tmp_path,
                                                      monkeypatch):
    """Several deployment keys open the same surface: comma-separated
    flag/env values and one-per-line keyfiles all resolve, and
    check_bearer accepts any configured key (rotation windows)."""
    from production_stack_tpu.utils import auth

    monkeypatch.delenv("VLLM_API_KEY", raising=False)
    monkeypatch.delenv("TPU_STACK_API_KEY", raising=False)
    monkeypatch.delenv("VLLM_API_KEY_FILE", raising=False)
    monkeypatch.delenv("TPU_STACK_API_KEY_FILE", raising=False)

    assert auth.resolve_api_keys("sk-a, sk-b,sk-c") == \
        ("sk-a", "sk-b", "sk-c")
    assert auth.resolve_api_key("sk-a, sk-b") == "sk-a"

    monkeypatch.setenv("VLLM_API_KEY", "sk-env1,sk-env2")
    assert auth.resolve_api_keys() == ("sk-env1", "sk-env2")
    # Explicit flag value wins over the env.
    assert auth.resolve_api_keys("sk-flag") == ("sk-flag",)

    monkeypatch.delenv("VLLM_API_KEY")
    keyfile = tmp_path / "keys.txt"
    keyfile.write_text("# rotation window\nsk-old\n\nsk-new\n")
    monkeypatch.setenv("VLLM_API_KEY_FILE", str(keyfile))
    assert auth.resolve_api_keys() == ("sk-old", "sk-new")

    # A configured-but-unreadable keyfile fails closed (refuses startup)
    # instead of silently disabling the bearer gate.
    monkeypatch.setenv("VLLM_API_KEY_FILE", str(tmp_path / "missing.txt"))
    with pytest.raises(RuntimeError, match="unreadable"):
        auth.resolve_api_keys()
    monkeypatch.setenv("VLLM_API_KEY_FILE", str(keyfile))

    keys = ("sk-old", "sk-new")
    assert auth.check_bearer("Bearer sk-old", keys)
    assert auth.check_bearer("Bearer sk-new", keys)
    assert not auth.check_bearer("Bearer sk-other", keys)
    assert not auth.check_bearer("sk-old", keys)  # missing Bearer prefix
    assert not auth.check_bearer(None, keys)
    # Single-key string form still works.
    assert auth.check_bearer("Bearer sk-old", "sk-old")
