"""Native (C++) component tests: xxhash parity, pickers, and the operator
binary reconciling against a fake Kubernetes API server."""

import asyncio
import json
import os
import subprocess

import pytest
import xxhash
from aiohttp import web

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD_DIR = os.path.join(REPO, "native", "build")


@pytest.fixture(autouse=True)
def _native(native_build):
    """Every test here needs native/build (conftest.py builds it)."""


def test_xxhash64_parity():
    from production_stack_tpu.native import xxhash64

    cases = [b"", b"a", b"abc", b"abcd", b"12345678", b"x" * 17,
             b"y" * 31, b"z" * 32, b"w" * 33, b"q" * 100, b"m" * 1000,
             "unicode ✓ text".encode()]
    for data in cases:
        assert xxhash64(data) == xxhash.xxh64_intdigest(data), data


def test_native_roundrobin():
    from production_stack_tpu.native import NativePicker

    p = NativePicker()
    p.set_endpoints(["http://b", "http://a", "http://c"])
    picks = [p.pick_roundrobin() for _ in range(6)]
    assert picks[:3] == ["http://a", "http://b", "http://c"]  # sorted order
    assert picks[3:] == picks[:3]


def test_native_prefix_stickiness():
    from production_stack_tpu.native import NativePicker

    p = NativePicker()
    p.set_endpoints(["http://e1", "http://e2", "http://e3", "http://e4"])
    prompt = "shared system prompt " * 20  # several 128-char chunks
    first = p.pick_prefix(prompt + "user A")
    # Same long prefix must route to the same endpoint.
    for suffix in ("user B", "user C", "user D"):
        assert p.pick_prefix(prompt + suffix) == first


def test_native_prefix_respects_endpoint_removal():
    from production_stack_tpu.native import NativePicker

    p = NativePicker()
    p.set_endpoints(["http://e1", "http://e2"])
    prompt = "p" * 300
    first = p.pick_prefix(prompt)
    p.remove_endpoint(first)
    remaining = [e for e in ("http://e1", "http://e2") if e != first]
    p.set_endpoints(remaining)
    assert p.pick_prefix(prompt) == remaining[0]


def test_native_kv_aware():
    from production_stack_tpu.native import NativePicker

    p = NativePicker()
    p.set_endpoints(["http://e1", "http://e2"])
    prompt = "k" * 400  # 4 chunks of 128 -> 3 full + remainder
    hashes = [
        xxhash.xxh64_intdigest(prompt[i:i + 128])
        for i in range(0, len(prompt), 128)
    ]
    endpoint, matched = p.pick_kv(prompt)
    assert endpoint is None and matched == 0
    p.kv_admit("http://e2", hashes)
    endpoint, matched = p.pick_kv(prompt)
    assert endpoint == "http://e2"
    assert matched == len(prompt)
    # Dead endpoints are filtered out.
    p.set_endpoints(["http://e1"])
    endpoint, _ = p.pick_kv(prompt)
    assert endpoint is None


# --------------------------------------------------------------------- #
# Operator binary against a fake K8s API server
# --------------------------------------------------------------------- #


class FakeK8s:
    """Tiny in-memory Kubernetes API server covering what the operator
    uses: CR lists + WATCH streams, deployments, services,
    serviceaccounts, pods, status subresources, and coordination.k8s.io
    Leases (with resourceVersion optimistic concurrency)."""

    def __init__(self):
        self.objects = {}  # path -> body dict
        self.crs = {}      # plural -> [cr dicts]
        self.pods = []
        self.status_updates = []
        self.leases = {}   # name -> lease dict
        self._lease_rv = 0
        self._watchers = []  # asyncio.Queue of event lines

    def emit_watch_event(self, event_type: str, obj: dict) -> None:
        line = json.dumps({"type": event_type, "object": obj})
        for q in list(self._watchers):
            q.put_nowait(line)

    def make_app(self):
        app = web.Application()
        app.router.add_route("*", "/{tail:.*}", self.handle)
        return app

    async def _serve_watch(self, request: web.Request):
        """Chunked watch stream: emits queued event lines until the
        client's timeoutSeconds elapses or it disconnects."""
        timeout = float(request.query.get("timeoutSeconds", "30"))
        resp = web.StreamResponse()
        resp.enable_chunked_encoding()
        resp.content_type = "application/json"
        await resp.prepare(request)
        q: asyncio.Queue = asyncio.Queue()
        self._watchers.append(q)
        deadline = asyncio.get_running_loop().time() + timeout
        try:
            while True:
                remain = deadline - asyncio.get_running_loop().time()
                if remain <= 0:
                    break
                try:
                    line = await asyncio.wait_for(q.get(), timeout=remain)
                except asyncio.TimeoutError:
                    break
                await resp.write(line.encode() + b"\n")
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        finally:
            self._watchers.remove(q)
        try:
            await resp.write_eof()
        except ConnectionResetError:
            pass
        return resp

    def _handle_lease(self, request, path, method, body):
        name = path.rstrip("/").split("/")[-1]
        if method == "GET":
            if name in self.leases:
                return web.json_response(self.leases[name])
            return web.json_response({"reason": "NotFound"}, status=404)
        if method == "POST":
            lease_name = body["metadata"]["name"]
            if lease_name in self.leases:
                return web.json_response(
                    {"reason": "AlreadyExists"}, status=409)
            self._lease_rv += 1
            body["metadata"]["resourceVersion"] = str(self._lease_rv)
            self.leases[lease_name] = body
            return web.json_response(body, status=201)
        if method == "PUT":
            existing = self.leases.get(name)
            if existing is None:
                return web.json_response({"reason": "NotFound"}, status=404)
            sent_rv = body.get("metadata", {}).get("resourceVersion")
            if sent_rv != existing["metadata"]["resourceVersion"]:
                # Optimistic concurrency: stale writers lose.
                return web.json_response({"reason": "Conflict"}, status=409)
            self._lease_rv += 1
            body["metadata"]["resourceVersion"] = str(self._lease_rv)
            self.leases[name] = body
            return web.json_response(body)
        return web.json_response({}, status=405)

    async def handle(self, request: web.Request) -> web.Response:
        path = "/" + request.match_info["tail"]
        method = request.method
        if "/leases" in path:
            body = (json.loads(await request.text())
                    if method in ("POST", "PUT") else None)
            return self._handle_lease(request, path, method, body)
        if "/pods" in path and method == "GET":
            return web.json_response({"items": self.pods})
        if "production-stack.tpu" in path:
            if method == "GET" and request.query.get("watch") == "true":
                return await self._serve_watch(request)
            parts = path.rstrip("/").split("/")
            if path.endswith("/status") and method == "PUT":
                body = json.loads(await request.text())
                self.status_updates.append((path, body))
                return web.json_response(body)
            plural = parts[-1]
            if method == "GET" and plural in self.crs:
                return web.json_response({"items": self.crs[plural]})
            if method == "PUT" and parts[-2] in self.crs:
                # Update of an individual CR (finalizers, spec edits).
                body = json.loads(await request.text())
                items = self.crs[parts[-2]]
                for i, cr in enumerate(items):
                    if cr["metadata"]["name"] == parts[-1]:
                        items[i] = body
                        return web.json_response(body)
                return web.json_response({"reason": "NotFound"}, status=404)
            return web.json_response({"items": []})
        # Core objects (deployments/services/serviceaccounts).
        if method == "GET":
            if path in self.objects:
                return web.json_response(self.objects[path])
            return web.json_response({"reason": "NotFound"}, status=404)
        if method == "POST":
            body = json.loads(await request.text())
            name = body["metadata"]["name"]
            self.objects[path + "/" + name] = body
            return web.json_response(body, status=201)
        if method == "PUT":
            body = json.loads(await request.text())
            self.objects[path] = body
            return web.json_response(body)
        return web.json_response({}, status=405)


def _run_operator(api_url: str):
    binary = os.path.join(BUILD_DIR, "tpu-stack-operator")
    return subprocess.run(
        [binary, "--api-base", api_url, "--namespace", "default", "--once"],
        capture_output=True, timeout=60,
    )


def test_operator_reconciles_tpuruntime():
    fake = FakeK8s()
    fake.crs["tpuruntimes"] = [{
        "metadata": {"name": "llama8b", "uid": "uid-1"},
        "spec": {
            "model": "meta-llama/Llama-3-8B",
            "replicas": 2,
            "port": 8000,
            "tensorParallelSize": 8,
            "maxModelLen": 4096,
            "tpu": {"chips": 8, "accelerator": "tpu-v5-lite-podslice",
                    "topology": "2x4"},
        },
    }]

    async def run():
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}"
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, url)
        await runner.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr

    dep_key = "/apis/apps/v1/namespaces/default/deployments/llama8b-engine"
    assert dep_key in fake.objects, list(fake.objects)
    dep = fake.objects[dep_key]
    assert dep["spec"]["replicas"] == 2
    container = dep["spec"]["template"]["spec"]["containers"][0]
    cmd = container["command"]
    assert "production_stack_tpu.engine.server" in cmd
    assert "meta-llama/Llama-3-8B" in cmd
    assert "--tensor-parallel-size" in cmd and "8" in cmd
    # TPU resources, not nvidia.com/gpu.
    assert container["resources"]["limits"] == {"google.com/tpu": 8}
    sel = dep["spec"]["template"]["spec"]["nodeSelector"]
    assert sel["cloud.google.com/gke-tpu-topology"] == "2x4"
    assert sel["cloud.google.com/gke-tpu-accelerator"] == \
        "tpu-v5-lite-podslice"
    # Service + status update happened.
    svc_key = "/api/v1/namespaces/default/services/llama8b-engine-service"
    assert svc_key in fake.objects
    assert any("tpuruntimes/llama8b/status" in p
               for p, _ in fake.status_updates)


def test_operator_reconciles_router_and_cache():
    fake = FakeK8s()
    fake.crs["tpurouters"] = [{
        "metadata": {"name": "rt", "uid": "uid-2"},
        "spec": {"replicas": 1, "port": 8080, "routingLogic": "roundrobin",
                 "serviceDiscovery": "k8s"},
    }]
    fake.crs["cacheservers"] = [{
        "metadata": {"name": "kvc", "uid": "uid-3"},
        "spec": {"replicas": 1, "port": 8200, "capacityGb": 16},
    }]

    async def run():
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{port}")
        await runner.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr

    router_dep = fake.objects[
        "/apis/apps/v1/namespaces/default/deployments/rt-router"]
    cmd = router_dep["spec"]["template"]["spec"]["containers"][0]["command"]
    assert "production_stack_tpu.router.app" in cmd
    assert "--routing-logic" in cmd and "roundrobin" in cmd
    assert "/api/v1/namespaces/default/serviceaccounts/rt-sa" in fake.objects

    cache_dep = fake.objects[
        "/apis/apps/v1/namespaces/default/deployments/kvc-cache"]
    ccmd = cache_dep["spec"]["template"]["spec"]["containers"][0]["command"]
    assert "production_stack_tpu.kv.cache_server" in ccmd


def test_operator_detects_drift():
    fake = FakeK8s()
    fake.crs["tpuruntimes"] = [{
        "metadata": {"name": "m", "uid": "u"},
        "spec": {"model": "tiny-llama", "replicas": 3, "port": 8000},
    }]
    # Pre-existing deployment with stale replicas.
    dep_key = "/apis/apps/v1/namespaces/default/deployments/m-engine"
    fake.objects[dep_key] = {
        "metadata": {"name": "m-engine", "resourceVersion": "42"},
        "spec": {
            "replicas": 1,
            "template": {"spec": {"containers": [{
                "name": "engine", "image": "production-stack-tpu:latest",
                "command": ["stale"],
            }]}},
        },
    }

    async def run():
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{port}")
        await runner.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    dep = fake.objects[dep_key]
    assert dep["spec"]["replicas"] == 3  # drift corrected
    assert dep["metadata"]["resourceVersion"] == "42"  # carried over


def test_operator_detects_resource_drift():
    """A TPU-chips edit on the CR must reconcile even when replicas, image
    and command all match (the reference compares resources/env too,
    vllmruntime_controller.go:624-706)."""
    fake = FakeK8s()
    fake.crs["tpuruntimes"] = [{
        "metadata": {"name": "m", "uid": "u"},
        "spec": {"model": "tiny-llama", "replicas": 1, "port": 8000,
                 "tpu": {"chips": 8}},
    }]

    async def boot(expected_chips):
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{port}")
        await runner.cleanup()
        assert proc.returncode == 0, proc.stderr
        dep_key = "/apis/apps/v1/namespaces/default/deployments/m-engine"
        c = fake.objects[dep_key]["spec"]["template"]["spec"]["containers"][0]
        limits = c["resources"]["limits"]
        assert float(limits["google.com/tpu"]) == expected_chips

    asyncio.run(boot(8))
    # The API server normalizes quantities to strings; same value must NOT
    # count as drift (no infinite update loop) ...
    dep_key = "/apis/apps/v1/namespaces/default/deployments/m-engine"
    c = fake.objects[dep_key]["spec"]["template"]["spec"]["containers"][0]
    c["resources"] = {"requests": {"google.com/tpu": "8"},
                      "limits": {"google.com/tpu": "8"}}
    before = json.dumps(fake.objects[dep_key], sort_keys=True)
    asyncio.run(boot(8))
    assert json.dumps(fake.objects[dep_key], sort_keys=True) == before

    # ... but a chips edit is drift and must be corrected.
    fake.crs["tpuruntimes"][0]["spec"]["tpu"]["chips"] = 4
    asyncio.run(boot(4))


def test_operator_loads_lora_adapters():
    fake = FakeK8s()
    lora_calls = []

    engine_app = web.Application()

    async def load_lora(request):
        lora_calls.append(await request.json())
        return web.json_response({"status": "ok"})

    engine_app.router.add_post("/v1/load_lora_adapter", load_lora)

    async def run():
        eng_runner = web.AppRunner(engine_app)
        await eng_runner.setup()
        eng_site = web.TCPSite(eng_runner, "127.0.0.1", 0)
        await eng_site.start()
        eng_port = eng_site._server.sockets[0].getsockname()[1]

        fake.crs["loraadapters"] = [{
            "metadata": {"name": "ad1", "uid": "u-l"},
            "spec": {"adapterName": "sql-adapter", "runtimeName": "m",
                     "rank": 8, "port": eng_port},
        }]
        fake.pods = [{
            "metadata": {"name": "m-pod-1", "labels": {"app": "m"}},
            "status": {"podIP": "127.0.0.1", "phase": "Running"},
        }]

        api_runner = web.AppRunner(fake.make_app())
        await api_runner.setup()
        api_site = web.TCPSite(api_runner, "127.0.0.1", 0)
        await api_site.start()
        api_port = api_site._server.sockets[0].getsockname()[1]

        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{api_port}")
        await api_runner.cleanup()
        await eng_runner.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    assert lora_calls == [{"lora_name": "sql-adapter", "lora_rank": 8}]
    assert any("loraadapters/ad1/status" in p and
               b["status"]["phase"] == "Loaded"
               for p, b in fake.status_updates)
    # A finalizer was installed so deletion can unload first
    # (ref loraadapter_controller.go:94-110).
    assert fake.crs["loraadapters"][0]["metadata"]["finalizers"] == \
        ["loraadapter.production-stack.tpu/finalizer"]


class _FakeEnginePod:
    """In-process engine pod exposing the LoRA HTTP API the operator
    drives, pre-seeded with already-loaded adapters."""

    def __init__(self, preloaded=()):
        self.adapters = list(preloaded)
        self.loads = []
        self.unloads = []
        self.app = web.Application()
        self.app.router.add_post("/v1/load_lora_adapter", self._load)
        self.app.router.add_post("/v1/unload_lora_adapter", self._unload)
        self.app.router.add_get("/v1/lora_adapters", self._list)
        self.app.router.add_post("/model/download", self._download)
        self.downloads = []
        self.runner = None
        self.port = None

    async def _load(self, request):
        body = await request.json()
        self.loads.append(body)
        if body["lora_name"] not in self.adapters:
            self.adapters.append(body["lora_name"])
        return web.json_response({"status": "ok"})

    async def _unload(self, request):
        body = await request.json()
        self.unloads.append(body)
        if body["lora_name"] in self.adapters:
            self.adapters.remove(body["lora_name"])
        return web.json_response({"status": "ok"})

    async def _list(self, request):
        return web.json_response({"adapters": [
            {"lora_name": n, "slot": i}
            for i, n in enumerate(self.adapters)
        ]})

    async def _download(self, request):
        body = await request.json()
        self.downloads.append(body)
        return web.json_response(
            {"path": "/models/" + body["model_id"].replace("/", "-")})

    async def start(self):
        self.runner = web.AppRunner(self.app)
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]

    async def stop(self):
        await self.runner.cleanup()


def test_operator_lora_unload_on_delete_removes_finalizer():
    """A deleting CR (deletionTimestamp set) unloads the adapter from the
    pods that hold it, then drops the finalizer
    (ref loraadapter_controller.go:869-900)."""
    pod = _FakeEnginePod(preloaded=["sql-adapter", "other"])
    fake = FakeK8s()

    async def setup_and_run():
        await pod.start()
        fake.crs["loraadapters"] = [{
            "metadata": {
                "name": "ad1", "uid": "u-l",
                "deletionTimestamp": "2026-07-30T00:00:00Z",
                "finalizers": [
                    "loraadapter.production-stack.tpu/finalizer",
                    "someone-elses/finalizer",
                ],
            },
            "spec": {"adapterName": "sql-adapter", "runtimeName": "m",
                     "port": pod.port},
        }]
        fake.pods = [{
            "metadata": {"name": "m-pod-0", "labels": {"app": "m"}},
            "status": {"podIP": "127.0.0.1", "phase": "Running"},
        }]
        api_runner = web.AppRunner(fake.make_app())
        await api_runner.setup()
        api_site = web.TCPSite(api_runner, "127.0.0.1", 0)
        await api_site.start()
        api_port = api_site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{api_port}")
        await api_runner.cleanup()
        await pod.stop()
        return proc

    proc = asyncio.run(setup_and_run())
    assert proc.returncode == 0, proc.stderr
    assert pod.unloads == [{"lora_name": "sql-adapter"}]
    assert pod.adapters == ["other"]
    # Our finalizer gone, foreign finalizer untouched.
    assert fake.crs["loraadapters"][0]["metadata"]["finalizers"] == \
        ["someone-elses/finalizer"]
    assert pod.loads == []


def test_operator_lora_equalized_placement_and_unload():
    """algorithm=equalized with replicas=2 must target the two pods with
    the fewest other adapters and unload from a stale third pod
    (ref placement enum loraadapter_types.go:70-79 +
    reconcileToDesiredState :582-610)."""
    # pod0 is busy (2 other adapters), pod1 empty, pod2 holds a stale copy.
    pods = [
        _FakeEnginePod(preloaded=["a1", "a2"]),
        _FakeEnginePod(),
        _FakeEnginePod(preloaded=["x1", "x2", "x3", "sql-adapter"]),
    ]
    fake = FakeK8s()

    # The CR carries ONE port while pods differ by IP, so each fake pod
    # binds the same port on its own loopback alias (127.0.0.2/.3 bind on
    # Linux without setup).
    async def run():
        addrs = ["127.0.0.1", "127.0.0.2", "127.0.0.3"]
        runners = []
        port = None
        for addr, p in zip(addrs, pods):
            runner = web.AppRunner(p.app)
            await runner.setup()
            site = web.TCPSite(runner, addr, port or 0)
            await site.start()
            if port is None:
                port = site._server.sockets[0].getsockname()[1]
            p.port = port
            runners.append(runner)
        fake.crs["loraadapters"] = [{
            "metadata": {"name": "ad1", "uid": "u-l",
                         "finalizers": [
                             "loraadapter.production-stack.tpu/finalizer"]},
            "spec": {"adapterName": "sql-adapter", "runtimeName": "m",
                     "port": port,
                     "deploymentConfig": {"algorithm": "equalized",
                                          "replicas": 2}},
        }]
        fake.pods = [{
            "metadata": {"name": f"m-pod-{i}", "labels": {"app": "m"}},
            "status": {"podIP": addr, "phase": "Running"},
        } for i, addr in enumerate(addrs)]
        api_runner = web.AppRunner(fake.make_app())
        await api_runner.setup()
        api_site = web.TCPSite(api_runner, "127.0.0.1", 0)
        await api_site.start()
        api_port = api_site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{api_port}")
        await api_runner.cleanup()
        for r in runners:
            await r.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    # pod1 (0 adapters) and pod2 (3 other adapters but already holding the
    # adapter -> effective load 3) vs pod0 (2 others): equalized order is
    # pod1(0), pod0(2), pod2(3) -> desired = {pod1, pod0}.
    assert [c["lora_name"] for c in pods[1].loads] == ["sql-adapter"]
    assert [c["lora_name"] for c in pods[0].loads] == ["sql-adapter"]
    # Stale copy on pod2 dropped.
    assert pods[2].unloads == [{"lora_name": "sql-adapter"}]
    assert "sql-adapter" not in pods[2].adapters
    st = [b for p, b in fake.status_updates
          if "loraadapters/ad1/status" in p][-1]
    assert st["status"]["loadedOn"] == 2
    assert sorted(st["status"]["loadedAdapters"]) == ["m-pod-0", "m-pod-1"]


def test_operator_lora_ordered_placement_is_deterministic():
    """algorithm=ordered picks the lexicographically-first N pod names."""
    pods = [_FakeEnginePod(), _FakeEnginePod()]
    fake = FakeK8s()

    async def run():
        addrs = ["127.0.0.2", "127.0.0.1"]  # API order != name order
        runners = []
        port = None
        for addr, p in zip(addrs, pods):
            runner = web.AppRunner(p.app)
            await runner.setup()
            site = web.TCPSite(runner, addr, port or 0)
            await site.start()
            if port is None:
                port = site._server.sockets[0].getsockname()[1]
            runners.append(runner)
        fake.crs["loraadapters"] = [{
            "metadata": {"name": "ad1", "uid": "u-l",
                         "finalizers": [
                             "loraadapter.production-stack.tpu/finalizer"]},
            "spec": {"adapterName": "sql-adapter", "runtimeName": "m",
                     "port": port,
                     "deploymentConfig": {"algorithm": "ordered",
                                          "replicas": 1}},
        }]
        # API returns m-pod-9 first; ordered placement must pick m-pod-1.
        fake.pods = [
            {"metadata": {"name": "m-pod-9", "labels": {"app": "m"}},
             "status": {"podIP": addrs[0], "phase": "Running"}},
            {"metadata": {"name": "m-pod-1", "labels": {"app": "m"}},
             "status": {"podIP": addrs[1], "phase": "Running"}},
        ]
        api_runner = web.AppRunner(fake.make_app())
        await api_runner.setup()
        api_site = web.TCPSite(api_runner, "127.0.0.1", 0)
        await api_site.start()
        api_port = api_site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{api_port}")
        await api_runner.cleanup()
        for r in runners:
            await r.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    assert [c["lora_name"] for c in pods[1].loads] == ["sql-adapter"]
    assert pods[0].loads == []


def test_operator_lora_huggingface_download_flow():
    """source.type=huggingface drives the downloader sidecar and persists
    adapterPath on the CR spec (ref loraadapter_controller.go:334-390)."""
    pod = _FakeEnginePod()
    fake = FakeK8s()

    async def run():
        await pod.start()
        fake.crs["loraadapters"] = [{
            "metadata": {"name": "ad1", "uid": "u-l",
                         "finalizers": [
                             "loraadapter.production-stack.tpu/finalizer"]},
            "spec": {"adapterName": "sql-adapter", "runtimeName": "m",
                     "port": pod.port,
                     "source": {"type": "huggingface",
                                "repository": "org/sql-lora",
                                "sidecarPort": pod.port}},
        }]
        fake.pods = [{
            "metadata": {"name": "m-pod-0", "labels": {"app": "m"}},
            "status": {"podIP": "127.0.0.1", "phase": "Running"},
        }]
        api_runner = web.AppRunner(fake.make_app())
        await api_runner.setup()
        api_site = web.TCPSite(api_runner, "127.0.0.1", 0)
        await api_site.start()
        api_port = api_site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{api_port}")
        await api_runner.cleanup()
        await pod.stop()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    assert pod.downloads == [{"model_id": "org/sql-lora"}]
    # The discovered path is passed to the engine and persisted on the CR.
    assert pod.loads[0]["lora_path"] == "/models/org-sql-lora"
    assert fake.crs["loraadapters"][0]["spec"]["source"]["adapterPath"] == \
        "/models/org-sql-lora"


def test_operator_lora_hf_download_preserves_fresh_finalizer():
    """The adapterPath-persisting PUT must build on the CR as updated by
    the same pass's finalizer PUT — a stale copy would strip the finalizer
    just installed (regression: review finding on lora_resolve_path)."""
    pod = _FakeEnginePod()
    fake = FakeK8s()

    async def run():
        await pod.start()
        # CR starts with NO finalizer: the operator adds one, then the
        # download flow persists adapterPath; both must survive.
        fake.crs["loraadapters"] = [{
            "metadata": {"name": "ad1", "uid": "u-l"},
            "spec": {"adapterName": "sql-adapter", "runtimeName": "m",
                     "port": pod.port,
                     "source": {"type": "huggingface",
                                "repository": "org/sql-lora",
                                "sidecarPort": pod.port}},
        }]
        fake.pods = [{
            "metadata": {"name": "m-pod-0", "labels": {"app": "m"}},
            "status": {"podIP": "127.0.0.1", "phase": "Running"},
        }]
        api_runner = web.AppRunner(fake.make_app())
        await api_runner.setup()
        api_site = web.TCPSite(api_runner, "127.0.0.1", 0)
        await api_site.start()
        api_port = api_site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{api_port}")
        await api_runner.cleanup()
        await pod.stop()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    cr = fake.crs["loraadapters"][0]
    assert cr["metadata"]["finalizers"] == \
        ["loraadapter.production-stack.tpu/finalizer"]
    assert cr["spec"]["source"]["adapterPath"] == "/models/org-sql-lora"


def test_operator_lora_defers_finalizer_when_unload_fails():
    """A deleting CR whose engine pod is unreachable must KEEP the
    finalizer (unload-on-delete is the finalizer's whole guarantee);
    removal happens only once every unload provably succeeded."""
    fake = FakeK8s()

    async def run():
        fake.crs["loraadapters"] = [{
            "metadata": {
                "name": "ad1", "uid": "u-l",
                "deletionTimestamp": "2026-07-30T00:00:00Z",
                "finalizers": [
                    "loraadapter.production-stack.tpu/finalizer"],
            },
            # Port 1 is never listening -> unload cannot be confirmed.
            "spec": {"adapterName": "sql-adapter", "runtimeName": "m",
                     "port": 1},
        }]
        fake.pods = [{
            "metadata": {"name": "m-pod-0", "labels": {"app": "m"}},
            "status": {"podIP": "127.0.0.1", "phase": "Running"},
        }]
        api_runner = web.AppRunner(fake.make_app())
        await api_runner.setup()
        api_site = web.TCPSite(api_runner, "127.0.0.1", 0)
        await api_site.start()
        api_port = api_site._server.sockets[0].getsockname()[1]
        proc = await asyncio.get_running_loop().run_in_executor(
            None, _run_operator, f"http://127.0.0.1:{api_port}")
        await api_runner.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    assert fake.crs["loraadapters"][0]["metadata"]["finalizers"] == \
        ["loraadapter.production-stack.tpu/finalizer"]


# --------------------------------------------------------------------- #
# Operator transport hardening: bearer auth + TLS (round 3)
# --------------------------------------------------------------------- #


def _minimal_runtime_cr():
    return [{
        "metadata": {"name": "auth-rt", "uid": "uid-a", "generation": 1},
        "spec": {"model": "tiny-llama", "replicas": 1, "port": 8000},
    }]


def test_operator_sends_bearer_token(tmp_path):
    """Every API request carries Authorization: Bearer <token> when a
    token file is configured (ServiceAccount transport, ref
    operator/cmd/main.go in-cluster rest.Config)."""
    fake = FakeK8s()
    fake.crs["tpuruntimes"] = _minimal_runtime_cr()
    seen = []
    inner = fake.handle

    async def capture(request):
        seen.append(request.headers.get("Authorization"))
        return await inner(request)

    fake.handle = capture
    token_file = tmp_path / "token"
    token_file.write_text("sekret-rotating-token\n")

    async def run():
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        binary = os.path.join(BUILD_DIR, "tpu-stack-operator")
        proc = await asyncio.get_running_loop().run_in_executor(
            None, lambda: subprocess.run(
                [binary, "--api-base", f"http://127.0.0.1:{port}",
                 "--namespace", "default", "--once",
                 "--token-file", str(token_file)],
                capture_output=True, timeout=60))
        await runner.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    assert seen and all(h == "Bearer sekret-rotating-token" for h in seen)
    dep_key = "/apis/apps/v1/namespaces/default/deployments/auth-rt-engine"
    assert dep_key in fake.objects


def test_operator_https_verified(tmp_path):
    """The operator reconciles over TLS with server-cert verification
    against a CA file (direct apiserver transport, no proxy sidecar)."""
    import ssl

    cert = tmp_path / "cert.pem"
    key = tmp_path / "key.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "2",
         "-subj", "/CN=127.0.0.1",
         "-addext", "subjectAltName=IP:127.0.0.1"],
        check=True, capture_output=True, timeout=60)

    fake = FakeK8s()
    fake.crs["tpuruntimes"] = _minimal_runtime_cr()
    token_file = tmp_path / "token"
    token_file.write_text("tls-token")
    seen = []
    inner = fake.handle

    async def capture(request):
        seen.append(request.headers.get("Authorization"))
        return await inner(request)

    fake.handle = capture

    async def run():
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(str(cert), str(key))
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0, ssl_context=ctx)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        binary = os.path.join(BUILD_DIR, "tpu-stack-operator")
        proc = await asyncio.get_running_loop().run_in_executor(
            None, lambda: subprocess.run(
                [binary, "--api-base", f"https://127.0.0.1:{port}",
                 "--namespace", "default", "--once",
                 "--token-file", str(token_file),
                 "--ca-file", str(cert)],
                capture_output=True, timeout=60))
        await runner.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0, proc.stderr
    dep_key = "/apis/apps/v1/namespaces/default/deployments/auth-rt-engine"
    assert dep_key in fake.objects, (proc.stderr, list(fake.objects))
    assert seen and all(h == "Bearer tls-token" for h in seen)


def test_operator_https_rejects_untrusted_ca(tmp_path):
    """Verification is real: a server whose cert is NOT in the CA bundle
    must get zero successful reconciliation writes."""
    import ssl

    for stem in ("good", "bad"):
        subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", str(tmp_path / f"{stem}.key"),
             "-out", str(tmp_path / f"{stem}.pem"), "-days", "2",
             "-subj", "/CN=127.0.0.1",
             "-addext", "subjectAltName=IP:127.0.0.1"],
            check=True, capture_output=True, timeout=60)

    fake = FakeK8s()
    fake.crs["tpuruntimes"] = _minimal_runtime_cr()

    async def run():
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(str(tmp_path / "bad.pem"),
                            str(tmp_path / "bad.key"))
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0, ssl_context=ctx)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        binary = os.path.join(BUILD_DIR, "tpu-stack-operator")
        proc = await asyncio.get_running_loop().run_in_executor(
            None, lambda: subprocess.run(
                [binary, "--api-base", f"https://127.0.0.1:{port}",
                 "--namespace", "default", "--once",
                 "--ca-file", str(tmp_path / "good.pem")],
                capture_output=True, timeout=60))
        await runner.cleanup()
        return proc

    proc = asyncio.run(run())
    assert proc.returncode == 0
    assert not fake.objects  # handshake refused -> nothing written


def _start_operator(api_url: str, *extra):
    binary = os.path.join(BUILD_DIR, "tpu-stack-operator")
    return subprocess.Popen(
        [binary, "--api-base", api_url, "--namespace", "default",
         "--health-port", "0", *extra],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def test_operator_watch_event_reconciles_within_a_second():
    """The apiserver watch stream wakes the reconcile loop immediately:
    with an effectively-infinite poll interval, a CR added + watch event
    emitted must materialize its Deployment in well under the interval
    (ref: controller-runtime informers vs the old adaptive polling)."""
    fake = FakeK8s()
    fake.crs["tpuruntimes"] = [{
        "metadata": {"name": "first", "uid": "uid-1"},
        "spec": {"model": "tiny-llama", "replicas": 1, "port": 8000},
    }]

    async def run():
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}"
        proc = _start_operator(url, "--interval", "600",
                               "--max-interval", "600")
        try:
            # Initial pass (runs immediately at startup).
            for _ in range(100):
                if any(k.endswith("first-engine") for k in fake.objects):
                    break
                await asyncio.sleep(0.05)
            assert any(k.endswith("first-engine") for k in fake.objects)

            # Let the operator settle into its 600 s wait + its watch
            # streams connect.
            await asyncio.sleep(1.0)

            new_cr = {
                "metadata": {"name": "second", "uid": "uid-2",
                             "resourceVersion": "7"},
                "spec": {"model": "tiny-llama", "replicas": 1,
                         "port": 8000},
            }
            fake.crs["tpuruntimes"].append(new_cr)
            t0 = asyncio.get_running_loop().time()
            fake.emit_watch_event("ADDED", new_cr)
            deadline = t0 + 2.0
            while asyncio.get_running_loop().time() < deadline:
                if any(k.endswith("second-engine") for k in fake.objects):
                    break
                await asyncio.sleep(0.02)
            latency = asyncio.get_running_loop().time() - t0
            assert any(k.endswith("second-engine") for k in fake.objects), \
                "watch event did not trigger a reconcile"
            assert latency < 1.0, f"event->reconcile took {latency:.2f}s"
        finally:
            proc.kill()
            proc.wait(timeout=10)
            await runner.cleanup()

    asyncio.run(run())


def test_operator_leader_election_standby_and_failover():
    """With --leader-elect only the lease holder reconciles; a standby
    replica takes over once the holder's lease expires (ref
    operator/cmd/main.go EnableLeaderElection)."""
    fake = FakeK8s()
    fake.crs["tpuruntimes"] = [{
        "metadata": {"name": "m", "uid": "uid-1"},
        "spec": {"model": "tiny-llama", "replicas": 1, "port": 8000},
    }]

    async def run():
        runner = web.AppRunner(fake.make_app())
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}"
        flags = ("--leader-elect", "--lease-duration", "2",
                 "--interval", "1", "--max-interval", "1")
        a = _start_operator(url, "--identity", "op-a", *flags)
        b = None
        try:
            # A acquires the lease and reconciles.
            for _ in range(200):
                if (fake.leases.get("tpu-stack-operator", {}).get(
                        "spec", {}).get("holderIdentity") == "op-a"
                        and any(k.endswith("m-engine")
                                for k in fake.objects)):
                    break
                await asyncio.sleep(0.05)
            assert fake.leases["tpu-stack-operator"]["spec"][
                "holderIdentity"] == "op-a"

            # B starts as standby: with the deployment deleted it must
            # NOT recreate it while A holds the lease.
            b = _start_operator(url, "--identity", "op-b", *flags)
            await asyncio.sleep(1.0)  # B is up and observing
            fake.objects = {k: v for k, v in fake.objects.items()
                            if not k.endswith("m-engine")}
            await asyncio.sleep(1.0)
            # A (the leader) recreates it; kill A and delete again to
            # isolate B's standby behavior.
            a.kill()
            a.wait(timeout=10)
            fake.objects = {k: v for k, v in fake.objects.items()
                            if not k.endswith("m-engine")}
            await asyncio.sleep(0.8)  # < lease duration: B still standby
            assert not any(k.endswith("m-engine") for k in fake.objects), \
                "standby replica acted while the lease was live"

            # Lease expires -> B acquires and reconciles.
            for _ in range(200):
                if any(k.endswith("m-engine") for k in fake.objects):
                    break
                await asyncio.sleep(0.05)
            assert any(k.endswith("m-engine") for k in fake.objects), \
                "standby never took over after lease expiry"
            assert fake.leases["tpu-stack-operator"]["spec"][
                "holderIdentity"] == "op-b"
        finally:
            if b is not None:
                b.kill()
                b.wait(timeout=10)
            if a.poll() is None:
                a.kill()
                a.wait(timeout=10)
            await runner.cleanup()

    asyncio.run(run())
