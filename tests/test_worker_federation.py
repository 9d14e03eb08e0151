"""Multi-worker telemetry plane (obs/federation.py + router/workers.py):
merge-semantics units (counters sum, per-worker gauge labels vs the
documented max/sum exceptions, ring stamping and newest-first order,
``?worker=`` validation, divergence reports), flag-off parity via
registry sample deltas (``--router-workers`` unset must add no
``vllm_router:worker_*`` series and no ``worker`` label anywhere), and
the tier-1-safe pre-fork smoke: a real ``--router-workers 2``
subprocess whose aggregated ``/metrics`` carries both worker labels
with summed counters, torn down leak-free."""

import argparse
import asyncio
import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.obs import federation
from production_stack_tpu.router import metrics as router_metrics
from production_stack_tpu.router import routing_logic as rl
from production_stack_tpu.router.app import build_app
from production_stack_tpu.router.engine_stats import EngineStatsScraper
from production_stack_tpu.router.request_stats import RequestStatsMonitor
from production_stack_tpu.testing.fake_engine import FakeEngine
from production_stack_tpu.utils.misc import SingletonABCMeta, SingletonMeta


@pytest.fixture(autouse=True)
def _reset_singletons():
    def _reset():
        for cls in (
            rl.RoundRobinRouter, rl.SessionRouter, rl.PrefixAwareRouter,
            rl.KvawareRouter, rl.DisaggregatedPrefillRouter,
        ):
            SingletonABCMeta._reset_instance(cls)
        SingletonMeta._reset_instance(RequestStatsMonitor)
        SingletonMeta._reset_instance(EngineStatsScraper)

    _reset()
    yield
    _reset()


# ---------------------------------------------------------------------------
# Merge semantics units (pure functions, no router)
# ---------------------------------------------------------------------------


def _family(name, type_, samples):
    return {"name": name, "type": type_, "documentation": "d",
            "samples": samples}


def test_counters_sum_and_created_takes_min():
    merged = federation.merge_metric_families({
        0: [_family("vllm_router:x", "counter", [
            ["vllm_router:x_total", {"path": "/a"}, 3.0],
            ["vllm_router:x_created", {"path": "/a"}, 100.0]])],
        1: [_family("vllm_router:x", "counter", [
            ["vllm_router:x_total", {"path": "/a"}, 4.0],
            ["vllm_router:x_created", {"path": "/a"}, 90.0]])],
    })
    samples = {s[0]: s for s in merged[0]["samples"]}
    assert samples["vllm_router:x_total"][2] == 7.0
    # Counters never grow a worker label: the fleet series must stay
    # continuous across worker-count changes.
    assert "worker" not in samples["vllm_router:x_total"][1]
    assert samples["vllm_router:x_created"][2] == 90.0


def test_plain_gauges_become_per_worker_series():
    merged = federation.merge_metric_families({
        0: [_family("vllm_router:event_loop_lag_seconds", "gauge", [
            ["vllm_router:event_loop_lag_seconds", {"stat": "p99"}, 0.5]])],
        1: [_family("vllm_router:event_loop_lag_seconds", "gauge", [
            ["vllm_router:event_loop_lag_seconds", {"stat": "p99"}, 0.1]])],
    })
    samples = merged[0]["samples"]
    # A p99 must never be summed across loops — each worker keeps its
    # own labeled series.
    assert len(samples) == 2
    assert {s[1]["worker"] for s in samples} == {"0", "1"}
    assert sorted(s[2] for s in samples) == [0.1, 0.5]


def test_gauge_max_and_gauge_sum_exceptions():
    name = "vllm_router:healthy_pods_total"
    assert name in federation.GAUGE_MAX
    merged = federation.merge_metric_families({
        0: [_family(name, "gauge", [[name, {}, 4.0]])],
        1: [_family(name, "gauge", [[name, {}, 4.0]])],
    })
    # Every worker watches the same fleet: max, not 2x the pod count.
    assert merged[0]["samples"] == [[name, {}, 4.0]]

    name = "vllm_router:loop_stalls_total"
    assert name in federation.GAUGE_SUM
    merged = federation.merge_metric_families({
        0: [_family(name, "gauge", [[name, {"bucket": "1x"}, 2.0]])],
        1: [_family(name, "gauge", [[name, {"bucket": "1x"}, 3.0]])],
    })
    # Monotone per-process totals mirrored as gauges: sum.
    assert merged[0]["samples"] == [[name, {"bucket": "1x"}, 5.0]]


def test_render_exposition_shape():
    text = federation.render_exposition([
        _family("m", "gauge", [["m", {"a": 'v"\\x\n'}, 1.5]]),
    ]).decode()
    assert "# HELP m d\n" in text
    assert "# TYPE m gauge\n" in text
    assert 'm{a="v\\"\\\\x\\n"} 1.5' in text


def test_merge_rings_stamps_and_orders_newest_first():
    merged = federation.merge_rings({
        0: [{"time_unix": 10.0}, {"time_unix": 30.0}],
        1: [{"time_unix": 20.0}, {"time_unix": 40.0}],
    })
    assert [r["time_unix"] for r in merged] == [40.0, 30.0, 20.0, 10.0]
    assert [r["worker"] for r in merged] == [1, 0, 1, 0]
    assert len(federation.merge_rings(
        {0: [{"t": 1.0}, {"t": 2.0}]}, time_key="t", limit=1)) == 1


def test_parse_worker_param_validation():
    assert federation.parse_worker_param(None, [0, 1]) is None
    assert federation.parse_worker_param("1", [0, 1]) == 1
    with pytest.raises(ValueError, match="worker must be an integer"):
        federation.parse_worker_param("zzz", [0, 1])
    with pytest.raises(ValueError, match="unknown worker 7"):
        federation.parse_worker_param("7", [0, 1])


def test_divergence_report_flags_mismatched_views():
    agree = {"trie_digest": {"xor": "aa"}, "breaker_view": {}}
    report = federation.divergence_report(
        [{"worker": 0, "divergence": agree},
         {"worker": 1, "divergence": dict(agree)}])
    assert set(report) == set(federation.DIVERGENCE_KINDS)
    assert not any(v["diverged"] for v in report.values())

    report = federation.divergence_report([
        {"worker": 0, "divergence": agree},
        {"worker": 1, "divergence": {"trie_digest": {"xor": "bb"},
                                     "breaker_view": {}}},
    ])
    assert report["trie_digest"]["diverged"]
    assert report["trie_digest"]["views"] == {
        "0": {"xor": "aa"}, "1": {"xor": "bb"}}
    assert not report["breaker_view"]["diverged"]


# ---------------------------------------------------------------------------
# Flag-off parity: single-worker mode adds nothing to the registry
# ---------------------------------------------------------------------------


def _worker_series_count() -> int:
    return sum(
        len(m.samples)
        for metric in (router_metrics.worker_state_divergence,
                       router_metrics.worker_snapshot_errors)
        for m in metric.collect())


def _worker_labeled_samples() -> list:
    return [
        (m.name, s.labels)
        for fam in router_metrics.REGISTRY.collect()
        for m in [fam]
        for s in m.samples
        if federation.WORKER_LABEL in s.labels
    ]


async def _start(app: web.Application):
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}"


def _args(**overrides) -> argparse.Namespace:
    from production_stack_tpu.router.parser import build_parser

    args = build_parser().parse_args([])
    for k, v in overrides.items():
        setattr(args, k, v)
    return args


async def test_flag_off_parity_no_worker_series_no_worker_label():
    """``--router-workers`` unset: a served request, a scrape, and the
    always-on local plane (/debug/snapshot, /debug/workers) must add no
    ``vllm_router:worker_*`` sample and no ``worker`` label to the
    shared registry (deltas, not absolutes — other tests share it)."""
    before = _worker_series_count()
    engine = FakeEngine(model="test-model", ttft=0.0)
    erunner, eurl = await _start(engine.make_app())
    args = _args(static_backends=eurl, static_models="test-model",
                 routing_logic="roundrobin", engine_stats_interval=60)
    app = build_app(args)
    rrunner, rurl = await _start(app)
    try:
        assert app["state"].worker_count == 1
        async with aiohttp.ClientSession() as s:
            body = {"model": "test-model", "prompt": "hi",
                    "max_tokens": 4, "stream": True}
            async with s.post(f"{rurl}/v1/completions", json=body) as r:
                assert r.status == 200
                async for _ in r.content:
                    pass
            async with s.get(f"{rurl}/metrics") as r:
                assert r.status == 200
                exposition = await r.text()
            # The local plane is registered even in single-worker mode
            # (it is the federation feed) but reports local-only views.
            async with s.get(f"{rurl}/debug/snapshot") as r:
                assert r.status == 200
                snap = await r.json()
            async with s.get(f"{rurl}/debug/workers") as r:
                assert r.status == 200
                workers = await r.json()
    finally:
        await rrunner.cleanup()
        await erunner.cleanup()
    assert snap["worker"] == 0 and snap["workers"] == 1
    assert [row["worker"] for row in workers["per_worker"]] == [0]
    assert _worker_series_count() == before
    assert 'worker="' not in exposition
    assert _worker_labeled_samples() == []


# ---------------------------------------------------------------------------
# Pre-fork smoke: 2 real workers, aggregated scrape, leak-free teardown
# ---------------------------------------------------------------------------


# The smoke test injects no delay, so none of its waits asserts a speed:
# each is a liveness deadline far beyond a busy host's (six xdist workers
# share it), and every poll ends as soon as its condition holds.
LIVENESS_S = 60.0


def _get(url: str, timeout: float = LIVENESS_S) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def _post_completion(url: str, timeout: float = LIVENESS_S) -> int:
    req = urllib.request.Request(
        url + "/v1/completions",
        data=json.dumps({"model": "test-model", "prompt": "hi",
                         "max_tokens": 4}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        resp.read()
        return resp.status


@pytest.fixture
def short_tmp_path():
    """A directory of this test's own whose path is short: a worker's
    socket path has to fit AF_UNIX's 108 bytes, and pytest's ``tmp_path``
    under xdist does not leave room."""
    with tempfile.TemporaryDirectory(prefix="fed-") as path:
        yield pathlib.Path(path)


async def test_two_worker_smoke_aggregated_scrape_and_teardown(
        short_tmp_path):
    """Spawn ``--router-workers 2``, serve a couple of requests, and
    assert the aggregated ``/metrics`` shows both worker labels and a
    summed request counter; SIGTERM must exit 0 leaving no child
    processes and no socket directory behind. The router gets a
    temporary directory of its own: the shared one may hold the socket
    directory of another test file's two-worker router
    (tests/test_relay_pump.py, on another xdist worker)."""
    engine = FakeEngine(model="test-model", ttft=0.0)
    erunner, eurl = await _start(engine.make_app())
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    rurl = f"http://127.0.0.1:{port}"
    proc = subprocess.Popen(
        [sys.executable, "-m", "production_stack_tpu.router.app",
         "--host", "127.0.0.1", "--port", str(port),
         "--router-workers", "2",
         "--static-backends", eurl, "--static-models", "test-model",
         "--routing-logic", "roundrobin",
         "--engine-stats-interval", "60",
         "--log-level", "warning"],
        env=dict(os.environ, TPU_STACK_LOG_LEVEL="warning",
                 TMPDIR=str(short_tmp_path)))
    try:
        # Healthy is both workers answering the fan-in: the port answers
        # as soon as one of them listens, before the other's socket is up.
        deadline = time.monotonic() + LIVENESS_S
        while True:
            try:
                await asyncio.to_thread(_get, rurl + "/health", 2.0)
                ready = json.loads(await asyncio.to_thread(
                    _get, rurl + "/debug/workers", 2.0))
                if len(ready["per_worker"]) == 2:
                    break
            except OSError:
                pass
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "2-worker router never became healthy") from None
            await asyncio.sleep(0.2)

        assert len(list(short_tmp_path.glob("tpu-router-workers-*"))) == 1
        n_requests = 4
        for _ in range(n_requests):
            assert await asyncio.to_thread(
                _post_completion, rurl) == 200

        workers = json.loads(await asyncio.to_thread(
            _get, rurl + "/debug/workers"))
        assert [row["worker"] for row in workers["per_worker"]] == [0, 1]
        assert workers["workers_failed"] == []
        pids = {row["pid"] for row in workers["per_worker"]}
        assert len(pids) == 2

        # The finished-request gauge lags the response by the relay's
        # bookkeeping; poll the aggregated scrape until it has them.
        deadline = time.monotonic() + LIVENESS_S
        while True:
            exposition = (await asyncio.to_thread(
                _get, rurl + "/metrics")).decode()
            total = sum(
                float(line.split()[-1])
                for line in exposition.splitlines()
                if line.startswith(
                    "vllm_router:num_finished_requests{"))
            if total == n_requests or time.monotonic() > deadline:
                break
            await asyncio.sleep(0.1)
        # Unlabeled per-process gauges export from every worker, so both
        # labels appear regardless of how SO_REUSEPORT balanced the load.
        assert 'worker="0"' in exposition
        assert 'worker="1"' in exposition
        # Per-worker gauge series sum to the fleet total we sent.
        assert total == n_requests
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=LIVENESS_S)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
        await erunner.cleanup()
    assert rc == 0
    # Leak-free: the child worker is gone (only our direct child is
    # waitable; a surviving grandchild would keep the port bound) and
    # the UDS directory was removed.
    with pytest.raises(OSError):
        await asyncio.to_thread(_get, rurl + "/health", 2.0)
    assert list(short_tmp_path.glob("tpu-router-workers-*")) == []
