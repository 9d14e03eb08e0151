"""SLO engine e2e + units (hermetic): outcome classification through the
real router against fake engines, flag-off parity, the canary prober,
the fleet event journal (ring bound, privileged /debug/events, Grafana
annotations export), and a toy run of the saturation harness proving
the classifier reconciles.

Outcome taxonomy under test (router/slo.py): every request that reaches
the handler terminates as exactly one of ok / slow / shed / failed /
client_abort, and with --slo-config off none of that code runs.
"""

import argparse
import asyncio
import time

import aiohttp
import pytest
import yaml
from aiohttp import web

from production_stack_tpu.obs.events import EventJournal
from production_stack_tpu.router import metrics as router_metrics
from production_stack_tpu.router import routing_logic as rl
from production_stack_tpu.router.app import build_app
from production_stack_tpu.router.engine_stats import EngineStatsScraper
from production_stack_tpu.router.request_stats import RequestStatsMonitor
from production_stack_tpu.router.slo import (
    OUTCOMES,
    CanaryProber,
    SLOEngine,
)
from production_stack_tpu.testing.fake_engine import FakeEngine
from production_stack_tpu.utils.misc import SingletonABCMeta, SingletonMeta


# ---------------------------------------------------------------------------
# Unit: SLOEngine objective resolution + accounting
# ---------------------------------------------------------------------------


def test_objectives_precedence_tenant_beats_model_beats_default():
    eng = SLOEngine({
        "default": {"ttft_p99_s": 2.0, "inter_token_p99_s": 0.5},
        "models": {"big": {"ttft_p99_s": 5.0}},
        "tenants": {"premium": {"ttft_p99_s": 1.0}},
    })
    assert eng.objectives()["ttft_p99_s"] == 2.0
    assert eng.objectives(model="big")["ttft_p99_s"] == 5.0
    # Tenant override wins even when the model also overrides.
    assert eng.objectives(tenant="premium", model="big")["ttft_p99_s"] == 1.0
    # Non-overridden keys fall through to the default.
    assert eng.objectives(model="big")["inter_token_p99_s"] == 0.5


def test_objectives_adapter_entry_beats_base_model_entry():
    eng = SLOEngine({
        "default": {"ttft_p99_s": 2.0, "inter_token_p99_s": 0.5},
        "models": {"base-8b": {"ttft_p99_s": 5.0},
                   "sql-adapter": {"ttft_p99_s": 1.5}},
        "tenants": {"premium": {"ttft_p99_s": 1.0}},
    })
    # Adapter traffic names the adapter as ``model``; its own entry
    # wins over the base model's.
    obj = eng.objectives(model="sql-adapter", base_model="base-8b")
    assert obj["ttft_p99_s"] == 1.5
    # An adapter WITHOUT its own entry inherits the base model's
    # objectives instead of the default.
    obj = eng.objectives(model="other-adapter", base_model="base-8b")
    assert obj["ttft_p99_s"] == 5.0
    # Non-overridden keys still fall through to the default.
    assert obj["inter_token_p99_s"] == 0.5
    # Tenant override beats both.
    obj = eng.objectives(tenant="premium", model="sql-adapter",
                         base_model="base-8b")
    assert obj["ttft_p99_s"] == 1.0
    # Non-LoRA traffic: base_model is None (or equals model) — exactly
    # the old resolution.
    assert eng.objectives(model="base-8b")["ttft_p99_s"] == 5.0
    assert eng.objectives(
        model="base-8b", base_model="base-8b")["ttft_p99_s"] == 5.0


def test_latency_outcome_uses_adapter_resolution():
    eng = SLOEngine({
        "default": {"ttft_p99_s": 2.0},
        "models": {"base-8b": {"ttft_p99_s": 5.0},
                   "sql-adapter": {"ttft_p99_s": 0.5}},
    })
    # 1s TTFT: fine for the base model, a violation for the adapter.
    assert eng.latency_outcome(
        None, "other-adapter", ttft_s=1.0, base_model="base-8b") == "ok"
    assert eng.latency_outcome(
        None, "sql-adapter", ttft_s=1.0, base_model="base-8b") == "slow"


def test_objectives_config_junk_is_ignored_not_fatal():
    eng = SLOEngine({
        "default": {"ttft_p99_s": "fast", "unknown_knob": 3,
                    "inter_token_p99_s": True},
        "tenants": {"t": None},
    })
    # Junk values fall back to the built-in defaults; classification
    # still works (never a crash on the request path).
    assert eng.objectives()["ttft_p99_s"] == 2.0
    assert eng.objectives()["inter_token_p99_s"] == 0.5
    assert eng.latency_outcome("t", None, ttft_s=0.1) == "ok"


def test_latency_outcome_boundaries():
    eng = SLOEngine({"default": {"ttft_p99_s": 1.0,
                                 "inter_token_p99_s": 0.2}})
    assert eng.latency_outcome(None, None, ttft_s=0.99) == "ok"
    assert eng.latency_outcome(None, None, ttft_s=1.01) == "slow"
    assert eng.latency_outcome(None, None, inter_token_s=0.3) == "slow"
    # Unknown timings never violate (a proxy that saw no chunks cannot
    # judge inter-token latency).
    assert eng.latency_outcome(None, None) == "ok"


def test_observe_counts_and_goodput_window():
    eng = SLOEngine()
    for outcome in ("ok", "ok", "ok", "slow"):
        eng.observe(outcome, tenant="t1", model="m")
    # Unknown outcome strings are folded into failed, never raised.
    eng.observe("exploded", tenant="t1", model="m")
    counts = eng.counts()
    assert counts["ok"] == 3 and counts["slow"] == 1
    assert counts["failed"] == 1
    assert sum(counts.values()) == 5
    assert eng.goodput(300.0) == pytest.approx(3 / 5)
    # An empty window is None (unknown), not 0 or 1.
    assert SLOEngine().goodput(300.0) is None
    assert set(counts) == set(OUTCOMES)


def test_from_file_rejects_non_mapping(tmp_path):
    p = tmp_path / "slo.yaml"
    p.write_text("- not\n- a\n- mapping\n")
    with pytest.raises(ValueError, match="YAML mapping"):
        SLOEngine.from_file(str(p))
    p.write_text("")  # empty file -> all defaults
    eng = SLOEngine.from_file(str(p))
    assert eng.objectives()["availability"] == 0.999


# ---------------------------------------------------------------------------
# Unit: EventJournal ring
# ---------------------------------------------------------------------------


def test_event_journal_ring_is_bounded():
    j = EventJournal("test", capacity=4)
    for i in range(10):
        j.record("failover", endpoint=f"http://e{i}")
    assert len(j.snapshot(limit=100)) == 4
    # Totals survive eviction.
    assert j.recorded_total == 10
    assert j.kind_counts() == {"failover": 10}
    # Newest first.
    assert j.snapshot(limit=1)[0]["endpoint"] == "http://e9"
    s = j.summary()
    assert s["buffered"] == 4 and s["recorded_total"] == 10


def test_event_journal_kind_filter_and_grafana_shape():
    j = EventJournal("test")
    j.record("breaker_open", endpoint="http://a", failures=3)
    j.record("lease_sweep", endpoint="http://b", swept=2)
    assert [e["kind"] for e in j.snapshot(kind="lease_sweep")] == [
        "lease_sweep"]
    annotations = j.to_grafana(kind="breaker_open")
    assert len(annotations) == 1
    a = annotations[0]
    assert isinstance(a["time"], int)  # epoch millis
    assert a["time"] >= int(time.time() * 1000) - 60_000
    assert a["tags"] == ["breaker_open", "http://a"]
    assert a["text"] == "breaker_open: failures=3"


# ---------------------------------------------------------------------------
# E2E: router + fake engine
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


def _slo_file(tmp_path, config) -> str:
    p = tmp_path / "slo.yaml"
    p.write_text(yaml.safe_dump(config))
    return str(p)


async def _router_one_engine(engine=None, **argover):
    engine = engine or FakeEngine(model="test-model", ttft=0.01,
                                  tokens_per_sec=500.0)
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
    return engine, eurl, app, rurl, [erunner, rrunner]


async def _cleanup(runners):
    for r in reversed(runners):
        await r.cleanup()


async def _complete(s, rurl, **extra):
    body = {"model": "test-model", "prompt": "hi", "max_tokens": 4,
            "stream": True, **extra}
    async with s.post(f"{rurl}/v1/completions", json=body) as resp:
        status = resp.status
        async for _ in resp.content:
            pass
        return status


async def _wait_counts(state, total, timeout_s=60.0):
    # A liveness deadline, not a speed: the poll ends as soon as the
    # outcomes are in, however long a host shared by six test workers
    # takes to classify them.
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if sum(state.slo.counts().values()) >= total:
            return state.slo.counts()
        await asyncio.sleep(0.02)
    return state.slo.counts()


async def test_outcome_classification_ok_slow_failed(tmp_path):
    """One request per latency outcome plus an unroutable model, each
    classified exactly once (counts sum to requests seen). Both bounds
    follow from the delay the fake engine injects before its first token,
    not from how fast this host relays it: the first is a thousand times
    that delay, the second half of it, which no host can meet."""
    injected_ttft = 0.05
    path = _slo_file(tmp_path, {
        "default": {"ttft_p99_s": 1000 * injected_ttft,
                    "inter_token_p99_s": 1000 * injected_ttft},
        "models": {"test-model": {"ttft_p99_s": 1000 * injected_ttft}},
    })
    engine, eurl, app, rurl, runners = await _router_one_engine(
        engine=FakeEngine(model="test-model", ttft=injected_ttft,
                          tokens_per_sec=500.0),
        slo_config=path)
    state = app["state"]
    assert state.slo is not None and state.slo.source == path
    try:
        async with aiohttp.ClientSession() as s:
            assert await _complete(s, rurl) == 200            # -> ok
            state.slo.models["test-model"]["ttft_p99_s"] = injected_ttft / 2
            assert await _complete(s, rurl) == 200            # -> slow
            assert await _complete(s, rurl, model="nope") == 400  # -> failed
            counts = await _wait_counts(state, 3)

            # Goodput gauge refreshes at scrape time with the 2/3 ratio
            # (the failed request burns budget; nothing is excluded here
            # because no client aborted).
            async with s.get(f"{rurl}/metrics") as resp:
                text = await resp.text()
    finally:
        await _cleanup(runners)
    assert counts["ok"] == 1 and counts["slow"] == 1
    assert counts["failed"] == 1 and counts["client_abort"] == 0
    assert sum(counts.values()) == 3
    assert 'vllm_router:goodput_ratio{window="5m"}' in text
    assert ('vllm_router:request_outcomes_total{'
            'model="test-model",outcome="ok",tenant="default"} 1.0') in text


async def test_outcome_classification_client_abort(tmp_path):
    """A client that hangs up mid-stream classifies client_abort — not
    failed (the engine did nothing wrong) and not ok."""
    engine = FakeEngine(model="test-model", ttft=0.01, tokens_per_sec=5.0)
    _, eurl, app, rurl, runners = await _router_one_engine(
        engine=engine,
        slo_config=_slo_file(tmp_path, {"default": {"ttft_p99_s": 30.0}}))
    state = app["state"]
    try:
        async with aiohttp.ClientSession() as s:
            resp = await s.post(
                f"{rurl}/v1/completions",
                json={"model": "test-model", "prompt": "hi",
                      "max_tokens": 200, "stream": True})
            assert resp.status == 200
            await resp.content.readany()  # first chunk arrived...
            resp.close()                  # ...then the client vanishes
        counts = await _wait_counts(state, 1)
    finally:
        await _cleanup(runners)
    assert counts["client_abort"] == 1
    assert sum(counts.values()) == 1


def _outcome_sample_count() -> int:
    return sum(len(m.samples)
               for m in router_metrics.request_outcomes.collect())


def _canary_sample_count() -> int:
    return sum(len(m.samples)
               for m in router_metrics.canary_probes.collect())


async def test_flag_off_no_slo_state_and_no_series():
    """Without --slo-config / --canary-interval nothing is constructed
    and no outcome/canary series ever appears: the deltas across a
    served request are zero (the global registry may carry series from
    other tests, so deltas — not absolutes — are the invariant)."""
    before_outcomes = _outcome_sample_count()
    before_canary = _canary_sample_count()
    engine, eurl, app, rurl, runners = await _router_one_engine()
    state = app["state"]
    try:
        assert state.slo is None
        assert state.canary is None
        async with aiohttp.ClientSession() as s:
            assert await _complete(s, rurl) == 200
    finally:
        await _cleanup(runners)
    assert _outcome_sample_count() == before_outcomes
    assert _canary_sample_count() == before_canary


async def test_debug_events_served_and_privileged(tmp_path):
    """/debug/events serves the journal (newest first + Grafana shape)
    and sits behind the API key like the other debug surfaces."""
    engine, eurl, app, rurl, runners = await _router_one_engine(
        api_key="sekret")
    state = app["state"]
    state.events.record("failover", endpoint="http://old:1",
                        attempt=2)
    state.events.record("breaker_open", endpoint="http://old:1")
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{rurl}/debug/events") as resp:
                assert resp.status == 401  # privileged, no bearer
            hdr = {"Authorization": "Bearer sekret"}
            async with s.get(f"{rurl}/debug/events", headers=hdr) as resp:
                assert resp.status == 200
                payload = await resp.json()
            async with s.get(f"{rurl}/debug/events?format=grafana",
                             headers=hdr) as resp:
                assert resp.status == 200
                annotations = await resp.json()
            async with s.get(f"{rurl}/debug/events?kind=failover",
                             headers=hdr) as resp:
                only = await resp.json()
    finally:
        await _cleanup(runners)
    kinds = [e["kind"] for e in payload["events"]]
    assert kinds[:2] == ["breaker_open", "failover"]  # newest first
    assert payload["recorded_total"] >= 2
    assert {a["tags"][0] for a in annotations} >= {"failover",
                                                   "breaker_open"}
    assert all(e["kind"] == "failover" for e in only["events"])
    assert only["events"]


async def test_canary_probe_measures_ttft_and_records_failures(tmp_path):
    """The prober hits replicas directly: a healthy engine yields a TTFT
    sample; a torn-down one records a connect failure (the signal the
    TPUStackCanaryFailing alert consumes)."""
    engine, eurl, app, rurl, runners = await _router_one_engine(
        slo_config=_slo_file(tmp_path, {}))
    state = app["state"]
    prober = CanaryProber(state, interval_s=60.0, prompt_tokens=4,
                          max_tokens=2, events=state.events)
    try:
        eps = state.service_discovery.get_endpoint_info()
        assert len(eps) == 1
        ttft = await prober.probe(eps[0])
        assert ttft is not None and 0 < ttft < 10
        assert prober.probes_run == 1 and prober.failures == 0
        # Probes bypass the request path: nothing was classified.
        assert sum(state.slo.counts().values()) == 0

        await runners[0].cleanup()  # tear the engine down
        assert await prober.probe(eps[0]) is None
        assert prober.failures == 1
        fails = state.events.snapshot(kind="canary_failure")
        assert fails and fails[0]["endpoint"] == eps[0].url
        assert fails[0]["attributes"]["reason"] == "connect"
    finally:
        await _cleanup(runners[1:])


def test_saturation_toy_run_reconciles():
    """The harness at toy scale: every offered request reaches the
    router and gets exactly one outcome (the 10k-user artifact run is
    bench.py's BENCH_SATURATION=1; this keeps the machinery honest in
    the tier-1 suite)."""
    from production_stack_tpu.testing.saturation import run_saturation

    result = asyncio.run(run_saturation(
        steps=(10, 25), requests_per_user=2, replicas=2,
        collapse_threshold=0.9))
    assert result["outcomes_reconcile_all"] is True
    assert result["total_requests"] == 70
    for rung in result["rungs"]:
        assert rung["unreached"] == 0
        assert rung["outcomes_classified"] == rung["requests"]
        assert rung["goodput"] is not None
    assert sum(result["engine_requests"]) == 70
