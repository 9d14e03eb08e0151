"""Observability assets stay truthful: every metric name the Grafana
dashboard queries must actually be exposed by the router or the engine,
and the autoscaling manifests must target metrics that exist.

(The reference ships a 19-panel dashboard, observability/vllm-dashboard.json;
ours is generated by observability/gen_dashboard.py.)
"""

import json
import os
import re

import pytest
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DASHBOARD = os.path.join(REPO, "observability", "tpu-stack-dashboard.json")

_METRIC_RE = re.compile(r"\b((?:vllm_router|vllm|tpu):[a-zA-Z0-9_]+)")


def _router_metric_names():
    from production_stack_tpu.router import metrics as m

    names = set()
    for collector in m.REGISTRY._collector_to_names:  # noqa: SLF001
        for metric in collector.collect():
            names.add(metric.name)
            for sample in metric.samples:
                names.add(sample.name)
    return names


def _engine_metric_names():
    """Metric names the engine's /metrics exposition emits, scraped from
    the literal format strings in engine/server.py."""
    src = open(os.path.join(
        REPO, "production_stack_tpu", "engine", "server.py")).read()
    return set(_METRIC_RE.findall(src))


def _dashboard_exprs():
    d = json.load(open(DASHBOARD))
    for p in d["panels"]:
        for t in p.get("targets", []):
            yield p["title"], t["expr"]


def test_dashboard_metrics_exist():
    known = _router_metric_names() | _engine_metric_names()
    # prometheus_client appends _total/_sum/_count/_bucket/_created to
    # histogram/counter families; accept any known prefix.
    missing = []
    for title, expr in _dashboard_exprs():
        for name in _METRIC_RE.findall(expr):
            base = re.sub(r"_(total|sum|count|bucket|created)$", "", name)
            if name not in known and base not in known:
                missing.append((title, name))
    assert not missing, f"dashboard queries unknown metrics: {missing}"


def test_dashboard_has_distribution_panels():
    """Parity with the reference dashboard's latency/TTFT distribution
    bargauges (observability/vllm-dashboard.json)."""
    d = json.load(open(DASHBOARD))
    bargauges = [p for p in d["panels"] if p["type"] == "bargauge"]
    assert len(bargauges) >= 3
    exprs = " ".join(t["expr"] for p in bargauges for t in p["targets"])
    assert "e2e_request_latency_seconds_bucket" in exprs
    assert "time_to_first_token_seconds_bucket" in exprs
    panels = [p for p in d["panels"] if p["type"] != "row"]
    assert len(panels) >= 19  # reference panel count


def test_engine_stage_metrics_exposed():
    """The request-lifecycle decomposition (tentpole): the engine must
    expose sum/count pairs per stage plus the slow-request counter and an
    always-on HBM headroom gauge."""
    names = _engine_metric_names()
    required = {
        "tpu:queue_time_seconds_sum", "tpu:queue_time_seconds_count",
        "tpu:prefill_time_seconds_sum", "tpu:prefill_time_seconds_count",
        "tpu:decode_time_seconds_sum", "tpu:decode_time_seconds_count",
        "tpu:slow_requests_total",
        "tpu:hbm_headroom_bytes",
    }
    assert required <= names, f"missing: {sorted(required - names)}"


def test_dashboard_has_request_lifecycle_row():
    d = json.load(open(DASHBOARD))
    rows = [p["title"] for p in d["panels"] if p["type"] == "row"]
    assert "Request Lifecycle" in rows
    exprs = " ".join(e for _, e in _dashboard_exprs())
    for name in ("tpu:queue_time_seconds", "tpu:prefill_time_seconds",
                 "tpu:decode_time_seconds", "tpu:slow_requests_total",
                 "tpu:hbm_headroom_bytes"):
        assert name in exprs, f"dashboard never queries {name}"


def test_dashboard_json_matches_generator(tmp_path):
    """Drift check: the committed JSON must be exactly what
    gen_dashboard.py produces (regenerate and commit together)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gen_dashboard",
        os.path.join(REPO, "observability", "gen_dashboard.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # import is side-effect free
    built = mod.build_dashboard()
    committed = json.load(open(DASHBOARD))
    assert built == committed, (
        "tpu-stack-dashboard.json drifted from its generator; run "
        "`python observability/gen_dashboard.py` and commit the result")


def test_histograms_observe_through_monitor():
    """The distribution panels are fed by the router's own histograms:
    driving the stats monitor must populate buckets."""
    from production_stack_tpu.router import metrics
    from production_stack_tpu.router.request_stats import (
        RequestStatsMonitor,
    )

    mon = RequestStatsMonitor(sliding_window_size=10.0)
    url = "http://hist-test:1"
    mon.on_new_request(url, "r1", 100.0)
    mon.on_request_response(url, "r1", 100.5)   # ttft 0.5
    mon.on_token(url, "r1", 100.6)              # itl 0.1
    mon.on_request_complete(url, "r1", 101.0)   # latency 1.0

    text = metrics.render_metrics().decode()
    assert ('vllm_router:time_to_first_token_seconds_count'
            '{server="http://hist-test:1"} 1.0') in text
    assert ('vllm_router:e2e_request_latency_seconds_count'
            '{server="http://hist-test:1"} 1.0') in text
    assert ('vllm_router:time_per_output_token_seconds_count'
            '{server="http://hist-test:1"} 1.0') in text
    # 1.0s latency lands in the le=1.0 bucket.
    assert ('vllm_router:e2e_request_latency_seconds_bucket'
            '{le="1.0",server="http://hist-test:1"} 1.0') in text


def test_autoscaling_manifests_parse_and_target_known_metrics():
    adir = os.path.join(REPO, "deploy", "autoscaling")
    adapter = yaml.safe_load(open(os.path.join(
        REPO, "observability", "prom-adapter.yaml")))
    # Names the prometheus-adapter actually exports to the metrics API —
    # the only names an HPA Object/External metric can resolve.
    adapter_exported = {r["name"]["as"]
                       for r in adapter["rules"]["custom"]}
    adapter_series = {r["name"]["matches"]
                      for r in adapter["rules"]["custom"]}

    hpa = yaml.safe_load(open(os.path.join(adir, "hpa-engine.yaml")))
    assert hpa["kind"] == "HorizontalPodAutoscaler"
    assert hpa["spec"]["maxReplicas"] >= hpa["spec"]["minReplicas"]
    hpa_metric = hpa["spec"]["metrics"][0]["object"]["metric"]["name"]
    assert hpa_metric in adapter_exported, (
        f"HPA scales on {hpa_metric!r} but prom-adapter only exports "
        f"{sorted(adapter_exported)}")

    keda = yaml.safe_load(open(os.path.join(adir, "keda-engine.yaml")))
    assert keda["kind"] == "ScaledObject"
    query = keda["spec"]["triggers"][0]["metadata"]["query"]
    # KEDA queries Prometheus directly, so the raw series must exist
    # (adapter-rule source series or router exposition).
    assert any(s in query for s in adapter_series), query
    assert "vllm_router:num_requests_waiting" in query


def test_dashboard_has_slo_row_and_event_annotations():
    d = json.load(open(DASHBOARD))
    rows = [p["title"] for p in d["panels"] if p["type"] == "row"]
    assert "SLO & Goodput" in rows
    exprs = " ".join(e for _, e in _dashboard_exprs())
    for name in ("vllm_router:request_outcomes_total",
                 "vllm_router:goodput_ratio",
                 "vllm_router:canary_ttft_seconds",
                 "vllm_router:canary_failures_total"):
        assert name in exprs, f"dashboard never queries {name}"
    # Fleet event journal overlay (GET /debug/events?format=grafana).
    names = [a["name"] for a in d["annotations"]["list"]]
    assert "Fleet events" in names


def test_alert_rules_match_generator():
    """Drift check: the committed PrometheusRule must be exactly what
    gen_alerts.py produces (regenerate and commit together)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gen_alerts",
        os.path.join(REPO, "observability", "gen_alerts.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    committed = yaml.safe_load(open(os.path.join(
        REPO, "observability", "tpu-stack-alerts.yaml")))
    assert mod.build_alerts() == committed, (
        "tpu-stack-alerts.yaml drifted from its generator; run "
        "`python observability/gen_alerts.py` and commit the result")
    assert committed["kind"] == "PrometheusRule"
    alerts = [r["alert"] for g in committed["spec"]["groups"]
              for r in g["rules"]]
    # Multi-window burn-rate pair plus the named satellites.
    for required in ("TPUStackGoodputFastBurn", "TPUStackGoodputSlowBurn",
                     "TPUStackCanaryFailing", "TPUStackBreakerOpen",
                     "TPUStackLeaseSweepStorm",
                     "TPUStackBandwidthCollapse"):
        assert required in alerts, f"missing alert {required}"


def test_alert_rules_query_documented_metrics():
    """scripts/check_alert_rules.py is the enforcement: every metric an
    alert expr references must be documented in observability/README.md
    (which check_metrics_documented.py ties to what the tree emits)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_alert_rules",
        os.path.join(REPO, "scripts", "check_alert_rules.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bad = mod.undocumented()
    assert not bad, f"alert rules query undocumented metrics: {bad}"
    assert sum(1 for _ in mod.alert_exprs()) >= 6


def test_emitted_metrics_are_documented():
    """Every tpu:/vllm_router: series the source tree emits must be
    documented in observability/README.md (scripts/
    check_metrics_documented.py is the enforcement; a new metric fails
    here until its README entry lands with it)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_metrics_documented",
        os.path.join(REPO, "scripts", "check_metrics_documented.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = sorted(
        name for name in mod.emitted_metrics()
        if name not in mod.documented_metrics()[0]
        and not any(name.startswith(p)
                    for p in mod.documented_metrics()[1]))
    assert not missing, (
        f"metrics missing from observability/README.md: {missing}")
    assert len(mod.emitted_metrics()) > 40  # scanner actually scanned


def test_queue_wait_by_cause_and_first_token_span():
    """Concurrent requests on a real engine server: every request's
    ``engine.queue`` span splits into what it stood behind (decode steps,
    prefill steps of other requests, the rest), the parts summing to the
    span; ``engine.first_token`` runs from the end of prefill to the
    first emitted token; and ``POST /debug/profile`` takes the Python
    tracer as an option that is off by default."""
    import asyncio

    import aiohttp

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.server import (
        EngineServer,
        run_engine_server,
    )

    server = EngineServer(EngineConfig(
        model="tiny-llama", max_model_len=256, max_num_seqs=4,
        num_blocks=128, max_loras=0))
    rids = [f"cause-{i}" for i in range(6)]

    async def run():
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                async def one(rid, delay):
                    await asyncio.sleep(delay)
                    async with s.post(url + "/v1/completions", json={
                        "model": "tiny-llama", "prompt": f"hello {rid} " * 3,
                        "max_tokens": 24, "temperature": 0.0,
                        "ignore_eos": True,
                    }, headers={"X-Request-Id": rid}) as r:
                        assert r.status == 200
                        await r.read()

                # The later ones arrive while the earlier ones decode.
                await asyncio.gather(*[one(rid, 0.15 * i)
                                       for i, rid in enumerate(rids)])
                traces = []
                for rid in rids:
                    async with s.get(url + f"/debug/traces/{rid}") as r:
                        assert r.status == 200
                        traces.append(await r.json())
                async with s.post(url + "/debug/profile", json={
                        "duration_s": 0.2, "python_tracer": "yes"}) as r:
                    assert r.status == 400
                async with s.post(url + "/debug/profile",
                                  json={"duration_s": 0.2}) as r:
                    profile = await r.json()
                return traces, profile
        finally:
            await runner.cleanup()

    try:
        traces, profile = asyncio.run(run())
    finally:
        server.core.stop()

    assert profile["python_tracer"] is False
    behind_something = 0
    for trace in traces:
        spans = {sp["name"]: sp for sp in trace["spans"]}
        queue = spans["engine.queue"]
        attrs = queue["attributes"]
        parts = (attrs["behind_decode_s"], attrs["behind_prefill_s"],
                 attrs["behind_other_s"])
        assert all(p >= 0.0 for p in parts)
        assert sum(parts) == pytest.approx(queue["duration_s"], abs=1e-3)
        assert attrs["steps_waited"] >= 0
        if attrs["behind_decode_s"] + attrs["behind_prefill_s"] > 0:
            assert attrs["steps_waited"] >= 1
            behind_something += 1
        first = spans["engine.first_token"]
        prefill = spans["engine.prefill"]
        assert first["parent_span_id"] == spans["engine.request"]["span_id"]
        assert prefill["end_unix"] <= first["start_unix"] + 1e-6
        assert first["start_unix"] <= first["end_unix"]
        # The decode span keeps its start: the end of prefill.
        assert spans["engine.decode"]["start_unix"] == pytest.approx(
            first["start_unix"], abs=1e-6)
    # Requests that arrived while others decoded waited behind them.
    assert behind_something >= 1
