"""Step flight recorder: roofline math and ring semantics, the
``/debug/steps`` surface, engine integration (records appear with the
right kinds during real generation), the recorder-overhead A/B bound,
the loop's phases and the fields they add to a record, and the hermetic
prefill-profile artifact schema."""

import asyncio
import collections
import json
import math
import os
import subprocess
import sys
import threading
import time
import timeit
import types

import jax
import pytest
from aiohttp import web

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import TokenDelivery
from production_stack_tpu.obs.debug import add_step_debug_routes
from production_stack_tpu.obs.steps import (
    DEVICE_PEAKS,
    STEP_KINDS,
    StepRecorder,
    device_hbm_bytes_per_s,
)
from production_stack_tpu.obs.trace import StageClock

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Unit: ring + roofline accounting
# ---------------------------------------------------------------------------


def test_ring_truncation_newest_first():
    rec = StepRecorder(capacity=5)
    for i in range(10):
        rec.record("decode_burst", 0.01, tokens=i)
    assert rec.recorded_total == 10
    snap = rec.snapshot()
    assert len(snap) == 5  # ring bounded at capacity
    assert [r["step"] for r in snap] == [10, 9, 8, 7, 6]  # newest first
    assert [r["step"] for r in rec.snapshot(limit=2)] == [10, 9]


def test_kind_filter_and_stats_always_complete():
    rec = StepRecorder(capacity=16)
    # Every known kind is present in the rollups even before any record,
    # so the per-kind Prometheus series never vanish between scrapes.
    assert set(rec.kind_stats()) == set(STEP_KINDS)
    assert all(v["count"] == 0 for v in rec.kind_stats().values())
    rec.record("prefill", 0.2, tokens=64)
    rec.record("decode_burst", 0.1, tokens=16)
    rec.record("decode_burst", 0.1, tokens=16)
    snap = rec.snapshot(kind="decode_burst")
    assert len(snap) == 2 and all(r["kind"] == "decode_burst" for r in snap)
    stats = rec.kind_stats()
    assert stats["prefill"]["count"] == 1 and stats["prefill"]["tokens"] == 64
    assert stats["decode_burst"]["count"] == 2
    assert stats["spec_verify"]["count"] == 0
    # Unknown kinds must not crash the engine loop; they get their own
    # rollup bucket.
    rec.record("experimental", 0.05)
    assert rec.kind_stats()["experimental"]["count"] == 1


def test_roofline_byte_estimate():
    rec = StepRecorder(param_bytes=100, kv_token_bytes=2)
    r = rec.record("decode_burst", 0.5, rows=2, tokens=8, forwards=4,
                   kv_read_tokens=10, kv_write_tokens=5)
    # forwards x weights + (kv reads + writes) x per-token KV cost.
    assert r["hbm_bytes"] == 4 * 100 + (10 + 5) * 2
    assert rec.kind_stats()["decode_burst"]["hbm_bytes"] == r["hbm_bytes"]


def test_bandwidth_utilization_window():
    rec = StepRecorder(param_bytes=0, kv_token_bytes=1,
                       hbm_bytes_per_s=1000.0, window_s=60.0)
    assert rec.bandwidth_utilization() == 0.0  # empty ring
    r = rec.record("decode_burst", 2.0, kv_write_tokens=1000)
    # 1000 bytes over 2 s of model-active time against a 1000 B/s floor.
    assert rec.bandwidth_utilization(now=r["ts_unix"]) == pytest.approx(0.5)
    # Steps that STARTED before the window are excluded (start is
    # ts_unix - wall_s, i.e. 2 s before the record timestamp).
    assert rec.bandwidth_utilization(now=r["ts_unix"] + 59.0) == 0.0


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_device_hbm_peak_comes_from_the_table(monkeypatch):
    """One table keyed by device_kind. A device without a published peak
    (the CPU) has no utilization — absent, not computed against another
    chip's figure; an unknown TPU is an error, not a default."""
    monkeypatch.delenv("TPU_STACK_HBM_GBS", raising=False)
    assert device_hbm_bytes_per_s(_Device("tpu", "TPU v5 lite")) == 819e9
    assert DEVICE_PEAKS["TPU v5e"]["hbm_bytes_per_s"] == 819e9
    assert device_hbm_bytes_per_s() is None
    assert device_hbm_bytes_per_s(_Device("cpu", "cpu")) is None
    with pytest.raises(ValueError, match="TPU v99"):
        device_hbm_bytes_per_s(_Device("tpu", "TPU v99"))
    rec = StepRecorder(capacity=4, param_bytes=1)
    rec.record("decode_burst", 1.0)
    assert rec.bandwidth_utilization() is None
    assert rec.summary()["bandwidth_utilization"] is None
    # The deployment's override wins, on any device, and must parse.
    monkeypatch.setenv("TPU_STACK_HBM_GBS", "1e9")
    assert device_hbm_bytes_per_s(_Device("cpu", "cpu")) == 1e9
    monkeypatch.setenv("TPU_STACK_HBM_GBS", "not-a-number")
    with pytest.raises(ValueError):
        device_hbm_bytes_per_s()


# ---------------------------------------------------------------------------
# /debug/steps endpoint
# ---------------------------------------------------------------------------


def _get_json(recorder, path):
    app = web.Application()
    add_step_debug_routes(app.router, recorder)

    async def run():
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        import aiohttp
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://127.0.0.1:{port}{path}") as resp:
                    return resp.status, await resp.json()
        finally:
            await runner.cleanup()

    return asyncio.run(run())


def test_debug_steps_schema_and_filters():
    rec = StepRecorder(capacity=8, param_bytes=10, kv_token_bytes=2)
    rec.record("prefill", 0.2, rows=1, tokens=64, forwards=1,
               kv_write_tokens=64)
    for _ in range(3):
        rec.record("decode_burst", 0.05, rows=2, tokens=8, forwards=4,
                   kv_read_tokens=100, kv_write_tokens=8, batched=True)

    status, doc = _get_json(rec, "/debug/steps")
    assert status == 200
    for key in ("capacity", "recorded_total", "param_bytes",
                "kv_token_bytes", "hbm_bytes_per_s", "window_s",
                "bandwidth_utilization", "kinds", "steps"):
        assert key in doc, key
    assert doc["recorded_total"] == 4
    assert set(doc["kinds"]) >= set(STEP_KINDS)
    assert len(doc["steps"]) == 4
    for r in doc["steps"]:
        for key in ("step", "ts_unix", "kind", "wall_s", "rows", "tokens",
                    "forwards", "kv_read_tokens", "kv_write_tokens",
                    "hbm_bytes", "batched"):
            assert key in r, key

    status, doc = _get_json(rec, "/debug/steps?kind=decode_burst&limit=2")
    assert status == 200
    assert len(doc["steps"]) == 2
    assert all(r["kind"] == "decode_burst" for r in doc["steps"])


def test_debug_steps_validation():
    rec = StepRecorder()
    status, doc = _get_json(rec, "/debug/steps?limit=abc")
    assert status == 400 and "limit" in doc["error"]
    status, doc = _get_json(rec, "/debug/steps?limit=0")
    assert status == 400 and ">= 1" in doc["error"]
    status, doc = _get_json(rec, "/debug/steps?kind=nope")
    assert status == 400
    # The error names the valid kinds so the 400 is self-documenting.
    assert all(k in doc["error"] for k in STEP_KINDS)


# ---------------------------------------------------------------------------
# Engine integration + overhead A/B
# ---------------------------------------------------------------------------


def _make_engine(**over):
    kwargs = dict(
        model="tiny-llama",
        max_model_len=128,
        max_num_seqs=4,
        block_size=4,
        num_blocks=96,
        min_prefill_bucket=16,
        max_loras=0,
    )
    kwargs.update(over)
    eng = EngineCore(EngineConfig(**kwargs), devices=jax.devices()[:1])
    eng.start()
    return eng


def _generate(engine, rid, max_tokens, timeout=120):
    import queue
    q = queue.Queue()

    def on_token(token, finish):
        q.put((token, finish))

    engine.add_request(
        rid, [1, 2, 3, 4, 5],
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True),
        on_token)
    n = 0
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            token, finish = q.get(timeout=5)
        except queue.Empty:
            continue
        if token is not None:
            n += 1
        if finish is not None:
            return n
    raise TimeoutError("generation did not finish")


def test_engine_populates_recorder_and_stats():
    eng = _make_engine()
    try:
        _generate(eng, "sr-1", 8)
        rec = eng.step_recorder
        assert rec is not None
        kinds = {r["kind"] for r in rec.snapshot()}
        # One whole-prompt prefill plus fused decode bursts.
        assert "prefill" in kinds
        assert "decode_burst" in kinds
        # The core fills param_bytes in lazily from the live weights, so
        # roofline bytes are non-zero.
        assert rec.param_bytes > 0
        assert all(r["hbm_bytes"] > 0 for r in rec.snapshot())
        stats = eng.stats()
        assert stats["step_records_total"] == rec.recorded_total > 0
        assert stats["step_kind_stats"]["prefill"]["count"] >= 1
        # No published peak for the CPU: the utilization is absent.
        assert stats["model_bandwidth_utilization"] is None
    finally:
        eng.stop()


def test_recorder_disabled_by_config():
    eng = _make_engine(step_recorder=False)
    try:
        _generate(eng, "sr-off", 4)
        assert eng.step_recorder is None
        stats = eng.stats()
        assert stats["step_records_total"] == 0
        assert stats["step_kind_stats"] == {}
    finally:
        eng.stop()


# The paces the bound is held against. One row: a decode forward of the
# benchmark's first configuration takes 11.4-11.8 ms on a v5e
# (PERF_LEDGER.jsonl, ``decode_token_step_ms.*``, PR 30), and one row gains
# one token a forward. The widest cell: 128 rows x 8 steps a burst at 3,000
# tokens/s (``laguna-s-backlog-wide``, ledger, PRs 37-38), a token every
# 0.33 ms, where the ~15-20 us a token that PR 38 put into the path cost
# 4.5% of the cell's tokens/s and would have passed the first case.
CHIP_TOKEN_S = 0.010
PACES = {"one row, 10 ms a token": (1, CHIP_TOKEN_S),
         "128 rows x 8 steps at 3,000 tokens/s": (128, 1 / 3000)}
BURST_STEPS = 8


def _best_s(fn, number=2000, repeat=7):
    """Seconds one call of ``fn`` takes, alone in this process: the best
    of ``repeat`` timings of ``number`` calls, which a busy neighbour can
    only raise."""
    return min(timeit.repeat(fn, number=number, repeat=repeat)) / number


@pytest.mark.parametrize("pace", list(PACES))
def test_recorder_overhead_under_one_percent(pace):
    """What the recorder does for one request, counted, times what each
    call costs alone, is under 1% of the time a chip takes to stream the
    request's tokens. (The walls of two CPU generations, recorder on and
    off, are a speed of a host that five other test workers share: not
    compared.) Counted are
    the calls the engine made on the loop's clock while it served 64
    tokens; ``idle_wait`` is left out, being entered only while the
    engine has nothing to do. At the widest cell's pace the same calls
    are counted per burst, with what a burst's flush does beside them
    for each of its rows and once (``EngineCore._flush_pending_burst``,
    ``_emit_seq``): the stamp of the request's clock, the delivery held,
    released and timed, the two markers."""
    rows, token_s = PACES[pace]
    eng = _make_engine()
    n_tokens = 64
    methods = ("loop_step", "phase", "start", "note", "note_program",
               "record")
    try:
        _generate(eng, "warm", n_tokens)
        rec = eng.step_recorder
        assert rec is eng._steps
        calls = collections.Counter()
        for name in methods:
            def counted(*args, _fn=getattr(rec, name), _name=name, **kwargs):
                calls[_name] += 1
                return _fn(*args, **kwargs)
            setattr(rec, name, counted)
        before = rec.phase_stats()["idle_wait"]["count"], rec.recorded_total
        assert _generate(eng, "counted", n_tokens) == n_tokens
        calls["phase"] -= rec.phase_stats()["idle_wait"]["count"] - before[0]
        steps = rec.recorded_total - before[1]
    finally:
        eng.stop()
    # A prefill and at least one burst, each in the ring.
    assert 2 <= steps <= calls["record"], calls
    assert calls["phase"] >= 4 * steps  # schedule, build, enqueue, emit

    alone = StepRecorder(capacity=1024, param_bytes=1, kv_token_bytes=1)
    with alone.loop_step(True):
        def phase():
            with alone.phase("build"):
                pass

        def step():
            alone.start()
            alone.record("decode_burst", rows=1, tokens=8, forwards=8)

        unit = {
            "phase": _best_s(phase),
            "note": _best_s(lambda: alone.note(
                waiting=0, running=1, kv_blocks_live=1, kv_blocks_cached=0,
                kv_blocks_free=63)),
            "note_program": _best_s(
                lambda: alone.note_program("decode_k8", 8)),
            "start": 0.0,  # timed with the record it opens
            "record": _best_s(step),
        }

    def iteration():
        with alone.loop_step(True):
            pass

    unit["loop_step"] = _best_s(iteration)
    cost = sum(calls[name] * unit[name] for name in methods)
    if rows > 1:
        # One burst of rows x BURST_STEPS tokens: an iteration's calls
        # (those above, per recorded step), then per row and per burst
        # what the flush adds.
        n_tokens = rows * BURST_STEPS
        cost /= steps
        clock, output, sample = StageClock(), [1, 2, 3], [0.0, 0, 0]
        seqs = [types.SimpleNamespace(req=types.SimpleNamespace(trace=clock))
                for _ in range(rows)]

        class Stream:  # takes a burst, as the server's does
            def __call__(self, payload, finish):
                pass

            def on_burst(self, items):
                pass

        delivery, lock = TokenDelivery(Stream()), threading.RLock()

        def one_timed_delivery():  # as ``_emit_seq`` makes and times it
            delivery.hold()
            delivery(7, None)
            with lock:
                t0 = time.perf_counter()
                delivered = delivery.release()
                sample[0] += time.perf_counter() - t0
            sample[1] += True
            sample[2] += delivered

        def markers():
            alone.mark(alone.recorded_total, "deliver_wake_s",
                       time.perf_counter())
            alone.mark(alone.recorded_total, "deliver_drain_s",
                       time.perf_counter())

        per_row = {
            "stamp": _best_s(lambda: clock.delivered(time.time(), output)),
            "delivery": _best_s(one_timed_delivery)
            - _best_s(lambda: delivery(7, None)),
        }
        per_burst = {
            "traced_rows": _best_s(lambda: any(
                s.req.trace is None for s in seqs), number=200),
            "open_step": _best_s(alone.open_step),
            "markers": _best_s(markers),
            "note_sum": _best_s(lambda: alone.note_sum(
                emit_tokens=1024, emit_finished=3, emit_callback_s=1e-3,
                emit_callback_samples=1024, emit_rows=128,
                emit_callbacks=128)),
        }
        unit.update(per_row, **per_burst)
        cost += rows * sum(per_row.values()) + sum(per_burst.values())
    budget = 0.01 * n_tokens * token_s
    assert cost <= budget, (
        f"recorder overhead above 1%: {cost * 1e6:.0f} us for {n_tokens} "
        f"tokens ({dict(calls)} calls at "
        f"{ {k: round(v * 1e6, 2) for k, v in unit.items()} } us) against "
        f"{budget * 1e6:.0f} us")


# ---------------------------------------------------------------------------
# The loop's one clock: phases, the new record fields, stats() from totals
# ---------------------------------------------------------------------------

OLD_STATS_KEYS = ("prefill_time_total", "decode_time_total",
                  "flush_time_total", "dispatch_enqueue_s", "prefill_count",
                  "decode_burst_count", "dispatch_count_total")


def test_phases_nest_and_each_keeps_its_own_time():
    rec = StepRecorder(capacity=4)
    with rec.loop_step(annotate=False):
        with rec.phase("schedule"):
            with rec.phase("idle_wait"):
                time.sleep(0.02)
        rec.note(waiting=3, running=1)
        rec.start()
        with rec.phase("build"):
            time.sleep(0.01)
            with rec.phase("enqueue"):
                time.sleep(0.02)
            rec.note_program("decode_k8")
            rec.note_program("decode_k8")
        r = rec.record("decode_burst", rows=1)
    # The step's phases are those after start(); what came before is the
    # gap. A phase's time excludes the phases inside it.
    assert set(r["phases"]) == {"build", "enqueue"}
    assert set(r["gap_phases"]) == {"schedule", "idle_wait"}
    assert r["phases"]["enqueue"] >= 0.02 > r["phases"]["build"] >= 0.01
    assert r["gap_phases"]["idle_wait"] >= 0.02 > r["gap_phases"]["schedule"]
    assert sum(r["phases"].values()) <= r["wall_s"] + 1e-3
    assert r["wall_s"] == pytest.approx(sum(r["phases"].values()), abs=2e-3)
    assert r["start_unix"] <= r["end_unix"] == r["ts_unix"]
    assert r["end_unix"] - r["start_unix"] == pytest.approx(r["wall_s"],
                                                            abs=2e-3)
    assert (r["program"], r["waiting"], r["running"]) == ("decode_k8", 3, 1)
    totals = rec.phase_stats()
    assert totals["enqueue"]["count"] == 1
    assert totals["enqueue"]["seconds"] == pytest.approx(
        r["phases"]["enqueue"], abs=1e-5)
    assert totals["readback"] == {"seconds": 0.0, "count": 0,
                                  "cpu_seconds": 0.0}
    assert rec.summary()["phases"] == totals
    # A record made with its own wall time stands alone.
    alone = rec.record("prefill", 0.5)
    assert alone["phases"] == {} and alone["program"] == ""
    assert alone["end_unix"] - alone["start_unix"] == pytest.approx(0.5)


def test_phase_from_another_thread_counts_in_the_totals_only():
    import threading

    rec = StepRecorder(capacity=4)
    with rec.loop_step(annotate=False):
        rec.start()
        def dispatch_elsewhere():
            with rec.phase("enqueue"):
                pass

        other = threading.Thread(target=dispatch_elsewhere)
        other.start()
        other.join(timeout=10)
        assert not other.is_alive()
        r = rec.record("decode_burst")
    assert r["phases"] == {}
    assert rec.phase_stats()["enqueue"]["count"] == 1


def test_busy_between_clips_to_the_interval_and_skips_the_own_step():
    rec = StepRecorder(capacity=8)
    now = time.time()
    ring = [("decode_burst", now - 1.0, now - 0.9),
            ("prefill", now - 0.8, now - 0.7),
            ("spec_verify", now - 0.6, now - 0.5),
            ("experimental", now - 0.45, now - 0.42),
            ("prefill", now - 0.4, now - 0.1)]  # the request's own step
    for kind, start, end in ring:
        r = rec.record(kind, end - start)
        r["start_unix"], r["end_unix"] = start, end
    # Arrived in the middle of the first burst, started prefill inside the
    # last record.
    got = rec.busy_between(now - 0.95, now - 0.3)
    assert got["decode"] == pytest.approx(0.05 + 0.1)
    assert got["prefill"] == pytest.approx(0.1)
    assert got["steps"] == 3
    assert rec.busy_between(now - 0.05, now) == {
        "decode": 0.0, "prefill": 0.0, "steps": 0}


def test_engine_records_phases_programs_and_pool_counts():
    eng = _make_engine()
    try:
        _generate(eng, "ph-1", 20)
        _generate(eng, "ph-2", 20)
        rec = eng.step_recorder
        records = rec.snapshot()
        assert {r["kind"] for r in records} >= {"prefill", "decode_burst"}
        for r in records:
            assert r["start_unix"] <= r["end_unix"]
            # Phases are timed inside the step and exclude one another.
            assert sum(r["phases"].values()) <= r["wall_s"] + 1e-3
            assert r["wall_s"] - sum(r["phases"].values()) <= 0.05
            assert set(r["phases"]) <= {"build", "enqueue", "readback",
                                        "emit"}
            assert "schedule" in r["gap_phases"]
            assert r["gap_before_s"] >= 0.0
            assert (r["kv_blocks_live"] + r["kv_blocks_cached"]
                    + r["kv_blocks_free"]) == eng.num_blocks == 96
            assert r["program"]
        by_kind = {r["kind"]: r["program"] for r in records}
        assert by_kind["decode_burst"] == "decode_k8"
        assert by_kind["prefill"].startswith("prefill")
        assert by_kind["prefill"] != by_kind["decode_burst"]
        # The second prompt repeats the first: its blocks were cached.
        assert any(r["kv_blocks_cached"] > 0 for r in records)
        assert any(r["kv_blocks_live"] > 0 and r["running"] > 0
                   for r in records if r["kind"] == "decode_burst")
        # Records follow one another on one clock.
        ordered = sorted(records, key=lambda r: r["step"])
        for a, b in zip(ordered, ordered[1:]):
            assert a["end_unix"] <= b["start_unix"] + 1e-4
    finally:
        eng.stop()


def _wait_idle(eng, timeout=30):
    """Until the loop has settled its last burst: the final record of a
    request is made after its last token is delivered."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        with eng._lock:
            idle = (not eng.scheduler.has_work()
                    and eng._pending_burst is None)
        if idle:
            time.sleep(0.2)  # the step in flight, if any, records
            return
        time.sleep(0.01)
    raise TimeoutError("the engine loop did not go idle")


def test_stats_keeps_its_keys_with_the_recorders_totals():
    eng = _make_engine()
    try:
        _generate(eng, "st-1", 12)
        _wait_idle(eng)
        stats = eng.stats()
        kinds = eng.step_recorder.kind_stats()
        phases = eng.step_recorder.phase_stats()
        for key in OLD_STATS_KEYS:
            assert key in stats, key
        assert stats["prefill_count"] == kinds["prefill"]["count"] == 1
        assert stats["decode_burst_count"] == kinds["decode_burst"]["count"]
        assert stats["decode_burst_count"] >= 2
        assert stats["prefill_time_total"] == round(
            kinds["prefill"]["wall_s"] + kinds["prefill_chunk"]["wall_s"], 3)
        assert stats["decode_time_total"] == round(
            kinds["decode_burst"]["wall_s"], 3)
        assert stats["flush_time_total"] == round(
            phases["readback"]["seconds"], 3)
        assert stats["dispatch_enqueue_s"] == round(
            phases["enqueue"]["seconds"], 3)
        # One dispatch per step program call: the prefill and each burst.
        assert stats["dispatch_count_total"] == phases["enqueue"]["count"] \
            == stats["prefill_count"] + stats["decode_burst_count"]
        assert stats["prefill_time_total"] > 0 < stats["decode_time_total"]
    finally:
        eng.stop()


def test_recorder_off_keeps_the_totals_and_makes_no_record():
    eng = _make_engine(step_recorder=False)
    try:
        _generate(eng, "off-1", 12)
        assert eng.step_recorder is None
        assert eng._steps.recorded_total == 0 and eng._steps.snapshot() == []
        stats = eng.stats()
        assert stats["prefill_count"] == 1
        assert stats["decode_burst_count"] >= 2
        assert stats["dispatch_count_total"] >= 3
        assert stats["prefill_time_total"] > 0 < stats["decode_time_total"]
        # Off means not annotated either: the profiler's classes were
        # never looked up.
        assert eng._steps._annotations is None
    finally:
        eng.stop()


def test_debug_steps_shows_the_new_fields():
    rec = StepRecorder(capacity=4)
    with rec.loop_step(annotate=False):
        rec.note(kv_blocks_live=2, kv_blocks_cached=1, kv_blocks_free=5)
        rec.start()
        with rec.phase("build"):
            rec.note_program("prefill")
            rec.note_program("prefill_cached")
        rec.record("prefill", rows=1, tokens=8)
    status, doc = _get_json(rec, "/debug/steps")
    assert status == 200
    assert set(doc["phases"]) >= {"idle_wait", "schedule", "build",
                                  "enqueue", "readback", "emit"}
    (r,) = doc["steps"]
    for key in ("start_unix", "end_unix", "phases", "gap_before_s",
                "gap_phases", "program", "waiting", "running",
                "kv_blocks_live", "kv_blocks_cached", "kv_blocks_free"):
        assert key in r, key
    assert r["program"] == "prefill+prefill_cached"
    assert (r["kv_blocks_live"], r["kv_blocks_cached"],
            r["kv_blocks_free"]) == (2, 1, 5)


def test_obs_imports_without_jax():
    code = ("import sys\n"
            "sys.modules['jax'] = None  # any import of it now fails\n"
            "from production_stack_tpu.obs import debug, steps\n"
            "rec = steps.StepRecorder()\n"
            "with rec.loop_step(annotate=True):\n"
            "    rec.start()\n"
            "    with rec.phase('build'):\n"
            "        pass\n"
            "    assert rec.record('prefill')['phases'].keys() == {'build'}\n"
            "print('ok')\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


# ---------------------------------------------------------------------------
# Prefill decomposition profiler: hermetic artifact schema
# ---------------------------------------------------------------------------


def test_prefill_profile_hermetic_schema():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "benchmarks", "prefill_profile.py"),
         "--hermetic"],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["metric"] == "prefill_profile"
    assert doc["hermetic"] is True
    assert doc["backend"] == "cpu"
    assert doc["chunks"], "profiler produced no per-chunk rows"
    for row in doc["chunks"]:
        for key in ("offset", "context", "full_s", "noattn_s", "nowrite_s",
                    "bare_matmul_s"):
            assert key in row, key
            assert row[key] is not None
        for key in ("attention_est_s", "copy_est_s", "matmul_est_s"):
            assert key in row["components"], key
        # A number, of either sign: each is the difference of two walls
        # of a tiny CPU program, which a neighbouring test worker moves
        # by more than the program takes. The schema is what is held.
        assert math.isfinite(row["full_s"])
        assert math.isfinite(row["bare_matmul_s"])
    assert doc["floors"] is None  # the CPU has no HBM peak to floor by
    # The committed artifact must match the schema the profiler emits
    # today (drift check for BENCH_PREFILL_PROFILE_*.json).
    committed = os.path.join(REPO_ROOT, "BENCH_PREFILL_PROFILE_r11.json")
    with open(committed) as f:
        art = json.load(f)
    assert art["metric"] == "prefill_profile"
    assert set(art["chunks"][0]["components"]) == \
        set(doc["chunks"][0]["components"])
