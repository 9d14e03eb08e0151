"""The host's half of a token is measured per burst and never per token
(``obs/steps.py``, "The budget"): what the loop's clock and the request's
trace read while a burst is flushed is counted here at two burst lengths
and held to the budget's own arithmetic; then the fields those reads fill
(CPU beside wall, what an ``emit`` delivered, the two markers of the
hand-over to the server's loop)."""

import collections
import sys
import threading
import time

import jax
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.server import _TokenStream
from production_stack_tpu.obs.steps import StepRecorder
from production_stack_tpu.obs.trace import StageClock

SEQUENCES = 4
BURSTS = (2, 8)  # decode steps a burst
CLOCKS = ("time", "perf_counter", "thread_time")
# A flush of one burst to S sequences may read, by the budget: one wall
# stamp a sequence; the two edges of ``readback`` and ``emit`` on both
# clocks, the two markers' postings and two reads around one callback a
# sequence; and it may post two markers.
BUDGET = {"time": (0, 1), "perf_counter": (6, 2), "thread_time": (4, 0),
          "post": (2, 0)}


class FakeLoop:
    """Takes what is posted to it, from any thread, and runs it later in
    the order it came: the server's loop as far as the core can tell.
    ``ended`` counts the streams whose last delivery it was handed."""

    def __init__(self):
        self.posted = []
        self.ended = threading.Semaphore(0)

    def call_soon_threadsafe(self, fn, *args):
        self.posted.append((fn, args))
        if _is_delivery(fn) and args[0][-1][1] is not None:
            self.ended.release()


def _is_delivery(fn) -> bool:
    """A stream's ``queue.put_nowait`` of a list of (token, finish), and
    not a marker."""
    return getattr(fn, "__name__", "") == "put_nowait"


def _engine(decode_steps: int) -> EngineCore:
    eng = EngineCore(EngineConfig(
        model="tiny-llama", max_model_len=256, max_num_seqs=SEQUENCES,
        num_blocks=64, decode_steps=decode_steps, min_prefill_bucket=16,
        max_loras=0), devices=jax.devices()[:1])
    eng.start()
    return eng


def _serve(eng, loop, tokens: int, sequences: int = SEQUENCES):
    """``sequences`` requests of ``tokens`` tokens each through streams
    like the server's, all running before any decodes; returns their
    clocks once the engine has finished them."""
    clocks = []
    with eng._step_lock:  # every request waits before the first step
        for i in range(sequences):
            clocks.append(StageClock())
            eng.add_request(
                f"budget-{tokens}-{i}", [1, 2, 3, 4, 5 + i],
                SamplingParams(temperature=0.0, max_tokens=tokens,
                               ignore_eos=True),
                _TokenStream(loop), trace=clocks[-1])
    for _ in range(sequences):
        assert loop.ended.acquire(timeout=120)
    return clocks


@pytest.fixture(scope="module")
def counted():
    """{K: [counts of one flush, ...]} for the flushes of bursts that
    delivered K tokens to each of the sequences: the calls the engine
    thread made of each clock and of the server's hook inside
    ``_flush_pending_burst``, ``readback`` and the markers included, and
    under ``handed`` all that the loop was handed meanwhile."""
    out = {}
    real = {name: getattr(time, name) for name in CLOCKS}
    for K in BURSTS:
        eng = _engine(K)
        loop = FakeLoop()
        calls = collections.Counter()
        inside = threading.local()

        def counting(name):
            def clock():
                if getattr(inside, "on", False):
                    calls[name] += 1
                return real[name]()
            return clock

        def post(fn, *args):
            calls["post"] += 1
            loop.call_soon_threadsafe(fn, *args)

        flush, flushes = eng._flush_pending_burst, []

        def flush_counted():
            before = eng.generation_tokens_total, len(loop.posted)
            calls.clear()
            inside.on = True
            try:
                flush()
            finally:
                inside.on = False
            if eng.generation_tokens_total - before[0] == K * SEQUENCES:
                flushes.append(dict(
                    calls, handed=len(loop.posted) - before[1]))

        try:
            _serve(eng, loop, 2 * K + 1)  # warm: compiles
            eng.post_to_server_loop = post
            eng._flush_pending_burst = flush_counted
            for name in CLOCKS:
                setattr(time, name, counting(name))
            _serve(eng, loop, 4 * K + 1)
        finally:
            for name in CLOCKS:
                setattr(time, name, real[name])
            eng.stop()
        out[K] = flushes
    return out


@pytest.mark.parametrize("name", [*CLOCKS, "post"])
def test_a_burst_reads_no_clock_per_token(counted, name):
    """Eight times the tokens, the same reads; and no more than the
    budget allows for these sequences."""
    fixed, per_sequence = BUDGET[name]
    assert len(counted[2]) >= 2 and len(counted[8]) >= 2
    short = {f.get(name, 0) for f in counted[2]}
    long = {f.get(name, 0) for f in counted[8]}
    assert short == long and len(short) == 1, (name, counted)
    assert 0 < short.pop() <= fixed + per_sequence * SEQUENCES


@pytest.mark.parametrize("K", BURSTS)
def test_a_burst_is_handed_over_once_a_sequence(counted, K):
    """What a burst gives a sequence reaches its stream as one delivery:
    the loop is handed one a sequence and the two markers, whether the
    burst is of two steps or of eight."""
    assert len(counted[K]) >= 2
    assert {f["handed"] for f in counted[K]} == {SEQUENCES + 2}, counted[K]


def _spin(seconds: float) -> None:
    """Hold the processor for ``seconds`` of this thread's own time,
    however long the machine takes to give them."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def test_cpu_beside_wall_with_nested_phases_excluded():
    rec = StepRecorder(capacity=4)
    with rec.loop_step(annotate=False):
        with rec.phase("schedule"):
            with rec.phase("idle_wait"):
                time.sleep(0.03)
        rec.start()
        with rec.phase("build"):
            time.sleep(0.03)  # off the processor: build's own
            with rec.phase("emit"):
                _spin(0.03)  # on it: emit's, and none of build's
        r = rec.record("decode_burst", rows=1)
    assert set(r["phases_cpu"]) == set(r["phases"]) == {"build", "emit"}
    assert set(r["gap_phases_cpu"]) == set(r["gap_phases"]) == {
        "schedule", "idle_wait"}
    for wall, cpu in ((r["phases"], r["phases_cpu"]),
                      (r["gap_phases"], r["gap_phases_cpu"])):
        # (the two clocks are the kernel's: around a blocking call they
        # disagree by microseconds)
        assert all(0.0 <= cpu[p] <= wall[p] + 5e-5 for p in wall)
    assert r["phases_cpu"]["emit"] >= 0.02
    assert r["phases_cpu"]["build"] < 0.01 <= 0.03 <= r["phases"]["build"]
    assert r["gap_phases_cpu"]["idle_wait"] < 0.01
    totals = rec.phase_stats()
    assert totals["emit"]["cpu_seconds"] == pytest.approx(
        r["phases_cpu"]["emit"], abs=1e-5)
    assert totals["readback"]["cpu_seconds"] == 0.0


def test_amend_by_step_early_late_and_gone():
    rec = StepRecorder(capacity=2)
    with rec.loop_step(annotate=False):
        assert rec.open_step() is None
        rec.start()
        assert rec.open_step() == 1
        rec.amend(1, deliver_wake_s=0.5)  # before the record is made
        first = rec.record("decode_burst")
    assert first["deliver_wake_s"] == 0.5
    rec.mark(1, "deliver_drain_s", time.perf_counter() - 0.25)
    assert 0.25 <= first["deliver_drain_s"] < 0.35
    for _ in range(2):
        rec.record("decode_burst", 0.01)
    rec.amend(1, late=True)  # has left the ring: dropped
    assert all("late" not in r for r in rec.snapshot())
    # what an iteration that made no record left under the next number
    with rec.loop_step(annotate=False):
        rec.start()
        rec.amend(rec.open_step(), stale=True)
    with rec.loop_step(annotate=False):
        rec.start()
        assert "stale" not in rec.record("decode_burst")


def test_amend_from_another_thread_never_lands_on_the_wrong_step():
    """The server's loop amends while the engine thread records: under a
    short switch interval a field reaches the record of its own step or
    none, whether it came before the record or after."""
    rec = StepRecorder(capacity=64)
    stop = threading.Event()

    def amender():
        while not stop.is_set():
            n = rec.recorded_total
            rec.amend(n + 1, early=n + 1)
            rec.amend(n, late=n)
            rec.amend(n - 100, gone=True)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    workers = [threading.Thread(target=amender) for _ in range(3)]
    try:
        for w in workers:
            w.start()
        kept = []
        deadline = time.time() + 20
        while len(kept) < 3000 and time.time() < deadline:
            with rec.loop_step(annotate=False):
                rec.start()
                kept.append(rec.record("decode_burst", rows=1))
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=10)
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers) and len(kept) == 3000
    assert all(r.get("early", r["step"]) == r["step"]
               and r.get("late", r["step"]) == r["step"]
               and "gone" not in r for r in kept)
    assert sum("late" in r for r in kept) > 0
    assert len(rec._early) <= 1  # only the step not yet recorded may wait


@pytest.fixture(scope="module")
def served():
    """A run of four requests over three and more bursts, with the hook:
    (records oldest first, what the fake loop was handed, the engine's
    token count, the requests' clocks)."""
    eng, loop = _engine(8), FakeLoop()
    eng.post_to_server_loop = loop.call_soon_threadsafe
    try:
        clocks = _serve(eng, loop, 21)
        time.sleep(0.3)  # the trailing flush
        records = eng.step_recorder.snapshot()[::-1]
        return records, loop.posted, eng.generation_tokens_total, clocks
    finally:
        eng.stop()


def test_emit_counts_add_up_to_the_engines_own(served):
    records, posted, generated, clocks = served
    # a request's first token is its prefill's, and is not in the count
    assert (sum(r.get("emit_tokens", 0) for r in records) == generated
            == SEQUENCES * 20)
    assert sum(r.get("emit_finished", 0) for r in records) == SEQUENCES
    bursts = [r for r in records if r.get("emit_tokens")]
    # every delivery is timed, so the samples are the tokens themselves
    assert all(1 <= r["emit_callbacks"] == r["emit_rows"] <= SEQUENCES
               and r["emit_callback_samples"] == r["emit_tokens"]
               and 0.0 < r["emit_callback_s"] <= r["phases"]["emit"]
               for r in bursts)
    # ``emit_callbacks`` is what the loop was handed, less the markers
    # and each request's first token (a prefill's flush hands that over,
    # and like ``emit_tokens`` the count is of bursts); a burst that ends
    # a request hands its reason over with the tokens
    deliveries = [args[0] for fn, args in posted if _is_delivery(fn)]
    assert (sum(r.get("emit_callbacks", 0) for r in records)
            == len(deliveries) - SEQUENCES == 3 * SEQUENCES)
    assert sum(items[-1] == (None, "length") and len(items) > 1
               for items in deliveries) == SEQUENCES
    assert [c.tokens for c in clocks] == [21] * SEQUENCES


def test_markers_amend_their_step_after_its_last_token(served):
    records, posted, _, _ = served
    by_step = {r["step"]: r for r in records}
    assert not any("deliver_wake_s" in r for r in records)  # nothing ran
    open_step, tokens, seen = None, 0, []
    for fn, args in posted:  # the loop's turn: in the order posted
        if getattr(fn, "__name__", "") == "mark":
            step, field, _ = args
            if field == "deliver_wake_s":
                open_step, tokens = step, 0
            else:
                # every token of the burst is in its queue by now
                assert step == open_step
                assert tokens == by_step[step]["emit_tokens"] > 0
                seen.append(step)
        else:
            tokens += sum(token is not None for token, _ in args[0])
        fn(*args)
    assert seen == [r["step"] for r in records if r.get("emit_tokens")]
    for step in seen:
        assert by_step[step]["deliver_drain_s"] > 0
        assert by_step[step]["deliver_wake_s"] > 0


def test_no_hook_no_fields():
    eng, loop = _engine(8), FakeLoop()
    try:
        _serve(eng, loop, 10, sequences=2)
        records = eng.step_recorder.snapshot()
    finally:
        eng.stop()
    assert any(r.get("emit_tokens") for r in records)
    assert not any("deliver_wake_s" in r or "deliver_drain_s" in r
                   for r in records)
    assert all(_is_delivery(fn) for fn, _ in loop.posted)


def test_stream_gap_is_the_longest_interval_between_deliveries(served):
    _, _, _, clocks = served
    for clock in clocks:
        assert (clock.first_token <= clock.gap_start < clock.gap_end
                <= clock.last_token)
        assert 1 <= clock.gap_at_token < 21
    once = StageClock()
    once.delivered(time.time(), [])
    assert once.gap_end == 0.0 and once.tokens == 0
