"""Step flight recorder: per-engine-step records + roofline accounting.

The request-level flight recorder (:mod:`production_stack_tpu.obs.trace`)
answers "where did THIS request's time go"; this module answers "what was
the device doing, step by step". ``EngineCore._loop`` appends one record
per model step — prefill, budgeted prefill chunk step, fused decode
burst, or speculative verify burst — carrying the batch composition, the
scheduled token count, the measured wall time, and an *estimated* HBM
byte count from a small roofline model:

    bytes ≈ forwards × param_bytes            (weight reads)
          + kv_read_tokens  × kv_token_bytes  (paged-attention KV reads)
          + kv_write_tokens × kv_token_bytes  (KV page writes)

``param_bytes`` is what ONE forward reads of the weights
(``models/registry.py::forward_weight_bytes``): the parameter tree's
bytes, the layer stack counted once for every pass a family makes over it
(``Family.layer_passes``: 4 x the 48 layers + the rest = 20.1 GB of
Ouro-2.6B's 5.34 GB tree), so the utilization means for a looped model
what it means for the others. ``kv_token_bytes`` covers every page layer
of a token (a page layer a pass and layer there).

That is the same weights+KV traffic model behind
``BENCH_DECODE_PROFILE_r05.json``'s floors, so the derived
``tpu:model_bandwidth_utilization`` gauge (achieved bytes/s over the
recent step window vs the device HBM floor) is directly comparable to
the profiled ``gap_vs_combined_floor``.

The recorder also times the engine loop (``phase``): where the host
spent each step — scheduling, building arrays, enqueueing, waiting for
the device in ``device_get``, emitting tokens — goes into the step's
record and, under the same names, into the host plane of any
``jax.profiler`` trace (``engine.<phase>`` inside one ``engine.step`` per
loop iteration), so a gap on the device can be read against what the
host was doing, on the profiler's own clock.

A phase reads the thread's CPU clock beside the wall clock
(``phases_cpu`` / ``gap_phases_cpu``, the same keys: wall less CPU is the
time the engine thread held no processor). What a step's ``emit``
delivered rides in its record: ``emit_tokens``, ``emit_rows``,
``emit_finished`` (differences of counters the engine keeps anyway),
``emit_callbacks`` (the deliveries a burst's flush made to requests'
callbacks: one a sequence), ``emit_callback_s`` (the time those
deliveries took, each one timed) over ``emit_callback_samples`` (the
tokens they carried: ``emit_tokens`` again, kept under its name for the
readers that scale one by the other), and ``deliver_wake_s`` /
``deliver_drain_s``, which the server's loop writes later through
``amend`` when it runs the two markers a burst posts to it
(``EngineCore._flush_pending_burst``). A ``decode_burst`` record also
says how many of its rows took their first token on the device, the
burst built while their prefill still ran (``first_on_device_rows``,
noted once a burst by ``EngineCore._do_decode``).

**The budget: per burst, never per token.** The recorder runs in every
run: there is no "tracing off", so what it does is in the judged path.
The engine thread and the server's loop share one interpreter lock, and
at 128 rows a token leaves every 0.33 ms: 15-20 us a token cost the widest
cell 4.5% of its tokens/s (PERF_LEDGER.jsonl, PR 38). So nothing here, and
nothing the engine does for this module, runs once per token. Per phase:
two reads of each clock. Per sequence and burst: one wall stamp
(``StageClock.delivered``) and two ``perf_counter`` reads around one
delivery. Per burst: two callbacks posted to the server's loop. The next
span is added per burst too (``tests/test_emit_budget.py`` counts).

The engine keeps to the same budget towards its callers. The callback
of ``EngineCore.add_request(request_id, prompt, sampling, on_token)`` is
called ``on_token(payload | None, finish | None)``; what a burst gives
one sequence is one *delivery*: its tokens of the burst in order and,
where the burst ends the sequence, the reason behind them. A callback
that offers ``on_burst(items)`` gets a delivery as one call with the
list of ``(payload, finish)`` pairs (the server's stream hands it to its
loop with one ``call_soon_threadsafe``, where a call a token cost the
widest cell 1,024 of them a burst and the device waited for it); a plain
callable gets the pairs one call each, inside that one delivery
(``engine/scheduler.py::TokenDelivery``).

Everything here is stdlib-only and cheap: one dict append under a lock
per engine step and a dozen timed phases (steps are milliseconds to
seconds of device time; the record is microseconds of host time — the
recorder-overhead test holds it to <1% tokens/s at one row's pace and at
128 rows x 8 steps a burst and 3,000 tokens/s). JAX is imported
only when the first phase is annotated, and its absence makes the
annotations no-ops: the router imports this package.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

# Step kinds, in scheduling order. "fused" is reserved for the planned
# single fused prefill+decode step program (ROADMAP open item 1) so the
# /debug/steps schema and the Prometheus label set are stable when it
# lands.
STEP_KINDS = ("prefill", "prefill_chunk", "decode_burst", "spec_verify",
              "fused")

# What a waiting request is behind while a step of each kind holds the
# loop (``busy_between``; a fused step carries a decode burst).
KIND_CLASS = {"prefill": "prefill", "prefill_chunk": "prefill",
              "decode_burst": "decode", "spec_verify": "decode",
              "fused": "decode"}

# Phases of the engine loop, in the order a step meets them. The first two
# lie before a step's start (in ``gap_before_s``), the rest inside it.
PHASES = ("idle_wait", "schedule", "build", "enqueue", "readback", "emit")

# Published peaks of one device, keyed by JAX's ``device_kind``: the one
# table every roofline figure in the repo reads. Source: Google Cloud TPU
# documentation, the per-generation system-architecture pages ("TPU v4",
# "TPU v5e", "TPU v5p", "TPU v6e"). Decimal units.
DEVICE_PEAKS = {
    "TPU v4": {"hbm_bytes_per_s": 1228e9, "bf16_flops_per_s": 275e12},
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    "TPU v5p": {"hbm_bytes_per_s": 2765e9, "bf16_flops_per_s": 459e12},
    "TPU v5": {"hbm_bytes_per_s": 2765e9, "bf16_flops_per_s": 459e12},
    "TPU v6 lite": {"hbm_bytes_per_s": 1640e9, "bf16_flops_per_s": 918e12},
    "TPU v6e": {"hbm_bytes_per_s": 1640e9, "bf16_flops_per_s": 918e12},
}


def device_hbm_bytes_per_s(device=None) -> Optional[float]:
    """Peak HBM bytes/s of ``device`` (a ``jax.Device``) for the
    utilization gauge, or None where there is no peak to compare with
    (no device given, or not a TPU): the utilization is then absent, not
    computed against another chip's figure. ``TPU_STACK_HBM_GBS``
    (decimal bytes/s) overrides the table for a deployment. A TPU whose
    kind is not in the table is an error, not a default."""
    override = os.environ.get("TPU_STACK_HBM_GBS", "")
    if override:
        return float(override)
    if device is None or device.platform != "tpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]["hbm_bytes_per_s"]
    except KeyError:
        raise ValueError(
            f"no published peaks for TPU device_kind "
            f"{device.device_kind!r}: add it to obs/steps.py::DEVICE_PEAKS "
            f"with its source") from None


def _profiler_annotations():
    """``jax.profiler``'s (TraceAnnotation, StepTraceAnnotation), or
    (None, None) where JAX is not installed: the phases are then timed
    and not annotated."""
    try:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation
    except ImportError:
        return None, None
    return TraceAnnotation, StepTraceAnnotation


def _rounded(seconds: Dict[str, float]) -> Dict[str, float]:
    return {k: round(v, 6) for k, v in seconds.items()}


class _Phase:
    """One timed interval of the loop (``StepRecorder.phase``). Phases
    nest, and a phase's time is its own: what the phases inside it took
    is theirs, so the phases of a step add up to no more than its wall
    time. Beside the wall clock it reads the thread's CPU clock at the
    same two edges: wall less CPU is the time the engine thread held no
    processor (the interpreter lock, a blocking call, the kernel)."""

    __slots__ = ("rec", "name", "t0", "cpu0", "inner", "inner_cpu", "into",
                 "ann")

    def __init__(self, rec: "StepRecorder", name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        rec = self.rec
        self.inner = self.inner_cpu = 0.0
        self.into = self.ann = None
        if threading.get_ident() == rec._loop_thread:
            self.into = rec._into
            rec._open.append(self)
            if rec._annotate is not None:
                self.ann = rec._annotate("engine." + self.name)
                self.ann.__enter__()
        self.t0 = time.perf_counter()
        self.cpu0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        # the CPU interval inside the wall interval: CPU <= wall
        cpu = time.thread_time() - self.cpu0
        took = time.perf_counter() - self.t0
        rec, name = self.rec, self.name
        own, own_cpu = took - self.inner, cpu - self.inner_cpu
        if self.ann is not None:
            self.ann.__exit__(*exc)
        if self.into is not None:
            rec._open.pop()
            if rec._open:
                rec._open[-1].inner += took
                rec._open[-1].inner_cpu += cpu
            wall, on_cpu = self.into
            wall[name] = wall.get(name, 0.0) + own
            on_cpu[name] = on_cpu.get(name, 0.0) + own_cpu
        with rec._lock:
            total = rec._phase_totals.setdefault(name, [0.0, 0, 0.0])
            total[0] += own
            total[1] += 1
            total[2] += own_cpu
        return False


class _LoopStep:
    """One iteration of the engine loop (``StepRecorder.loop_step``)."""

    __slots__ = ("rec", "annotate", "ann")

    def __init__(self, rec: "StepRecorder", annotate: bool):
        self.rec, self.annotate, self.ann = rec, annotate, None

    def __enter__(self):
        rec = self.rec
        rec._loop_thread = threading.get_ident()
        rec._start = None
        rec._into = rec._gap
        rec._notes = {}
        rec._programs = []
        rec._annotate = None
        if self.annotate:
            if rec._annotations is None:
                rec._annotations = _profiler_annotations()
            rec._annotate, step_annotation = rec._annotations
            if step_annotation is not None:
                self.ann = step_annotation(
                    "engine.step", step_num=rec.recorded_total + 1)
                self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class StepRecorder:
    """Bounded ring buffer of per-step records plus per-kind rollups, and
    the one clock of the engine loop (``loop_step``, ``phase``, ``start``,
    ``record``).

    Thread-safe: the engine thread records, ``/metrics`` and
    ``/debug/steps`` read concurrently from the event loop. The phases of
    a step are the engine thread's; a ``phase`` entered on another thread
    (an embedding or an adapter load dispatching under the step lock)
    counts in the totals only.
    """

    def __init__(
        self,
        capacity: int = 1024,
        param_bytes: int = 0,
        kv_token_bytes: int = 0,
        hbm_bytes_per_s: Optional[float] = None,
        window_s: float = 60.0,
    ):
        self.capacity = max(1, int(capacity))
        # Roofline constants. param_bytes (the weight bytes one forward
        # reads) is often unknown at construction (weights load after the
        # recorder exists); the core fills it in lazily before the first
        # record.
        self.param_bytes = int(param_bytes)
        self.kv_token_bytes = int(kv_token_bytes)
        # None: no peak is known for this device (the CPU), and the
        # utilization is absent.
        self.hbm_bytes_per_s = hbm_bytes_per_s
        self.window_s = float(window_s)
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        # kind -> [wall_s_sum, count, tokens, hbm_bytes]
        self._kinds: Dict[str, List[float]] = {
            k: [0.0, 0, 0, 0] for k in STEP_KINDS}
        self.recorded_total = 0
        # phase -> [own seconds, entries, own CPU seconds], kept whether
        # or not the steps go to the ring.
        self._phase_totals: Dict[str, List[float]] = {
            p: [0.0, 0, 0.0] for p in PHASES}
        # The loop's clock (engine thread only). ``_into`` is where a
        # phase entered now adds its time: the gap before a step until
        # ``start``, the step's own phases from there to ``record``.
        self._loop_thread: Optional[int] = None
        self._annotations = None  # jax.profiler's classes, found once
        self._annotate = None
        self._open: List[_Phase] = []
        # (wall seconds, CPU seconds) by phase
        self._gap: tuple = ({}, {})
        self._into: tuple = self._gap
        self._start: Optional[tuple] = None
        self._notes: dict = {}
        self._programs: List[str] = []
        self._last_end: Optional[float] = None  # perf_counter
        # step -> fields that reached ``amend`` before the step's record
        # was made (under the lock: other threads amend)
        self._early: Dict[int, dict] = {}

    # -- the loop's clock -------------------------------------------------

    def loop_step(self, annotate: bool = True) -> _LoopStep:
        """Context manager around one iteration of the engine loop. With
        ``annotate`` the iteration is an ``engine.step`` in a profiler
        trace, numbered like the record it will make, and each phase
        inside it an ``engine.<phase>``."""
        return _LoopStep(self, annotate)

    def phase(self, name: str) -> _Phase:
        """Context manager that times ``name`` (one of ``PHASES``) into
        the totals, into the current step's record, and into the
        profiler's trace."""
        return _Phase(self, name)

    def start(self) -> None:
        """The step proper starts here: scheduling is done, what follows
        until ``record`` is the step's wall time."""
        self._into = ({}, {})
        self._start = (time.perf_counter(), time.time(), self._into)
        if self._early:
            # left by an iteration that opened this number and made no
            # record
            with self._lock:
                self._early.clear()

    def open_step(self) -> Optional[int]:
        """The number under which the step now open will be recorded, for
        ``amend``; None between steps."""
        return None if self._start is None else self.recorded_total + 1

    def note(self, **fields) -> None:
        """Fields of the record the current iteration will make
        (``waiting``, ``running``, the pool counts)."""
        self._notes.update(fields)

    def note_sum(self, **counts) -> None:
        """Counts that add up over the dispatches of one iteration."""
        for name, value in counts.items():
            self._notes[name] = self._notes.get(name, 0) + value

    def note_program(self, name: str, padded_tokens: int = 0) -> None:
        """A step program dispatched in this iteration, and for a prefill
        program the token positions it computes on (its rows x bucket,
        padding included: ``padded_tokens`` of the record, beside the
        real ``tokens``)."""
        if name not in self._programs:
            self._programs.append(name)
        if padded_tokens:
            self.note_sum(padded_tokens=padded_tokens)

    # -- recording --------------------------------------------------------

    def record(
        self,
        kind: str,
        wall_s: Optional[float] = None,
        *,
        rows: int = 0,
        tokens: int = 0,
        forwards: int = 1,
        kv_read_tokens: int = 0,
        kv_write_tokens: int = 0,
        batched: bool = False,
        ring: bool = True,
    ) -> Optional[dict]:
        """Append one step record; returns it (tests inspect the shape).
        Without ``wall_s`` the record is the step opened by ``start``: its
        wall time runs from there to now, and it carries the phases and
        the gap before it. With ``wall_s`` the record stands alone.
        ``ring=False`` keeps the rollups and makes no record."""
        now_perf, now = time.perf_counter(), time.time()
        phases = gap = ({}, {})
        notes: dict = {}
        gap_before = 0.0
        if wall_s is None:
            start_perf, start_unix, phases = self._start
            wall_s = now_perf - start_perf
            gap, self._gap = self._gap, ({}, {})
            notes = dict(self._notes, program="+".join(self._programs))
            if self._last_end is not None:
                gap_before = start_perf - self._last_end
            self._last_end = now_perf
        else:
            start_unix = now - wall_s
        self._start = None
        self._into = self._gap
        hbm_bytes = (
            forwards * self.param_bytes
            + (kv_read_tokens + kv_write_tokens) * self.kv_token_bytes
        )
        rec = None
        with self._lock:
            if ring:
                self.recorded_total += 1
                rec = {
                    "step": self.recorded_total,
                    "ts_unix": now,
                    "kind": kind,
                    "wall_s": round(wall_s, 6),
                    "rows": rows,
                    "tokens": tokens,
                    "forwards": forwards,
                    "kv_read_tokens": kv_read_tokens,
                    "kv_write_tokens": kv_write_tokens,
                    "hbm_bytes": hbm_bytes,
                    "batched": batched,
                    "start_unix": start_unix,
                    "end_unix": now,
                    "phases": _rounded(phases[0]),
                    "phases_cpu": _rounded(phases[1]),
                    "gap_before_s": round(gap_before, 6),
                    "gap_phases": _rounded(gap[0]),
                    "gap_phases_cpu": _rounded(gap[1]),
                    "program": "", "padded_tokens": 0,
                    "waiting": 0, "running": 0,
                    "kv_blocks_live": 0, "kv_blocks_cached": 0,
                    "kv_blocks_free": 0, **notes,
                    **self._early.pop(self.recorded_total, {}),
                }
                self._ring.append(rec)
            agg = self._kinds.setdefault(kind, [0.0, 0, 0, 0])
            agg[0] += wall_s
            agg[1] += 1
            agg[2] += tokens
            agg[3] += hbm_bytes
        return rec

    def amend(self, step: int, **fields) -> None:
        """Add ``fields`` to the record numbered ``step``, from any
        thread: what becomes known only after the step was recorded (or,
        on another thread, even before). Fields for a step not recorded
        yet wait for its record; for one that has left the ring they are
        dropped."""
        with self._lock:
            if step > self.recorded_total:
                self._early.setdefault(step, {}).update(fields)
            elif self._ring:
                at = step - self._ring[0]["step"]  # the ring's are in a row
                if at >= 0:
                    self._ring[at].update(fields)

    def mark(self, step: int, field: str, since: float) -> None:
        """Amend record ``step`` with the seconds from ``since``
        (``perf_counter``) to now: what a marker posted to another
        thread's queue runs when its turn comes."""
        self.amend(step, **{field: round(time.perf_counter() - since, 6)})

    # -- retrieval --------------------------------------------------------

    def snapshot(self, limit: Optional[int] = None,
                 kind: Optional[str] = None) -> List[dict]:
        """Newest-first list of records, optionally filtered by kind."""
        with self._lock:
            recs = list(self._ring)
        out = []
        for rec in reversed(recs):
            if kind is not None and rec["kind"] != kind:
                continue
            out.append(rec)
            if limit is not None and len(out) >= limit:
                break
        return out

    def kind_stats(self) -> Dict[str, dict]:
        """Lifetime per-kind rollups (every known kind always present, so
        the Prometheus series never vanish between scrapes)."""
        with self._lock:
            return {
                k: {"wall_s": v[0], "count": v[1], "tokens": v[2],
                    "hbm_bytes": v[3]}
                for k, v in self._kinds.items()
            }

    def phase_stats(self) -> Dict[str, dict]:
        """Lifetime seconds, entries and CPU seconds of each phase (its
        own time: the phases nested in it are counted under their
        names)."""
        with self._lock:
            return {k: {"seconds": v[0], "count": v[1], "cpu_seconds": v[2]}
                    for k, v in self._phase_totals.items()}

    def busy_between(self, t0: float, t1: float) -> dict:
        """Seconds of ``[t0, t1]`` (unix) during which a step that ended
        inside it held the loop, by ``KIND_CLASS``, and how many such
        steps: what a request that arrived at ``t0`` and started its
        prefill at ``t1`` stood behind. Its own prefill step ends after
        ``t1`` and is left out. Only as far back as the ring reaches."""
        out = {"decode": 0.0, "prefill": 0.0, "steps": 0}
        with self._lock:
            for rec in reversed(self._ring):
                if rec["end_unix"] <= t0:
                    break
                cls = KIND_CLASS.get(rec["kind"])
                if rec["end_unix"] > t1 or cls is None:
                    continue
                out[cls] += rec["end_unix"] - max(rec["start_unix"], t0)
                out["steps"] += 1
        return out

    def bandwidth_utilization(
            self, now: Optional[float] = None) -> Optional[float]:
        """Achieved HBM bytes/s over the recent step window divided by the
        device floor: estimated bytes moved by steps that STARTED inside
        the window, over their summed wall time (model-active seconds, not
        wall-clock — idle gaps between steps are not a bandwidth claim).
        None where the device has no published peak."""
        if self.hbm_bytes_per_s is None:
            return None
        if now is None:
            now = time.time()
        cutoff = now - self.window_s
        with self._lock:
            wall = 0.0
            moved = 0
            for rec in self._ring:
                if rec["ts_unix"] - rec["wall_s"] >= cutoff:
                    wall += rec["wall_s"]
                    moved += rec["hbm_bytes"]
        if wall <= 0.0 or self.hbm_bytes_per_s <= 0.0:
            return 0.0
        return (moved / wall) / self.hbm_bytes_per_s

    def summary(self) -> dict:
        """Header block for /debug/steps (everything but the records)."""
        return {
            "capacity": self.capacity,
            "recorded_total": self.recorded_total,
            "param_bytes": self.param_bytes,
            "kv_token_bytes": self.kv_token_bytes,
            "hbm_bytes_per_s": self.hbm_bytes_per_s,
            "window_s": self.window_s,
            "bandwidth_utilization": self.bandwidth_utilization(),
            "kinds": self.kind_stats(),
            "phases": self.phase_stats(),
        }
