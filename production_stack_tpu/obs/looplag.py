"""Event-loop introspection: lag monitor, blocking-call detector, and
on-loop component attribution.

The router (and each engine server) is a single asyncio event loop;
when that loop stalls, every in-flight request pays the delay at once.
This module measures the three things needed to turn "the router is the
ceiling" into attributed evidence:

``LoopMonitor``
    A self-rescheduling ``loop.call_later`` tick that measures
    scheduling delay (how late the tick fired versus when it asked to
    run) into a bounded ring with p50/p99/max rollups, plus severity-
    bucketed stall counters (multiples of the stall threshold).

``BlockingCallDetector``
    A daemon watchdog thread that notices when the loop hasn't ticked
    for the stall threshold, samples the loop thread's stack via
    ``sys._current_frames()``, and aggregates offending frames into a
    top-blockers table (stall counts + cumulative stall seconds keyed
    by ``file:line:func``) — executor-worthy work hiding on the loop is
    named, not guessed.

``LoopComponentTimers``
    On-loop CPU-seconds per named component. ``wrap()`` drives a
    coroutine resume-by-resume, timing only the synchronous slices that
    actually hold the loop (awaited off-loop time is excluded);
    ``measure()`` covers plain synchronous sections.

Everything here is stdlib-only and hermetic: ``observe()`` and
``sample()`` accept explicit ``now`` values so tests can replay
synthetic stalls without a live loop. Metric export lives with each
server's scrape path (``router/metrics.py`` mirrors into the prometheus
registry; ``engine/server.py`` emits hand-rolled ``tpu:`` lines), and
``GET /debug/loop`` (privileged) serves the same rollups plus the
top-blockers table.
"""

from __future__ import annotations

import sys
import threading
import time
import types
from collections import deque
from typing import Any, Callable, Dict, List, Optional

#: Stall severity buckets: (label, multiple of the stall threshold).
#: Each stall increments exactly one bucket — the highest it reaches —
#: so the buckets are disjoint and their sum is the total stall count.
STALL_BUCKETS = (("1x", 1.0), ("5x", 5.0), ("20x", 20.0))

#: Default stall threshold: a callback holding the loop for 100 ms is
#: already ~100 concurrent requests' worth of added latency.
DEFAULT_STALL_THRESHOLD_S = 0.1

#: Default tick interval. Lag resolution is one interval; 50 ms keeps
#: the tick itself invisible in profiles (20 wakeups/s).
DEFAULT_TICK_INTERVAL_S = 0.05

#: Router components the attribution shim knows about. Shims are
#: installed by the router wiring; the tuple exists so the metrics
#: surface and docs agree on the label set.
ROUTER_COMPONENTS = (
    "qos_admission",
    "fleet_pull",
    "kv_controller",
    "streaming_relay",
    "relay_feed",
    "slo_classify",
    "metrics_scrape",
)

#: Attribution key used when the watchdog cannot resolve the loop
#: thread's frame (thread not yet registered, or already exited).
UNATTRIBUTED = "unattributed"


def _frame_location(frame) -> str:
    """``file:line:func`` with the filename shortened to its last two
    path components (enough to disambiguate, short enough to label)."""
    code = frame.f_code
    parts = code.co_filename.replace("\\", "/").split("/")
    short = "/".join(parts[-2:])
    return f"{short}:{frame.f_lineno}:{code.co_name}"


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = max(0, min(len(sorted_vals) - 1,
                     int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class LoopComponentTimers:
    """Cumulative on-loop CPU-seconds per named component."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._seconds: Dict[str, float] = {}
        self._calls: Dict[str, int] = {}

    def add(self, component: str, seconds: float) -> None:
        with self._lock:
            self._seconds[component] = (
                self._seconds.get(component, 0.0) + seconds)
            self._calls[component] = self._calls.get(component, 0) + 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._seconds)

    def stats(self) -> Dict[str, dict]:
        with self._lock:
            return {
                comp: {
                    "seconds": round(self._seconds[comp], 6),
                    "calls": self._calls.get(comp, 0),
                }
                for comp in sorted(self._seconds)
            }

    def measure(self, component: str):
        """Context manager timing a synchronous on-loop section."""
        return _MeasureCtx(self, component)

    async def wrap(self, component: str, coro):
        """Coroutine measuring ``coro``'s on-loop time (a real coroutine,
        not the bare generator below: ``create_task`` takes nothing else).

        Drives the coroutine resume-by-resume: each ``send``/``throw``
        runs synchronously on the event loop, so the sum of those
        slices is exactly the CPU time the component held the loop.
        Time parked on an await (the ``yield`` back to the loop) is not
        counted. The total is recorded once, when the coroutine
        finishes, errors, or is cancelled.
        """
        return await _drive(coro, lambda s: self.add(component, s))


class _MeasureCtx:
    __slots__ = ("_timers", "_component", "_t0")

    def __init__(self, timers: LoopComponentTimers, component: str):
        self._timers = timers
        self._component = component

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc_info):
        self._timers.add(self._component,
                         time.perf_counter() - self._t0)
        return False


@types.coroutine
def _drive(coro, record: Callable[[float], None]):
    """Generator-coroutine that forwards every resume into ``coro``
    while timing only the synchronous slices (see ``wrap``)."""
    total = 0.0
    value: Any = None
    exc: Optional[BaseException] = None
    try:
        while True:
            t0 = time.perf_counter()
            try:
                if exc is not None:
                    pending, exc = exc, None
                    yielded = coro.throw(pending)
                else:
                    yielded = coro.send(value)
            except StopIteration as stop:
                total += time.perf_counter() - t0
                return stop.value
            except BaseException:
                total += time.perf_counter() - t0
                raise
            total += time.perf_counter() - t0
            value = None
            try:
                value = yield yielded
            except BaseException as caught:  # incl. CancelledError
                exc = caught
    finally:
        record(total)


class BlockingCallDetector(threading.Thread):
    """Watchdog thread attributing loop stalls to the blocking frame.

    Polls at a fraction of the stall threshold; whenever the monitored
    loop hasn't ticked for at least the threshold it samples the loop
    thread's current stack and charges the elapsed stall time to the
    innermost frame's ``file:line:func``. Attribution uses a watermark
    (``now - max(last_tick, previous_poll)``) so cumulative attributed
    seconds track the full stall duration even when the watchdog
    itself is scheduled late under load.
    """

    def __init__(self, monitor: "LoopMonitor",
                 poll_s: Optional[float] = None):
        super().__init__(daemon=True,
                         name=f"loop-watchdog-{monitor.service}")
        self.monitor = monitor
        self.poll_s = (poll_s if poll_s is not None
                       else max(0.01, monitor.stall_threshold_s / 4.0))
        self._stop_event = threading.Event()
        self._lock = threading.Lock()
        # key -> {"stalls": int, "samples": int, "stall_s": float,
        #         "stack": [..]}; "stalls" counts distinct stall
        # episodes in which this frame was sampled.
        self._blockers: Dict[str, dict] = {}
        self._stalled = False
        self._stall_keys: set = set()
        self._watermark: Optional[float] = None
        self._charge_floor = 0.0
        self.samples_total = 0
        self.stall_s_attributed = 0.0
        self.stall_s_unattributed = 0.0

    def mark_boundary(self, now: Optional[float] = None) -> None:
        """Clamp attribution at a measurement-window boundary. A stall
        that straddles the boundary otherwise charges its pre-boundary
        seconds into the new window's delta, which is how the r13
        artifact recorded a per-rung ``loop_stall_attribution`` of 1.37
        (> 1.0): the harness snapshots blocker/stall counters at rung
        start, but the first in-rung poll charged time reaching back to
        a tick *before* the snapshot. Callers (e.g. the saturation
        harness at each rung boundary) invoke this right where they
        snapshot, and no in-window charge will predate it."""
        self._charge_floor = time.monotonic() if now is None else now

    def run(self) -> None:
        while not self._stop_event.wait(self.poll_s):
            try:
                self.sample()
            except Exception:  # pragma: no cover - never kill watchdog
                pass

    def stop(self) -> None:
        self._stop_event.set()

    def sample(self, now: Optional[float] = None,
               frame: Any = None) -> bool:
        """One watchdog pass. Public (with explicit ``now``/``frame``)
        so tests can replay stalls deterministically. Returns whether a
        stall was observed."""
        mon = self.monitor
        last = mon.last_tick()
        if last is None:
            return False
        if now is None:
            now = time.monotonic()
        if (now - last) < mon.stall_threshold_s:
            self._stalled = False
            self._stall_keys.clear()
            self._watermark = None
            return False
        new_stall = not self._stalled
        self._stalled = True
        if frame is None:
            frames = sys._current_frames()
            frame = (frames.get(mon.loop_thread_id)
                     if mon.loop_thread_id is not None else None)
        if frame is None:
            key, stack = UNATTRIBUTED, []
        else:
            key = _frame_location(frame)
            stack = []
            walker = frame
            while walker is not None and len(stack) < 8:
                stack.append(_frame_location(walker))
                walker = walker.f_back
            walker = None
        # Charge the elapsed stall time since the last attribution
        # point: the tick that started the stall on the first poll, the
        # previous poll afterwards.
        floor = last if self._watermark is None else self._watermark
        floor = max(floor, self._charge_floor)
        charged = max(0.0, now - max(last, floor))
        self._watermark = now
        with self._lock:
            self.samples_total += 1
            rec = self._blockers.setdefault(
                key, {"stalls": 0, "samples": 0, "stall_s": 0.0,
                      "stack": []})
            if new_stall or key not in self._stall_keys:
                rec["stalls"] += 1
                self._stall_keys.add(key)
            if new_stall:
                self._stall_keys = {key}
            rec["samples"] += 1
            rec["stall_s"] += charged
            rec["stack"] = stack
            if key == UNATTRIBUTED:
                self.stall_s_unattributed += charged
            else:
                self.stall_s_attributed += charged
        frame = None
        return True

    def top_blockers(self, limit: int = 10) -> List[dict]:
        """Blocker table sorted by cumulative stall seconds, worst
        first."""
        with self._lock:
            items = [
                {"frame": key,
                 "stalls": rec["stalls"],
                 "samples": rec["samples"],
                 "stall_s": round(rec["stall_s"], 6),
                 "stack": list(rec["stack"])}
                for key, rec in self._blockers.items()
            ]
        items.sort(key=lambda r: r["stall_s"], reverse=True)
        return items[:limit]

    def blocker_snapshot(self) -> Dict[str, dict]:
        """Cheap copy of per-key counters (no stacks) for delta
        computation across a measurement window."""
        with self._lock:
            return {key: {"stalls": rec["stalls"],
                          "stall_s": rec["stall_s"]}
                    for key, rec in self._blockers.items()}


class LoopMonitor:
    """Event-loop lag monitor (tick + ring + rollups) and facade over
    the watchdog and component timers.

    ``start()`` must be called on the loop being monitored (it captures
    the loop and its thread id); ``stop()`` is idempotent.
    """

    def __init__(self, service: str, *,
                 stall_threshold_s: float = DEFAULT_STALL_THRESHOLD_S,
                 interval_s: Optional[float] = None,
                 capacity: int = 4096,
                 watchdog_poll_s: Optional[float] = None):
        if stall_threshold_s <= 0:
            raise ValueError("stall_threshold_s must be positive")
        self.service = service
        self.stall_threshold_s = float(stall_threshold_s)
        self.interval_s = (float(interval_s) if interval_s is not None
                           else min(DEFAULT_TICK_INTERVAL_S,
                                    self.stall_threshold_s / 2.0))
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=self.capacity)  # (seq, t, lag)
        self.samples_total = 0
        self.lag_s_sum = 0.0
        self.stall_s_sum = 0.0
        self.stall_counts: Dict[str, int] = {
            label: 0 for label, _ in STALL_BUCKETS}
        self.components = LoopComponentTimers()
        self.detector = BlockingCallDetector(
            self, poll_s=watchdog_poll_s)
        self.loop_thread_id: Optional[int] = None
        self._loop = None
        self._handle = None
        self._last_tick: Optional[float] = None
        self._expected: Optional[float] = None
        self._started = False

    # -- lifecycle ----------------------------------------------------

    def start(self) -> None:
        """Begin ticking on the running loop and start the watchdog."""
        import asyncio

        if self._started:
            return
        self._loop = asyncio.get_running_loop()
        self.loop_thread_id = threading.get_ident()
        self._started = True
        now = time.monotonic()
        self._last_tick = now
        self._expected = now + self.interval_s
        self._handle = self._loop.call_later(self.interval_s, self._tick)
        self.detector.start()

    def stop(self) -> None:
        self._started = False
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
        self.detector.stop()
        if self.detector.is_alive():
            self.detector.join(timeout=1.0)

    def _tick(self) -> None:
        now = time.monotonic()
        self.observe(max(0.0, now - self._expected), now=now)
        self._last_tick = now
        if self._started:
            self._expected = now + self.interval_s
            self._handle = self._loop.call_later(
                self.interval_s, self._tick)

    # -- recording / queries ------------------------------------------

    def observe(self, lag_s: float,
                now: Optional[float] = None) -> None:
        """Record one lag sample (public for synthetic-stall tests)."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            self.samples_total += 1
            self.lag_s_sum += lag_s
            self._ring.append((self.samples_total, now, lag_s))
            if lag_s >= self.stall_threshold_s:
                self.stall_s_sum += lag_s
                label = STALL_BUCKETS[0][0]
                for name, mult in STALL_BUCKETS:
                    if lag_s >= self.stall_threshold_s * mult:
                        label = name
                self.stall_counts[label] += 1

    def last_tick(self) -> Optional[float]:
        return self._last_tick

    def seq(self) -> int:
        """Sequence number of the newest sample (monotonic; use as the
        ``since_seq`` marker for windowed percentiles)."""
        return self.samples_total

    def percentiles(self, since_seq: int = 0,
                    window_s: Optional[float] = None,
                    now: Optional[float] = None) -> dict:
        """p50/p99/max over ring samples newer than ``since_seq`` and,
        when ``window_s`` is given, no older than that many seconds."""
        with self._lock:
            entries = list(self._ring)
        if window_s is not None:
            if now is None:
                now = time.monotonic()
            cutoff = now - window_s
            entries = [e for e in entries if e[1] >= cutoff]
        if since_seq:
            entries = [e for e in entries if e[0] > since_seq]
        lags = sorted(e[2] for e in entries)
        return {
            "count": len(lags),
            "p50": round(_percentile(lags, 0.50), 6),
            "p99": round(_percentile(lags, 0.99), 6),
            "max": round(lags[-1], 6) if lags else 0.0,
        }

    def stalls(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.stall_counts)

    def summary(self, now: Optional[float] = None) -> dict:
        """One-call rollup of everything (served at /debug/loop)."""
        pct = self.percentiles(now=now)
        with self._lock:
            samples = self.samples_total
            lag_sum = self.lag_s_sum
            stall_s = self.stall_s_sum
            stalls = dict(self.stall_counts)
        det = self.detector
        return {
            "service": self.service,
            "interval_s": self.interval_s,
            "stall_threshold_s": self.stall_threshold_s,
            "capacity": self.capacity,
            "samples_total": samples,
            "lag_s_sum": round(lag_sum, 6),
            "lag": pct,
            "stalls": stalls,
            "stall_s_measured": round(stall_s, 6),
            "stall_s_attributed": round(det.stall_s_attributed, 6),
            "stall_s_unattributed": round(det.stall_s_unattributed, 6),
            "watchdog_poll_s": det.poll_s,
            "watchdog_samples": det.samples_total,
            "components": self.components.stats(),
        }

    def fed_snapshot(self, lag_window_s: Optional[float] = None,
                     blockers: int = 10) -> dict:
        """Worker-local state for the federation plane. ``lag_window_s``
        adds a windowed percentile rollup (the saturation harness reads
        per-worker lag p99 over exactly one rung's elapsed time)."""
        out = {
            "summary": self.summary(),
            "top_blockers": self.detector.top_blockers(limit=blockers),
        }
        if lag_window_s is not None:
            out["window"] = dict(
                self.percentiles(window_s=float(lag_window_s)),
                window_s=float(lag_window_s))
        return out
