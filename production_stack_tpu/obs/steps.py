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

That is the same weights+KV traffic model behind
``BENCH_DECODE_PROFILE_r05.json``'s floors, so the derived
``tpu:model_bandwidth_utilization`` gauge (achieved bytes/s over the
recent step window vs the device HBM floor) is directly comparable to
the profiled ``gap_vs_combined_floor``.

Everything here is stdlib-only and cheap: one dict append under a lock
per engine step (steps are milliseconds to seconds of device time; the
record is microseconds of host time — the recorder-overhead A/B test
holds it to <1% tokens/s).
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


class StepRecorder:
    """Bounded ring buffer of per-step records plus per-kind rollups.

    Thread-safe: the engine thread records, ``/metrics`` and
    ``/debug/steps`` read concurrently from the event loop.
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
        # Roofline constants. param_bytes is often unknown at construction
        # (weights load after the recorder exists); the core fills it in
        # lazily before the first record.
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

    # -- recording --------------------------------------------------------

    def record(
        self,
        kind: str,
        wall_s: float,
        *,
        rows: int = 0,
        tokens: int = 0,
        forwards: int = 1,
        kv_read_tokens: int = 0,
        kv_write_tokens: int = 0,
        batched: bool = False,
    ) -> dict:
        """Append one step record; returns it (tests inspect the shape)."""
        hbm_bytes = (
            forwards * self.param_bytes
            + (kv_read_tokens + kv_write_tokens) * self.kv_token_bytes
        )
        with self._lock:
            self.recorded_total += 1
            rec = {
                "step": self.recorded_total,
                "ts_unix": time.time(),
                "kind": kind,
                "wall_s": round(wall_s, 6),
                "rows": rows,
                "tokens": tokens,
                "forwards": forwards,
                "kv_read_tokens": kv_read_tokens,
                "kv_write_tokens": kv_write_tokens,
                "hbm_bytes": hbm_bytes,
                "batched": batched,
            }
            self._ring.append(rec)
            agg = self._kinds.setdefault(kind, [0.0, 0, 0, 0])
            agg[0] += wall_s
            agg[1] += 1
            agg[2] += tokens
            agg[3] += hbm_bytes
        return rec

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
        }
