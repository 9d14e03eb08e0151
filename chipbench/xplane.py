"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device busy
time, per-operation self time, and idle gaps by what the host was doing.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per executed operation. Operations nest (a ``while``
spans its body), so busy time is the union of the intervals and an
operation's time is its self time: its duration less its children's.
Host planes (``/host:...``) hold the host threads' TraceMe events, which
name what the host did during a gap on the device.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SHORT_GAP_S = 50e-6


def load(path: str):
    """{plane: {line: [(name, start_s, duration_s)]}} of an xplane file
    (or the newest one under a profile directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    return from_profile_data(data)


def from_profile_data(data):
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, ev.start_ns * 1e-9,
                               ev.duration_ns * 1e-9))
    return planes


def union_s(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(events):
    """[(name, self_seconds)] for nested (name, start, duration) events:
    each event's duration less that of the events directly inside it."""
    out, stack = [], []  # stack of [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1] - 1e-12:
            done = stack.pop()
            out.append((done[0], max(done[2], 0.0)))
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    out.extend((name, max(self, 0.0)) for name, _, self in stack)
    return out


def op_family(name: str) -> str:
    """``fusion.123`` and ``fusion.7`` are one family: ``fusion``. A name
    may be ``%fusion.12 = ...``: keep the bare name."""
    name = name.lstrip("%").split(" ")[0].split("(")[0]
    return re.sub(r"[.\d]+$", "", name) or name


def _gaps(intervals):
    """Idle (start, end) stretches between merged busy intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])]


def reduce(planes: dict, top: int = 10) -> dict:
    """busy_s and window_s averaged over the device planes, per-family
    self seconds of device operations, and idle gaps by cause."""
    devices = {n: p for n, p in planes.items()
               if DEVICE_PLANE.match(n) and p.get(OPS_LINE)}
    if not devices:
        raise ValueError(
            f"no device plane with an '{OPS_LINE}' line in the trace "
            f"(planes: {sorted(planes)})")
    host_events = []
    for name, plane in planes.items():
        if name.startswith("/host:"):
            for events in plane.values():
                host_events.extend(events)
    host_events.sort(key=lambda e: e[1])
    busy, window, families, counts, causes = [], [], {}, {}, {}
    for plane in devices.values():
        events = plane[OPS_LINE]
        spans = [(s, s + d) for _, s, d in events]
        busy.append(union_s(spans))
        window.append(max(e for _, e in spans) - min(s for s, _ in spans))
        for name, seconds in self_times(events):
            fam = op_family(name)
            families[fam] = families.get(fam, 0.0) + seconds
            counts[fam] = counts.get(fam, 0) + 1
        gaps = _gaps(spans)
        ends = sorted((s + d, n) for n, s, d in events)
        for g0, g1 in gaps:
            length = g1 - g0
            if length <= SHORT_GAP_S:
                cause = "short_gaps"
            else:
                mid = 0.5 * (g0 + g1)
                covering = [e for e in host_events
                            if e[1] <= mid <= e[1] + e[2]]
                if covering:
                    cause = "host:" + min(covering, key=lambda e: e[2])[0]
                else:
                    before = [n for t, n in ends if t <= g0 + 1e-9]
                    cause = "after:" + (op_family(before[-1]) if before
                                        else "start")
            causes[cause] = causes.get(cause, 0.0) + length
    n = len(devices)
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "busy_s": sum(busy) / n, "window_s": sum(window) / n,
        "devices": n,
        "op_seconds": {k: v / n for k, v in families.items()},
        "op_counts": {k: v / n for k, v in counts.items()},
        "device_ops": [[k, v / n] for k, v in by_time(families)],
        "idle_gaps": [[k[:80], v / n] for k, v in by_time(causes)],
    }


def kernel_seconds(reduced: dict, kernels) -> float:
    """Self seconds of the operation families whose name contains one of
    ``kernels``."""
    return sum(v for k, v in reduced["op_seconds"].items()
               if any(key in k for key in kernels))
