"""Idle seconds of the device under one phase of the engine loop, as a
share, in percent, of the traced span. Every gap of the device's ``XLA
Ops`` line, short ones included, is put down to the ``engine.<phase>``
annotation open on the engine thread at the gap's midpoint, the innermost
where phases nest and at any depth below it: a gap during a transfer or a
dispatch that ``engine.build`` or ``engine.enqueue`` made is theirs, where
``chipbench.xplane.reduce`` names the gap after the runtime's own event.
The annotations and the device share the profiler's clock. A gap under no
phase (between two iterations, or in an iteration's own code between two
phases) is ``outside``; the phases' shares and ``outside`` add up to
``device_idle_pct``. Nothing where the run has no trace or the program
annotates no phase."""
import bisect

from chipbench import tracefile, xplane

STEP = "engine.step"
_cache: dict = {}


def innermost(events):
    """[(start, end, name)] that do not overlap: at each time the
    innermost of properly nested ``(name, start, duration)`` events."""
    out, stack, at = [], [], 0.0  # stack of (name, end)

    def close(until):
        nonlocal at
        while stack and stack[-1][1] <= until:
            name, end = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for name, start, duration in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and start > at:
            out.append((at, start, stack[-1][0]))
        at = start
        stack.append((name, start + duration))
    close(float("inf"))
    return out


def idle_by_phase(planes: dict) -> dict:
    """{"window_s", "idle": {phase or "outside": seconds}} of a trace as
    ``chipbench.xplane.load`` gives it, averaged over the device planes;
    None where no phase is annotated."""
    phases = [e for name, plane in planes.items() if name.startswith("/host:")
              for events in plane.values() for e in events
              if e[0].startswith("engine.") and e[0] != STEP]
    devices = [p[xplane.OPS_LINE] for n, p in planes.items()
               if xplane.DEVICE_PLANE.match(n) and p.get(xplane.OPS_LINE)]
    if not phases or not devices:
        return None
    open_at = innermost(phases)
    starts = [start for start, _, _ in open_at]
    idle, window = {}, 0.0
    for events in devices:
        spans = [(s, s + d) for _, s, d in events]
        window += max(e for _, e in spans) - min(s for s, _ in spans)
        for g0, g1 in xplane._gaps(spans):
            mid = 0.5 * (g0 + g1)
            i = bisect.bisect_right(starts, mid) - 1
            name = (open_at[i][2][len("engine."):]
                    if i >= 0 and mid < open_at[i][1] else "outside")
            idle[name] = idle.get(name, 0.0) + g1 - g0
    n = len(devices)
    return {"window_s": window / n,
            "idle": {k: v / n for k, v in idle.items()}}


def read(ctx, params):
    if ctx.device is None:
        return None
    path = getattr(ctx, "profile", None) or tracefile.newest()
    if not path:
        return None
    if path not in _cache:
        _cache.clear()
        _cache[path] = idle_by_phase(xplane.load(path))
    found = _cache[path]
    if found is None or found["window_s"] <= 0:
        return None
    return 100.0 * found["idle"].get(params["phase"], 0.0) / found["window_s"]
