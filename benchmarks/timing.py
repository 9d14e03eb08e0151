"""Shared timing methodology of the kernel micro-benchmarks.

Every timed sequence ENDS IN A REAL READBACK (the host then has the
bytes, whatever the runtime's `block_until_ready` does), and the constant
dispatch + transfer cost is cancelled by DIFFERENCING two pipelined runs
of different depth: wall(N2) - wall(N1) over (N2 - N1) iterations is the
per-iteration device time.
"""

from __future__ import annotations

import time

import numpy as np


def timed_per_call(fn, *args, n1: int = 2, n2: int = 12,
                   readback=lambda out: np.asarray(out)) -> float:
    """Per-invocation device seconds for ``fn(*args)`` (see module
    docstring). Runs one warmup (compile + settle), then interleaved
    (n1, n2, n1, n2) pipelined batches, each ended by ``readback`` on
    the last output."""
    readback(fn(*args))
    walls = {}
    for n in (n1, n2, n1, n2):
        t0 = time.perf_counter()
        last = None
        for _ in range(n):
            last = fn(*args)
        readback(last)
        walls.setdefault(n, []).append(time.perf_counter() - t0)
    return (min(walls[n2]) - min(walls[n1])) / (n2 - n1)
