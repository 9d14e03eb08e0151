"""The one general traffic generator: a traffic file in, a schedule out.

A traffic file (``traffic/<name>.json``) names a generator module
(``generators/<generator>.py``), its ``params``, a ``traffic_seed``, the
``ramp_s`` before the window, ``max_outstanding`` (0: open loop, every
request leaves at its due time; n: at most n in flight, the next leaves
when one ends), ``tail_s`` of further arrivals after the window, and the
latency ``limits``. The schedule is drawn from ``traffic_seed`` alone:
``--seed`` makes the weights and never reaches this file, so every run of
a cell offers the same requests at the same due times. No JAX here.
"""

from __future__ import annotations

import random


def build(registry, traffic: dict, seconds: float, vocab: int) -> dict:
    """{"preload": [token lists], "requests": [{due, prompt, max_tokens,
    id}], "window": [start, end]} for a window of ``seconds``."""
    gen = registry.module("generators", traffic["generator"])
    rng = random.Random(traffic["traffic_seed"])
    start = float(traffic["ramp_s"])
    end = start + float(seconds)
    out = gen.generate(traffic["params"], rng,
                       end + float(traffic.get("tail_s", 0.0)), vocab)
    for i, req in enumerate(out["requests"]):
        req["id"] = f"cb-{i:06d}"
    out["window"] = [start, end]
    return out
