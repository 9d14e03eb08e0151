"""A fixed list of unrelated requests, all due when traffic starts; the
generator keeps ``max_outstanding`` (traffic file, top level) of them in
flight, so the engine's queue is never empty. Parameters (``params``):
``requests``, ``prompt_tokens`` / ``answer_tokens`` ({median, sigma, min,
max}: lognormal, clipped). No two prompts share a prefix: the first token
of each is its index.
"""

from __future__ import annotations

from chipbench.generators.sessions import _length, _tokens


def generate(params: dict, rng, horizon_s: float, vocab: int) -> dict:
    requests = []
    for i in range(params["requests"]):
        n = _length(rng, params["prompt_tokens"])
        prompt = [259 + i % (vocab - 259)] + _tokens(rng, n - 1, vocab)
        requests.append({"due": 0.0, "prompt": prompt,
                         "max_tokens": _length(rng,
                                               params["answer_tokens"])})
    return {"preload": [], "requests": requests}
