"""Multi-turn chat sessions over a fixed pool, turns arriving as a Poisson
process. Parameters (traffic file, ``params``): ``sessions``,
``system_prompt_tokens``, ``sessions_per_system_prompt``,
``user_tokens`` / ``answer_tokens`` ({median, sigma, min, max}: lognormal,
clipped), ``max_history_tokens``, ``rate_per_s``, ``think_s_per_token``,
``think_s_min``.

A turn's prompt is the session's history (system prompt, then every
earlier user turn and its *scheduled* answer tokens) plus the new user
turn, so the whole schedule is known before the run and does not depend
on what the model says. A session whose next prompt would pass
``max_history_tokens`` is replaced by a fresh one on the same system
prompt. Sessions start with between zero and a full history of earlier
turns (``preload``: what set-up prefills), so the pool is in its steady
state when traffic starts. A session is not given a turn before its
previous answer can have ended (``think_s_min`` + answer tokens x
``think_s_per_token``); if no session is free the one free soonest is
taken.
"""

from __future__ import annotations

import math


def _length(rng, spec) -> int:
    n = int(round(rng.lognormvariate(math.log(spec["median"]),
                                     spec["sigma"])))
    return max(spec["min"], min(spec["max"], n))


def _tokens(rng, n: int, vocab: int):
    # ids the byte tokenizer renders as one printable character each
    return [rng.randrange(259, vocab) for _ in range(n)]


def generate(params: dict, rng, horizon_s: float, vocab: int) -> dict:
    p = params
    n_groups = -(-p["sessions"] // p["sessions_per_system_prompt"])
    systems = [_tokens(rng, p["system_prompt_tokens"], vocab)
               for _ in range(n_groups)]

    def fresh(slot):
        return {"slot": slot, "history": list(
            systems[slot // p["sessions_per_system_prompt"]]), "free_at": 0.0}

    def next_turn(session):
        """(prompt, answer_len), replacing the session when it is full."""
        user = _tokens(rng, _length(rng, p["user_tokens"]), vocab)
        answer_len = _length(rng, p["answer_tokens"])
        if (len(session["history"]) + len(user) + answer_len
                > p["max_history_tokens"]):
            session.update(fresh(session["slot"]), free_at=session["free_at"])
        prompt = session["history"] + user
        session["history"] = prompt + _tokens(rng, answer_len, vocab)
        return prompt, answer_len

    pool, preload = [], []
    for slot in range(p["sessions"]):
        session = fresh(slot)
        # somewhere between new and full, evenly over the pool
        target = p["system_prompt_tokens"] + (
            (p["max_history_tokens"] - p["system_prompt_tokens"])
            * slot // p["sessions"])
        while len(session["history"]) < target:
            before = len(session["history"])
            next_turn(session)
            if len(session["history"]) < before:
                break  # replaced: full
        pool.append(session)
        preload.append(list(session["history"]))

    requests, t = [], 0.0
    while True:
        t += rng.expovariate(p["rate_per_s"])
        if t >= horizon_s:
            break
        free = [s for s in pool if s["free_at"] <= t]
        session = (free[rng.randrange(len(free))] if free
                   else min(pool, key=lambda s: s["free_at"]))
        prompt, answer_len = next_turn(session)
        session["free_at"] = t + p["think_s_min"] + (
            answer_len * p["think_s_per_token"])
        requests.append({"due": t, "prompt": prompt,
                         "max_tokens": answer_len,
                         "session": session["slot"]})
    return {"preload": preload, "requests": requests}
