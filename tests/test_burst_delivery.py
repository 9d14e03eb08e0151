"""What a burst gives one sequence reaches its callback as one delivery
(``scheduler.TokenDelivery``, ``server._TokenStream``), and nobody
downstream can tell: the SSE bytes of a request are those of a per-token
reference (the stream as it was, a plain callable and a hand-over a
token, on the same engine and seed), event for event, where the burst
ends the sequence, where the handler does, with logprobs, with ``n``
choices and through a verify burst; and a plain callable sees a call a
token, in order, and the reason last and once, aborts included."""

import asyncio
import re
import threading

import pytest

from production_stack_tpu.engine import server as server_mod
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.server import EngineServer, run_engine_server

# decode_steps is 8: a request's first token is its prefill's, the eight
# after it are its first burst's (output indices 1..8)
ENGINE = dict(model="tiny-llama", max_model_len=256, max_num_seqs=4,
              block_size=8, num_blocks=64, max_loras=0)
PROMPT = "hello world"
REPETITIVE = [3, 4] * 10  # prompt lookup drafts from it


class _PerTokenStream:
    """The server's stream as it was before a burst came as one delivery:
    offers no ``on_burst``, so the core calls it once per token, and each
    call is a hand-over to the loop."""

    def __init__(self, loop):
        self.loop = loop
        self.queue = asyncio.Queue()

    def __call__(self, token_id, finish):
        self.loop.call_soon_threadsafe(
            self.queue.put_nowait, (token_id, finish))

    async def __aiter__(self):
        while True:
            token_id, finish = await self.queue.get()
            yield token_id, finish
            if finish is not None:
                return


def _collect(core, prompt_ids, sampling, callback=None, rid="probe"):
    """Run one request on ``core`` to its end: the (payload, finish)
    calls a plain callable got, which calls ``callback`` inside each with
    their number so far."""
    calls, done = [], threading.Event()

    def on_token(payload, finish):
        calls.append((payload, finish))
        if callback is not None:
            callback(len(calls))
        if finish is not None:
            done.set()

    core.add_request(rid, list(prompt_ids), sampling, on_token)
    assert done.wait(120)
    return calls


def _greedy(max_tokens, **kw):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens,
                          ignore_eos=True, **kw)


async def _events(session, port, path, body, rid):
    """The SSE events of one streamed request, as the bytes between the
    blank lines, with the second it was created in taken out."""
    async with session.post(
            f"http://127.0.0.1:{port}{path}", json=dict(body, stream=True),
            headers={"X-Request-Id": rid}) as resp:
        assert resp.status == 200, await resp.text()
        raw = await resp.read()
    return re.sub(rb'"created": \d+', b'"created": 0', raw).split(b"\n\n")


def _serve_both_ways(config, cases):
    """{case: {"per_token": events, "burst": events}} from one server,
    each case streamed through the per-token reference first and then
    through the server's own stream; ``cases(core)`` makes {case: (path,
    body)} from the engine once it runs. Also the engine's step records
    and counters afterwards."""
    server = EngineServer(EngineConfig(**config))

    async def run():
        import aiohttp

        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        own, out = server_mod._TokenStream, {}
        try:
            made = cases(server.core)
            async with aiohttp.ClientSession() as session:
                for mode, stream in (("per_token", _PerTokenStream),
                                     ("burst", own)):
                    server_mod._TokenStream = stream
                    for case, (path, body) in made.items():
                        out.setdefault(case, {})[mode] = await _events(
                            session, port, path, body, f"eq-{case}")
        finally:
            server_mod._TokenStream = own
            await runner.cleanup()
        return out

    try:
        out = asyncio.run(run())
        return (out, server.core.step_recorder.snapshot(),
                server.core.stats())
    finally:
        server.core.stop()


def _text_of(events) -> str:
    import json

    text = ""
    for event in events:
        if event.startswith(b"data: {"):
            for choice in json.loads(event[6:])["choices"]:
                text += choice.get("text") or ""
    return text


PLAIN_CASES = ("length_mid_burst", "stop_id_mid_burst",
               "stop_string_mid_burst", "logprobs", "n2", "chat")


@pytest.fixture(scope="module")
def plain():
    found = {}

    def cases(core):
        tok = core.tokenizer
        free = [p for p, _ in _collect(
            core, tok.encode(PROMPT), _greedy(24)) if p is not None]
        # a token of the first burst, not its first nor its last, that
        # the answer has not held before: stopping on it ends the
        # sequence mid-burst
        at = next(i for i in range(4, 8) if free[i] not in free[:i])
        stop = tok.decode(free[at - 1:at + 1])
        text = tok.decode(free)
        assert text.find(stop) == at - 1 and stop.isascii()
        found.update(text=text, at=at, stop=stop)
        base = {"model": "tiny-llama", "prompt": PROMPT,
                "temperature": 0.0, "ignore_eos": True, "max_tokens": 24}
        return {
            "length_mid_burst": ("/v1/completions",
                                 dict(base, max_tokens=12)),
            "stop_id_mid_burst": ("/v1/completions", dict(
                base, stop_token_ids=[free[at]])),
            "stop_string_mid_burst": ("/v1/completions",
                                      dict(base, stop=[stop])),
            "logprobs": ("/v1/completions", dict(base, logprobs=2)),
            "n2": ("/v1/completions", dict(
                base, n=2, temperature=0.8, seed=7, max_tokens=13)),
            "chat": ("/v1/chat/completions", {
                "model": "tiny-llama", "temperature": 0.0,
                "ignore_eos": True, "max_tokens": 21, "logprobs": True,
                "top_logprobs": 2,
                "messages": [{"role": "user", "content": PROMPT}]}),
        }

    out, records, stats = _serve_both_ways(ENGINE, cases)
    return out, records, stats, found


@pytest.mark.parametrize("case", PLAIN_CASES)
def test_sse_bytes_equal_the_per_token_reference(plain, case):
    out, _, _, _ = plain
    events = out[case]
    assert events["burst"] == events["per_token"]
    # a chunk a token, as before: the events are not coalesced
    assert len(events["burst"]) >= 7 and events["burst"][-2:] == [
        b"data: [DONE]", b""]


def test_the_cases_end_where_they_say(plain):
    out, records, stats, found = plain
    at, text = found["at"], found["text"]
    assert _text_of(out["length_mid_burst"]["burst"]) == text[:12]
    assert b'"finish_reason": "length"' in out["length_mid_burst"]["burst"][-3]
    # the stop id's own token is emitted, the token that completes the
    # stop string is not, and nothing the burst held after either reaches
    # the client
    assert _text_of(out["stop_id_mid_burst"]["burst"]) == text[:at + 1]
    assert _text_of(out["stop_string_mid_burst"]["burst"]) == text[:at]
    for case in ("stop_id_mid_burst", "stop_string_mid_burst"):
        assert b'"finish_reason": "stop"' in out[case]["burst"][-3]
    # the engine delivered per sequence and burst to both kinds of stream
    bursts = [r for r in records if r.get("emit_tokens")]
    assert bursts and all(
        r["emit_callbacks"] == r["emit_rows"]
        and r["emit_callback_samples"] == r["emit_tokens"] for r in bursts)
    assert stats["emit_callbacks_total"] == sum(
        r["emit_callbacks"] for r in bursts)
    assert (stats["generation_tokens_total"]
            > 4 * stats["emit_callbacks_total"])


@pytest.fixture(scope="module")
def speculative():
    """The served events, and (draft length, tokens of it accepted) of
    every verify burst."""
    from production_stack_tpu.engine import core as core_mod

    def cases(core):
        return {"verify_burst": ("/v1/completions", {
            "model": "tiny-llama", "prompt": REPETITIVE,
            "temperature": 0.0, "ignore_eos": True, "max_tokens": 30})}

    accepted_of, verdicts = core_mod.accepted_prefix_len, []

    def watched(draft, sampled):
        j = accepted_of(draft, sampled)
        verdicts.append((len(draft), j))
        return j

    core_mod.accepted_prefix_len = watched
    try:
        out, records, _ = _serve_both_ways(
            dict(ENGINE, speculative_num_tokens=4), cases)
    finally:
        core_mod.accepted_prefix_len = accepted_of
    return out, records, verdicts


def test_sse_bytes_equal_through_a_verify_burst(speculative):
    out, records, verdicts = speculative
    events = out["verify_burst"]
    assert events["burst"] == events["per_token"]
    assert len(events["burst"]) >= 10
    # some verify burst accepted a part of its draft and not the rest
    assert any(0 < j < n for n, j in verdicts), verdicts
    # (a step's record holds the flush of the burst before it)
    flushed = [r for r in records if r.get("emit_tokens")]
    assert flushed and all(r["emit_callbacks"] == 1 for r in flushed)
    assert any(1 < r["emit_tokens"] < 5 for r in flushed)


# ---------------------------------------------------------------------------
# A plain callable, and an abort
# ---------------------------------------------------------------------------


class _Bursts:
    """A callback that takes a burst, and keeps what it was given."""

    def __init__(self, abort_after=None, abort=None):
        self.deliveries, self.done = [], threading.Event()
        self.abort_after, self.abort = abort_after, abort

    def __call__(self, payload, finish):
        self.on_burst([(payload, finish)])

    def on_burst(self, items):
        self.deliveries.append(list(items))
        if sum(map(len, self.deliveries)) == self.abort_after:
            self.abort()
        if items[-1][1] is not None:
            self.done.set()


@pytest.fixture(scope="module")
def core():
    from production_stack_tpu.engine.core import EngineCore
    import jax

    eng = EngineCore(EngineConfig(**ENGINE), devices=jax.devices()[:1])
    eng.start()
    try:
        yield eng
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def free_run(core):
    calls = _collect(core, core.tokenizer.encode(PROMPT),
                     _greedy(21, logprobs=2))
    assert len(calls) == 22
    return calls


def test_plain_callable_sees_a_call_a_token_and_the_reason_last(free_run):
    """The shape of ``chipbench/check.py``'s: ``(payload, None)`` per
    token, in order, then ``(None, reason)``, once."""
    *tokens, last = free_run
    assert last == (None, "length")
    assert all(finish is None and isinstance(payload, tuple)
               and set(payload[1]) == {"logprob", "top"}
               for payload, finish in tokens)


def test_a_burst_taker_gets_the_same_pairs_a_delivery_a_burst(core,
                                                              free_run):
    taker = _Bursts()
    core.add_request("taker", core.tokenizer.encode(PROMPT),
                     _greedy(21, logprobs=2), taker)
    assert taker.done.wait(120)
    assert [pair for items in taker.deliveries for pair in items] == free_run
    # the prefill's first token, two bursts of eight, and four tokens
    # with the reason behind them in the same delivery
    assert [len(items) for items in taker.deliveries] == [1, 8, 8, 5]


@pytest.mark.parametrize("after", [9, 5], ids=["between_bursts",
                                                "mid_burst"])
def test_abort_from_inside_a_plain_callable_is_its_last_call(core, free_run,
                                                             after):
    """A callable that aborts its request when it has seen ``after``
    tokens sees the sentinel next and nothing of the burst's rest, as
    when every token was a call."""
    rid = f"abort-plain-{after}"
    calls = _collect(
        core, core.tokenizer.encode(PROMPT), _greedy(21, logprobs=2),
        lambda n: n == after and core.abort_request(rid), rid=rid)
    assert calls == free_run[:after] + [(None, "abort")]


def test_abort_between_bursts_reaches_a_burst_taker_the_same(core, free_run):
    taker = _Bursts(abort_after=9,
                    abort=lambda: core.abort_request("abort-taker"))
    core.add_request("abort-taker", core.tokenizer.encode(PROMPT),
                     _greedy(21, logprobs=2), taker)
    assert taker.done.wait(120)
    assert [pair for items in taker.deliveries
            for pair in items] == free_run[:9] + [(None, "abort")]
    assert [len(items) for items in taker.deliveries] == [1, 8, 1]


def test_abort_from_another_thread_mid_burst_keeps_its_place(core):
    """The sentinel of an abort that arrives while a burst is being
    emitted rides behind the tokens emitted before it, in the same
    delivery; nothing is delivered after it."""
    from production_stack_tpu.engine.scheduler import TokenDelivery

    seen = []
    delivery = TokenDelivery(
        lambda payload, finish: seen.append((payload, finish)))
    delivery.hold()
    delivery(1, None)
    delivery(2, None)
    other = threading.Thread(target=lambda: delivery(None, "abort"))
    other.start()
    other.join(timeout=10)
    delivery(3, None)  # emitted before the engine saw the slot empty
    assert seen == [] and delivery.release()
    assert seen == [(1, None), (2, None), (None, "abort")]
    assert not delivery.release()
    delivery(None, "abort")  # outside a burst: a delivery of its own
    assert len(seen) == 3  # ... which a callable that has ended is spared


def test_aborts_racing_a_bursts_emission_never_overtake_its_tokens():
    """Threads abort requests while another emits bursts to them as the
    engine thread does (tokens held without the lock, released under
    it; an abort empties the slot and calls the sentinel under it):
    under a short switch interval every callback sees its tokens in
    order, every one of them that was emitted while the sequence still
    held its slot, then the sentinel once, and nothing after it."""
    import sys
    import time

    from production_stack_tpu.engine.scheduler import TokenDelivery

    lock, rounds = threading.RLock(), 3000
    seen = [[] for _ in range(rounds)]
    sure = [[] for _ in range(rounds)]  # emitted before any abort
    slots = [True] * rounds  # the sequence still holds its slot
    deliveries = [TokenDelivery(lambda p, f, calls=calls: calls.append((p, f)))
                  for calls in seen]
    started = threading.Event()

    def aborter(offset):
        started.wait(10)
        for i in range(offset, rounds, 3):
            with lock:
                if slots[i]:
                    slots[i] = False
                    deliveries[i](None, "abort")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    workers = [threading.Thread(target=aborter, args=(k,)) for k in range(3)]
    try:
        for w in workers:
            w.start()
        deadline = time.time() + 60
        live, token = list(range(rounds)), 0
        while live and time.time() < deadline:  # a burst to every live one
            started.set()
            for i in live:
                deliveries[i].hold()
                for _ in range(8):
                    if not slots[i]:
                        break
                    token += 1
                    deliveries[i](token, None)
                    if slots[i]:
                        sure[i].append(token)
                with lock:
                    deliveries[i].release()
            live = [i for i in live if slots[i]]
    finally:
        for w in workers:
            w.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not live and not any(w.is_alive() for w in workers)
    for calls, emitted in zip(seen, sure):
        *tokens, last = calls
        assert last == (None, "abort")
        assert all(f is None for _, f in tokens)
        got = [p for p, _ in tokens]
        # (one more may follow: emitted as the abort emptied the slot)
        assert got[:len(emitted)] == emitted and len(got) <= len(emitted) + 1
