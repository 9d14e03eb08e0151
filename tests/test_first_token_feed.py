"""The hand-over from a prefill to the decode burst behind it: the row's
first token reaches its first burst on the device (``EngineCore._exec_op``
scatters the prefill's sample into the burst's feedback array), so the
burst is built and enqueued while the prefill still runs, and the host
reads the token after the burst's dispatch. The streams are those of the
host's path (every first token read back before the burst is built), which
``_host_path`` forces by the one rule that tells the rows apart."""

import threading
import time

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.structured.api import StructuredSpec

CHUNK = 64  # groups of 4 at rung 32 and of 2 at rung 64; over 64: two spans
STEPS = 4


def _engine(**kw) -> EngineCore:
    kw = {"max_model_len": 512, "max_num_seqs": 4, "block_size": 8,
          "num_blocks": 256, "max_loras": 0, "prefill_chunk_size": CHUNK,
          "min_prefill_bucket": 16, "decode_steps": STEPS, **kw}
    return EngineCore(EngineConfig(model="tiny-llama", **kw),
                      devices=jax.devices()[:1])


def _host_path(core: EngineCore) -> EngineCore:
    """The same engine with every row on the host's path: the parent's
    order (first token read back, then the burst built)."""
    core._feeds_first_token = lambda req: False
    return core


def _prompt(n: int, salt: int) -> "list[int]":
    return [(7 * i + 31 * salt) % 200 + 1 for i in range(n)]


class Streams:
    """Callbacks for a set of requests: each stream's tokens, logprobs and
    finish reason, and one log of deliveries in the order they came."""

    def __init__(self, log=None):
        self.tokens, self.logprobs, self.finish = {}, {}, {}
        self.done = {}
        self.log = [] if log is None else log

    def callback(self, rid: str):
        self.tokens[rid], self.logprobs[rid] = [], []
        self.done[rid] = threading.Event()

        def on_token(payload, finish):
            if payload is not None:
                token, lp = (payload if isinstance(payload, tuple)
                             else (payload, None))
                self.tokens[rid].append(int(token))
                if lp is not None:
                    self.logprobs[rid].append(lp["logprob"])
                self.log.append(("token", rid))
            if finish is not None:
                self.finish[rid] = finish
                self.done[rid].set()
        return on_token

    def wait(self, *rids):
        for rid in rids or list(self.done):
            assert self.done[rid].wait(240), f"{rid} timed out"


def _add(core, streams, requests: dict):
    """Queue ``requests`` (rid -> (prompt, SamplingParams)) in one go."""
    with core._lock:
        for rid, (prompt, sampling) in requests.items():
            core.add_request(rid, list(prompt), sampling,
                             streams.callback(rid))
    if not core._thread.is_alive():
        core.start()


def _serve(core, requests: dict, one_at_a_time: bool = False) -> Streams:
    streams = Streams()
    if one_at_a_time:
        for rid, request in requests.items():
            _add(core, streams, {rid: request})
            streams.wait(rid)
    else:
        _add(core, streams, requests)
        streams.wait()
    return streams


def _bursts(core) -> "list[dict]":
    return core.step_recorder.snapshot(kind="decode_burst")[::-1]


def _nothing_held(core):
    """No slot, no live page, no deferred readback: what an idle engine
    holds, whatever ended its requests. (The burst scheduled past the
    last stream's end is read back by the loop's next, idle, turn.)"""
    deadline = time.time() + 60
    while True:
        with core._step_lock, core._lock:
            idle = (core._pending_burst is None
                    and not core._pending_prefills)
            if idle:
                assert core.scheduler.slots == [None] * len(
                    core.scheduler.slots)
                live, cached, free = core.kv_mgr.block_counts()
                assert (live, cached + free) == (0, core.num_blocks)
                return
        assert time.time() < deadline, "the engine never came to rest"
        time.sleep(0.01)


# ---------------------------------------------------------------------------
# (a) the streams are the streams of the same requests alone
# ---------------------------------------------------------------------------

SAMPLINGS = {
    "greedy": lambda i: {"temperature": 0.0},
    "seeded": lambda i: {"temperature": 1.0, "seed": 1000 + i, "top_k": 20},
}


def _mixed(kind: str) -> "tuple[dict, dict, dict]":
    """(the warm prompt, first wave, second wave): a same-rung group of four, a prompt of
    two spans and a prefix hit queued together over four slots, so that
    the later ones are prefilled as rows leave; then two more that arrive
    while bursts are in flight."""
    def sampling(i, max_tokens):
        return SamplingParams(max_tokens=max_tokens, ignore_eos=True,
                              logprobs=1, **SAMPLINGS[kind](i))
    warm = _prompt(40, 99)
    first = {f"group{i}": (_prompt(30 - 2 * i, i), sampling(i, 5 + 4 * i))
             for i in range(4)}
    first["two_spans"] = (_prompt(100, 7), sampling(7, 9))
    first["prefix_hit"] = (warm + _prompt(9, 8), sampling(8, 7))
    second = {"late_short": (_prompt(12, 11), sampling(11, 6)),
              "late_long": (_prompt(50, 12), sampling(12, 10))}
    return {"warm": (warm, sampling(99, 3))}, first, second


@pytest.mark.parametrize("kind", list(SAMPLINGS))
def test_mixed_run_gives_each_stream_what_it_gets_alone(kind):
    warm, first, second = _mixed(kind)
    alone = _engine()
    try:
        want = _serve(alone, {**warm, **first, **second},
                      one_at_a_time=True)
    finally:
        alone.stop()
    core = _engine()
    try:
        streams = _serve(core, warm)
        _add(core, streams, first)
        # the second wave arrives once a burst of the first has delivered
        while len(streams.tokens["group3"]) < 2:
            assert not streams.done["group3"].wait(0.005)
        _add(core, streams, second)
        streams.wait()
        stats = core.stats()
        bursts = _bursts(core)
        _nothing_held(core)
    finally:
        core.stop()
    for rid in want.tokens:
        assert streams.tokens[rid] == want.tokens[rid], rid
        assert streams.finish[rid] == want.finish[rid] == "length"
        np.testing.assert_allclose(
            streams.logprobs[rid], want.logprobs[rid], atol=2e-2,
            err_msg=rid)
    # (g) every prefilled row is counted once, under one path or the
    # other, and a burst's record says how many it took on the device
    feed = stats["first_token_feed_total"]
    assert feed["device"] + feed["host"] == len(want.tokens)
    assert feed["device"] >= len(first)
    assert sum(b["first_on_device_rows"] for b in bursts) == feed["device"]
    assert stats["prefill_group_count"] == 1
    assert stats["cached_tokens_total"] >= 32  # the hit's four blocks


@pytest.mark.parametrize("case", ["plain", "chunked", "prefill_batch_1"])
def test_streams_equal_the_host_paths_under_every_prefill_path(case):
    """The three places a row takes its slot (a single prefill, a group,
    the final chunk of the chunked step plan, alone and batched) against
    the same engine with every first token read back first."""
    kw = {"plain": {}, "prefill_batch_1": {"prefill_batch": 1},
          "chunked": {"enable_chunked_prefill": True,
                      "max_num_batched_tokens": 96,
                      "prefill_chunk_size": 32}}[case]
    requests = {
        f"r{i}": (_prompt(n, i), SamplingParams(
            max_tokens=6 + 3 * i, ignore_eos=True, temperature=0.0,
            logprobs=1))
        for i, n in enumerate((28, 26, 70, 24, 45, 90))}
    got = {}
    for path in ("device", "host"):
        core = _engine(**kw)
        if path == "host":
            _host_path(core)
        try:
            got[path] = (_serve(core, requests), core.stats())
            _nothing_held(core)
        finally:
            core.stop()
    (streams, stats), (want, want_stats) = got["device"], got["host"]
    assert streams.tokens == want.tokens
    for rid in requests:
        np.testing.assert_allclose(streams.logprobs[rid],
                                   want.logprobs[rid], atol=1e-5)
    assert want_stats["first_token_feed_total"] == {
        "device": 0, "host": len(requests)}
    feed = stats["first_token_feed_total"]
    assert feed["device"] + feed["host"] == len(requests)
    assert feed["device"] >= len(requests) - 1
    if case == "chunked":
        assert stats["prefill_chunks_total"] > len(requests)


# ---------------------------------------------------------------------------
# (b) order: nothing is read back between a prefill and its burst
# ---------------------------------------------------------------------------

def _log_ops_and_readbacks(core, monkeypatch, log):
    """Every op dispatched and every device readback of the engine's
    thread into ``log``: ("op", name, n) and ("readback", n), n the number
    of the op whose output is read (None: not an op's)."""
    outs = {}
    exec_op = core._exec_op

    def recording(name, static, arrays):
        out = exec_op(name, static, arrays)
        n = len([e for e in log if e[0] == "op"])
        log.append(("op", name, n))
        if out is not None:
            outs[id(out)] = (n, out)  # kept alive: an id is not reused
        return out

    core._exec_op = recording
    device_get = jax.device_get

    def counting(x):
        if threading.current_thread() is core._thread:
            log.append(("readback", outs.get(id(x), (None,))[0]))
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", counting)


@pytest.mark.parametrize("prompts", [(20,), (20, 40, 10), (30, 28, 26, 24)],
                         ids=["one", "three_alone", "group_of_four"])
def test_no_readback_between_a_prefill_and_the_burst_behind_it(
        prompts, monkeypatch):
    log = []
    core = _engine()
    _log_ops_and_readbacks(core, monkeypatch, log)
    streams = Streams(log)
    try:
        _add(core, streams, {
            f"r{i}": (_prompt(n, i), SamplingParams(
                max_tokens=7, ignore_eos=True, temperature=0.0))
            for i, n in enumerate(prompts)})
        streams.wait()
    finally:
        core.stop()
    ops = [e for e in log if e[0] == "op"]
    names = [e[1] for e in ops]
    assert "feed" not in names  # the scatter lives inside the prefill op
    first_burst = names.index("decode")
    assert set(names[:first_burst]) == {"prefill"}
    at = log.index(ops[first_burst])
    # up to the burst's dispatch the host has read back every prefill
    # but the last (each after the next one's dispatch), and not that one
    read = [e[1] for e in log[:at] if e[0] == "readback"]
    assert read == list(range(first_burst - 1))
    # then the last prefill's sample, before anything of the burst
    assert log[at + 1] == ("readback", first_burst - 1)
    # and every stream's first token is delivered before the burst's
    # readback, which is behind the next burst's dispatch
    burst_read = log.index(("readback", first_burst))
    firsts = [e for e in log[:burst_read] if e[0] == "token"]
    assert sorted(rid for _, rid in firsts) == sorted(streams.tokens)
    assert names[first_burst + 1] == "decode"
    assert log.index(ops[first_burst + 1]) < burst_read
    assert all(len(t) == 7 for t in streams.tokens.values())


@pytest.mark.parametrize("kind", list(SAMPLINGS))
def test_a_prefill_shorter_than_the_bursts_build_keeps_stream_and_order(
        kind, monkeypatch):
    """A one-token uncached prompt: its prefill program has long ended
    when the burst behind it is built (a build of 50 ms here, where the
    program takes under one), so the token waits on the device for the
    burst, and on the host for the burst's dispatch: the stream is the
    host path's, and the order that of a long prefill."""
    def sampling(i):
        return SamplingParams(max_tokens=6, ignore_eos=True, logprobs=1,
                              **SAMPLINGS[kind](i))
    requests = {"one_token": ([17], sampling(1)),
                "beside": (_prompt(20, 2), sampling(2))}
    host = _host_path(_engine(enable_prefix_caching=False))
    try:
        want = _serve(host, requests, one_at_a_time=True)
    finally:
        host.stop()
    log = []
    core = _engine(enable_prefix_caching=False)
    _log_ops_and_readbacks(core, monkeypatch, log)
    sampling_for = core._sampling_for

    def slow_build(req):
        time.sleep(0.05)
        return sampling_for(req)

    core._sampling_for = slow_build
    streams = Streams(log)
    try:
        _add(core, streams, {"one_token": requests["one_token"]})
        streams.wait()
        alone = [e for e in log if e[0] != "token"]
        _add(core, streams, {"beside": requests["beside"]})
        streams.wait("beside")
        feed = core.stats()["first_token_feed_total"]
        _nothing_held(core)
    finally:
        core.stop()
    assert streams.tokens == want.tokens
    for rid in requests:
        np.testing.assert_allclose(streams.logprobs[rid],
                                   want.logprobs[rid], atol=1e-5)
    assert feed == {"device": 2, "host": 0}
    # prefill, burst, then the prefill's token read, and the burst's own
    # tokens only behind the next burst's dispatch
    assert alone[:5] == [("op", "prefill", 0), ("op", "decode", 1),
                         ("readback", 0), ("op", "decode", 2),
                         ("readback", 1)]
    assert log.index(("token", "one_token")) < log.index(("readback", 1))


# ---------------------------------------------------------------------------
# (c) a first token that ends its request
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ending", ["stop_id", "eos"])
def test_a_first_token_that_ends_its_request_costs_one_discarded_burst(
        ending):
    prompt, other = _prompt(22, 3), _prompt(35, 4)
    greedy = {"temperature": 0.0, "ignore_eos": True}
    core = _engine(enable_prefix_caching=False)  # the prompts repeat
    try:
        alone = _serve(core, {
            "first": (prompt, SamplingParams(max_tokens=1, **greedy)),
            "other": (other, SamplingParams(max_tokens=14, **greedy))},
            one_at_a_time=True)
        (first,) = alone.tokens["first"]
        eos = int(core.tokenizer.eos_token_id)
        before = dict(core.stats()["first_token_feed_total"])
        generated = core.generation_tokens_total
        if ending == "stop_id":
            ends = SamplingParams(max_tokens=12, temperature=0.0,
                                  ignore_eos=True, stop_token_ids=[first])
            want = [first]
        else:
            ends = SamplingParams(max_tokens=12, temperature=0.0,
                                  logit_bias={eos: 100.0})
            want = [eos]
        streams = _serve(core, {
            "other": (other, SamplingParams(max_tokens=14, **greedy)),
            "ends": (prompt, ends)})
        assert streams.tokens["ends"] == want
        assert streams.finish["ends"] == "stop"
        assert streams.tokens["other"] == alone.tokens["other"]
        # both took the device's path: the ending row's burst was built
        # before anyone knew, and none of its tokens reached the stream
        # or the counter of generated tokens
        feed = core.stats()["first_token_feed_total"]
        assert feed["device"] - before["device"] == 2
        assert feed["host"] == before["host"]
        assert core.generation_tokens_total - generated == 13
        _nothing_held(core)
        # the slot and the pages serve the next request as a fresh engine
        again = _serve(core, {"other": (
            other, SamplingParams(max_tokens=14, **greedy))})
        assert again.tokens["other"] == alone.tokens["other"]
        _nothing_held(core)
    finally:
        core.stop()


# ---------------------------------------------------------------------------
# (d) an abort between a prefill's dispatch and its flush
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("when", ["before_the_burst_is_built",
                                  "behind_the_bursts_dispatch"])
def test_an_abort_before_the_first_token_is_read_leaks_nothing(when):
    greedy = {"temperature": 0.0, "ignore_eos": True}
    core = _engine(enable_prefix_caching=False)  # the prompts repeat
    aborted = []

    def abort_once():
        with core._lock:
            running = "victim" in core.scheduler._running_by_id
        if running and not aborted:
            assert any(e["req"].request_id == "victim"
                       for e in core._pending_prefills)
            aborted.append(core.abort_request("victim"))

    if when == "before_the_burst_is_built":
        next_action = core.scheduler.next_action

        def aborting():
            action = next_action()
            if action[0] == "decode":
                abort_once()
            return action
        core.scheduler.next_action = aborting
    else:
        exec_op = core._exec_op

        def aborting(name, static, arrays):
            out = exec_op(name, static, arrays)
            if name == "decode":
                abort_once()
            return out
        core._exec_op = aborting
    try:
        streams = _serve(core, {
            "stays": (_prompt(35, 4), SamplingParams(max_tokens=10,
                                                     **greedy)),
            "victim": (_prompt(22, 3), SamplingParams(max_tokens=10,
                                                      **greedy))})
        assert aborted == [True]
        assert streams.tokens["victim"] == []
        assert streams.finish["victim"] == "abort"
        feed = core.stats()["first_token_feed_total"]
        assert feed == ({"device": 1, "host": 1}
                        if when == "before_the_burst_is_built"
                        else {"device": 2, "host": 0})
        _nothing_held(core)
        # the slot's feedback row and counts serve the next request
        again = _serve(core, {"victim": (
            _prompt(22, 3), SamplingParams(max_tokens=10, **greedy))})
        assert len(again.tokens["victim"]) == 10
        _nothing_held(core)
        alone = _serve(_host_path(core), {"victim": (
            _prompt(22, 3), SamplingParams(max_tokens=10, **greedy))})
        assert again.tokens["victim"] == alone.tokens["victim"]
        assert streams.tokens["stays"] == _serve(core, {"stays": (
            _prompt(35, 4), SamplingParams(max_tokens=10, **greedy))}
        ).tokens["stays"]
    finally:
        core.stop()


# ---------------------------------------------------------------------------
# (e) penalties: the first token is counted once
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("penalty", [{"frequency_penalty": 1.5},
                                     {"presence_penalty": 1.5}],
                         ids=["frequency", "presence"])
def test_a_fresh_rows_first_token_is_counted_once(penalty):
    """The burst that takes the token on the device resets the slot's
    counts and counts the token, as the burst behind a host flush does: a
    token counted twice or not at all bends a penalised greedy stream."""
    def requests(**penalty):
        return {f"r{i}": (_prompt(20 + 3 * i, i), SamplingParams(
            max_tokens=24, temperature=0.0, ignore_eos=True, **penalty))
            for i in range(3)}
    got = {}
    for path in ("device", "host", "unpenalised"):
        core = _engine()
        if path == "host":
            _host_path(core)
        try:
            # a first wave leaves its counts behind in every slot's row
            _serve(core, {f"before{i}": (_prompt(18, 20 + i), SamplingParams(
                max_tokens=9, temperature=0.0, ignore_eos=True))
                for i in range(4)})
            got[path] = _serve(core, requests(
                **({} if path == "unpenalised" else penalty))).tokens
            if path == "device":
                assert core.stats()["first_token_feed_total"] == {
                    "device": 7, "host": 0}
        finally:
            core.stop()
    assert got["device"] == got["host"]
    assert got["device"] != got["unpenalised"]  # the penalty engaged


def test_a_slots_counts_hold_the_first_token_once():
    """The row of the penalty counts after one request alone: the first
    token and every live step its bursts scheduled (the records' tokens:
    the burst scheduled past the end counts what it covers), whatever
    the slot held before."""
    core = _engine(max_num_seqs=1)
    try:
        greedy = {"temperature": 0.0, "ignore_eos": True}
        _serve(core, {"before": (_prompt(18, 20), SamplingParams(
            max_tokens=9, **greedy))})
        _nothing_held(core)
        scheduled = sum(b["tokens"] for b in _bursts(core))
        _serve(core, {"r": (_prompt(20, 1), SamplingParams(
            max_tokens=11, **greedy))})
        _nothing_held(core)
        scheduled = sum(b["tokens"] for b in _bursts(core)) - scheduled
        assert core.stats()["first_token_feed_total"]["device"] == 2
        assert int(np.asarray(core._token_counts)[0].sum()) == 1 + scheduled
    finally:
        core.stop()


def test_a_resumed_row_with_penalties_takes_the_host_path():
    """A tight pool preempts the younger request; it comes back with its
    prior outputs, and with penalties on its counts are rebuilt from them
    and the token just sampled, which the host must read first."""
    requests = {
        "old": (_prompt(8, 1), SamplingParams(
            max_tokens=60, temperature=0.0, ignore_eos=True,
            frequency_penalty=0.5)),
        "young": (_prompt(48, 2), SamplingParams(
            max_tokens=60, temperature=0.0, ignore_eos=True,
            frequency_penalty=0.5))}
    got = {}
    for path in ("device", "host"):
        core = _engine(block_size=4, num_blocks=30, max_model_len=128)
        if path == "host":
            _host_path(core)
        try:
            got[path] = (_serve(core, requests).tokens, core.stats(),
                         core.scheduler.num_preempted_total)
            _nothing_held(core)
        finally:
            core.stop()
    (tokens, stats, preempted), (want, _stats, want_preempted) = (
        got["device"], got["host"])
    assert preempted >= 1 and want_preempted >= 1
    assert tokens == want
    feed = stats["first_token_feed_total"]
    assert feed["host"] >= 1 and feed["device"] >= 2
    assert feed["device"] + feed["host"] == 2 + preempted


# ---------------------------------------------------------------------------
# (f) what keeps the host path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["speculation", "structured_row",
                                  "beside_a_structured_row", "max_tokens_1",
                                  "fused_step"])
def test_rows_that_need_the_tokens_value_keep_the_host_path(case):
    greedy = {"temperature": 0.0, "ignore_eos": True}
    plain = SamplingParams(max_tokens=6, **greedy)
    kw, requests, want = {}, {"a": (_prompt(20, 1), plain),
                              "b": (_prompt(40, 2), plain)}, (0, 2)
    if case == "speculation":
        kw = {"speculative_num_tokens": 3}
    elif case == "structured_row":
        requests = {"a": (_prompt(20, 1), SamplingParams(
            max_tokens=6, temperature=0.0,
            structured=StructuredSpec("regex", "[ab]{3,5}")))}
        want = (0, 1)
    elif case == "beside_a_structured_row":
        requests = {"a": (_prompt(20, 1), SamplingParams(
            max_tokens=12, temperature=0.0,
            structured=StructuredSpec("regex", "[ab]{9,12}"))),
            "b": (_prompt(40, 2), plain)}
    elif case == "max_tokens_1":
        requests = {"a": (_prompt(20, 1), SamplingParams(
            max_tokens=1, **greedy)), "b": (_prompt(40, 2), plain)}
        want = (1, 1)
    else:
        kw = {"enable_chunked_prefill": True, "fused_step": True,
              "max_num_batched_tokens": 64, "prefill_chunk_size": 32}
        requests = {"a": (_prompt(20, 1), SamplingParams(
            max_tokens=16, **greedy))}
        want = None
    core = _engine(**kw)
    try:
        streams = _serve(core, requests)
        if case == "fused_step":
            # a row whose final chunk rides a fused pair sits its burst
            # out, as before; one prefilled with nothing to fuse is fed
            late = Streams()
            _add(core, late, {"c": (_prompt(24, 3), plain)})
            late.wait()
            feed = core.stats()["first_token_feed_total"]
            assert feed["device"] + feed["host"] == 2
            want = (feed["device"], 2 - feed["device"])
        stats = core.stats()
        assert stats["first_token_feed_total"] == {
            "device": want[0], "host": want[1]}
        assert all(f in ("length", "stop") for f in streams.finish.values())
        _nothing_held(core)
    finally:
        core.stop()
    device = _host_path(_engine(**kw))
    try:
        assert _serve(device, requests).tokens == streams.tokens
    finally:
        device.stop()


def test_the_scatter_is_the_only_new_program_and_warm_up_compiles_it():
    """One tiny jitted function a row count of the prefill programs, all
    compiled in warm-up: serving compiles nothing, and the decode program
    is called with the arguments it always had."""
    core = _engine(max_model_len=128, num_blocks=64, max_num_seqs=2,
                   prefill_batch=2)
    try:
        core.warmup()
        feed = core._feed_first_tokens_fn
        rows = {1, *(core.config.prefill_group_rows(b)
                     for b in core.config.prefill_buckets(plain=True))} - {0}
        assert feed._cache_size() == len(rows)
        programs = {K: fn._cache_size()
                    for K, fn in core._multi_decode_fns.items()}
        prefill = (core._prefill_fn._cache_size(),
                   core._prefill_cached_fn._cache_size())
        _serve(core, {f"r{i}": (_prompt(20 + i, i), SamplingParams(
            max_tokens=6, temperature=0.0, ignore_eos=True))
            for i in range(4)})
        assert core.stats()["first_token_feed_total"]["device"] == 4
        assert feed._cache_size() == len(rows)
        assert {K: fn._cache_size()
                for K, fn in core._multi_decode_fns.items()} == programs
        assert (core._prefill_fn._cache_size(),
                core._prefill_cached_fn._cache_size()) == prefill
    finally:
        core.stop()
