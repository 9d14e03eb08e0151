"""The group of a plain prefill: waiting uncached one-span prompts of one
plain-ladder rung share one ``[R, rung]`` ``prefill`` dispatch, R the
rung's one compiled row count (4 at 384 and 512, 2 at 640 and 768 of a
1024 chunk). Each member gets the tokens and KV pages of the single path;
no row is padding; nobody waits for a mate and the head of the queue is
never passed over; everything else keeps the single path."""

import threading

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams

CHUNK = 1024  # groups of 4 at rungs 384, 512 and of 2 at 640, 768


def _config(prefill_batch: int = 4, model: str = "tiny-llama",
            **kw) -> EngineConfig:
    kw = {"max_model_len": 2048, "max_num_seqs": 8, "block_size": 32,
          "num_blocks": 256, "max_loras": 0, "prefill_chunk_size": CHUNK,
          "decode_steps": 4, **kw}
    return EngineConfig(model=model, prefill_batch=prefill_batch, **kw)


def _prompt(n: int, salt: int) -> "list[int]":
    return [(7 * i + 31 * salt) % 200 + 1 for i in range(n)]


def _serve(core: EngineCore, prompts: "dict[str, list[int]]",
           max_tokens: int = 6, **sampling) -> "dict[str, list[int]]":
    """Queue every prompt in one go (so they wait together whether or not
    the loop runs yet), start the loop if needed, collect the outputs."""
    events = {}
    outs = {rid: [] for rid in prompts}

    def cb_for(rid):
        done = threading.Event()
        events[rid] = done

        def cb(t, f):
            if t is not None:
                outs[rid].append(int(t[0]) if isinstance(t, tuple)
                                 else int(t))
            if f is not None:
                done.set()
        return cb

    sampling = {"temperature": 0.0, **sampling}
    with core._lock:
        for rid, ids in prompts.items():
            core.add_request(rid, ids, SamplingParams(
                max_tokens=max_tokens, ignore_eos=True, **sampling),
                cb_for(rid))
    if not core._thread.is_alive():
        core.start()
    for rid, done in events.items():
        assert done.wait(180), f"{rid} timed out"
    return outs


def _prefills(core: EngineCore) -> "list[dict]":
    """The prefill step records, oldest first."""
    return core.step_recorder.snapshot(kind="prefill")[::-1]


def _pages(core: EngineCore, tokens: "list[int]"):
    got = core.extract_kv(tokens)
    return np.asarray(got["k"], np.float32), np.asarray(got["v"], np.float32)


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-laguna"])
@pytest.mark.parametrize("rows,length,sampling", [
    (2, 700, {}), (4, 500, {}), (2, 600, {}),
    (4, 380, {"temperature": 1.0, "seed": 11}),
])
def test_group_gives_each_member_the_single_paths_tokens_and_pages(
        model, rows, length, sampling):
    """A sliding layer (window 24 < every length) and the expert layer's
    ``valid`` mask see B > 1 with tiny-laguna; a seeded row samples as it
    would alone."""
    prompts = {f"r{i}": _prompt(length - 3 * i, i) for i in range(rows)}
    served = {}
    for batch in (4, 1):
        core = EngineCore(_config(batch, model), devices=jax.devices()[:1])
        try:
            outs = _serve(core, prompts, **sampling)
            pages = {rid: _pages(core, ids) for rid, ids in prompts.items()}
            served[batch] = (outs, pages, _prefills(core), core.stats())
        finally:
            core.stop()
    outs, pages, records, stats = served[4]
    rung = core.config.bucket_for(length, plain=True)
    assert [(r["rows"], r["program"], r["padded_tokens"], r["forwards"])
            for r in records] == [(rows, "prefill", rows * rung, 1)]
    assert records[0]["tokens"] == sum(len(p) for p in prompts.values())
    assert (stats["prefill_group_count"], stats["prefill_group_rows"]) == (
        1, rows)
    want_outs, want_pages, want_records, want_stats = served[1]
    assert [r["rows"] for r in want_records] == [1] * rows
    assert want_stats["prefill_group_count"] == 0
    assert stats["prompt_tokens_total"] == want_stats["prompt_tokens_total"]
    for rid in prompts:
        assert outs[rid] == want_outs[rid] and len(outs[rid]) == 6, rid
        for got, want in zip(pages[rid], want_pages[rid]):
            assert got.shape == want.shape and got.shape[0] >= 4
            np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("length,mates,groups", [
    (600, 2, [2]), (600, 3, [2, 1]), (600, 5, [2, 2, 1]),
    (500, 3, [1, 1, 1]), (500, 4, [4]), (500, 6, [4, 1, 1]),
    (200, 4, [1, 1, 1, 1]), (900, 2, [1, 1])])
def test_mates_go_in_the_rungs_row_count_or_alone_never_a_padded_row(
        length, mates, groups):
    prompts = {f"r{i}": _prompt(length - i, i) for i in range(mates)}
    core = EngineCore(_config(), devices=jax.devices()[:1])
    try:
        outs = _serve(core, prompts, max_tokens=2)
        records = _prefills(core)
    finally:
        core.stop()
    assert [r["rows"] for r in records] == groups
    rung = core.config.bucket_for(length, plain=True)
    assert all(r["padded_tokens"] == rung * r["rows"] for r in records)
    assert all(len(o) == 2 for o in outs.values())


def _warm_then(core, n):
    """Serve and finish a prompt, so its pages are in the prefix cache."""
    _serve(core, {"warm": _prompt(n, 99)}, max_tokens=2)
    return _prompt(n, 99)


@pytest.mark.parametrize("case", [
    "mixed_rungs", "cached_prefix", "cached_mate", "two_chunks",
    "full_slot_table", "disabled"])
def test_everything_else_takes_the_single_path(case):
    kw, batch = {}, 4
    if case == "full_slot_table":
        kw = {"max_num_seqs": 1}
    if case == "disabled":
        batch = 1
    core = EngineCore(_config(batch, **kw), devices=jax.devices()[:1])
    try:
        if case == "mixed_rungs":
            prompts = {"a": _prompt(600, 0), "b": _prompt(700, 1)}
        elif case == "cached_prefix":
            head = _warm_then(core, 80)
            prompts = {"a": head[:64] + _prompt(540, 1),
                       "b": head[:64] + _prompt(541, 2)}
        elif case == "cached_mate":
            head = _warm_then(core, 80)
            prompts = {"a": _prompt(600, 1), "b": head[:64] + _prompt(541, 2)}
        elif case == "two_chunks":
            prompts = {"a": _prompt(1100, 0), "b": _prompt(1101, 1)}
        else:
            prompts = {"a": _prompt(600, 0), "b": _prompt(599, 1)}
        before = len(_prefills(core))
        outs = _serve(core, prompts, max_tokens=3)
        records = _prefills(core)[before:]
        stats = core.stats()
    finally:
        core.stop()
    assert [r["rows"] for r in records] == [1, 1]
    assert stats["prefill_group_count"] == 0
    assert all(len(o) == 3 for o in outs.values())
    if case in ("cached_prefix", "cached_mate"):
        assert records[-1]["program"] == "prefill_cached"
    if case == "two_chunks":
        assert [r["forwards"] for r in records] == [2, 2]


def test_the_head_is_never_passed_over():
    """The queue is A (rung 640), B (rung 768), C, D (rung 640): A runs
    now with C (3 mates make 2 + 1), B next and alone though D waits, D
    last."""
    prompts = {"A": _prompt(600, 0), "B": _prompt(700, 1),
               "C": _prompt(610, 2), "D": _prompt(620, 3)}
    core = EngineCore(_config(), devices=jax.devices()[:1])
    try:
        _serve(core, prompts, max_tokens=2)
        records = _prefills(core)
    finally:
        core.stop()
    assert [(r["rows"], r["tokens"]) for r in records] == [
        (2, 1210), (1, 700), (1, 620)]


def test_a_group_that_cannot_form_leaves_its_mates_where_they_wait():
    """The pool refuses the last mate its pages: the others give theirs
    back and nobody moves, so X, which queued before M3, is served before
    it."""
    prompts = {"H": _prompt(500, 0), "M1": _prompt(499, 1),
               "M2": _prompt(498, 2), "X": _prompt(600, 3),
               "M3": _prompt(497, 4)}
    core = EngineCore(_config(), devices=jax.devices()[:1])
    allocate, refused = core.kv_mgr.allocate_prompt, []

    def refuse_once(seq_id, *args, **kw):
        if seq_id == "M3" and not refused:
            refused.append(set(core.kv_mgr.seqs))
            return None
        return allocate(seq_id, *args, **kw)

    core.kv_mgr.allocate_prompt = refuse_once
    try:
        outs = _serve(core, prompts, max_tokens=2)
        records = _prefills(core)
        stats = core.stats()
    finally:
        core.stop()
    assert refused == [{"H", "M1", "M2"}]
    assert [(r["rows"], r["tokens"]) for r in records] == [
        (1, 500), (1, 499), (1, 498), (1, 600), (1, 497)]
    assert stats["prefill_group_count"] == 0
    assert all(len(o) == 2 for o in outs.values())


def test_mates_share_no_first_page():
    """M2 and M3 begin alike: grouped, M3 would find M2's first page
    registered and not written. It stays behind (M4 goes in its place),
    and is a cached prompt once M2 has run."""
    same = _prompt(32, 50)
    prompts = {"H": _prompt(500, 0), "M1": _prompt(499, 1),
               "M2": same + _prompt(460, 2), "M3": same + _prompt(461, 3),
               "M4": _prompt(498, 4)}
    served = {}
    for batch in (4, 1):
        core = EngineCore(_config(batch), devices=jax.devices()[:1])
        try:
            served[batch] = _serve(core, prompts, max_tokens=4)
            records = _prefills(core)
        finally:
            core.stop()
        if batch == 4:
            assert [(r["rows"], r["program"], r["tokens"]) for r in records
                    ] == [(4, "prefill", 500 + 499 + 492 + 498),
                          (1, "prefill_cached", 461)]
    assert served[4] == served[1]


def test_unwritten_pages_leave_the_prefix_map():
    from production_stack_tpu.engine.kvcache import KVCacheManager

    kv = KVCacheManager(16, 4)
    tokens = list(range(1, 14))
    kv.allocate_prompt("kept", tokens[:9])
    _, cached, _ = kv.allocate_prompt("dropped", tokens)
    assert cached == 8 and len(kv.allocator.prefix_map) == 3
    kv.free_unwritten("dropped")
    assert len(kv.allocator.prefix_map) == 2  # "kept"'s two full blocks
    _, cached, _ = kv.allocate_prompt("again", tokens)
    assert cached == 8
    kv.free_unwritten("gone")  # unknown: nothing to do
    # Pages that were to come back from the offload tier count as cached
    # and are as unwritten as the fresh ones.
    kv.external_lookup = lambda h: True
    _, cached, restores = kv.allocate_prompt("restored", list(range(50, 63)))
    assert cached == 12 and len(restores) == 3
    kv.free_unwritten("restored", restores)
    assert len(kv.allocator.prefix_map) == 3  # "again"'s full blocks


def test_a_more_important_mate_goes_first():
    """Mates join in the order the scheduler would serve them: with one
    row to give, the interactive prompt gets it, not the batch prompt that
    queued before it."""
    core = EngineCore(_config(max_num_seqs=2), devices=jax.devices()[:1])
    done = {rid: threading.Event() for rid in "hbi"}
    try:
        with core._lock:
            for rid, n, priority in (("h", 600, 0), ("b", 610, 1),
                                     ("i", 620, 0)):
                core.add_request(
                    rid, _prompt(n, n), SamplingParams(
                        max_tokens=2, temperature=0.0, ignore_eos=True),
                    lambda t, f, rid=rid: f and done[rid].set(),
                    priority=priority)
        core.start()
        assert all(e.wait(180) for e in done.values())
        records = _prefills(core)
    finally:
        core.stop()
    assert [(r["rows"], r["tokens"]) for r in records] == [
        (2, 1220), (1, 610)]


@pytest.fixture(scope="module")
def warm():
    core = EngineCore(_config(max_model_len=1024, block_size=64,
                              num_blocks=128), devices=jax.devices()[:1])
    core.warmup()
    core.start()
    yield core
    core.stop()


def test_warmup_covers_every_group_the_rule_can_return(warm):
    """No program is fetched on a group's first use: the warm prompts of a
    cell are served one at a time and cannot reach these shapes."""
    from chipbench.run import CompileWatch

    cfg = warm.config
    pairs = [(cfg.prefill_group_rows(rung), rung)
             for rung in cfg.prefill_buckets(plain=True)
             if cfg.prefill_group_rows(rung)]
    assert pairs == [(4, 384), (4, 512), (2, 640), (2, 768)]
    plain = [b for b in cfg.prefill_buckets(plain=True) if b <= CHUNK]
    assert warm._prefill_fn._cache_size() == len(plain) + len(pairs)
    watch = CompileWatch()
    for rows, rung in pairs:
        before = len(_prefills(warm))
        _serve(warm, {f"w{rows}-{rung}-{i}":  # uncached: its own salt
                      _prompt(rung - i, rung // 6 + i)
                      for i in range(rows)}, max_tokens=1)
        assert [r["rows"] for r in _prefills(warm)[before:]] == [rows]
    assert warm._prefill_fn._cache_size() == len(plain) + len(pairs)
    assert watch.events == []


def test_a_burst_fed_by_a_burst_runs_the_program_warmup_compiled(warm):
    """The feedback tokens of a first burst are zeros placed like a
    burst's own output: one decode program a table width, not a second
    one at the first burst that follows another."""
    from chipbench.run import CompileWatch

    decode = warm._multi_decode_fn(warm.config.decode_steps)
    programs = decode._cache_size()
    watch = CompileWatch()
    forwards = warm.stats()["decode_forward_steps_total"]
    outs = _serve(warm, {"long": _prompt(20, 77)}, max_tokens=30)
    assert len(outs["long"]) == 30
    assert warm.stats()["decode_forward_steps_total"] - forwards >= 28
    assert decode._cache_size() == programs
    assert watch.events == []


@pytest.mark.parametrize("chunked", [False, True])
def test_warmup_compiles_the_step_plans_rows_only_under_chunked_prefill(
        chunked):
    """The [prefill_batch, chunk] ``prefill_cached`` variants belong to
    the chunked step plan: without it no step calls them."""
    core = EngineCore(_config(max_model_len=128, prefill_chunk_size=64,
                              enable_chunked_prefill=chunked),
                      devices=jax.devices()[:1])
    try:
        core.warmup()
        cfg = core.config
        single = sum(  # a table width per power of two from the tight one
            len({min(max(4, 2 ** k), cfg.max_blocks_per_seq)
                 for k in range(10)
                 if 2 ** k >= -(-b // cfg.block_size)})
            for b in cfg.prefill_buckets() if b <= 64)
        rows = len({min(4 * 2 ** k, core._prefill_batch_maxb())
                    for k in range(10)})
        assert core._prefill_cached_fn._cache_size() == single + (
            rows if chunked else 0)
    finally:
        core.stop()


@pytest.mark.parametrize("chunk,batch,want", [
    (1024, 4, {384: 4, 512: 4, 640: 2, 768: 2}),
    (1024, 2, {640: 2, 768: 2, 896: 2, 1024: 2}), (1024, 1, {}), (0, 4, {}),
    (2048, 4, {640: 4, 768: 4, 896: 4, 1024: 4}),
    (128, 4, {64: 4, 128: 2}),
])
def test_group_rows_are_a_function_of_the_rung(chunk, batch, want):
    from production_stack_tpu.engine.config import PREFILL_GROUP_PROGRAMS

    cfg = EngineConfig(max_model_len=4096, prefill_chunk_size=chunk,
                       prefill_batch=batch)
    got = {rung: cfg.prefill_group_rows(rung)
           for rung in cfg.prefill_buckets(plain=True)}
    assert {r: n for r, n in got.items() if n} == want
    assert len(want) <= PREFILL_GROUP_PROGRAMS
    for rung, rows in want.items():  # over a chunk's tokens, within two
        assert chunk < rows * rung <= 2 * chunk
    assert cfg.prefill_group_rows(100) == 0  # no rung of the ladder
