"""The bucket ladder of the plain (uncached) prefill program: above 256
tokens it steps by 128 up to the chunk, so a prompt just over a power of two
no longer pays for twice its width; every other program keeps the powers of
two. And the counter that says how much of a prefill step was padding."""

import queue

import jax
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams


def powers_of_two(cfg: EngineConfig) -> "list[int]":
    """The ladder every program had before the plain one got its own."""
    out, b = [], cfg.min_prefill_bucket
    while b < cfg.max_model_len:
        out.append(b)
        b *= 2
    return out + [cfg.max_model_len]


SIZES = [  # (max_model_len, prefill_chunk_size)
    (4096, 1024), (2048, 1024), (8192, 2048), (4096, 1000), (512, 1024),
    (300, 1024), (2048, 0), (128, 1024),
]


@pytest.mark.parametrize("max_model_len,chunk", SIZES)
def test_plain_ladder(max_model_len, chunk):
    cfg = EngineConfig(max_model_len=max_model_len, prefill_chunk_size=chunk)
    plain = cfg.prefill_buckets(plain=True)
    old = powers_of_two(cfg)
    assert cfg.prefill_buckets() == old
    assert plain == sorted(set(plain))
    assert set(old) <= set(plain)
    top = min(chunk or max_model_len, max_model_len)
    added = sorted(set(plain) - set(old))
    assert all(256 < b < top and b % 128 == 0 for b in added), added
    # between 256 and the chunk no step is wider than 128
    rungs = [b for b in plain if 256 <= b <= top]
    assert all(b - a <= 128 for a, b in zip(rungs, rungs[1:])), rungs
    assert plain[-1] == max_model_len


@pytest.mark.parametrize("program", ["cached", "plain"])
@pytest.mark.parametrize("max_model_len,chunk", SIZES[:4])
def test_bucket_for(program, max_model_len, chunk):
    cfg = EngineConfig(max_model_len=max_model_len, prefill_chunk_size=chunk)
    old = powers_of_two(cfg)
    for length in range(1, max_model_len + 1):
        was = next(b for b in old if length <= b)
        if program == "cached":
            assert cfg.bucket_for(length) == was
            continue
        now = cfg.bucket_for(length, plain=True)
        # the same program as before, or a narrower one
        assert length <= now <= was
        if 256 < length <= min(chunk, max_model_len) - 128:
            assert now - length < 128
    with pytest.raises(ValueError):
        cfg.bucket_for(max_model_len + 1, plain=program == "plain")


def make_engine() -> EngineCore:
    cfg = EngineConfig(
        model="tiny-llama", max_model_len=1024, max_num_seqs=2,
        block_size=64, num_blocks=40, max_loras=0, decode_steps=2,
        prefill_batch=1,
        enable_prefix_caching=False)
    eng = EngineCore(cfg, devices=jax.devices()[:1])
    eng.start()
    return eng


def prompt(n: int) -> "list[int]":
    return [(7 * i) % 200 + 1 for i in range(n)]


def generate(eng: EngineCore, n: int, max_tokens: int, rid: str):
    q: "queue.Queue" = queue.Queue()
    eng.add_request(
        rid, prompt(n),
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True),
        lambda token, finish: q.put((token, finish)))
    tokens = []
    while True:
        token, finish = q.get(timeout=120)  # queue.Empty fails the test
        if token is not None:
            tokens.append(token)
        if finish is not None:
            return tokens


@pytest.fixture(scope="module")
def warm_engine():
    eng = make_engine()
    eng.warmup()
    yield eng
    eng.stop()


def test_warmup_counts_one_plain_program_per_rung(warm_engine):
    cfg = warm_engine.config
    plain = cfg.prefill_buckets(plain=True)
    assert plain == [32, 64, 128, 256, 384, 512, 640, 768, 896, 1024]
    assert warm_engine._prefill_fn._cache_size() == len(plain)
    # cached programs: one per old bucket and table width (4, 8, 16 blocks)
    cached = sum(3 if b <= 256 else 2 if b == 512 else 1
                 for b in cfg.prefill_buckets())
    assert warm_engine._prefill_cached_fn._cache_size() == cached
    assert warm_engine.warmup_variants["prefill"] == len(plain) + cached


def test_no_compile_at_any_rung_and_the_padding_is_counted(warm_engine):
    """After warm-up a plain prefill at each rung fetches no program (the
    events ``compiles_in_window`` counts), its step record says how wide
    the program was, and the lifetime counter advances by that width."""
    from chipbench.run import CompileWatch

    eng = warm_engine
    watch = CompileWatch()
    programs = eng._prefill_fn._cache_size()
    for n in (20, 200, 257, 500, 600, 700, 800, 1000):
        bucket = eng.config.bucket_for(n, plain=True)
        before = eng.stats()["prefill_padded_tokens_total"]
        generate(eng, n, 1, f"rung-{n}")
        assert (eng.stats()["prefill_padded_tokens_total"] - before
                == bucket)
        rec = eng.step_recorder.snapshot(kind="prefill", limit=1)[0]
        assert rec["program"] == "prefill"
        assert rec["tokens"] == n
        assert rec["padded_tokens"] == bucket >= rec["tokens"]
    assert eng._prefill_fn._cache_size() == programs
    assert watch.events == []


def test_600_token_prompt_reads_the_same_at_640_as_at_1024(monkeypatch):
    """Greedy output of an uncached 600-token prompt, padded to 640 by the
    plain ladder, is token for token what the 1024 bucket gave."""
    outputs = {}
    for ladder in ("powers_of_two", "plain"):
        if ladder == "powers_of_two":
            monkeypatch.setattr(
                EngineConfig, "prefill_buckets",
                lambda self, plain=False: powers_of_two(self))
        else:
            monkeypatch.undo()
        eng = make_engine()
        try:
            outputs[ladder] = generate(eng, 600, 12, "p600")
            rec = eng.step_recorder.snapshot(kind="prefill", limit=1)[0]
            assert rec["padded_tokens"] == (
                1024 if ladder == "powers_of_two" else 640)
            decode = eng.step_recorder.snapshot(kind="decode_burst")
            assert decode and all(r["padded_tokens"] == 0 for r in decode)
        finally:
            eng.stop()
    assert len(outputs["plain"]) == 12
    assert outputs["plain"] == outputs["powers_of_two"]
