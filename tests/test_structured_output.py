"""Structured output: regex/schema -> byte DFA -> token FSM compiler
units, corpus replay, engine conformance (greedy parity, spec decode,
chunked prefill, compile budget), and router e2e over both request
surfaces (docs/structured_output.md)."""

import json
import os
import queue
import subprocess
import sys
import time

import pytest

from production_stack_tpu.structured.api import (
    StructuredSpec, compile_char_dfa, parse_structured)
from production_stack_tpu.structured.corpus import (
    CORPUS_PATH, case_request_fields, case_spec, load_corpus)
from production_stack_tpu.structured.regex_dfa import (
    MAX_REPEAT, StructuredError, compile_regex)
from production_stack_tpu.structured.schema import (
    schema_to_regex, validate_instance)
from production_stack_tpu.structured.tokenfsm import (
    FSMState, StructuredCache, TokenFSM, mask_row_bytes, token_byte_table)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- regex_dfa


def test_regex_dfa_fullmatch_and_example():
    dfa = compile_regex(r"[0-9]{4}-[0-9]{2}")
    assert dfa.fullmatch("2026-08")
    assert not dfa.fullmatch("2026-8")
    assert not dfa.fullmatch("2026-081")
    # example() is a member of the language by construction.
    assert dfa.fullmatch(dfa.example())


def test_regex_dfa_utf8_literals():
    dfa = compile_regex("café{2}")
    assert dfa.fullmatch("caféé")
    assert not dfa.fullmatch("café")


def test_regex_dfa_rejects_unsupported():
    for pattern in [
        r"(a)\1",       # backreference: not regular
        r"(?=a)b",      # lookahead
        r"a{2,1}",      # reversed repeat bounds
        r"*a",          # dangling quantifier
        r"[z-a]",       # inverted range
        r"a{%d}" % (MAX_REPEAT + 1),  # repeat cap
        r"(a",          # unbalanced group
    ]:
        with pytest.raises(StructuredError):
            compile_regex(pattern)


def test_regex_dfa_alternation_and_classes():
    dfa = compile_regex(r"(cat|dog)s?")
    for good in ["cat", "dogs"]:
        assert dfa.fullmatch(good)
    assert not dfa.fullmatch("cats?")
    neg = compile_regex(r"[^0-9]+")
    assert neg.fullmatch("abc")
    assert not neg.fullmatch("a1c")


# ------------------------------------------------------------------- schema


def test_schema_lowering_object():
    schema = {"type": "object",
              "properties": {"name": {"type": "string"},
                             "age": {"type": "integer"}},
              "required": ["name", "age"]}
    dfa = compile_regex(schema_to_regex(schema))
    assert dfa.fullmatch('{"name":"ada","age":36}')
    # Wrong order, missing prop, and pretty-printing all fall outside
    # the compact-JSON generation contract.
    assert not dfa.fullmatch('{"age":36,"name":"ada"}')
    assert not dfa.fullmatch('{"name":"ada"}')
    assert not dfa.fullmatch('{ "name": "ada", "age": 36 }')


def test_schema_suffix_optional_rule():
    # Optional property after the last required one: both forms match.
    ok = {"type": "object",
          "properties": {"a": {"type": "integer"},
                         "b": {"type": "boolean"}},
          "required": ["a"]}
    dfa = compile_regex(schema_to_regex(ok))
    assert dfa.fullmatch('{"a":1}')
    assert dfa.fullmatch('{"a":1,"b":true}')
    # Optional BEFORE a required property is interleaved optionality —
    # not expressible as a reasonable regex; must 400, not mis-compile.
    bad = {"type": "object",
           "properties": {"opt": {"type": "boolean"},
                          "req": {"type": "integer"}},
           "required": ["req"]}
    with pytest.raises(StructuredError):
        schema_to_regex(bad)


def test_schema_unsupported_keywords_rejected():
    for schema in [
        {"allOf": [{"type": "string"}]},
        {"not": {"type": "string"}},
        {"$ref": "#/defs/x"},
        {"type": "object", "patternProperties": {".*": {}}},
    ]:
        with pytest.raises(StructuredError):
            schema_to_regex(schema)


def test_validate_instance_independent_of_regex():
    schema = {"type": "array", "items": {"type": "integer"},
              "minItems": 1, "maxItems": 3}
    assert validate_instance(schema, [1, 2])
    assert not validate_instance(schema, [])
    assert not validate_instance(schema, [1, "x"])
    assert not validate_instance(schema, [1, 2, 3, 4])


# ---------------------------------------------------------- request surface


def test_parse_structured_surfaces():
    assert parse_structured({}) is None
    assert parse_structured({"response_format": {"type": "text"}}) is None
    spec = parse_structured({"guided_regex": "[ab]+"})
    assert (spec.kind, spec.spec) == ("regex", "[ab]+")
    # guided_json accepts an object or its JSON-string form; both
    # canonicalize identically.
    schema = {"type": "object", "properties": {"x": {"type": "integer"}},
              "required": ["x"]}
    as_obj = parse_structured({"guided_json": schema})
    as_str = parse_structured({"guided_json": json.dumps(schema)})
    assert as_obj == as_str and as_obj.kind == "json_schema"
    rf = parse_structured({"response_format": {
        "type": "json_schema",
        "json_schema": {"name": "out", "schema": schema}}})
    assert rf == as_obj
    assert parse_structured(
        {"response_format": {"type": "json_object"}}).kind == "json_object"
    for bad in [
        {"guided_regex": ""},
        {"guided_json": "not json"},
        {"guided_json": [1]},
        {"response_format": {"type": "yaml"}},
        {"response_format": {"type": "json_schema"}},
        {"guided_regex": "[ab]+", "guided_json": schema},  # conflicting
    ]:
        with pytest.raises(StructuredError):
            parse_structured(bad)


# ------------------------------------------------------------------- corpus


def test_corpus_replay():
    cases = load_corpus()
    assert len(cases) >= 30
    assert len({c["name"] for c in cases}) == len(cases)
    for case in cases:
        dfa = compile_char_dfa(case_spec(case))
        for pos in case["positive"]:
            assert dfa.fullmatch(pos), (case["name"], pos)
            if case["kind"] == "json_schema":
                assert validate_instance(case["spec"], json.loads(pos)), \
                    (case["name"], pos)
        for neg in case["negative"]:
            assert not dfa.fullmatch(neg), (case["name"], neg)


def test_corpus_lint_script():
    """scripts/check_corpus_valid.py is the CI lint over the same file;
    it must agree."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_corpus_valid.py")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert os.path.exists(CORPUS_PATH)


# ----------------------------------------------------------------- tokenfsm


class _ByteTok:
    """Byte-level tokenizer shape: ids 0..255 are raw bytes, 256/257 are
    BOS/EOS (mirrors the engine's byte-level fallback tokenizer)."""

    bos_token_id = 256
    eos_token_id = 257
    pad_token_id = 258


def _token_fsm(pattern: str, vocab: int = 260) -> TokenFSM:
    tok = _ByteTok()
    return TokenFSM(compile_regex(pattern), token_byte_table(tok, vocab),
                    tok.eos_token_id, vocab)


def test_token_fsm_mask_rows():
    fsm = _token_fsm("[ab]{2}")
    row = fsm.mask_row(fsm.start)

    def bit(v):
        return (row[v // 8] >> (v % 8)) & 1

    assert bit(ord("a")) and bit(ord("b"))
    assert not bit(ord("c")) and not bit(257)  # EOS: not yet accepting
    s1 = fsm.advance(fsm.start, ord("a"))
    s2 = fsm.advance(s1, ord("b"))
    row2 = fsm.mask_row(s2)
    assert (row2[257 // 8] >> (257 % 8)) & 1   # accepting -> EOS allowed
    assert not (row2[ord("a") // 8] >> (ord("a") % 8)) & 1
    assert fsm.is_complete(s2)
    # Specials (BOS/PAD) are never admitted.
    assert not bit(256) and not bit(258)
    assert mask_row_bytes(260) == len(row)


def test_fsm_state_violation_dead_latch():
    st = FSMState(_token_fsm("[ab]{2}"))
    assert st.masking
    assert st.advance(ord("a"))
    assert not st.advance(ord("z"))   # leaves the language: False ONCE
    assert st.dead and not st.masking
    assert st.advance(ord("z"))       # latched: no repeat violations


def test_fsm_state_eos_paths():
    st = FSMState(_token_fsm("[ab]{2}"))
    assert not st.advance(257)        # EOS while non-accepting: violation
    st2 = FSMState(_token_fsm("[ab]{2}"))
    st2.advance(ord("a")), st2.advance(ord("b"))
    assert st2.accepting
    assert st2.advance(257)           # EOS while accepting: clean finish


def test_structured_cache_lru_and_counters():
    tok = _ByteTok()
    cache = StructuredCache(max_entries=2)

    def get(rx):
        return cache.get("regex", rx, tok, "tok-key", 260, 257,
                         lambda: compile_regex(rx))

    a = get("[ab]+")
    assert get("[ab]+") is a          # hit: same immutable FSM
    assert cache.compile_seconds_total > 0
    a.mask_row(0)
    assert cache.mask_states_total == 1
    get("[cd]+")
    get("[ef]+")                      # third distinct spec: evicts LRU
    assert cache.evictions_total == 1 and len(cache) == 2
    assert get("[ab]+") is not a      # evicted -> recompiled


# ----------------------------------------------------- engine (real, CPU)


def _make_engine(**over):
    import jax

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.core import EngineCore

    kwargs = dict(model="tiny-llama", max_model_len=128, max_num_seqs=4,
                  block_size=4, num_blocks=96, min_prefill_bucket=16,
                  max_loras=0)
    kwargs.update(over)
    eng = EngineCore(EngineConfig(**kwargs), devices=jax.devices()[:1])
    eng.start()
    return eng


def _collect(eng, prompt_ids, body, rid, timeout=120):
    from production_stack_tpu.engine.sampling import SamplingParams

    q = queue.Queue()
    eng.add_request(rid, prompt_ids, SamplingParams.from_request(body),
                    lambda t, f: q.put((t, f)))
    tokens = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            token, finish = q.get(timeout=5)
        except queue.Empty:
            continue
        if token is not None:
            tokens.append(token)
        if finish is not None:
            return tokens, finish
    raise TimeoutError("generation did not finish")


def _text(eng, tokens):
    eos = eng.tokenizer.eos_token_id
    return eng.tokenizer.decode([t for t in tokens if t != eos])


@pytest.fixture(scope="module")
def eng():
    # No full warmup: lazy compile traces only the buckets these tests
    # actually use, keeping the module inside the tier-1 time budget.
    e = _make_engine()
    yield e
    e.stop()


def test_engine_guided_regex_conforms(eng):
    tokens, finish = _collect(
        eng, eng.tokenizer.encode("value:"),
        {"temperature": 0, "max_tokens": 16, "guided_regex": "[ab]{3}"},
        rid="st-rx")
    text = _text(eng, tokens)
    dfa = compile_char_dfa(StructuredSpec("regex", "[ab]{3}"))
    assert dfa.fullmatch(text), text
    assert finish == "stop"           # EOS only legal once accepting
    assert eng.stats()["structured_violations_total"] == 0


def test_engine_guided_json_conforms(eng):
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"}},
              "required": ["ok"]}
    tokens, finish = _collect(
        eng, eng.tokenizer.encode("emit json"),
        {"temperature": 0, "max_tokens": 32, "guided_json": schema},
        rid="st-js")
    text = _text(eng, tokens)
    assert validate_instance(schema, json.loads(text)), text
    assert finish == "stop"
    assert eng.stats()["structured_requests_total"] >= 2
    assert eng.stats()["structured_violations_total"] == 0


def test_engine_greedy_parity_when_non_binding(eng):
    """A constraint that allows every token must not change greedy
    output: masking is additive shaping, not a different sampler."""
    prompt = eng.tokenizer.encode("parity prompt")
    plain, _ = _collect(
        eng, prompt, {"temperature": 0, "max_tokens": 8}, rid="par-u")
    masked, _ = _collect(
        eng, prompt, {"temperature": 0, "max_tokens": 8,
                      "guided_regex": r"(.|\s)*"}, rid="par-m")
    assert plain == masked


def test_engine_structured_compile_budget(eng):
    """Zero new compiled program shapes: the mask is a data input, so a
    structured request must not trace anything a plain request of the
    same shape didn't."""
    def jit_cache_sizes():
        fns = [eng._prefill_fn, eng._prefill_cached_fn]
        fns += list(eng._multi_decode_fns.values())
        fns += list(eng._spec_verify_fns.values())
        return sum(f._cache_size() for f in fns)

    prompt = eng.tokenizer.encode("budget")
    _collect(eng, prompt, {"temperature": 0, "max_tokens": 8},
             rid="st-budget-plain")
    before = jit_cache_sizes()
    _collect(eng, prompt,
             {"temperature": 0, "max_tokens": 8,
              "guided_regex": "[ab]{4}"}, rid="st-budget")
    assert jit_cache_sizes() == before


def test_engine_violation_counted_on_truncation(eng):
    """max_tokens exhausted with the automaton mid-grammar counts a
    violation (truncated member of the language)."""
    before = eng.stats()["structured_violations_total"]
    tokens, finish = _collect(
        eng, eng.tokenizer.encode("v"),
        {"temperature": 0, "max_tokens": 2, "guided_regex": "[ab]{6}"},
        rid="st-trunc")
    assert finish == "length"
    assert eng.stats()["structured_violations_total"] == before + 1


def test_engine_spec_decode_structured_parity(eng):
    """Speculative decoding must be byte-identical under greedy for a
    structured request: drafts are verified under per-position masks."""
    # A grammar whose every member is shorter than max_tokens: a free
    # integer (or string) may ramble under random weights until the
    # length cap cuts it mid-structure, which is counted as a violation
    # in BOTH engines and says nothing about the drafts.
    body = {"temperature": 0, "max_tokens": 32,
            "guided_json": {"type": "object",
                            "properties": {"ok": {"type": "boolean"},
                                           "n": {"enum": [1, 22, 333]}},
                            "required": ["ok", "n"]}}
    prompt = eng.tokenizer.encode("spec parity")
    plain, _ = _collect(eng, prompt, dict(body), rid="sp-p")
    spec_eng = _make_engine(speculative_num_tokens=4)
    try:
        spec, finish = _collect(spec_eng, prompt, dict(body), rid="sp-s")
        assert spec_eng.stats()["structured_violations_total"] == 0
    finally:
        spec_eng.stop()
    assert plain == spec
    assert finish == "stop" and json.loads(_text(eng, spec))["n"] in (
        1, 22, 333)


def test_engine_chunked_prefill_structured(eng):
    """Chunked prefill only touches the boundary: the first sampled
    token is masked like any decode step, so conformance and greedy
    output match the unchunked engine."""
    body = {"temperature": 0, "max_tokens": 8, "guided_regex": "[ab]{3}"}
    prompt = eng.tokenizer.encode("chunked prefill structured prompt " * 2)
    plain, _ = _collect(eng, prompt, dict(body), rid="ch-p")
    chunked = _make_engine(enable_chunked_prefill=True,
                           max_num_batched_tokens=32)
    try:
        out, _ = _collect(chunked, prompt, dict(body), rid="ch-c")
        text = _text(chunked, out)
        assert compile_char_dfa(
            StructuredSpec("regex", "[ab]{3}")).fullmatch(text)
        assert chunked.stats()["structured_violations_total"] == 0
    finally:
        chunked.stop()
    assert plain == out


# ------------------------------------------------------------- router e2e


def test_router_corpus_conformance_both_surfaces():
    """All 30 corpus cases through the REAL router to fake engines, on
    the guided surface and the OpenAI response_format surface; an
    uncompilable schema 400s at the router."""
    import asyncio

    from production_stack_tpu.testing.structured_ab import (
        run_corpus_conformance)

    for surface in ("guided", "response_format"):
        result = asyncio.run(run_corpus_conformance(surface=surface))
        assert result["conformance"] == 1.0, result["failed"]
        assert result["cases"] >= 30
        assert result["rejects_uncompilable"]
        assert result["engine_structured_requests"] >= result["cases"]
