"""What a profiler trace can call things by: the step programs' module
names, the model parts' named scopes in each program's lowering, and the
engine loop's ``engine.step`` / ``engine.<phase>`` annotations on a host
plane of a real ``jax.profiler`` trace (CPU)."""

import glob
import os
import queue
import re

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import (
    MAX_LOGIT_BIAS,
    MAX_STOP_IDS,
    SamplingParams,
)
from production_stack_tpu.obs.steps import PHASES

# docs/profiling.md lists these; chipbench's scope_share reader matches them.
MODEL_SCOPES = ("embed", "attn_proj", "kv_write", "attention", "mlp", "head",
                "sample")


def _has_scope(text: str, scope: str) -> bool:
    """An operation of the lowering lies under ``scope``. Inside a scan's
    body the location path starts at the body (``"mlp/dot_general"``),
    elsewhere at the program (``"jit(decode_k8)/head/..."``)."""
    return re.search(rf'["/]{scope}/', text) is not None


def _engine(**over):
    kwargs = dict(model="tiny-llama", max_model_len=128, max_num_seqs=4,
                  block_size=4, num_blocks=96, min_prefill_bucket=16,
                  max_loras=2, max_lora_rank=4)
    kwargs.update(over)
    eng = EngineCore(EngineConfig(**kwargs), devices=jax.devices()[:1])
    eng.start()
    return eng


def _generate(eng, rid, prompt, max_tokens):
    done = queue.Queue()
    eng.add_request(
        rid, prompt,
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True),
        lambda token, finish: finish is not None and done.put(finish))
    return done.get(timeout=120)


class _Tap:
    """Stands in for a jitted step program: keeps the text of its lowering
    for the arguments the engine calls it with, then calls it."""

    def __init__(self, fn, texts):
        self.fn, self.texts, self.__name__ = fn, texts, fn.__name__

    def __call__(self, *args):
        if self.__name__ not in self.texts:
            self.texts[self.__name__] = self.fn.lower(*args).as_text(
                debug_info=True)
        return self.fn(*args)


@pytest.fixture(scope="module")
def lowerings():
    """{program name: lowering text} of the step programs a tiny Llama
    engine with LoRA slots runs for two prompts that share a prefix, plus
    the speculative verify program on the warm-up's arguments."""
    eng = _engine()
    texts = {}
    try:
        K = eng.config.decode_steps
        eng._prefill_fn = _Tap(eng._prefill_fn, texts)
        eng._prefill_cached_fn = _Tap(eng._prefill_cached_fn, texts)
        eng._multi_decode_fns[K] = _Tap(eng._multi_decode_fn(K), texts)
        prompt = list(range(1, 14))
        _generate(eng, "names-1", prompt, 4)
        _generate(eng, "names-2", prompt + [20, 21], 4)
        B, Ks = eng.config.max_num_seqs, 4
        spec = eng._spec_verify_fn(Ks)
        texts[spec.__name__] = spec.lower(
            eng.params, eng.kv,
            np.zeros((B, Ks), np.int32), np.zeros((B,), np.int32),
            np.full((B, Ks), -1, np.int64), np.zeros((B, 4), np.int32),
            np.ones((B,), np.int32), np.zeros((B,), np.int32),
            np.zeros((B,), np.float32), np.zeros((B,), np.int32),
            np.ones((B,), np.float32), np.zeros((B,), np.int64),
            np.zeros((B,), np.int32), np.zeros((B,), np.int32),
            np.zeros((B, MAX_LOGIT_BIAS), np.int32),
            np.zeros((B, MAX_LOGIT_BIAS), np.float32),
            np.zeros((B, MAX_STOP_IDS), np.int32),
            np.zeros((B, MAX_STOP_IDS), np.float32),
            np.zeros((B, Ks, eng._mask_row_bytes), np.uint8),
            np.zeros((B, Ks), bool)).as_text(debug_info=True)
        records = eng.step_recorder.snapshot()
    finally:
        eng.stop()
    return texts, records


@pytest.mark.parametrize(
    "program", ["prefill", "prefill_cached", "decode_k8", "spec_verify_k4"])
def test_step_program_lowering_holds_its_name_and_every_scope(
        lowerings, program):
    texts, _ = lowerings
    assert program in texts, sorted(texts)
    text = texts[program]
    # The module's name is the program's, not that of a local ``fwd``.
    assert f"@jit_{program}" in text
    assert "jit_fwd" not in text and "jit(fwd)" not in text
    for scope in MODEL_SCOPES + ("lora",):
        assert _has_scope(text, scope), f"{program}: no scope {scope!r}"
    # The adapter's part nests in the projection it adds to.
    assert "attn_proj/lora/" in text


def test_step_records_name_the_program_they_dispatched(lowerings):
    _, records = lowerings
    programs = {r["kind"]: r["program"] for r in records}
    assert programs["decode_burst"] == "decode_k8"
    assert {r["program"] for r in records if r["kind"] == "prefill"} == {
        "prefill", "prefill_cached"}


@pytest.mark.parametrize("model", ["tiny-mixtral", "tiny-opt"])
def test_other_architectures_share_the_scope_names(model):
    eng = _engine(model=model, max_loras=0)
    texts = {}
    try:
        K = eng.config.decode_steps
        eng._prefill_fn = _Tap(eng._prefill_fn, texts)
        eng._multi_decode_fns[K] = _Tap(eng._multi_decode_fn(K), texts)
        _generate(eng, "arch-1", list(range(1, 10)), 3)
    finally:
        eng.stop()
    assert set(texts) == {"prefill", f"decode_k{K}"}
    for program, text in texts.items():
        for scope in MODEL_SCOPES:
            assert _has_scope(text, scope), f"{model} {program}: {scope!r}"


def test_profiler_trace_holds_the_loop_annotations(tmp_path):
    """A short CPU trace of a few steps: ``engine.step`` and the phases a
    step always passes through lie on a host plane, on the profiler's
    clock, the step numbered like the record it made."""
    eng = _engine(max_loras=0)
    try:
        _generate(eng, "warm", list(range(1, 10)), 9)  # compile first
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _generate(eng, "traced", list(range(30, 40)), 20)
        finally:
            jax.profiler.stop_trace()
        last_step = eng.step_recorder.recorded_total
    finally:
        eng.stop()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "the profiler wrote no trace"
    data = jax.profiler.ProfileData.from_file(found[0])
    names, step_nums = set(), set()
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    names.add(ev.name)
                if ev.name == "engine.step":
                    step_nums.update(
                        int(v) for k, v in ev.stats if k == "step_num")
    assert "engine.step" in names
    for phase in ("schedule", "build", "enqueue", "readback", "emit"):
        assert f"engine.{phase}" in names, sorted(names)
    assert names <= {"engine.step"} | {f"engine.{p}" for p in PHASES}
    assert step_nums and max(step_nums) <= last_step + 1
