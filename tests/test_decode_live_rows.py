"""The decode step asks the attention kernel for the live tokens of the
live rows only (PR 32): ``models/decoder.py::attend`` hands a context of 0
for every row that writes no token this step (slot -1), and the decode
burst's step record counts what the kernel then copies
(``kv_fetch_tokens``) beside what those rows hold (``kv_live_tokens``)."""

import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import build_model, decoder, get_model_config

BS = 8


@pytest.mark.parametrize("arch", ["tiny-llama", "tiny-opt", "tiny-mixtral"])
def test_attend_zeroes_the_context_of_a_row_that_writes_no_token(
        arch, monkeypatch):
    """Through ``apply``, on the CPU: rows 1 and 3 carry slot -1 (a row no
    sequence holds, a burst step past a sequence's allowance). The
    dispatcher is handed 0 for them whatever their ``context_lens`` say,
    and the live rows' logits do not depend on what those rows hold."""
    cfg = get_model_config(arch)
    init_params, apply = build_model(cfg)
    params = init_params(cfg, jax.random.key(0))
    L, NB, B = cfg.num_layers, 12, 4
    rng = np.random.default_rng(0)
    pages = tuple(
        jnp.asarray(rng.normal(size=(L, NB, BS, cfg.num_kv_heads,
                                     cfg.head_dim)), jnp.float32)
        for _ in range(2))
    tables = jnp.asarray(rng.permutation(NB)[:B * 2].reshape(B, 2), jnp.int32)
    context = jnp.asarray([5, 1, 11, 9], jnp.int32)
    slots = jnp.asarray([tables[0, 0] * BS + 4, -1,
                         tables[2, 1] * BS + 2, -1], jnp.int32)
    seen = []
    dispatch = decoder.paged_decode_attention

    def spy(q, k_pages, v_pages, block_tables, context_lens, layer, **kw):
        seen.append(np.asarray(context_lens))
        return dispatch(q, k_pages, v_pages, block_tables, context_lens,
                        layer, **kw)

    monkeypatch.setattr(decoder, "paged_decode_attention", spy)

    def logits(tokens, context):
        with jax.disable_jit():
            out, _ = apply(
                params, cfg, tokens[:, None], (context - 1)[:, None], pages,
                slots[:, None], tables, context, jnp.ones_like(context),
                mode="decode")
        return np.asarray(out[:, 0])

    first = logits(jnp.asarray([3, 4, 5, 6], jnp.int32), context)
    assert len(seen) == L
    assert all(list(c) == [5, 0, 11, 0] for c in seen)
    other = logits(jnp.asarray([3, 9, 5, 1], jnp.int32),
                   context.at[1].set(16).at[3].set(2))
    assert all(list(c) == [5, 0, 11, 0] for c in seen)
    np.testing.assert_array_equal(first[[0, 2]], other[[0, 2]])
    assert np.isfinite(first).all()


def _generate(eng, n, max_tokens, rid):
    q: "queue.Queue" = queue.Queue()
    eng.add_request(
        rid, [(7 * i) % 200 + 1 for i in range(n)],
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True),
        lambda token, finish: q.put((token, finish)))
    while q.get(timeout=120)[1] is None:
        pass


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_decode_burst_records_what_the_kernel_fetches(path, monkeypatch):
    """One sequence in an engine of four rows, bursts of four steps: a
    record's ``kv_fetch_tokens`` is the live pages, whole, of the one live
    row at each step it may use (an empty row and a step past the
    allowance fetch nothing), counted only where the kernel runs; the
    lifetime total is in ``stats()``."""
    monkeypatch.setattr(EngineCore, "_paged_attn_path", lambda self: path)
    eng = EngineCore(EngineConfig(
        model="tiny-llama", max_model_len=256, max_num_seqs=4,
        block_size=BS, num_blocks=64, max_loras=0, decode_steps=4,
        prefill_batch=1, enable_prefix_caching=False),
        devices=jax.devices()[:1])
    eng.start()
    try:
        _generate(eng, 21, 7, "one")  # 1 token from prefill, 6 from bursts
        _generate(eng, 21, 2, "two")
    finally:
        eng.stop()  # the loop's last iteration has made its record
    bursts = eng.step_recorder.snapshot(kind="decode_burst")[::-1]
    total = eng.stats()["kv_fetch_tokens_total"]
    assert bursts and any(r["tokens"] < r["forwards"] for r in bursts)
    if path == "xla":
        assert total == 0
        assert not any("kv_fetch_tokens" in r for r in bursts)
        return
    expected, live = [], []
    for r in bursts:
        assert r["rows"] == 1
        context0 = r["kv_read_tokens"] // r["forwards"]
        # r["tokens"] of the burst's steps are the sequence's to use
        expected.append(sum(-(-(context0 + s) // BS) * BS
                            for s in range(r["tokens"])))
        live.append(sum(context0 + s for s in range(r["tokens"])))
    assert bursts[0]["kv_read_tokens"] == 4 * 22  # contexts 22..25:
    assert expected[0] == (3 + 3 + 3 + 4) * BS  # 3, 3, 3, 4 pages of 8
    assert [r["kv_fetch_tokens"] for r in bursts] == expected
    # what those pages hold; below kv_read_tokens where a burst's steps
    # run past the sequence's allowance
    assert [r["kv_live_tokens"] for r in bursts] == live
    assert any(r["kv_live_tokens"] < r["kv_read_tokens"] for r in bursts)
    assert total == sum(expected)
