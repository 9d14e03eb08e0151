"""Flash cached-prefill kernel + fused-step correctness pins.

Ops level (interpret mode, CPU test mesh): the pallas prefill kernel —
prefix pages streamed via DMAs, fresh suffix attended from VMEM — must
match the XLA gather reference (``context_prefill_attention``) on bf16
and int8 pages, ragged prefix/suffix lengths, GQA groups, multi-tile
query spans, and the chunked-score reference path; misaligned shapes
must fall back to XLA through the dispatcher without error.

Engine level: ``--fused-step`` off must be byte-identical to the
pre-fused engine; fused-on greedy streams must be byte-identical to
alternating dispatches (including structured-output and spec-decode
traffic); warmup must compile ZERO new program variants for the fused
path; and the dispatch-path metric must export both label values.
"""

import collections
import json
import os
import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import production_stack_tpu.ops.attention as att
from production_stack_tpu.ops.attention import (
    _gather_ctx,
    _page_tile_ok,
    context_prefill_attention,
    attention_path,
    quantize_kv,
)
from production_stack_tpu.ops.pallas_prefill_attention import (
    prefill_tile,
    pallas_prefill_attention,
)


def _setup_prefill(B, T, KVH, group, D, L, NB, bs, MAXB, *,
                   quantized=False, seed=0, layer=1,
                   prefix=None, take=None):
    """Build pages + a fresh chunk whose suffix slots the pages already
    hold (the engine's write-then-attend layout): the reference regathers
    the suffix from HBM while the kernel attends it from ``k_new`` — for
    parity the two encodings must be numerically identical, so the fresh
    values are derived FROM the (de)quantized page content."""
    rng = np.random.default_rng(seed)
    H = KVH * group
    S = MAXB * bs
    assert NB >= B * MAXB
    tables = rng.permutation(NB)[: B * MAXB].reshape(B, MAXB).astype(
        np.int32)
    if prefix is None:
        prefix = rng.integers(0, S - T + 1, size=(B,))
    prefix = np.asarray(prefix, np.int32)
    if take is None:
        take = rng.integers(1, T + 1, size=(B,))
    take = np.asarray(take, np.int32)
    total = (prefix + take).astype(np.int32)
    positions = (prefix[:, None] + np.arange(T)[None, :]).astype(np.int32)

    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    ctx_k = rng.normal(size=(B, S, KVH, D)).astype(np.float32)
    ctx_v = rng.normal(size=(B, S, KVH, D)).astype(np.float32)

    if quantized:
        qk, sk = quantize_kv(jnp.asarray(ctx_k))
        qv, sv = quantize_kv(jnp.asarray(ctx_v))
        qk, sk = np.asarray(qk), np.asarray(sk)
        qv, sv = np.asarray(qv), np.asarray(sv)
        # Both paths must see the SAME suffix values: the dequantized
        # page content is the ground truth.
        ctx_k = qk.astype(np.float32) * sk[..., None]
        ctx_v = qv.astype(np.float32) * sv[..., None]
        kd = rng.integers(-127, 127, size=(L, NB, bs, KVH, D)).astype(
            np.int8)
        vd = rng.integers(-127, 127, size=(L, NB, bs, KVH, D)).astype(
            np.int8)
        ks = np.ones((L, NB, bs * KVH), np.float32)
        vs = np.ones((L, NB, bs * KVH), np.float32)
        for b in range(B):
            for j in range(MAXB):
                pg = tables[b, j]
                kd[layer, pg] = qk[b, j * bs:(j + 1) * bs]
                vd[layer, pg] = qv[b, j * bs:(j + 1) * bs]
                ks[layer, pg] = sk[b, j * bs:(j + 1) * bs].reshape(-1)
                vs[layer, pg] = sv[b, j * bs:(j + 1) * bs].reshape(-1)
        k_pages = (jnp.asarray(kd), jnp.asarray(ks))
        v_pages = (jnp.asarray(vd), jnp.asarray(vs))
    else:
        kd = rng.normal(size=(L, NB, bs, KVH, D)).astype(np.float32)
        vd = rng.normal(size=(L, NB, bs, KVH, D)).astype(np.float32)
        for b in range(B):
            for j in range(MAXB):
                kd[layer, tables[b, j]] = ctx_k[b, j * bs:(j + 1) * bs]
                vd[layer, tables[b, j]] = ctx_v[b, j * bs:(j + 1) * bs]
        k_pages = jnp.asarray(kd)
        v_pages = jnp.asarray(vd)

    # The chunk's fresh K/V: exactly the context rows at the query
    # positions (what write_kv_pages scattered one op earlier).
    gather = np.take_along_axis
    k_new = gather(ctx_k, positions[:, :, None, None], axis=1)
    v_new = gather(ctx_v, positions[:, :, None, None], axis=1)
    return dict(
        q=q, k_pages=k_pages, v_pages=v_pages,
        tables=jnp.asarray(tables), positions=jnp.asarray(positions),
        total=jnp.asarray(total), layer=jnp.int32(layer),
        k_new=jnp.asarray(k_new), v_new=jnp.asarray(v_new),
        take=jnp.asarray(take),
    )


def _run_both(s, *, scale=0.09, rtol=2e-3, atol=2e-3, **kernel_kw):
    ref = context_prefill_attention(
        s["q"], s["k_pages"], s["v_pages"], s["tables"], s["positions"],
        s["total"], s["layer"], scale=scale)
    got = pallas_prefill_attention(
        s["q"], s["k_pages"], s["v_pages"], s["tables"], s["positions"],
        s["total"], s["layer"], s["k_new"], s["v_new"], s["take"],
        scale=scale, interpret=True, **kernel_kw)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=rtol, atol=atol)
    assert np.isfinite(np.asarray(got)).all()
    return ref, got


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("MAXB", [4, 8])
def test_prefill_kernel_matches_reference(group, MAXB):
    B, T, KVH, D, L, bs = 3, 12, 8, 128, 2, 8
    NB = B * MAXB + 2
    # Row 0: empty prefix (first chunk — suffix-only attention).
    prefix = [0, 16, MAXB * bs - T]
    s = _setup_prefill(B, T, KVH, group, D, L, NB, bs, MAXB,
                       prefix=prefix, seed=group + MAXB)
    _run_both(s)


def test_prefill_kernel_int8_pages():
    """int8 pages dequantize on-chip; parity is exact up to f32 order
    because the fresh suffix values are the dequantized page rows."""
    B, T, KVH, group, D, L, bs, MAXB = 3, 12, 8, 2, 128, 2, 16, 4
    NB = B * MAXB + 2
    s = _setup_prefill(B, T, KVH, group, D, L, NB, bs, MAXB,
                       quantized=True, prefix=[0, 9, 40], seed=7)
    _run_both(s)


def test_prefill_kernel_multi_tile_queries():
    """T spanning several query tiles: the DMA ring's global step
    crosses tile AND row boundaries (each tile re-streams its row's
    prefix), and the untile round trip must be exact."""
    B, T, KVH, group, D, L, bs, MAXB = 2, 24, 8, 1, 128, 1, 8, 8
    NB = B * MAXB + 1
    s = _setup_prefill(B, T, KVH, group, D, L, NB, bs, MAXB,
                       prefix=[5, 33], take=[24, 17], seed=11, layer=0)
    _run_both(s, q_tile=8)  # nq = 3


def test_prefill_kernel_all_rows_suffix_only():
    """Every row at prefix 0 (a batched first-chunk step): no page ever
    streams; the kernel's empty partials (m=-inf, l=0) must merge into
    a pure fresh-suffix softmax."""
    B, T, KVH, group, D, L, bs, MAXB = 2, 8, 8, 2, 128, 1, 8, 4
    NB = B * MAXB
    s = _setup_prefill(B, T, KVH, group, D, L, NB, bs, MAXB,
                       prefix=[0, 0], take=[8, 3], seed=5, layer=0)
    _run_both(s)


def test_prefill_kernel_matches_chunked_score_reference(monkeypatch):
    """Parity against the reference's own online-softmax (chunked
    scores) path, forced at toy shapes."""
    B, T, KVH, group, D, L, bs, MAXB = 2, 8, 8, 2, 128, 1, 8, 8
    NB = B * MAXB
    s = _setup_prefill(B, T, KVH, group, D, L, NB, bs, MAXB,
                       prefix=[3, 30], seed=13, layer=0)
    monkeypatch.setattr(att, "_CHUNKED_SCORE_BYTES", 0)
    monkeypatch.setattr(att, "_CHUNKED_SCORE_SPAN", 32)
    _run_both(s)


def test_dispatcher_falls_back_on_misaligned_shapes():
    """head_dim 32 fails the tile gate: the dispatcher must serve the
    XLA reference (exactly — same code path) even when fresh values are
    passed."""
    B, T, KVH, group, D, L, bs, MAXB = 2, 8, 8, 2, 32, 1, 8, 4
    NB = B * MAXB
    s = _setup_prefill(B, T, KVH, group, D, L, NB, bs, MAXB, seed=17,
                       layer=0)
    ref = context_prefill_attention(
        s["q"], s["k_pages"], s["v_pages"], s["tables"], s["positions"],
        s["total"], s["layer"], scale=0.2)
    got = context_prefill_attention(
        s["q"], s["k_pages"], s["v_pages"], s["tables"], s["positions"],
        s["total"], s["layer"], scale=0.2,
        k_new=s["k_new"], v_new=s["v_new"], suffix_lens=s["take"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_dispatcher_kernel_failure_raises(monkeypatch):
    """With the platform gate forced open on CPU, the pallas call cannot
    lower. The dispatcher chose the kernel at trace time from shapes and
    platform; a kernel that then fails raises — nothing turns the failure
    into the reference path (on the chip it would surface when the
    enclosing jit compiles, where no fallback is possible anyway)."""
    B, T, KVH, group, D, L, bs, MAXB = 2, 8, 8, 1, 128, 1, 8, 4
    NB = B * MAXB
    s = _setup_prefill(B, T, KVH, group, D, L, NB, bs, MAXB, seed=19,
                       layer=0)
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    with pytest.raises(Exception, match="(?i)pallas|mosaic|interpret"):
        context_prefill_attention(
            s["q"], s["k_pages"], s["v_pages"], s["tables"], s["positions"],
            s["total"], s["layer"], scale=0.1,
            k_new=s["k_new"], v_new=s["v_new"], suffix_lens=s["take"])


_use_pallas_real = att._use_pallas


class _FakeTpu:
    platform = "tpu"


def test_page_tile_gate_and_path_label():
    assert _page_tile_ok(8, 8, 128)
    assert _page_tile_ok(16, 8, 128)
    assert not _page_tile_ok(8, 12, 128)  # OPT kv heads
    assert not _page_tile_ok(8, 8, 64)  # head_dim
    assert not _page_tile_ok(4, 8, 128)  # block_size
    # On the CPU test mesh the runtime gate closes the pallas path.
    assert attention_path(16, 8, 128, True) == "xla"
    assert attention_path(8, 12, 128, False) == "xla"


def test_path_label_env_override(monkeypatch):
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    assert attention_path(16, 8, 128, True) == "pallas"
    assert attention_path(8, 8, 128, True) == "pallas"  # no scale-row gate
    assert attention_path(8, 12, 128, False) == "xla"
    # A pool sharded over kv heads: the kernel sees kvh / shards heads.
    assert attention_path(64, 32, 128, False, kv_shards=4) == "pallas"
    assert attention_path(64, 8, 128, False, kv_shards=4) == "xla"
    assert attention_path(64, 32, 128, True, kv_shards=4) == "xla"
    monkeypatch.setenv("TPU_STACK_FORCE_XLA_ATTENTION", "1")
    monkeypatch.setattr(att, "_use_pallas", _use_pallas_real)
    monkeypatch.setattr(att.jax, "devices", lambda: [_FakeTpu()])
    assert attention_path(64, 8, 128, False) == "xla"  # the override
    monkeypatch.delenv("TPU_STACK_FORCE_XLA_ATTENTION")
    assert attention_path(64, 8, 128, False) == "pallas"


def test_gather_ctx_accumulation_dtype_explicit():
    """Both page encodings must honor out_dtype, and BOTH must default
    to float32 — the reference accumulation dtype the kernel parity
    tolerances are calibrated against."""
    L, NB, bs, KVH, D = 1, 4, 8, 8, 16
    rng = np.random.default_rng(0)
    pages = jnp.asarray(rng.normal(size=(L, NB, bs, KVH, D)), jnp.bfloat16)
    tables = jnp.asarray([[0, 1]], jnp.int32)
    assert _gather_ctx(pages, tables, jnp.int32(0)).dtype == jnp.float32
    assert _gather_ctx(
        pages, tables, jnp.int32(0), out_dtype=jnp.bfloat16
    ).dtype == jnp.bfloat16
    data = jnp.asarray(
        rng.integers(-127, 127, size=(L, NB, bs, KVH, D)), jnp.int8)
    scales = jnp.asarray(
        rng.uniform(0.01, 1.0, size=(L, NB, bs * KVH)), jnp.float32)
    assert _gather_ctx((data, scales), tables,
                       jnp.int32(0)).dtype == jnp.float32
    got16 = _gather_ctx((data, scales), tables, jnp.int32(0),
                        out_dtype=jnp.bfloat16)
    assert got16.dtype == jnp.bfloat16
    # The dequant multiply itself stays f32 and casts ONCE at the end.
    want = (np.asarray(data[0, [0, 1]], np.float32).reshape(1, 2 * bs, KVH, D)
            * np.asarray(scales[0, [0, 1]]).reshape(1, 2 * bs, KVH)[..., None]
            ).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got16), want)


def test_prefill_tile_fits_vmem_budget():
    """The tile chooser: aligned tiles inside the budget at every serving
    width, narrower query tiles as the head count grows."""
    from production_stack_tpu.ops.pallas_paged_attention import (
        VMEM_BUDGET, ring_bytes)

    widths = {}
    for T, H in [(12, 16), (1024, 16), (1024, 24), (2048, 32), (1024, 64)]:
        for quantized in (False, True):
            tq, pages, ring = prefill_tile(
                T, H, 64, 8, 128, 1 if quantized else 2, 128, quantized)
            assert tq % 8 == 0 and tq >= 8
            assert (pages * 64) % 128 == 0 and ring >= 2
            assert ring_bytes(ring, pages, 64, 8, 128,
                              1 if quantized else 2) < VMEM_BUDGET
            widths[T, H, quantized] = tq
    assert widths[1024, 64, False] < widths[1024, 16, False]
    # Nothing fits: the caller is told, not handed a tile that will not
    # compile.
    assert prefill_tile(1024, 4096, 64, 8, 128, 2, 128, False) is None


# ---------------------------------------------------------------------------
# Engine level: the fused step program (--fused-step)
# ---------------------------------------------------------------------------

import time  # noqa: E402

from test_chunked_prefill import exec_plan, mk_req, run_requests  # noqa: E402
from test_engine_core import make_engine  # noqa: E402

from production_stack_tpu.engine.kvcache import KVCacheManager  # noqa: E402
from production_stack_tpu.engine.scheduler import Scheduler  # noqa: E402

CHUNKED = dict(enable_chunked_prefill=True, max_num_batched_tokens=32)


def _jit_cache_sizes(eng):
    """Compiled variants of every step program but the decode bursts'
    (those: ``_assert_decode_variants``)."""
    fns = [eng._prefill_fn, eng._prefill_cached_fn]
    fns += list(eng._spec_verify_fns.values())
    return sum(f._cache_size() for f in fns)


def _decode_shapes(eng):
    """{K: operand shapes of the decode bursts this engine dispatches from
    now on}: one compiled variant of ``decode_k<K>`` each, and no more."""
    seen = collections.defaultdict(set)
    exec_op = eng._exec_op

    def recording(name, static, arrays):
        if name == "decode":
            seen[static["K"]].add(tuple(np.shape(a) for a in arrays))
        return exec_op(name, static, arrays)

    eng._exec_op = recording
    return seen


def _assert_decode_variants(eng, seen):
    assert ({K: f._cache_size() for K, f in eng._multi_decode_fns.items()}
            == {K: len(shapes) for K, shapes in seen.items()}), (
        "a decode program has a variant beside its operand shapes")


def _run_mixed(eng):
    """Three plain greedy prompts plus one structured request, all
    submitted at once (prefill chunks interleave with running decodes —
    the fused scheduler's engagement condition)."""
    from production_stack_tpu.engine.sampling import SamplingParams

    streams = run_requests(
        eng,
        [list(range(1, 60)), list(range(7, 19)), list(range(101, 140))],
        [12, 12, 12])
    q = queue.Queue()
    eng.add_request(
        "structured", list(range(31, 72)),
        SamplingParams.from_request(
            {"temperature": 0, "max_tokens": 8,
             "guided_regex": "[ab]{4}"}),
        lambda t, f: q.put((t, f)))
    tokens = []
    deadline = time.time() + 300
    while time.time() < deadline:
        try:
            token, finish = q.get(timeout=10)
        except queue.Empty:
            continue
        if token is not None:
            tokens.append(token)
        if finish is not None:
            streams["structured"] = (tokens, finish)
            break
    else:
        raise TimeoutError("structured request did not finish")
    return streams


def test_fused_streams_equal_alternating():
    """--fused-step greedy byte-identity against the alternating-dispatch
    engine (structured composition included), zero new compiled
    variants, and the flag-off registry surface."""
    ref = make_engine(**CHUNKED)
    try:
        ref_decodes = _decode_shapes(ref)
        expected = _run_mixed(ref)
        assert ref.prefill_chunks_total >= 4
        # Flag-off registry parity: the fused path exports, but at zero.
        s = ref.stats()
        assert s["fused_steps_total"] == 0
        assert set(s["prefill_attention_dispatch_total"]) == \
            {"pallas", "xla"}
        # The CPU test mesh always takes the gather reference.
        assert s["prefill_attention_dispatch_total"]["pallas"] == 0
        assert s["prefill_attention_dispatch_total"]["xla"] >= 4
        assert "fused" not in {
            k for k, v in s["step_kind_stats"].items() if v["count"]}
        ref_variants = dict(ref.warmup_variants)
        # The decode programs: a variant an operand shape dispatched, in
        # either engine (the two schedules reach different table widths);
        # every other program is shape for shape the same in both.
        _assert_decode_variants(ref, ref_decodes)
        ref_cache = _jit_cache_sizes(ref)
    finally:
        ref.stop()

    eng = make_engine(fused_step=True, **CHUNKED)
    try:
        assert eng.warmup_variants == ref_variants, (
            "--fused-step must not compile any new program variants")
        decodes = _decode_shapes(eng)
        got = _run_mixed(eng)
        _assert_decode_variants(eng, decodes)
        assert _jit_cache_sizes(eng) == ref_cache, (
            "fused traffic traced a program shape alternating "
            "dispatches did not")
        assert eng.fused_steps_total >= 1, (
            "workload never engaged the fused step program")
        s = eng.stats()
        assert s["step_kind_stats"].get("fused", {}).get("count", 0) >= 1
    finally:
        eng.stop()
    assert got == expected


def test_fused_spec_decode_streams_equal():
    """Speculative decoding composes: spec bursts cannot ride the fused
    program (host drafting needs real tokens), so the capture degrades —
    and the streams must stay byte-identical."""
    # Repetitive prompts so prompt-lookup drafts actually accept.
    prompts = [[5, 6, 7, 8] * 9, list(range(3, 40))]
    max_tokens = [16, 16]
    ref = make_engine(speculative_num_tokens=4, **CHUNKED)
    try:
        expected = run_requests(ref, prompts, max_tokens)
    finally:
        ref.stop()
    eng = make_engine(speculative_num_tokens=4, fused_step=True, **CHUNKED)
    try:
        got = run_requests(eng, prompts, max_tokens)
    finally:
        eng.stop()
    assert got == expected


def test_fused_scheduler_action_emission():
    """Scheduler unit: "fused" only when BOTH a plan exists and
    sequences are running; prefill-only and decode-only steps keep
    their plain actions; flag off never emits "fused"."""
    for flag in (True, False):
        kv = KVCacheManager(64, 4, enable_prefix_caching=False)
        sched = Scheduler(
            kv, max_num_seqs=4, max_model_len=512, chunked_prefill=True,
            chunk_tokens=16, token_budget=16, fused_step=flag)
        warm = mk_req("warm", 8)
        sched.add(warm)
        action, plan = sched.next_action()
        assert action == "prefill_step"  # nothing running yet
        exec_plan(sched, kv, plan)
        assert sched.num_running == 1
        long = mk_req("long", 48)
        sched.add(long)
        action, plan = sched.next_action()
        assert action == ("fused" if flag else "prefill_step")
        exec_plan(sched, kv, plan)
        while long.num_computed_tokens < 48:
            action, plan = sched.next_action()
            if flag:
                assert action == "fused"
                assert sched._prefill_streak == 0
            if action in ("fused", "prefill_step"):
                exec_plan(sched, kv, plan)
        assert sched.next_action()[0] == "decode"


@pytest.mark.parametrize("group,MAXB,bs,T,window,prefix,take,q_tile,quantized", [
    (6, 16, 8, 24, 20, [0, 50, 100], [24, 17, 24], 8, False),
    (9, 16, 8, 24, 40, [5, 33, 104], [24, 17, 20], 8, False),
    (2, 32, 8, 16, 30, [0, 200, 77], [16, 9, 16], 0, True),
    (1, 8, 16, 12, 64, [0, 9, 100], [12, 12, 5], 0, False),
])
def test_prefill_kernel_window_lower_bound(monkeypatch, group, MAXB, bs, T,
                                           window, prefix, take, q_tile,
                                           quantized):
    """With ``window`` the query at position p sees the keys after
    ``p - window``, in the cached prefix (a per-row bound in the kernel,
    chunks that end before a tile's window not computed) and in the
    chunk's own fresh keys. The kernel, the one-shot reference and the
    reference's chunked online softmax agree (a query whose window starts
    past a chunk sees none of it, in either), and all differ from full
    attention."""
    B, KVH, D, L = 3, 8, 128, 2
    s = _setup_prefill(B, T, KVH, group, D, L, B * MAXB + 2, bs, MAXB,
                       prefix=prefix, take=take, seed=3, quantized=quantized)
    args = (s["q"], s["k_pages"], s["v_pages"], s["tables"], s["positions"],
            s["total"], s["layer"])
    want = context_prefill_attention(*args, scale=0.09, window=window)
    got = pallas_prefill_attention(
        *args, s["k_new"], s["v_new"], s["take"], scale=0.09, interpret=True,
        window=window, **({"q_tile": q_tile} if q_tile else {}))
    monkeypatch.setattr(att, "_CHUNKED_SCORE_BYTES", 1)
    monkeypatch.setattr(att, "_CHUNKED_SCORE_SPAN", 32)
    chunked = context_prefill_attention(*args, scale=0.09, window=window)
    live = np.arange(T)[None, :] < np.asarray(s["take"])[:, None]
    for other in (got, chunked):
        np.testing.assert_allclose(np.asarray(other)[live],
                                   np.asarray(want)[live],
                                   rtol=2e-3, atol=2e-3)
    assert np.isfinite(np.asarray(got)[live]).all()
    monkeypatch.undo()
    full = context_prefill_attention(*args, scale=0.09)
    assert np.abs(np.asarray(full) - np.asarray(want))[live].max() > 0.1
