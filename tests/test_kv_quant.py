"""Int8 KV cache (--kv-cache-dtype int8): greedy decode agreement with
bf16 token-for-token, capacity math (~2x blocks at equal HBM), offload
payload shrink, Pallas int8 kernel parity (interpret mode), and flag-off
parity (bf16 path structurally unchanged)."""

import queue
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore, kv_bytes_per_block
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.ops.attention import (
    kv_page_data,
    paged_attention_reference,
    quantize_kv,
    write_kv_pages,
)
from test_pallas_attention import dead_slots, live_tables


def make_engine(**over) -> EngineCore:
    kwargs = dict(
        model="tiny-llama",
        max_model_len=256,
        max_num_seqs=2,
        block_size=8,
        num_blocks=96,
        min_prefill_bucket=16,
        max_loras=0,
    )
    kwargs.update(over)
    eng = EngineCore(EngineConfig(**kwargs), devices=jax.devices()[:1])
    eng.start()
    return eng


def collect(engine: EngineCore, prompt, sampling, rid="r1", timeout=180):
    q: "queue.Queue" = queue.Queue()

    def on_token(token, finish):
        q.put((token, finish))

    engine.add_request(rid, prompt, sampling, on_token)
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


# Llama-3-8B KV dims: the model class the capacity acceptance targets.
# (tiny-llama's tiny head count is dominated by int8 sublane-32 padding
# and does NOT show the real ratio.)
_LLAMA8B = types.SimpleNamespace(
    arch="llama", num_layers=32, num_kv_heads=8, head_dim=128,
    dtype="bfloat16")


# Nats. Int8 KV pages perturb every logit a little; under random weights
# the top logits are near-tied (bf16 rounds two of them to the SAME value
# within the first five tokens of this prompt), so an argmax may flip —
# after which the two runs decode different texts and nothing further is
# comparable.
GREEDY_LOGPROB_TOL = 0.05


def test_greedy_decode_matches_bf16_token_for_token():
    """Acceptance (a): greedy decoding over an int8 KV cache tracks the
    bf16 cache on the XLA/CPU path, token for token until a near-tie.
    Held to the logits, not to the argmax alone: at every position both
    runs reach with the same history — the shared tokens and the one
    where they part — every top-3 candidate the two runs have in common
    has the same logprob within GREEDY_LOGPROB_TOL, and where they part,
    each run's choice is among the other's top 3 (so, by the line above,
    within the tolerance of the other's choice: a near-tie, the only way
    a faithful cache may flip a token)."""
    prompt = [1, 5, 9, 13, 17, 21, 2, 4]
    sp = SamplingParams(temperature=0.0, max_tokens=70, ignore_eos=True,
                        logprobs=3)
    outs = {}
    for dtype in ("bf16", "int8"):
        eng = make_engine(kv_cache_dtype=dtype)
        try:
            toks, finish = collect(eng, prompt, sp, rid=f"g-{dtype}")
            assert finish == "length"
            outs[dtype] = toks
        finally:
            eng.stop()
    bf16, int8 = outs["bf16"], outs["int8"]
    assert len(bf16) == len(int8) == 70
    same = next((i for i in range(70) if bf16[i][0] != int8[i][0]), 70)
    for i in range(min(same + 1, 70)):
        top_a, top_b = dict(bf16[i][1]["top"]), dict(int8[i][1]["top"])
        for token in top_a.keys() & top_b.keys():
            assert abs(top_a[token] - top_b[token]) <= GREEDY_LOGPROB_TOL, \
                (i, token, top_a, top_b)
        assert bf16[i][0] in top_b and int8[i][0] in top_a, (i, top_a, top_b)
        assert abs(top_a[bf16[i][0]] - top_a[int8[i][0]]) \
            <= GREEDY_LOGPROB_TOL, (i, top_a, top_b)


def test_capacity_doubles_at_equal_hbm_budget():
    """Acceptance (b): at llama-8B KV dims, int8 bytes-per-block buys
    >= 1.9x the blocks of bf16 for the same simulated HBM budget."""
    bs = 64
    bf16 = kv_bytes_per_block(_LLAMA8B, bs, "bf16")
    int8 = kv_bytes_per_block(_LLAMA8B, bs, "int8")
    ratio = bf16 / int8
    assert ratio >= 1.9, (bf16, int8, ratio)

    budget = 8 << 30  # 8 GB of HBM for the pool
    assert (budget // int8) >= 1.9 * (budget // bf16)

    # bf16 math unchanged: exact un-padded formula at aligned dims.
    assert bf16 == 32 * 2 * bs * 8 * 128 * 2


def test_offload_payload_at_most_055x_bf16():
    """Acceptance (c): a packed int8+scales offload block is <= 0.55x
    the bf16 payload for the same block shape (head_dim >= 64)."""
    import ml_dtypes

    from production_stack_tpu.kv.offload import pack_block

    # Real-ish block shape: npz entry overhead (~500 B per array) must
    # not dominate, as it would at toy dims.
    L, bs, KVH, D = 4, 32, 4, 128
    rng = np.random.default_rng(17)
    kb = rng.standard_normal((L, bs, KVH, D)).astype(ml_dtypes.bfloat16)
    vb = rng.standard_normal((L, bs, KVH, D)).astype(ml_dtypes.bfloat16)
    bf16_payload = pack_block(kb, vb)

    kd = rng.integers(-127, 128, (L, bs, KVH, D), np.int8)
    vd = rng.integers(-127, 128, (L, bs, KVH, D), np.int8)
    ks = rng.random((L, bs * KVH), np.float32)
    vs = rng.random((L, bs * KVH), np.float32)
    int8_payload = pack_block((kd, ks), (vd, vs))

    ratio = len(int8_payload) / len(bf16_payload)
    assert ratio <= 0.55, (len(int8_payload), len(bf16_payload), ratio)


def test_write_gather_quant_roundtrip():
    """write_kv_pages quantizes on scatter; the reference read path
    dequantizes: the round trip reproduces the written values within
    int8 symmetric-quantization error, and attention outputs match the
    bf16 cache closely."""
    L, NB, bs, KVH, D, B, H = 2, 12, 8, 2, 32, 3, 4
    rng = np.random.default_rng(23)
    k_new = jnp.asarray(rng.standard_normal((B, 1, KVH, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, 1, KVH, D)), jnp.float32)
    slots = jnp.asarray([[0], [9], [17]], jnp.int32)  # blocks 0, 1, 2

    def pages(quantized):
        z = jnp.zeros((L, NB, bs, KVH, D), jnp.float32)
        if not quantized:
            return z, z
        zq = jnp.zeros((L, NB, bs, KVH, D), jnp.int8)
        s = jnp.ones((L, NB, bs * KVH), jnp.float32)
        return (zq, s), (zq, s)

    kq, vq = write_kv_pages(*pages(True), k_new, v_new, slots, jnp.int32(1))
    kf, vf = write_kv_pages(*pages(False), k_new, v_new, slots, jnp.int32(1))

    # Dequantize the written slots and compare to the float scatter.
    data, scales = kq
    deq = (np.asarray(data, np.float32).reshape(L, NB * bs, KVH, D)
           * np.asarray(scales, np.float32).reshape(L, NB * bs, KVH)[
               ..., None]).reshape(L, NB, bs, KVH, D)
    err = np.abs(deq - np.asarray(kf))
    ref = np.abs(np.asarray(kf)).max()
    assert err.max() <= ref / 127 + 1e-6, err.max()

    # Attention over the quantized pages tracks the float pages.
    tables = jnp.asarray([[0, 1], [1, 2], [2, 0]], jnp.int32)
    ctx = jnp.asarray([1, 2, 2], jnp.int32)
    qv = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    out_q = paged_attention_reference(
        qv, kq, vq, tables, ctx, jnp.int32(1), scale=0.2)
    out_f = paged_attention_reference(
        qv, kf, vf, tables, ctx, jnp.int32(1), scale=0.2)
    np.testing.assert_allclose(
        np.asarray(out_q), np.asarray(out_f), rtol=0.05, atol=0.05)


def test_pallas_int8_kernel_matches_reference():
    """The int8 Pallas kernel (page+scale DMAs, on-chip dequant) must
    match the XLA reference reading the SAME quantized pages. Dims sit
    on the dispatch gate's tile grid: D=128, bs*KVH=128."""
    from production_stack_tpu.ops.pallas_paged_attention import (
        pallas_paged_attention,
    )

    B, H, KVH, D, L, bs, MAXB = 4, 16, 8, 128, 3, 16, 4
    NB = B * MAXB + 2
    rng = np.random.default_rng(29)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(L, NB, bs, KVH, D)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(L, NB, bs, KVH, D)), jnp.float32)
    kd, ks = quantize_kv(kf)
    vd, vs = quantize_kv(vf)
    k_pages = (kd, ks.reshape(L, NB, bs * KVH))
    v_pages = (vd, vs.reshape(L, NB, bs * KVH))
    tables = jnp.asarray(
        rng.permutation(NB)[: B * MAXB].reshape(B, MAXB).astype(np.int32))
    ctx = jnp.asarray(
        rng.integers(1, MAXB * bs + 1, size=(B,)).astype(np.int32))
    for layer in (0, L - 1):
        ref = paged_attention_reference(
            q, k_pages, v_pages, tables, ctx, jnp.int32(layer), scale=0.1)
        got = pallas_paged_attention(
            q, k_pages, v_pages, tables, ctx, jnp.int32(layer),
            scale=0.1, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)


def _int8_rows(ctx, maxb, bs=16, seed=31):
    """Rows with these contexts over an int8 pool (the tables of
    ``test_pallas_attention.live_tables``)."""
    B, H, KVH, D, L = len(ctx), 16, 8, 128, 2
    rng = np.random.default_rng(seed)
    tables, NB = live_tables(ctx, maxb, bs, rng)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    sides = []
    for _ in range(2):
        data, scales = quantize_kv(jnp.asarray(
            rng.normal(size=(L, NB, bs, KVH, D)), jnp.float32))
        sides.append((data, scales.reshape(L, NB, bs * KVH)))
    return (q, sides[0], sides[1], jnp.asarray(tables),
            jnp.asarray(np.asarray(ctx, np.int32)))


@pytest.mark.parametrize("empty", [0, -3], ids=["zero", "negative"])
def test_pallas_int8_rows_that_hold_nothing_give_zeros(empty):
    """The int8 kernel too: no copy (pages or scale rows) and zeros for a
    context of 0 or less, the live rows bit for bit what they are alone,
    at contexts on both sides of a page's and a chunk's edge."""
    from production_stack_tpu.ops.pallas_paged_attention import (
        pallas_paged_attention,
    )

    ctx = [empty, 17, empty, 128, 129, empty, 1, 300, empty]
    q, k_pages, v_pages, tables, ctx = _int8_rows(ctx, 32)
    live = np.asarray(ctx) > 0
    got = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1,
        interpret=True)
    ref = paged_attention_reference(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1)
    assert not np.asarray(got)[~live].any()
    assert not np.asarray(ref)[~live].any()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)
    alone = pallas_paged_attention(
        q[live], k_pages, v_pages, tables[live], ctx[live], jnp.int32(1),
        scale=0.1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got)[live], np.asarray(alone))


@pytest.mark.parametrize("maxb", [3, 8])
def test_pallas_int8_context_past_the_table_is_cut_to_it(maxb):
    """A context that claims more tokens than its table has pages for
    reads the table's tokens (pages and scale rows alike), as the
    reference does; maxb 3: a table that is no whole chunk."""
    from production_stack_tpu.ops.pallas_paged_attention import (
        pallas_paged_attention,
    )

    full = maxb * 16
    q, k_pages, v_pages, tables, held = _int8_rows([full, 5, full, 0], maxb)
    claimed = jnp.asarray([full + 1, 5, full + 1000, 0], jnp.int32)
    got = pallas_paged_attention(
        q, k_pages, v_pages, tables, claimed, jnp.int32(1), scale=0.1,
        interpret=True)
    ref = paged_attention_reference(
        q, k_pages, v_pages, tables, claimed, jnp.int32(1), scale=0.1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)
    cut = pallas_paged_attention(
        q, k_pages, v_pages, tables, held, jnp.int32(1), scale=0.1,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(cut))


def test_pallas_int8_nothing_past_a_context_reaches_the_result():
    """Scales of every token slot no live context holds set to NaN (keys)
    and Inf (values), their int8 values to the extremes: the same finite
    output, bit for bit. An int8 value is finite whatever it is, so the
    zero the p @ v dot needs rides on the value scales."""
    from production_stack_tpu.ops.pallas_paged_attention import (
        pallas_paged_attention,
    )

    bs, KVH = 16, 8
    ctx = [2 * 128 + bs + 3, 5, 0, bs, 129, 1, bs + 1, 2 * bs - 1, 127, -1]
    q, k_pages, v_pages, tables, ctx = _int8_rows(ctx, 32, seed=37)
    clean = pallas_paged_attention(
        q, k_pages, v_pages, tables, ctx, jnp.int32(1), scale=0.1,
        interpret=True)
    NB = k_pages[0].shape[1]
    dead = dead_slots(tables, ctx, NB, bs)

    def poison(pages, value, extreme):
        data, scales = pages
        slot = dead[None, :, :, None]
        return (jnp.where(slot[..., None], jnp.int8(extreme), data),
                jnp.where(slot, value, scales.reshape(-1, NB, bs, KVH))
                .reshape(scales.shape))

    got = pallas_paged_attention(
        q, poison(k_pages, jnp.nan, 127), poison(v_pages, jnp.inf, -127),
        tables, ctx, jnp.int32(1), scale=0.1, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def test_flag_off_bf16_path_structurally_unchanged():
    """Parity guarantee: with the flag off (default) the KV pytree is
    bare bf16 arrays — no tuples, no scale leaves — and stats reports
    the bf16 per-token byte cost."""
    eng = make_engine()
    try:
        k_pages, v_pages = eng.kv
        assert not isinstance(k_pages, tuple)
        assert not isinstance(v_pages, tuple)
        assert kv_page_data(k_pages) is k_pages
        assert k_pages.dtype == jnp.bfloat16
        s = eng.stats()
        assert s["kv_cache_dtype"] == "bf16"
        mc = eng.model_config
        assert s["kv_cache_bytes_per_token"] == (
            kv_bytes_per_block(mc, eng.config.block_size, "bf16")
            // eng.config.block_size)
    finally:
        eng.stop()


def test_int8_kv_pytree_and_stats():
    """Flag on: each K/V leaf is an (int8 data, f32 scales) pair with the
    flat token-major scale layout, and stats reports the shrunken
    per-token cost with the dtype tag."""
    eng = make_engine(kv_cache_dtype="int8")
    try:
        k_pages, v_pages = eng.kv
        assert isinstance(k_pages, tuple) and isinstance(v_pages, tuple)
        data, scales = k_pages
        assert data.dtype == jnp.int8
        assert scales.dtype == jnp.float32
        L, NBLK, bs, KVH, D = data.shape
        assert scales.shape == (L, NBLK, bs * KVH)
        s = eng.stats()
        assert s["kv_cache_dtype"] == "int8"
        # Per-token cost reported from the int8 formula. (tiny-llama's
        # 2 kv-heads are dominated by int8 sublane padding, so the
        # <0.52x shrink shows at real dims — see the capacity test.)
        assert s["kv_cache_bytes_per_token"] == (
            kv_bytes_per_block(eng.model_config, eng.config.block_size,
                               "int8") // eng.config.block_size)
    finally:
        eng.stop()


@pytest.mark.slow
def test_compile_budget_unchanged_by_kv_dtype():
    """int8 KV swaps array dtypes inside the SAME program set: warmup
    must compile exactly as many prefill/decode/spec variants as bf16."""
    variants = {}
    for dtype in ("bf16", "int8"):
        eng = make_engine(kv_cache_dtype=dtype)
        try:
            eng.warmup()
            variants[dtype] = dict(eng.warmup_variants)
        finally:
            eng.stop()
    assert variants["int8"] == variants["bf16"], variants


def test_kv_cache_dtype_validation():
    with pytest.raises(ValueError):
        EngineConfig(model="tiny-llama", kv_cache_dtype="fp8")
