"""Eight 64-wide kv heads two to a 128-lane row of the pages
(``ops.attention.packed_page_dims``): both paged kernels, in interpret
mode on the CPU, through the dispatchers that spread the queries over
their own head's lanes and bring the outputs back, against the XLA
reference on the logical ``[.., KVH, D]`` pages; and the page write."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import production_stack_tpu.ops.attention as att
import production_stack_tpu.ops.pallas_paged_attention as decode_kernel
import production_stack_tpu.ops.pallas_prefill_attention as prefill_kernel
from test_prefill_kernel import _setup_prefill

KVH, D = 8, 64


@pytest.fixture
def kernels_in_interpret_mode(monkeypatch):
    """The dispatchers take the kernels' path, and the kernels run in
    interpret mode."""
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    for module, name in ((decode_kernel, "pallas_paged_attention"),
                         (prefill_kernel, "pallas_prefill_attention")):
        monkeypatch.setattr(module, name, functools.partial(
            getattr(module, name), interpret=True))
    att.TRACED_PATHS.clear()


def _packed(pages):
    rows, lanes = att.packed_page_dims(KVH, D)
    return pages.reshape(pages.shape[:3] + (rows, lanes))


def test_the_layout_is_a_view_of_the_same_values():
    assert att.packed_page_dims(8, 64) == (4, 128)
    assert att.packed_page_dims(8, 128) == (8, 128)
    assert att.packed_page_dims(8, 64, quantized=True) == (8, 64)
    assert att.packed_page_dims(12, 64) == (12, 64)  # six rows: no tile
    assert att.packed_page_dims(2, 32) == (2, 32)
    assert att.attention_path(64, 4, 128, False, packed=True) == (
        att.attention_path(64, 8, 128, False))
    assert att.attention_path(64, 4, 128, True, packed=True) == "xla"


@pytest.mark.parametrize("page,kv_shards", [
    ((4, 128), 1),   # a model of four kv heads of 128
    ((8, 128), 2),   # eight of 128, the pool sharded two ways
    ((16, 128), 4),
], ids=["4x128", "8x128-tp2", "16x128-tp4"])
def test_four_rows_take_the_kernels_only_where_the_pool_holds_four(
        monkeypatch, page, kv_shards):
    """The gate admits four rows for the rows ``packed_page_dims`` made
    and for a model's own four kv heads of 128 (PR 51: the same ``[bs, 4,
    128]`` pages), and for nothing else: a shard that is left four heads
    of a wider pool takes the reference, as it did before the packed
    layout, and so do four rows of int8."""
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    assert att.attention_path(64, *page, False, kv_shards) == (
        "pallas" if kv_shards == 1 else "xla")
    assert att.attention_path(64, *page, True, kv_shards) == "xla"
    assert not att._page_tile_ok(64, page[0] // kv_shards, 128)
    assert att._page_tile_ok(64, 4, 128, packed=True)
    assert att.attention_path(64, 4, 128, False, packed=True) == "pallas"
    # packed rows never come with a sharded pool (engine/core.py)
    assert att.attention_path(64, 4, 128, False, kv_shards, True) == (
        "pallas" if kv_shards == 1 else "xla")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group,MAXB", [(4, 4), (4, 16), (1, 8)])
def test_decode_kernel_on_packed_rows(kernels_in_interpret_mode, group, MAXB,
                                      dtype):
    B, L, bs = 5, 3, 16
    NB = B * MAXB + 2
    rng = np.random.default_rng(group * 100 + MAXB)
    q = jnp.asarray(rng.normal(size=(B, KVH * group, D)), dtype)
    k_pages = jnp.asarray(rng.normal(size=(L, NB, bs, KVH, D)), dtype)
    v_pages = jnp.asarray(rng.normal(size=(L, NB, bs, KVH, D)), dtype)
    tables = jnp.asarray(
        rng.permutation(NB)[:B * MAXB].reshape(B, MAXB), jnp.int32)
    ctx = jnp.asarray([1, MAXB * bs, 0, bs + 1, 7][:B], jnp.int32)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    for layer in (0, L - 1):
        want = att.paged_attention_reference(
            q, k_pages, v_pages, tables, ctx, jnp.int32(layer), scale=0.125)
        got = att.paged_decode_attention(
            q, _packed(k_pages), _packed(v_pages), tables, ctx,
            jnp.int32(layer), scale=0.125)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)
        # and the reference reads the packed pool as the logical one
        np.testing.assert_array_equal(
            np.asarray(att.paged_attention_reference(
                q, _packed(k_pages), _packed(v_pages), tables, ctx,
                jnp.int32(layer), scale=0.125)), np.asarray(want))
    assert att.TRACED_PATHS["decode", "pallas"] and not att.TRACED_PATHS[
        "decode", "xla"]


@pytest.mark.parametrize("group,MAXB,T", [(4, 4, 16), (4, 8, 40), (2, 8, 24)])
def test_cached_prefill_kernel_on_packed_rows(kernels_in_interpret_mode,
                                              group, MAXB, T):
    s = _setup_prefill(3, T, KVH, group, D, 2, 3 * MAXB + 2, 16, MAXB)
    want = att._context_prefill_reference(
        s["q"], s["k_pages"], s["v_pages"], s["tables"], s["positions"],
        s["total"], s["layer"], scale=0.125)
    got = att.context_prefill_attention(
        s["q"], _packed(s["k_pages"]), _packed(s["v_pages"]), s["tables"],
        s["positions"], s["total"], s["layer"], scale=0.125,
        k_new=s["k_new"], v_new=s["v_new"], suffix_lens=s["take"])
    take = np.asarray(s["take"])
    for b in range(3):  # rows past a row's own tokens are padding
        np.testing.assert_allclose(
            np.asarray(got)[b, :take[b]], np.asarray(want)[b, :take[b]],
            rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(att._context_prefill_reference(
            s["q"], _packed(s["k_pages"]), _packed(s["v_pages"]),
            s["tables"], s["positions"], s["total"], s["layer"],
            scale=0.125)), np.asarray(want), rtol=1e-6, atol=1e-6)
    assert att.TRACED_PATHS["prefill", "pallas"] and not att.TRACED_PATHS[
        "prefill", "xla"]


def test_the_page_write_fills_packed_rows_like_logical_ones():
    L, NB, bs, B, T = 2, 6, 8, 2, 5
    rng = np.random.default_rng(0)
    k_new = jnp.asarray(rng.normal(size=(B, T, KVH, D)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, T, KVH, D)), jnp.float32)
    slots = jnp.asarray([[3, 4, 5, 6, -1], [40, 41, -1, -1, -1]], jnp.int32)
    zeros = jnp.zeros((L, NB, bs, KVH, D))
    want = att.write_kv_pages(zeros, zeros, k_new, v_new, slots, jnp.int32(1))
    got = att.write_kv_pages(_packed(zeros), _packed(zeros), k_new, v_new,
                             slots, jnp.int32(1))
    for mine, theirs in zip(got, want):
        assert mine.shape == (L, NB, bs, 4, 128)
        np.testing.assert_array_equal(
            np.asarray(mine).reshape(theirs.shape), np.asarray(theirs))
