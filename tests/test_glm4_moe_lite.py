"""The glm4_moe_lite family (PR 44; GLM-4.7-Flash): one latent-attention
sublayer a layer at a head count that is no multiple of eight, a leading
dense layer, sigmoid-routed experts with a selection bias beside a
shared one, and the prediction module's mathematics.

The program (``models/glm4_moe_lite.py`` through ``decoder.attend_latent``
and ``models/moe.py``) is held to the plain reference
(``chipbench/reference/glm4_moe_lite.py``: float32, nothing absorbed, no
cache) on seeded random weights at the tiny size, in float32.
Tolerances: 2e-4 absolute on log-probabilities and on both cache sides,
which float32 accumulation order accounts for and which bf16 in place of
the test dtype fails by two orders of magnitude
(``test_bf16_fails_the_tolerance``).
"""

import dataclasses
import functools
import json
import os
import queue
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import (
    EngineCore,
    kv_bytes_per_block,
    kv_page_sides,
)
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import (
    build_model,
    decoder,
    get_model_config,
    glm4_moe_lite,
    llama,
    moe,
)
from production_stack_tpu.models.decoder import (
    Batch,
    attend_latent,
    latent_prefill_form,
)
from production_stack_tpu.models.registry import (
    arch_of_model_type,
    get_family,
    page_layers,
    page_sides,
)
from production_stack_tpu.models.weights import load_checkpoint
from production_stack_tpu.ops import attention as att
from production_stack_tpu.ops import pallas_mla_decode as kernel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench.reference import glm4_moe_lite as reference  # noqa: E402
import test_longcat  # noqa: E402
from test_longcat import BS, _latent_case, _Rows  # noqa: E402

SEED = 13
TOL = 2e-4
LAYERS = 4

with open(os.path.join(REPO, "tests", "chipbench", "data",
                       "tiny_glm4_moe_lite_config.json")) as _f:
    HF = json.load(_f)


def _model_dir(tmp_path, **changes):
    path = tmp_path / "model"
    path.mkdir(exist_ok=True)
    (path / "config.json").write_text(json.dumps({**HF, **changes}))
    return str(path)


@pytest.fixture(scope="module")
def cfg():
    return get_model_config("tiny-glm4-moe-lite").replace(dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return build_model(cfg)[0](cfg, jax.random.key(SEED))


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(2)
    return [rng.integers(0, 512, n) for n in (61, 45, 37)]


def _padded(sequences):
    tokens = np.zeros((len(sequences), max(map(len, sequences))), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s
    return tokens


@pytest.fixture(scope="module")
def wanted(sequences):
    return reference.forward(
        HF, SEED, _padded(sequences), [len(s) for s in sequences],
        keep_from=0, dtype="float32", kv_layers=tuple(range(LAYERS)))


def _hold(rows, wanted, row, positions, tol=TOL):
    logp, kv = wanted
    for t in positions:
        np.testing.assert_allclose(rows.logp[row][t], logp[row, t],
                                   atol=tol, rtol=0)
    c, k_r, beyond = rows.pages(row)
    n = len(rows.tokens[row])
    assert not beyond.any()  # the pool's lanes past the key stay zero
    for layer in range(LAYERS):
        np.testing.assert_allclose(c[layer], kv[layer][0][row, :n, 0],
                                   atol=tol)
        np.testing.assert_allclose(k_r[layer], kv[layer][1][row, :n, 0],
                                   atol=tol)


# --------------------------------------------------------------------- #
# The config reader and the record
# --------------------------------------------------------------------- #

def test_config_json_reads_as_the_preset(tmp_path, cfg):
    read = get_model_config(_model_dir(tmp_path))
    assert read.replace(name="tiny-glm4-moe-lite", dtype="float32") == cfg
    assert arch_of_model_type("glm4_moe_lite") == "glm4_moe_lite"
    assert read.num_layers == 4 and read.dense_layers == 1
    assert read.published_experts == 8 and read.num_experts == 4
    assert read.shared_expert_size == 64 and read.router_bias
    assert read.router_scoring == "sigmoid" and read.num_kv_heads == 1


@pytest.mark.parametrize("changes", [
    {"n_group": 2}, {"topk_group": 2}, {"topk_method": "greedy"},
    {"norm_topk_prob": False}, {"hidden_act": "gelu"},
    {"attention_bias": True}, {"partial_rotary_factor": 0.5},
    {"rope_scaling": {"type": "yarn", "factor": 4}},
    {"tie_word_embeddings": True}, {"num_nextn_predict_layers": 2},
], ids=lambda c: next(iter(c)))
def test_a_config_the_family_does_not_serve_is_refused(tmp_path, changes):
    """A key that names a mechanism the program lacks is refused by its
    name, never dropped."""
    with pytest.raises(ValueError, match=next(iter(changes))):
        get_model_config(_model_dir(tmp_path, **changes))


def test_the_record_says_what_a_page_is(cfg):
    assert page_sides(cfg) == ((1, 128), (1, 16))
    assert page_layers(cfg) == 4  # one attention sublayer a layer
    assert kv_page_sides(cfg) == (4, (1, 128), (1, 128))
    # the published widths at all 47 layers: 1,280 bytes a token and
    # layer in bf16 as the pool lays them out, 60,160 a token
    full = cfg.replace(kv_lora_rank=512, qk_rope_head_dim=64, num_layers=47,
                       dtype="bfloat16")
    assert kv_bytes_per_block(full, 64) == 64 * 60160
    family = get_family("glm4_moe_lite")
    assert family.stats == moe.STATS and not family.quant_keys
    assert not family.lora and not family.pipeline
    with pytest.raises(NotImplementedError, match="checkpoint"):
        load_checkpoint(cfg, "/nowhere")


def test_own_recipe_draws_the_programs_weights():
    """The reference's copy of the init recipe gives the program's
    weights bit for bit: layer l is entry l of a stacked leaf, expert e
    of sparse layer s entry s x held + e, a block of the dense MLP's
    columns is those columns, and the norm weights and the selection
    bias are drawn, not constants."""
    cfg = get_model_config("tiny-glm4-moe-lite")
    p = glm4_moe_lite.init_params(cfg, jax.random.key(SEED))
    keys = reference.split(reference.seed_key(SEED), 24)
    attn, dense, sparse = (keys[reference.ATTN], keys[reference.DENSE],
                           keys[reference.SPARSE])
    bf16 = jnp.bfloat16

    def same(mine, theirs):
        return bool(jnp.array_equal(mine.astype(theirs.dtype), theirs))

    stacked, norm = reference._stacked, reference._norm_weight
    assert same(norm(keys[reference.FINAL_NORM], 0, 128, bf16),
                p["final_norm"])
    assert same(norm(attn[0], 2, 128, bf16), p["attn"]["in_norm"][2])
    assert same(norm(attn[1], 3, 128, bf16), p["attn"]["post_norm"][3])
    assert same(stacked(attn[2], 3, (128, 48), 128, bf16),
                p["attn"]["wq_a"][3])
    assert same(norm(attn[3], 1, 48, bf16), p["attn"]["q_norm"][1])
    assert same(stacked(attn[4], 1, (5 * 40, 48), 48, bf16),
                p["attn"]["wq_b"][1])
    assert same(stacked(attn[5], 2, (128, 144), 128, bf16),
                p["attn"]["wkv_a"][2])
    assert same(norm(attn[6], 2, 128, bf16), p["attn"]["kv_norm"][2])
    assert same(stacked(attn[7], 2, (5, 128, 56), 128, bf16),
                p["attn"]["wkv_b"][2])
    assert same(stacked(attn[8], 1, (160, 128), 160, bf16),
                p["attn"]["wo"][1])
    assert same(reference._columns(dense[0], 0, (128, 256), 64, 32, 128,
                                   bf16), p["dense"]["w_gate"][0][:, 64:96])
    assert same(stacked(sparse[0], 2, (128, 8), 128, bf16),
                p["moe"]["router"][2])
    bias = reference.SPREAD * reference.normal_rows(
        sparse[1], jnp.uint32(2 * 8), 8)
    np.testing.assert_array_equal(bias, p["moe"]["router_bias"][2])
    assert p["moe"]["router_bias"].dtype == jnp.float32
    assert float(jnp.std(p["moe"]["router_bias"])) > 0.05
    assert same(stacked(sparse[4], 2 * 4 + 3, (64, 128), 64, bf16),
                p["moe"]["w_down"][2, 3])
    assert same(stacked(sparse[6], 1, (128, 64), 128, bf16),
                p["moe"]["shared_up"][1])


# --------------------------------------------------------------------- #
# The three modes against the reference
# --------------------------------------------------------------------- #

def test_prefill_holds_the_reference(cfg, params, sequences, wanted):
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 61), (1, 0, 45), (2, 0, 37)], width=64)
    for row, n in enumerate((61, 45, 37)):
        _hold(rows, wanted, row, range(n))


@pytest.mark.parametrize("width, form", [(16, "absorbed"),
                                         (32, "up_projected")])
def test_cached_prefill_holds_the_reference_in_both_forms(
        cfg, params, sequences, wanted, width, form):
    """A prompt past one chunk: the first chunk plain, the rest through
    the latent pages in chunks of ``width`` positions under a table of 64
    tokens. At these widths the two forms cross at 23 new tokens: the
    narrow chunks absorb (attention over the latents themselves), the
    wide ones up-project the gathered context; a padded last chunk and a
    row beside it that begins elsewhere."""
    rows = _Rows(cfg, params, sequences)
    tokens = rows.tables.shape[1] * BS
    assert latent_prefill_form(width, tokens, 5, 128, 24, 16, 32) == form
    rows.span("prefill", [(0, 0, 32)], width=32)
    rows.span("prefill", [(1, 0, 24)], width=32)
    at = [32, 24]
    while at[0] < 61 or at[1] < 45:
        spans = [(row, at[row], min(at[row] + width, n))
                 for row, n in ((0, 61), (1, 45)) if at[row] < n]
        rows.span("prefill_cached", spans, width=width)
        at = [min(at[0] + width, 61), min(at[1] + width, 45)]
    _hold(rows, wanted, 0, range(61))
    _hold(rows, wanted, 1, range(45))


def test_the_two_cached_forms_are_one_product_in_two_orders():
    """``attend_latent`` itself at a chunk of 8 and of 48 queries over
    the same pages (the rule picks absorbed and up-projected): the
    shared first 8 queries read the same, with a factor on the latent as
    LongCat has one."""
    case = _latent_case(np.random.default_rng(6), B=2, H=5, N=24, V=32,
                        blocks=8)
    rng = np.random.default_rng(7)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    outs = {}
    for T in (8, 48):
        assert latent_prefill_form(T, 64, 5, 128, 24, 16, 32) == (
            "absorbed" if T == 8 else "up_projected")
    q_nope, q_rope, c, k_rope = (draw(2, 48, 5, 24), draw(2, 48, 5, 16),
                                 draw(2, 48, 128), draw(2, 48, 16))
    for T in (8, 48):
        pos = jnp.asarray([[10], [3]]) + jnp.arange(T)[None, :]
        slot = (jnp.take_along_axis(case["tables"], pos // BS, axis=1) * BS
                + pos % BS)
        # both chunks hold the same 8 live tokens a row
        slot = jnp.where(jnp.arange(T)[None, :] < 8, slot, -1)
        batch = Batch(pos, slot, case["tables"], pos[:, 0] + 8,
                      jnp.full((2,), 8, jnp.int32))
        outs[T], _ = attend_latent(
            "prefill_cached", q_nope[:, :T], q_rope[:, :T], c[:, :T],
            k_rope[:, :T], case["w_up"], case["kv"], jnp.int32(1), batch,
            scale=40 ** -0.5, latent_scale=1.7)
    np.testing.assert_allclose(outs[8], outs[48][:, :8], atol=2e-5)


def test_the_two_forms_agree_where_the_rule_now_absorbs(monkeypatch):
    """LongCat's widths, a tail of 256 queries under a 32-block table of
    2,048 tokens: fewer multiply-adds said up-projected, the chip read
    absorbed 1.25 times faster and the rule absorbs since PR 45. The
    rule's own form and the up-projected one forced read the same within
    the file's tolerance."""
    H, C, N, R, V = WIDTHS["longcat"]
    T, S = 256, 2048
    assert _fewer_multiply_adds(T, S, H, C, N, R, V) == "up_projected"
    assert latent_prefill_form(T, S, H, C, N, R, V) == "absorbed"
    case = _latent_case(np.random.default_rng(8), B=1, H=H, N=N, R=R, C=C,
                        V=V, blocks=S // BS)
    rng = np.random.default_rng(9)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    pos = 1500 - T + jnp.arange(T)[None, :]
    slot = (jnp.take_along_axis(case["tables"], pos // BS, axis=1) * BS
            + pos % BS)
    batch = Batch(pos, slot, case["tables"], jnp.asarray([1500], jnp.int32),
                  jnp.asarray([T], jnp.int32))
    operands = (draw(1, T, H, N), draw(1, T, H, R), draw(1, T, C),
                draw(1, T, R), case["w_up"] * 11.0 * C ** -0.5, case["kv"],
                jnp.int32(1), batch)

    def attended():
        return attend_latent("prefill_cached", *operands,
                             scale=(N + R) ** -0.5, latent_scale=12 ** 0.5)[0]

    absorbed = attended()
    monkeypatch.setattr(decoder, "latent_prefill_form",
                        lambda *shapes: "up_projected")
    np.testing.assert_allclose(absorbed, attended(), atol=TOL)


def test_decode_through_the_latent_pages_holds_the_reference(
        cfg, params, sequences, wanted):
    """Absorbed decode over the pages at five heads, two rows of ragged
    contexts stepping together beside a row that holds nothing."""
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 50), (2, 0, 30)], width=64)
    for step in range(7):
        rows.span("decode", [(0, 50 + step, 51 + step),
                             (2, 30 + step, 31 + step)], idle_rows=1)
    _hold(rows, wanted, 2, range(37))
    for t in range(57):
        np.testing.assert_allclose(rows.logp[0][t], wanted[0][0, t],
                                   atol=TOL, rtol=0)


def _one_scan_behind_a_cond(cfg, mode, x, params, kv_pages, batch):
    """``glm4_moe_lite.run_layers`` as it was before PR 46, kept here:
    ONE scan over all layers, the attention leaves as its ``xs``, the
    dense and the sparse MLP each behind a ``lax.cond`` in its body."""
    L, d = cfg.num_layers, cfg.dense_layers
    dense = np.arange(L) < d
    valid = batch.slot_mapping >= 0

    def dense_mlp(h, layer):
        w = decoder.take(params["dense"], layer)
        return (moe.swiglu(h, w["w_gate"], w["w_up"], w["w_down"]),
                jnp.zeros((len(moe.STATS),), jnp.int32))

    def sparse_mlp(h, layer):
        return glm4_moe_lite._experts(cfg, h, params["moe"], layer - d,
                                      valid)

    def body(carry, per_layer):
        x, sides, stats, layer = carry
        x, sides = decoder.latent_attention(cfg, mode, x, per_layer, sides, layer,
                                      batch)
        h = llama.rms_norm(x, per_layer["post_norm"], cfg.rms_norm_eps)
        out, s = decoder.by_layer(dense, layer, dense_mlp, sparse_mlp, h,
                                  layer)
        return (x + out, tuple(sides), stats + s, layer + 1), None

    carry = (x, tuple(kv_pages), jnp.zeros((len(moe.STATS),), jnp.int32),
             jnp.int32(0))
    (x, sides, stats, _), _ = jax.lax.scan(body, carry, params["attn"])
    return x, sides, stats


def _through_the_three_modes(cfg, params, sequences):
    """Rows 0 and 2 driven through every mode: a plain prefill, cached
    chunks in both forms (16 wide absorbs, 32 wide up-projects: the last
    one padded), decode steps beside an idle row."""
    rows = _Rows(cfg, params, sequences)
    rows.span("prefill", [(0, 0, 24), (2, 0, 30)], width=32)
    rows.span("prefill_cached", [(0, 24, 40)], width=16)
    rows.span("prefill_cached", [(0, 40, 61)], width=32)
    for step in range(7):
        rows.span("decode", [(2, 30 + step, 31 + step)], idle_rows=1)
    return rows


@pytest.mark.parametrize("dense_layers", [1, 2])
def test_a_dense_prefix_then_a_scan_is_the_one_scan_behind_a_cond(
        tmp_path, monkeypatch, sequences, dense_layers):
    """The layer loop as two scans (the dense prefix, then the sparse
    layers with no ``lax.cond``: PR 46) in the three modes, at one
    leading dense layer and at two (a prefix longer than one): held to
    the reference, and bit for bit what the single scan with both MLPs
    behind a ``cond`` gives, logits and both sides of the pool."""
    cfg = get_model_config(_model_dir(
        tmp_path, first_k_dense_replace=dense_layers)).replace(
        dtype="float32")
    assert (cfg.dense_layers, cfg.num_layers) == (dense_layers, LAYERS)
    params = build_model(cfg)[0](cfg, jax.random.key(SEED))
    assert params["dense"]["w_up"].shape[0] == dense_layers
    assert params["moe"]["router"].shape[0] == LAYERS - dense_layers
    wanted = reference.forward(
        {**HF, "first_k_dense_replace": dense_layers}, SEED,
        _padded(sequences), [len(s) for s in sequences], keep_from=0,
        dtype="float32", kv_layers=tuple(range(LAYERS)))
    rows = _through_the_three_modes(cfg, params, sequences)
    _hold(rows, wanted, 0, range(61))
    _hold(rows, wanted, 2, range(37))

    before = functools.partial(decoder.apply, dataclasses.replace(
        glm4_moe_lite.FAMILY, loop=_one_scan_behind_a_cond))
    monkeypatch.setattr(test_longcat, "_jitted_apply", lambda cfg: jax.jit(
        lambda params, *args, mode: before(params, cfg, *args, mode=mode),
        static_argnames=("mode",)))
    single = _through_the_three_modes(cfg, params, sequences)
    for mine, theirs in zip(rows.kv, single.kv):
        np.testing.assert_array_equal(mine, theirs)
    for row in (0, 2):
        assert rows.logp[row].keys() == single.logp[row].keys()
        for t, logp in rows.logp[row].items():
            np.testing.assert_array_equal(logp, single.logp[row][t])


def test_bf16_fails_the_tolerance(params, sequences, wanted):
    """The tolerance is tight: the same program in bf16 misses it."""
    cfg16 = get_model_config("tiny-glm4-moe-lite")
    p16 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.ndim > 1 else x, params)
    rows = _Rows(cfg16, p16, sequences)
    rows.span("prefill", [(0, 0, 61)], width=64)
    worst = max(np.abs(rows.logp[0][t] - wanted[0][0, t]).max()
                for t in range(61))
    assert worst > 20 * TOL
    c, _, _ = rows.pages(0)
    assert np.abs(c[1] - wanted[1][1][0][0, :61, 0]).max() > 20 * TOL


def test_the_familys_scopes_are_on_its_programs_operations(cfg, params):
    """What the per-layer metrics file the device's time under
    (``chipbench/metrics/*.serve.json`` of the agent cell): each scope is
    on some operation of the programs that have its part."""
    rows = _Rows(cfg, params, [np.arange(40) % 512])
    _, apply = build_model(cfg)

    def text(mode, width):
        args = (np.zeros((1, width), np.int32), np.zeros((1, width), np.int32),
                rows.kv, np.zeros((1, width), np.int64),
                np.zeros((1, rows.tables.shape[1]), np.int32),
                np.ones((1,), np.int32), np.ones((1,), np.int32))
        return jax.jit(lambda p, *a: apply(p, cfg, *a, mode=mode)).lower(
            params, *args).as_text(debug_info=True)

    decode, wide, narrow = (text("decode", 1), text("prefill_cached", 32),
                            text("prefill_cached", 8))
    for scope in ("mla_proj", "mla_absorb", "attention", "moe_router",
                  "moe_experts", "moe_shared", "mlp"):
        assert f"{scope}/" in decode or f"/{scope}" in decode, scope
    assert "mla_up_context" in wide and "mla_up_context" not in narrow
    assert "mla_absorb" in narrow and "mla_absorb" not in wide


# --------------------------------------------------------------------- #
# The decode kernel at a head count that is no multiple of eight
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("heads", [5, 20])
def test_the_kernel_takes_any_number_of_heads(dtype, tol, heads):
    """Interpret mode against the XLA path at 5 and 20 heads: contexts of
    exactly k chunks, one token more and less, a tail in the first and
    the last sub-block, one token, rows that hold nothing."""
    bs, contexts = 64, [517, 0, 1012, 1, -1, 5, 512, 1541]
    rng = np.random.default_rng(0)
    L, C, lanes, R, B = 2, 128, 128, 16, len(contexts)
    MAXB = -(-max(contexts) // bs) + 1
    NB = B * MAXB
    c = jnp.asarray(rng.normal(size=(L, NB, bs, 1, C)), dtype)
    r = jnp.zeros((L, NB, bs, 1, lanes), dtype).at[..., :R].set(
        jnp.asarray(rng.normal(size=(L, NB, bs, 1, R)), dtype))
    q_abs = jnp.asarray(rng.normal(size=(B, heads, C)), dtype)
    q_rope = jnp.asarray(rng.normal(size=(B, heads, R)), dtype)
    tables = jnp.asarray(rng.permutation(NB).reshape(B, MAXB), jnp.int32)
    ctx = jnp.asarray(contexts, jnp.int32)
    got = kernel.pallas_mla_decode(q_abs, q_rope, c, r, tables, ctx, 1,
                                   scale=0.2, pages_per_block=8, ring=3,
                                   interpret=True)
    want = att.latent_decode_reference(q_abs, q_rope, c, r, tables, ctx, 1,
                                       scale=0.2)
    assert got.shape == (B, heads, C) and got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol)
    for row, n in enumerate(contexts):
        assert (n > 0) == bool(np.asarray(got[row], np.float32).any())


def test_the_gate_follows_the_heads_and_longcats_tile_stays(monkeypatch):
    """20 heads (GLM-4.7-Flash) are taken; what the page copies need is
    still asked; LongCat's tile is where PR 42 left it (16 pages, ring
    4, a full chunk as two spans) and 20 heads choose the same."""
    assert kernel.tiles_ok(64, 20, 512, 128, 2)
    assert kernel.tiles_ok(64, 5, 128, 128, 4)
    assert not kernel.tiles_ok(64, 0, 512, 128, 2)
    assert not kernel.tiles_ok(8, 20, 512, 128, 2)
    assert not kernel.tiles_ok(64, 20, 512, 64, 2)
    assert kernel.decode_tile(64, 64, 512, 128, 2, 128) == (16, 4)
    assert kernel.decode_tile(64, 20, 512, 128, 2, 128) == (16, 4)
    assert (kernel.MAX_PAGES_PER_BLOCK, kernel.RING, kernel.SPANS) == (
        16, 4, 2)
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    assert att.latent_decode_path(64, 20, 512, 128, "bfloat16") == "pallas"


WIDTHS = {"glm": (20, 512, 192, 64, 256),  # GLM-4.7-Flash: H, C, N, R, V
          "longcat": (64, 512, 128, 64, 128)}  # LongCat-Flash
# (widths, bucket, table blocks, ms a layer up-projected, absorbed): every
# reading of ``benchmarks/latent_prefill_forms.py`` on a TPU v5e (PR 44's
# ten again and PR 45's; the second of two calls that agree within 1%,
# ``chiprun_out/pr45/prefill_forms_2.jsonl``, PERF.md section 6).
READINGS = [
    ("glm", 64, 128, 0.7479, 0.2117),
    ("glm", 128, 128, 0.9055, 0.4009),
    ("glm", 256, 128, 1.4254, 0.8552),
    ("glm", 512, 128, 2.2479, 1.7652),
    ("glm", 1024, 128, 3.7221, 3.3781),
    ("longcat", 64, 128, 2.2289, 0.8933),
    ("longcat", 128, 128, 2.8185, 1.2921),
    ("longcat", 256, 128, 3.9414, 2.588),
    ("longcat", 512, 128, 6.5082, 5.4674),
    ("longcat", 1024, 128, 13.4225, 13.9393),
    ("longcat", 128, 32, 0.5057, 0.2782),
    ("longcat", 256, 32, 0.8806, 0.7029),
    ("longcat", 512, 32, 1.606, 1.4072),
    ("longcat", 1024, 32, 3.0208, 2.7089),
    ("longcat", 256, 64, 1.783, 1.2966),
    ("longcat", 512, 64, 2.9423, 2.7006),
    ("longcat", 1024, 64, 5.4555, 5.1731),
    ("glm", 512, 64, 1.0634, 0.8725),
    ("glm", 1024, 64, 1.8873, 1.7325),
    ("glm", 512, 32, 0.3858, 0.3742),
    ("glm", 1024, 32, 0.9149, 0.9178),
    ("longcat", 256, 16, 0.2932, 0.2844),
    ("longcat", 512, 16, 0.7699, 0.7449),
    ("longcat", 1024, 16, 1.3681, 1.4038),
    ("glm", 512, 16, 0.1724, 0.2107),
    ("glm", 1024, 16, 0.3089, 0.402),
    ("longcat", 128, 8, 0.118, 0.1141),
    ("longcat", 256, 8, 0.1514, 0.1824),
    ("longcat", 512, 8, 0.2013, 0.3257),
    ("glm", 256, 8, 0.0947, 0.0929),
    ("glm", 512, 8, 0.117, 0.1445),
    ("longcat", 128, 4, 0.0793, 0.0895),
    ("longcat", 256, 4, 0.0993, 0.1364),
    ("glm", 256, 4, 0.0667, 0.0754),
]


def _fewer_multiply_adds(T, S, H, C, N, R, V):
    """PR 44's rule: what the rule still says where nothing spills."""
    up_projected = S * H * C * (N + V) + T * S * H * (N + R + V)
    absorbed = T * H * C * (N + V) + T * S * H * (2 * C + R)
    return "absorbed" if absorbed < up_projected else "up_projected"


@pytest.mark.parametrize(
    "widths, bucket, table, up_projected_ms, absorbed_ms", READINGS,
    ids=[f"{r[0]}-{r[1]}-under-{r[2]}" for r in READINGS])
def test_the_rule_names_the_form_the_chip_read_faster(
        widths, bucket, table, up_projected_ms, absorbed_ms):
    """Every reading: the rule names the faster form; within 4% of a tie
    (six readings under short tables, where neither form's intermediates
    leave the chip) it may keep the form with fewer multiply-adds instead,
    and a pair whose form PR 45 changed is always the faster one."""
    shapes = (bucket, table * 64) + WIDTHS[widths]
    faster = ("absorbed" if absorbed_ms < up_projected_ms
              else "up_projected")
    form = latent_prefill_form(*shapes)
    if max(up_projected_ms, absorbed_ms) > 1.04 * min(
            up_projected_ms, absorbed_ms):
        assert form == faster
    else:
        assert form in (faster, _fewer_multiply_adds(*shapes))
    if form != _fewer_multiply_adds(*shapes):
        assert form == faster == "absorbed"


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_the_cached_prefills_form_is_a_rule_of_the_shapes(widths):
    """Under every table a chunk absorbs up to some bucket and
    up-projects from there on: one switch as the bucket grows, and a
    short context crosses no later than a longer one until the
    up-projected keys and values no longer stay on the chip. Under the
    128-block table GLM's widths then absorb at every bucket a chunk can
    have, and LongCat's up to a whole chunk, whose context
    ``dense_context_attention`` streams (a float32 accumulator of the
    latent's 512 lanes a span)."""
    buckets = (16, 32, 64, 128, 256, 512, 1024)
    absorbing = []
    for table in (4, 8, 16, 32, 64, 128):
        forms = [latent_prefill_form(t, table * 64, *WIDTHS[widths])
                 for t in buckets if t <= table * 64]
        assert forms == sorted(forms), (table, forms)  # absorbed first
        assert forms[0] == "absorbed"
        absorbing.append(forms.count("absorbed"))
    assert absorbing[:5] == sorted(absorbing[:5])  # to 64 blocks: unstreamed
    assert absorbing == {"glm": [4, 4, 5, 5, 7, 7],
                         "longcat": [3, 3, 4, 7, 7, 6]}[widths]


def test_the_forms_reading_runs_at_its_tiny_size():
    """``benchmarks/latent_prefill_forms.py`` (what the chip read the
    rule against, PERF.md section 6, PR 44) times both forms and the
    up-projection alone, and leaves the rule in place."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "benchmarks/latent_prefill_forms.py", "--tiny"],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()]
    assert [line["geometry"] for line in lines] == [
        "glm-4.7-flash", "longcat-flash"]
    for line in lines:
        assert line["rule"] == "absorbed"
        assert min(line[form + "_ms"] for form in (
            "up_projected", "absorbed", "up_only")) > 0


# --------------------------------------------------------------------- #
# The expert layer: sigmoid scores, selection bias, shares
# --------------------------------------------------------------------- #

def _expert_case(rng, N=24, Hd=32, I=16, E=8):
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(h=draw(1, N, Hd), router=draw(Hd, E), bias=0.3 * draw(E),
                w_gate=draw(1, E, Hd, I) / 4, w_up=draw(1, E, Hd, I) / 4,
                w_down=draw(1, E, I, Hd) / 4,
                shared=(draw(Hd, I) / 4, draw(Hd, I) / 4, draw(I, Hd) / 4))


ROUTING = {"scoring": "sigmoid", "eps": glm4_moe_lite.ROUTER_EPS}


def test_the_router_against_a_loop():
    """Sigmoid scores, the top 4 of score plus bias, weights the chosen
    scores without the bias over their sum, times 1.8."""
    case = _expert_case(np.random.default_rng(11))
    weights, experts = moe.route(case["h"][0], case["router"], 4,
                                 scaling=1.8, bias=case["bias"], **ROUTING)
    moved = 0
    for n in range(24):
        z = np.asarray(case["h"][0, n] @ case["router"], np.float64)
        score = 1 / (1 + np.exp(-z))
        picked = np.argsort(-(score + np.asarray(case["bias"])))[:4]
        moved += sorted(picked) != sorted(np.argsort(-score)[:4])
        assert sorted(picked) == sorted(np.asarray(experts[n]))
        for w, e in zip(np.asarray(weights[n]), np.asarray(experts[n])):
            assert w == pytest.approx(1.8 * score[e] / score[picked].sum(),
                                      rel=1e-5)
    assert moved  # the bias changes a selection somewhere
    np.testing.assert_allclose(weights.sum(-1), 1.8, rtol=1e-5)


@pytest.mark.parametrize("chips", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_whole_layer(chips):
    """Over all ``chips`` shares, the shares' routed parts plus the
    shared expert counted once are the uncut reference's whole expert
    layer; every assignment is counted on exactly one chip."""
    case = _expert_case(np.random.default_rng(12))
    held = 8 // chips
    experts = [tuple(case[k][0, e] for k in ("w_gate", "w_up", "w_down"))
               for e in range(8)]
    with jax.default_matmul_precision("highest"):
        routed, shared = reference.moe_layer(
            case["h"][0], case["router"], case["bias"], experts,
            case["shared"], first=0, top_k=4, scaling=1.8)
    total = np.zeros((24, 32))
    hits = 0
    for share in range(chips):
        mine = slice(share * held, (share + 1) * held)
        y, stats = moe.expert_layer(
            case["h"], {"router": case["router"],
                        **{k: case[k][:, mine]
                           for k in ("w_gate", "w_up", "w_down")}},
            k=4, at=0, share=share, scaling=1.8,
            routing={"bias": case["bias"], **ROUTING})
        total += np.asarray(y[0])
        hits += int(stats[0])
        mine_ref, _ = reference.moe_layer(
            case["h"][0], case["router"], case["bias"], experts[mine],
            case["shared"], first=share * held, top_k=4, scaling=1.8)
        np.testing.assert_allclose(y[0], mine_ref, atol=2e-4)
    np.testing.assert_allclose(total + np.asarray(shared),
                               np.asarray(routed) + np.asarray(shared),
                               atol=3e-4)
    assert hits == 24 * 4


def test_the_programs_layer_is_a_share_plus_the_shared_expert(cfg, params):
    """``_experts`` of the model file on sparse layer 1: the held block's
    routed part and the shared expert whole, as the reference's layer
    over the same weights."""
    rng = np.random.default_rng(13)
    h = jnp.asarray(rng.normal(size=(1, 24, 128)), jnp.float32)
    p = params["moe"]
    own = {k: v[1] for k, v in p.items()
           if k not in moe.EXPERT_STACKS}
    out, stats = glm4_moe_lite._experts(cfg, h, p, 1, None)
    experts = [tuple(p[k][1, e] for k in moe.EXPERT_STACKS)
               for e in range(4)]
    with jax.default_matmul_precision("highest"):
        routed, shared = reference.moe_layer(
            h[0], own["router"], own["router_bias"], experts,
            (own["shared_gate"], own["shared_up"], own["shared_down"]),
            first=0, top_k=3, scaling=1.8)
    np.testing.assert_allclose(out[0], routed + shared, atol=TOL)
    assert 0 < int(stats[0]) < 24 * 3  # some assignments are another chip's


# --------------------------------------------------------------------- #
# The prediction module
# --------------------------------------------------------------------- #

def test_mtp_logits_hold_the_reference(cfg, params, sequences):
    """``mtp_logits`` on the trunk's own hidden states against the
    reference's module over weights it draws itself from the module's
    seed: position i predicts token i + 2 from the state at i and the
    embedding of token i + 1."""
    mtp_seed = 29
    mtp = glm4_moe_lite.init_mtp_params(cfg, jax.random.key(mtp_seed))
    tokens = _padded(sequences[:2])
    lens = np.asarray([61, 45], np.int32)
    S, T = tokens.shape
    positions = np.broadcast_to(np.arange(T, dtype=np.int32), (S, T))
    _, apply = build_model(cfg)
    pool = tuple(jnp.zeros((4, 2 * S * 8, BS) + side, jnp.float32)
                 for side in kv_page_sides(cfg)[1:])
    tables = np.arange(S * 8, dtype=np.int32).reshape(S, 8)
    slots = np.where(np.arange(T)[None, :] < lens[:, None],
                     tables[:, :1] * BS + np.arange(T)[None, :], -1)
    hidden, _ = apply(params, cfg, tokens, positions, pool,
                      slots.astype(np.int64), tables, lens, lens,
                      mode="prefill", output_hidden=True)
    next_tokens = np.roll(tokens, -1, axis=1)
    mine = jax.nn.log_softmax(glm4_moe_lite.mtp_logits(
        params, mtp, cfg, next_tokens, hidden.astype(jnp.float32),
        positions), axis=-1)
    want = reference.mtp_logprobs(HF, SEED, mtp_seed, next_tokens, hidden,
                                  lens, dtype="float32")
    for row, n in enumerate(lens):
        np.testing.assert_allclose(mine[row, :n - 1], want[row, :n - 1],
                                   atol=TOL)
    # not the trunk's own next-token distribution
    trunk, _ = reference.forward(HF, SEED, tokens, lens, keep_from=0,
                                 dtype="float32")
    assert np.abs(want[0, :60] - trunk[0, :60]).max() > 0.1


# --------------------------------------------------------------------- #
# The engine
# --------------------------------------------------------------------- #

def _engine(**overrides):
    config = dict(
        model="tiny-glm4-moe-lite", max_model_len=256, max_num_seqs=4,
        block_size=16, num_blocks=96, decode_steps=4, prefill_batch=1,
        prefill_chunk_size=32, dtype="float32")
    config.update(overrides)
    return EngineCore(EngineConfig(**config), devices=jax.devices()[:1])


def _submit(eng, rid, prompt, max_tokens):
    q: "queue.Queue" = queue.Queue()
    eng.add_request(
        rid, list(prompt),
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True, logprobs=3),
        lambda token, finish: q.put((token, finish)))
    return q


def _collect(q):
    """(tokens, [{token: logprob} per position])."""
    tokens, tops = [], []
    while True:
        token, finish = q.get(timeout=300)
        if token is not None:
            tok, lp = token
            tokens.append(int(tok))
            tops.append({int(t): float(v) for t, v in lp["top"]}
                        | {int(tok): float(lp["logprob"])})
        if finish is not None:
            return tokens, tops


def _prompt(n, salt=0):
    return [(7 * i + salt) % 200 + 1 for i in range(n)]


def _holds_the_reference(seed, prompt, tokens, tops):
    """Every log-probability the engine reported (the sampled token's and
    the top 3) against the reference's full forward over prompt and
    answer."""
    both = np.asarray([list(prompt) + tokens], np.int32)
    logp, _ = reference.forward(HF, seed, both, [both.shape[1]],
                                keep_from=len(prompt) - 1, dtype="float32")
    for j, entries in enumerate(tops):
        for tok, lp in entries.items():
            assert lp == pytest.approx(float(logp[0, j, tok]), abs=TOL)


def test_the_engine_holds_the_reference_through_a_prefix_hit():
    """Chunked prefill (75 tokens in chunks of 32: the cached latent path
    in both forms), burst decode, then a second prompt that shares the
    first one's 64-token prefix and finds it cached: the engine's
    reported log-probabilities are the reference's, and the step records
    and the counter say which form each cached prefill took."""
    eng = _engine(min_prefill_bucket=16)
    eng.start()
    try:
        first = _prompt(75)
        got = _collect(_submit(eng, "a", first, 6))
        _holds_the_reference(eng.config.seed, first, *got)
        cached_before = eng.cached_tokens_total
        second = first[:64] + _prompt(9, salt=9)  # a 16-wide last chunk
        hit = _collect(_submit(eng, "b", second, 6))
        assert eng.cached_tokens_total - cached_before == 64
        _holds_the_reference(eng.config.seed, second, *hit)
        stats = eng.stats()
        assert stats["latent_decode_dispatch_total"]["xla"] >= 2
        forms = stats["latent_prefill_form_total"]
        assert forms["absorbed"] >= 1 and forms["up_projected"] >= 1
        records = eng.step_recorder.snapshot()
        for form, count in forms.items():
            assert sum(s.get(f"latent_prefill_{form}", 0)
                       for s in records) == count
        assert any("moe_experts_hit" in s for s in records)
    finally:
        eng.stop()


def test_a_preempted_row_holds_the_reference():
    """A pool too small for three long answers at once: a sequence is
    preempted and recomputed through the latent pages, and every row's
    log-probabilities are still the reference's."""
    eng = _engine(num_blocks=14, max_num_seqs=3)
    eng.start()
    try:
        prompts = {rid: _prompt(40, salt)
                   for rid, salt in (("x", 1), ("y", 2), ("z", 5))}
        queues = {rid: _submit(eng, rid, p, 40) for rid, p in prompts.items()}
        results = {rid: _collect(q) for rid, q in queues.items()}
        assert eng.stats()["num_preempted_total"] >= 1
        for rid, (tokens, tops) in results.items():
            assert len(tokens) == 40
            _holds_the_reference(eng.config.seed, prompts[rid], tokens, tops)
    finally:
        eng.stop()


REFUSED = [
    ({"speculative_num_tokens": 2}, "--speculative-num-tokens"),
    ({"speculative_draft_model": "tiny-llama", "speculative_num_tokens": 2},
     "--speculative-draft-model"),
    ({"kv_offload_bytes": 1 << 20}, "--kv-offload-bytes"),
    ({"kv_cache_dtype": "int8"}, "--kv-cache-dtype int8"),
    ({"quantization": "int8"}, "int8 quantization is supported"),
    ({"pipeline_parallel_size": 2}, "pipeline_parallel_size"),
    ({"tensor_parallel_size": 2}, "mesh of several devices"),
]


@pytest.mark.parametrize("flags, says", REFUSED,
                         ids=[says for _, says in REFUSED])
def test_start_up_refuses_by_name_what_the_family_is_not_taught(flags, says):
    """The prediction module is mathematics only: nothing drafts with it,
    and ``--speculative-num-tokens`` is refused by name like every other
    surface the latent pages are not taught."""
    two = {"pipeline_parallel_size", "tensor_parallel_size"} & set(flags)
    with pytest.raises(ValueError, match=says):
        EngineCore(EngineConfig(
            model="tiny-glm4-moe-lite", max_model_len=128, block_size=16,
            num_blocks=32, dtype="float32", **flags),
            devices=jax.devices()[:2 if two else 1])
