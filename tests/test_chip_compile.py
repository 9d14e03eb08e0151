"""The chip's compiler on the kernels of the main path, at real widths.

Interpret mode cannot see what Mosaic refuses: a slice off the tiling, a
shape cast it cannot lay out, more scoped VMEM than a kernel may take, a
kernel the partitioner cannot split. The TPU compiler is installed here
and compiles for a chip that is described, not attached — so every
kernel shape the default engine compiles for ``tpu-llama-3b`` and
``tpu-llama-1b`` (block_size 64) is compiled here for a v5e, about two
seconds each, at no chip time. A compile that passes is not a chip run.

Everything that touches the topology lives in fixtures of THIS file (one
process at a time may load the TPU library: see the on-chip-measurement
guide, section 2).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from production_stack_tpu.models import build_model, get_model_config
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops import attention as att
from production_stack_tpu.ops import pallas_grouped_matmul as gmm
from production_stack_tpu.ops.pallas_paged_attention import (
    pallas_paged_attention,
)
from production_stack_tpu.ops.pallas_prefill_attention import (
    pallas_prefill_attention,
)

BLOCK_SIZE = 64
LAYERS, NUM_BLOCKS = 4, 256  # pool dims the kernels only index into
MODELS = ("tpu-llama-3b", "tpu-llama-1b")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    compiles again), so the cache is off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _pages(sharding, kvh, head_dim, quantized):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    data = (LAYERS, NUM_BLOCKS, BLOCK_SIZE, kvh, head_dim)
    if quantized:
        return (spec(data, jnp.int8),
                spec((LAYERS, NUM_BLOCKS, BLOCK_SIZE * kvh), jnp.float32))
    return spec(data, jnp.bfloat16)


def _compile_with_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("tables", [4, 128])  # smallest, largest bucket
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("model", MODELS)
def test_decode_kernel_compiles_for_v5e(one_chip, model, quantized, tables):
    mc = get_model_config(model)
    B = 8  # the server's default max_num_seqs

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = _pages(one_chip, mc.num_kv_heads, mc.head_dim, quantized)
    _compile_with_kernel(
        lambda q, k, v, bt, cl, layer: pallas_paged_attention(
            q, k, v, bt, cl, layer, scale=mc.head_dim ** -0.5),
        spec((B, mc.num_heads, mc.head_dim), jnp.bfloat16), pages, pages,
        spec((B, tables)), spec((B,)), spec(()))


@pytest.mark.parametrize("tables", [4, 8, 16, 32, 64])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_kernel_compiles_at_the_cells_shapes(one_chip, quantized,
                                                    tables):
    """The benchmark's serving shapes (Mistral-7B's heads, 32 rows, 64-token
    pages) at every table bucket the engine compiles for a 4,096-token
    model: the tile comes from the VMEM estimate (``decode_tile``), whose
    rings, scale rings and scratch the v5e compiler has to accept, and the
    32-bit strided view of the page ring has to lay out for bf16 and for
    int8 pages alike. The chunk is 8 pages, or the table where it is
    narrower, and the ring 6 deep."""
    from production_stack_tpu.ops.pallas_paged_attention import decode_tile

    B, H, KVH, D = 32, 32, 8, 128

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert decode_tile(BLOCK_SIZE, KVH, D, 8, 1 if quantized else 2, tables,
                       quantized) == (min(tables, 8), 6)
    pages = _pages(one_chip, KVH, D, quantized)
    _compile_with_kernel(
        lambda q, k, v, bt, cl, layer: pallas_paged_attention(
            q, k, v, bt, cl, layer, scale=D ** -0.5),
        spec((B, H, D), jnp.bfloat16), pages, pages,
        spec((B, tables)), spec((B,)), spec(()))


# (prefill rows, chunk bucket) the default engine compiles, each with the
# smallest table bucket that holds the chunk and the largest (8192 tokens).
@pytest.mark.parametrize("rows,chunk,tables", [
    (1, 1024, 16), (1, 1024, 128), (4, 1024, 16), (4, 1024, 128),
    (1, 2048, 32), (1, 2048, 128)])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("model", MODELS)
def test_prefill_kernel_compiles_for_v5e(one_chip, model, quantized, rows,
                                         chunk, tables):
    mc = get_model_config(model)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = _pages(one_chip, mc.num_kv_heads, mc.head_dim, quantized)
    fresh = spec((rows, chunk, mc.num_kv_heads, mc.head_dim), jnp.bfloat16)
    _compile_with_kernel(
        lambda q, k, v, bt, pos, tl, layer, kn, vn, sl:
        pallas_prefill_attention(q, k, v, bt, pos, tl, layer, kn, vn, sl,
                                 scale=mc.head_dim ** -0.5),
        spec((rows, chunk, mc.num_heads, mc.head_dim), jnp.bfloat16),
        pages, pages, spec((rows, tables)), spec((rows, chunk)),
        spec((rows,)), spec(()), fresh, fresh, spec((rows,)))


def test_sharded_decode_attention_on_four_chips(topo, monkeypatch):
    """A pool sharded over kv heads on four chips. The partitioner
    refuses a bare pallas_call, so the dispatcher runs the kernel per
    shard where a shard's heads pass the tile gate (32 kv heads -> 8 per
    chip) and takes the reference, by a counted trace-time decision,
    where they do not (Llama's 8 -> 2 per chip). Either way attention
    stays local: no collective, and each chip is handed a quarter of the
    pool, not all of it. The per-shard kernel at the smallest, a middle
    and the widest table bucket of a 4,096-token model."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4),
                ("dp", "pp", "tp"))
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    D, B = 128, 8

    def spec(shape, dtype=jnp.int32, axes=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, axes))

    def compiled(kvh, heads, fn, tables=16):
        pages = spec((LAYERS, NUM_BLOCKS, BLOCK_SIZE, kvh, D), jnp.bfloat16,
                     P(None, None, None, "tp", None))
        return jax.jit(fn).lower(
            spec((B, heads, D), jnp.bfloat16, P(None, "tp", None)),
            pages, pages, spec((B, tables)), spec((B,)), spec(())).compile()

    def dispatch(q, k, v, bt, cl, layer):
        with att.kv_head_sharding(mesh, "tp"):
            return att.paged_decode_attention(q, k, v, bt, cl, layer,
                                              scale=D ** -0.5)

    with pytest.raises(Exception, match="shard_map"):
        compiled(32, 64, lambda q, k, v, bt, cl, layer:
                 pallas_paged_attention(q, k, v, bt, cl, layer,
                                        scale=D ** -0.5))

    pool_side = LAYERS * NUM_BLOCKS * BLOCK_SIZE * D * 2
    for kvh, heads, path, tables in (
            (32, 64, "pallas", 4), (32, 64, "pallas", 16),
            (32, 64, "pallas", 64), (8, 24, "xla", 16)):
        att.TRACED_PATHS.clear()
        program = compiled(kvh, heads, dispatch, tables)
        text = program.as_text()
        assert dict(att.TRACED_PATHS) == {("decode", path): 1}
        assert ("tpu_custom_call" in text) == (path == "pallas")
        assert not re.search(
            r"\b(all-gather|all-reduce|all-to-all|collective-permute)", text)
        held = program.memory_analysis().argument_size_in_bytes
        assert held < 2 * pool_side * kvh / 4 * 1.1


@pytest.mark.parametrize("arch,mode,rows,width", [
    ("llama", "decode", 32, 1), ("llama", "prefill", 1, 256),
    ("llama", "prefill_cached", 1, 256), ("mixtral", "decode", 32, 1)])
def test_layer_scan_reads_stacked_weights_in_place(one_chip, monkeypatch,
                                                   arch, mode, rows, width):
    """Every matrix of a layer is read by its matmul straight out of the
    stacked ``[L, ...]`` leaf. With three projection leaves, each reshaped
    to heads right after its matmul, the compiler sliced all three out of
    the stack into VMEM and transposed them, in every layer of every
    forward (PERF.md section 6, PR 30): a loop fusion or a copy whose
    result is as large as a layer's smallest matrix is that again.
    Mistral-7B's widths, the default server's LoRA slots; activations at
    these rows stay under that size. Mixtral's attention half is Llama's
    (models/llama.py::attention_half), so with two experts of those widths
    its scan holds to the same."""
    cfg = ModelConfig(
        name="mistral-7b-widths", arch=arch, vocab_size=32000,
        hidden_size=4096, num_layers=LAYERS, num_heads=32, num_kv_heads=8,
        head_dim=128, intermediate_size=14336, rope_theta=10000.0,
        num_experts=2 if arch == "mixtral" else 0)
    init_params, apply = build_model(cfg)
    monkeypatch.setattr(att, "_use_pallas", lambda: True)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: init_params(
            cfg, jax.random.key(0), lora_slots=8, lora_rank=16)))
    pages = _pages(one_chip, cfg.num_kv_heads, cfg.head_dim, False)
    # The pool is donated, as the engine's step programs donate it:
    # otherwise the program's entry copies it whole.
    text = jax.jit(
        lambda p, tok, pos, kv, slot, bt, cl, sl, aid: apply(
            p, cfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
            adapter_ids=aid), donate_argnums=(3,)).lower(
        params, spec((rows, width)), spec((rows, width)), (pages, pages),
        spec((rows, width)), spec((rows, 64)), spec((rows,)),
        spec((rows,)), spec((rows,))).compile().as_text()
    assert "tpu_custom_call" in text or mode == "prefill"

    smallest = cfg.hidden_size * cfg.num_kv_heads * cfg.head_dim
    moved, fused = [], False
    for line in text.splitlines():
        if not line.startswith(" "):  # a computation's header, or its end
            fused = line.startswith("%fused_computation")
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = bf16\[([\d,]+)\]", line)
        # Instructions of the program itself, not of a fusion's inside
        # (where a matmul's own slice of its operand lives).
        if m and not fused and (" copy(" in line or "kind=kLoop" in line):
            if np.prod([int(d) for d in m.group(2).split(",")]) >= smallest:
                moved.append((m.group(1), m.group(2)))
    assert not moved, moved


@pytest.mark.parametrize("tables", [4, 64])
@pytest.mark.parametrize("heads", [48, 72])  # query groups 6 and 9
@pytest.mark.parametrize("window", [None, 512])
def test_decode_kernel_compiles_at_lagunas_query_groups(one_chip, heads,
                                                        window, tables):
    """Laguna-S-2.1's decode rows (128 of them, 8 KV heads of 128, 64-token
    pages): 48 query heads in a full layer and 72 in a sliding one, whose
    kernel takes the window's front bound (a first live page per row, the
    walk starting at its chunk)."""
    B, KVH, D = 128, 8, 128

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = _pages(one_chip, KVH, D, False)
    bound = {} if window is None else {"window": window}
    _compile_with_kernel(
        lambda q, k, v, bt, cl, layer: pallas_paged_attention(
            q, k, v, bt, cl, layer, scale=D ** -0.5, **bound),
        spec((B, heads, D), jnp.bfloat16), pages, pages,
        spec((B, tables)), spec((B,)), spec(()))


def _traced_the_grouped_matmul_kernel(one_tile):
    """Every expert layer traced took the Pallas kernel: in the tokens'
    order where the program's row slots are ``one_tile`` (no ``sort`` is
    then left under ``moe_experts``), sorted by expert elsewhere."""
    path, other = (("pallas_one_tile", "pallas") if one_tile else
                   ("pallas", "pallas_one_tile"))
    assert att.TRACED_PATHS["grouped_matmul", path] >= 1
    assert att.TRACED_PATHS["grouped_matmul", other] == 0
    assert att.TRACED_PATHS["grouped_matmul", "xla"] == 0


def _holds_the_grouped_matmul_kernel(text, one_tile=False):
    """The expert layers' matmuls are the Pallas kernel, traced once per
    kind of layer, nothing of ``jax.lax.ragged_dot`` is left, and no
    expert stack (four dims, or the ``layers x held`` groups the kernel
    reads) is copied on its way there."""
    _traced_the_grouped_matmul_kernel(one_tile)
    assert "pallas_grouped_matmul" in text and "ragged-dot" not in text
    copied = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[(\d+,64|\d{3,}),\d{4},\d{4}\]\S* "
                           r"copy(-start)?\(", line)]
    assert not copied, copied


@pytest.mark.parametrize("mode,rows,width,tables", [
    ("decode", 128, 1, 16), ("prefill", 1, 1024, 16), ("prefill", 4, 512, 8),
    ("prefill_cached", 1, 1024, 32)])
def test_laguna_programs_compile_at_the_configurations_widths(
        one_chip, monkeypatch, tmp_path, mode, rows, width, tables):
    """``laguna-s-2.1-l8e64`` as the benchmark serves it (the model keys of
    its file, written to a ``config.json`` as ``chipbench.stack`` does):
    the three forward programs with the expert layer's counts compile for
    the v5e, with both attention kernels and the grouped-matmul kernel in
    them (no ``ragged_dot`` left), the weights are the 10.1 GB the
    configuration states, and no copy of an expert stack (or of any other
    weight) is made on the way to its matmul: a temporary as large as one
    layer's routed experts would be one."""
    import json
    import os
    import sys

    from production_stack_tpu.models import laguna

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from chipbench.registry import model_keys

    with open(os.path.join(repo, "chipbench", "configs",
                           "laguna-s-2.1-l8e64.json")) as f:
        (tmp_path / "config.json").write_text(
            json.dumps(model_keys(json.load(f))))
    cfg = get_model_config(str(tmp_path))
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    monkeypatch.setattr(gmm, "_platform", lambda: "tpu")
    att.TRACED_PATHS.clear()

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: laguna.init_params(cfg, jax.random.key(0))))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 10.1e9 - 1) < 0.05
    pages = spec((cfg.num_layers, NUM_BLOCKS, BLOCK_SIZE, cfg.num_kv_heads,
                  cfg.head_dim), jnp.bfloat16)
    last = mode != "decode"
    program = jax.jit(
        lambda p, kv, tok, pos, slot, bt, cl, sl: laguna.apply(
            p, cfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
            last_token=jnp.maximum(sl - 1, 0) if last else None,
            with_stats=True), donate_argnums=(1,)).lower(
        params, (pages, pages), spec((rows, width)), spec((rows, width)),
        spec((rows, width)), spec((rows, tables)), spec((rows,)),
        spec((rows,))).compile()
    text = program.as_text()
    assert "tpu_custom_call" in text
    assert ("pallas_paged_attention" in text) == (mode == "decode")
    assert ("pallas_prefill_attention" in text) == (mode == "prefill_cached")
    _holds_the_grouped_matmul_kernel(text)
    experts_of_a_layer = 64 * 3072 * 1024 * 2
    assert program.memory_analysis().temp_size_in_bytes < (
        experts_of_a_layer if mode == "decode" else 2 * experts_of_a_layer)


@pytest.mark.parametrize("tables", [4, 64])
def test_decode_kernel_compiles_at_the_packed_rows_of_64_wide_heads(
        one_chip, tables):
    """Eight kv heads of 64 lie two to a 128-lane row
    (``ops.attention.packed_page_dims``): the pool is ``[L, NB, bs, 4,
    128]``, which the v5e keeps in tiles of ``(4, 128)`` with no padding,
    and the decode kernel takes it as four heads of 128 with the queries
    spread over the lanes of their own head."""
    B, H, KVH, D = 32, 32, 8, 64
    rows, lanes = att.packed_page_dims(KVH, D)
    assert (rows, lanes) == (4, 128)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = _pages(one_chip, rows, lanes, False)
    text = _compile_with_kernel(
        lambda q, k, v, bt, cl, layer: pallas_paged_attention(
            att._packed_queries(q, rows, 2), k, v, bt, cl, layer,
            scale=D ** -0.5),
        spec((B, H, D), jnp.bfloat16), pages, pages, spec((B, tables)),
        spec((B,)), spec(()))
    assert re.search(r"bf16\[4,256,64,4,128\]\{4,3,2,1,0:T\(4,128\)\(2,1\)\}",
                     text)


def _lfm2_24b(tmp_path, monkeypatch, sharding, blocks=1024):
    """(cfg, the weights' shapes, ``spec``, the pool's three sides at
    ``blocks`` blocks) of ``lfm2-24b-a2b-l10`` as the benchmark serves
    it, with the kernels the chip takes."""
    from production_stack_tpu.engine.core import kv_page_dims
    from production_stack_tpu.models import lfm2
    from production_stack_tpu.models.registry import block_state_shape

    cfg = _benchmark_config(tmp_path, "lfm2-24b-a2b-l10")
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    monkeypatch.setattr(gmm, "_platform", lambda: "tpu")
    att.TRACED_PATHS.clear()

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: lfm2.init_params(cfg, jax.random.key(0))))
    layers, page_rows, lanes = kv_page_dims(cfg)
    assert (layers, page_rows, lanes) == (2, 4, 128)
    pages = spec((layers, blocks, BLOCK_SIZE, page_rows, lanes), jnp.bfloat16)
    held = block_state_shape(cfg)
    state = spec((held[0], blocks) + held[1:], jnp.bfloat16)
    return cfg, params, spec, (pages, pages, state)


@pytest.mark.parametrize("mode,rows,width,tables", [
    ("decode", 32, 1, 64), ("prefill", 4, 512, 8),
    ("prefill_cached", 1, 1024, 64)])
def test_lfm2_programs_compile_at_the_configurations_widths(
        one_chip, monkeypatch, tmp_path, mode, rows, width, tables):
    """``lfm2-24b-a2b-l10`` as the benchmark serves it: the three forward
    programs compile for the v5e with both attention kernels in them at
    the packed rows (no XLA path traced), the weights are the 10.5 GB the
    configuration states, and neither an expert stack nor a side of the
    pool is copied on the way through the layers' ``cond``: the pool here
    is 1,024 blocks (0.34 GB), and a temporary as large as one side of it
    or as one layer's experts would be such a copy."""
    from production_stack_tpu.engine.core import kv_page_dims
    from production_stack_tpu.models import lfm2
    from production_stack_tpu.models.registry import block_state_shape

    blocks = 1024
    cfg, params, spec, pool = _lfm2_24b(
        tmp_path, monkeypatch, one_chip, blocks)
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 10.5e9 - 1) < 0.03
    layers, page_rows, lanes = kv_page_dims(cfg)
    held = block_state_shape(cfg)
    last = mode != "decode"
    program = jax.jit(
        lambda p, kv, tok, pos, slot, bt, cl, sl: lfm2.apply(
            p, cfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
            last_token=jnp.maximum(sl - 1, 0) if last else None,
            with_stats=True), donate_argnums=(1,)).lower(
        params, pool, spec((rows, width)),
        spec((rows, width)), spec((rows, width)), spec((rows, tables)),
        spec((rows,)), spec((rows,))).compile()
    text = program.as_text()
    assert ("pallas_paged_attention" in text) == (mode == "decode")
    assert ("pallas_prefill_attention" in text) == (mode == "prefill_cached")
    assert not [k for k in att.TRACED_PATHS if k[1] == "xla"]
    # 32 rows x top 4 are one row tile: nothing is sorted by expert
    _holds_the_grouped_matmul_kernel(text, one_tile=mode == "decode")
    one_side = layers * blocks * BLOCK_SIZE * page_rows * lanes * 2
    experts_of_a_layer = 64 * 3 * 2048 * 1536 * 2
    temp = program.memory_analysis().temp_size_in_bytes
    assert temp < (one_side if mode == "decode" else experts_of_a_layer)
    # A side that a branch of the layers' ``cond`` hands back as it got it
    # is copied there in every layer, into the conditional's own result
    # (no temporary shows it): on the chip such copies of k and v were
    # three quarters of the device's time (PERF.md section 6, PR 36).
    sides = (rf"bf16\[{layers},{blocks},{BLOCK_SIZE},{page_rows},{lanes}\]",
             rf"bf16\[{held[0]},{blocks},{held[1]},{held[2]}\]")
    copied = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"= ({'|'.join(sides)})\S* copy(-start)?\(", line)]
    assert not copied, copied


@pytest.mark.parametrize("tables", [4, 8, 16, 32, 64, 128])
def test_latent_decode_kernel_compiles_at_the_cells_shapes(one_chip, tables):
    """``longcat-flash-l4e16``'s decode attention (128 rows, 64 heads
    over a 512-wide latent and a rotated key in a 128-lane row, 64-token
    pages, 8 page layers) at each table width the cell's six decode
    programs have: the v5e compiler takes the kernel's body (a full
    chunk as two spans, a row's last chunk over its live sub-blocks) at
    the tile the shapes choose, and the pool's one-row sides reach it as
    the same bytes in four dims (a bitcast: no copy of a side, no
    temporary at all)."""
    from production_stack_tpu.ops.pallas_mla_decode import (
        decode_tile,
        pallas_mla_decode,
    )

    B, H, C, lanes = 128, 64, 512, 128

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert decode_tile(BLOCK_SIZE, H, C, lanes, 2, tables) == (
        min(tables, 16), 4)
    program = jax.jit(
        lambda qa, qr, c, r, bt, cl, layer: pallas_mla_decode(
            qa, qr, c, r, bt, cl, layer, scale=192 ** -0.5)).lower(
        spec((B, H, C), jnp.bfloat16), spec((B, H, 64), jnp.bfloat16),
        spec((8, NUM_BLOCKS, BLOCK_SIZE, 1, C), jnp.bfloat16),
        spec((8, NUM_BLOCKS, BLOCK_SIZE, 1, lanes), jnp.bfloat16),
        spec((B, tables)), spec((B,)), spec(())).compile()
    text = program.as_text()
    assert "tpu_custom_call" in text and "pallas_mla_decode" in text
    copied = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"= bf16\[8,{NUM_BLOCKS},{BLOCK_SIZE},\S* "
                           r"copy(-start)?\(", line)]
    assert not copied, copied
    assert program.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("mode,rows,width,tables", [
    ("decode", 128, 1, 64), ("prefill", 1, 1024, 16),
    ("prefill_cached", 1, 1024, 32), ("prefill_cached", 1, 1024, 128)])
def test_longcat_programs_compile_at_the_configurations_widths(
        one_chip, monkeypatch, tmp_path, mode, rows, width, tables):
    """``longcat-flash-l4e16`` as the benchmark serves it (the model keys
    of its file, written to a ``config.json`` as ``chipbench.stack``
    does): the three forward programs with the expert layer's counts
    compile for the v5e; decode holds the latent kernel (traced once:
    the scan's body is one sublayer) and every mode the grouped-matmul
    kernel (no ``ragged_dot`` left); the weights are the 10.35 GB the
    configuration states; no expert stack and no side of the pool is
    copied; and the temporaries stay under what the pool leaves free
    (decode 0.08 GB: ``wq_b`` is stored [out, in] and ``wkv_b`` per head,
    the layouts the compiler otherwise makes of the whole stacks in
    every program, 0.44 GB; plain prefill of a whole chunk 0.81 GB, of
    which 0.13 are ``wkv_b`` with the latent's lanes last for the
    up-projection; cached prefill under a 128-block table 0.94 GB:
    float32 scores of 64 heads)."""
    import json
    import os
    import sys

    from production_stack_tpu.models import longcat

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from chipbench.registry import model_keys

    with open(os.path.join(repo, "chipbench", "configs",
                           "longcat-flash-l4e16.json")) as f:
        (tmp_path / "config.json").write_text(
            json.dumps(model_keys(json.load(f))))
    cfg = get_model_config(str(tmp_path))
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    monkeypatch.setattr(gmm, "_platform", lambda: "tpu")
    att.TRACED_PATHS.clear()

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: longcat.init_params(cfg, jax.random.key(0))))
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 10.35e9 - 1) < 0.01
    blocks = 4096  # a pool of 2.7 GB
    pages = (spec((8, blocks, BLOCK_SIZE, 1, 512), jnp.bfloat16),
             spec((8, blocks, BLOCK_SIZE, 1, 128), jnp.bfloat16))
    last = mode != "decode"
    program = jax.jit(
        lambda p, kv, tok, pos, slot, bt, cl, sl: longcat.apply(
            p, cfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
            last_token=jnp.maximum(sl - 1, 0) if last else None,
            with_stats=True), donate_argnums=(1,)).lower(
        params, pages, spec((rows, width)), spec((rows, width)),
        spec((rows, width)), spec((rows, tables)), spec((rows,)),
        spec((rows,))).compile()
    text = program.as_text()
    assert ("pallas_mla_decode" in text) == (mode == "decode")
    assert att.TRACED_PATHS["latent_decode", "pallas"] == (
        mode == "decode")
    _traced_the_grouped_matmul_kernel(one_tile=False)
    assert "pallas_grouped_matmul" in text and "ragged-dot" not in text
    copied = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"= bf16\[(4,16,\d{{4}},\d{{4}}|8,{blocks},"
                           rf"{BLOCK_SIZE},1,\d+)\]\S* copy(-start)?\(",
                           line)]
    assert not copied, copied
    assert program.memory_analysis().temp_size_in_bytes < (
        0.2e9 if mode == "decode" else 1.1e9)


@pytest.mark.parametrize("rows, tables", [(32, 128), (32, 64), (8, 16)])
def test_latent_decode_kernel_compiles_at_twenty_heads(one_chip, rows,
                                                       tables):
    """``glm-4.7-flash-e8v8``'s decode attention: 20 heads (no multiple
    of the eight rows of a sublane tile, nor of the sixteen a bf16 tile
    packs) over the same page as LongCat's, 47 page layers. The query
    block and the accumulator take all 20 heads as the whole of their
    dimension, the v5e compiler pads them in VMEM, and the kernel keeps
    LongCat's tile (16 pages, ring 4)."""
    from production_stack_tpu.ops.pallas_mla_decode import (
        decode_tile,
        pallas_mla_decode,
        tiles_ok,
    )

    H, C, lanes = 20, 512, 128

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert tiles_ok(BLOCK_SIZE, H, C, lanes, 2)
    assert decode_tile(BLOCK_SIZE, H, C, lanes, 2, tables) == (
        min(tables, 16), 4)
    program = jax.jit(
        lambda qa, qr, c, r, bt, cl, layer: pallas_mla_decode(
            qa, qr, c, r, bt, cl, layer, scale=256 ** -0.5)).lower(
        spec((rows, H, C), jnp.bfloat16), spec((rows, H, 64), jnp.bfloat16),
        spec((47, NUM_BLOCKS, BLOCK_SIZE, 1, C), jnp.bfloat16),
        spec((47, NUM_BLOCKS, BLOCK_SIZE, 1, lanes), jnp.bfloat16),
        spec((rows, tables)), spec((rows,)), spec(())).compile()
    text = program.as_text()
    assert "tpu_custom_call" in text and "pallas_mla_decode" in text
    assert program.memory_analysis().temp_size_in_bytes == 0


def _benchmark_config(tmp_path, name):
    """The ``ModelConfig`` of ``chipbench/configs/<name>.json``: its model
    keys written to a ``config.json`` as ``chipbench.stack`` does."""
    import json
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from chipbench.registry import model_keys

    with open(os.path.join(repo, "chipbench", "configs",
                           name + ".json")) as f:
        (tmp_path / "config.json").write_text(
            json.dumps(model_keys(json.load(f))))
    return get_model_config(str(tmp_path))


def _glm47_flash(tmp_path, monkeypatch, sharding):
    """(cfg, the weights' shapes, ``spec``) of ``glm-4.7-flash-e8v8`` as
    the benchmark serves it (the model keys of its file, written to a
    ``config.json`` as ``chipbench.stack`` does), all 47 layers, with the
    kernels the chip takes."""
    from production_stack_tpu.models import glm4_moe_lite

    cfg = _benchmark_config(tmp_path, "glm-4.7-flash-e8v8")
    assert (cfg.num_layers, cfg.num_heads, cfg.dense_layers) == (47, 20, 1)
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    monkeypatch.setattr(gmm, "_platform", lambda: "tpu")
    att.TRACED_PATHS.clear()

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(
            lambda: glm4_moe_lite.init_params(cfg, jax.random.key(0))))
    return cfg, params, spec


@pytest.mark.parametrize("mode,rows,width,tables", [
    ("decode", 32, 1, 128), ("prefill", 1, 1024, 16),
    ("prefill_cached", 1, 1024, 128), ("prefill_cached", 1, 512, 128),
    ("prefill_cached", 1, 256, 128)])
def test_glm47_flash_programs_compile_at_the_configurations_widths(
        one_chip, monkeypatch, tmp_path, mode, rows, width, tables):
    """``glm-4.7-flash-e8v8`` as the benchmark serves it, all 47 layers:
    the three forward programs with the expert layer's counts compile
    for the v5e; decode holds the latent kernel at 20 heads (traced once
    a program: the layer loop is two scans, the dense layer's and the
    46 sparse layers', and both call the one jitted ``_mla``) and every
    mode the grouped-matmul kernel; the weights are the 10.16 GB the configuration states; no
    expert stack and no side of the pool is copied; a cached prefill
    under a 128-block table absorbs at every bucket to a whole chunk of
    1,024 (the form the chip read faster, PR 45), float32 scores of
    0.67 GB among its temporaries; and the temporaries stay under what
    the pool leaves free (the attention stacks sliced ``[1:]`` for the
    sparse layers' scan were 2 GB of them, PR 46)."""
    from production_stack_tpu.models import decoder, glm4_moe_lite

    cfg, params, spec = _glm47_flash(tmp_path, monkeypatch, one_chip)
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 10.16e9 - 1) < 0.01
    blocks = 1280  # a pool of 4.9 GB: 81,920 tokens of 60,160 bytes
    pages = (spec((47, blocks, BLOCK_SIZE, 1, 512), jnp.bfloat16),
             spec((47, blocks, BLOCK_SIZE, 1, 128), jnp.bfloat16))
    last = mode != "decode"
    program = jax.jit(
        lambda p, kv, tok, pos, slot, bt, cl, sl: glm4_moe_lite.apply(
            p, cfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
            last_token=jnp.maximum(sl - 1, 0) if last else None,
            with_stats=True), donate_argnums=(1,)).lower(
        params, pages, spec((rows, width)), spec((rows, width)),
        spec((rows, width)), spec((rows, tables)), spec((rows,)),
        spec((rows,))).compile()
    text = program.as_text()
    assert ("pallas_mla_decode" in text) == (mode == "decode")
    assert att.TRACED_PATHS["latent_decode", "pallas"] == (
        mode == "decode")
    # 32 rows x top 4 are one row tile: nothing is sorted by expert
    _traced_the_grouped_matmul_kernel(one_tile=mode == "decode")
    assert "pallas_grouped_matmul" in text and "ragged-dot" not in text
    if mode == "prefill_cached":
        form = decoder.latent_prefill_form(
            width, tables * BLOCK_SIZE, 20, 512, 192, 64, 256)
        assert form == "absorbed"
        assert "mla_up_context" not in text
    copied = [line.strip()[:120] for line in text.splitlines()
              if re.search(rf"= bf16\[(46,8,\d{{4}},\d{{4}}|47,{blocks},"
                           rf"{BLOCK_SIZE},1,\d+)\]\S* copy(-start)?\(",
                           line)]
    assert not copied, copied
    assert program.memory_analysis().temp_size_in_bytes < (
        0.2e9 if mode == "decode" else 1.2e9)


def _while_bodies(text):
    """{name: lines} of the computations that some ``while`` of the
    optimised HLO ``text`` names as its body."""
    computations, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            computations[name] = []
        elif name is not None:
            computations[name].append(line)
    return {body: computations[body]
            for body in set(re.findall(r"body=%?([\w.\-]+)", text))}


def _bytes_copied(line):
    """Bytes of the buffer a ``copy-start`` line moves (its first result)."""
    dtype, dims = re.search(r"= \((\w+)\[([\d,]*)\]", line).groups()
    bits = int(re.sub(r"\D", "", dtype) or 8)  # pred: a byte
    return bits // 8 * int(np.prod([int(d) for d in dims.split(",") if d]))


def test_glm47_flash_decode_burst_prefetches_no_weight_in_the_layer_loop(
        one_chip, monkeypatch, tmp_path):
    """``glm-4.7-flash-e8v8``'s decode step **nested as the engine nests
    it** (engine/core.py::_make_multi_decode: ``apply(mode="decode")``
    inside a scan over the burst's 8 steps, the pool in the carry, each
    step's token fed to the next), at the configuration's full depth
    and pool (a compile of ~10 s, a scan's body compiles once; at 1 + 2
    layers the parent's compiler made no such prefetch, so the case
    cannot be cut in depth): no ``while`` body holds a ``copy-start``
    over 1 MB but the step loop's own one prefetch of the dense layer's
    matrices, and the layer loop holds no ``conditional``. With the
    dense MLP behind a ``lax.cond`` in one scan over all 47 layers the
    compiler prefetched two of its matrices into VMEM ahead of the
    ``conditional`` in every layer, 2 x 41.9 MB x 47 = 3.9 GB a forward,
    a quarter of the cell's device time, and ``apply`` compiled alone
    never showed it (PR 46)."""
    from production_stack_tpu.models import glm4_moe_lite

    cfg, params, spec = _glm47_flash(tmp_path, monkeypatch, one_chip)
    rows, steps, tables, blocks = 32, 8, 128, 1140

    def burst(p, kv, tokens, positions, slots, block_tables, contexts):
        def step(carry, step_slots):
            tokens, kv, s = carry
            logits, kv, stats = glm4_moe_lite.apply(
                p, cfg, tokens[:, None], (positions + s)[:, None], kv,
                step_slots[:, None], block_tables, contexts + s,
                jnp.ones_like(contexts), mode="decode", with_stats=True)
            sampled = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (sampled, kv, s + 1), (sampled, stats)

        (_, kv, _), (out, stats) = jax.lax.scan(
            step, (tokens, kv, jnp.int32(0)), slots.T)
        return out.T, kv, stats.sum(axis=0)

    pages = (spec((47, blocks, BLOCK_SIZE, 1, 512), jnp.bfloat16),
             spec((47, blocks, BLOCK_SIZE, 1, 128), jnp.bfloat16))
    program = jax.jit(burst, donate_argnums=(1,)).lower(
        params, pages, spec((rows,)), spec((rows,)), spec((rows, steps)),
        spec((rows, tables)), spec((rows,))).compile()
    text = program.as_text()
    assert "pallas_mla_decode" in text
    bodies = _while_bodies(text)
    # the burst's step loop holds the layer loop; the layer loop holds
    # the latent decode kernel and no further loop
    layer_loops = [lines for lines in bodies.values()
                   if any("pallas_mla_decode" in line for line in lines)
                   and not any(" while(" in line for line in lines)]
    assert len(layer_loops) == 1 and len(bodies) == 2

    def large_copies(lines):
        return [line.strip()[:100] for line in lines
                if " copy-start(" in line and _bytes_copied(line) > 1 << 20]

    in_layer_loop = {
        "conditional": [line.strip()[:100] for line in layer_loops[0]
                        if " conditional(" in line],
        "copy-start over 1 MB": large_copies(layer_loops[0])}
    assert not any(in_layer_loop.values()), in_layer_loop
    # what the step loop holds is the dense layer's own read, once a
    # forward: the bytes the model owes
    once = [line for lines in bodies.values() for line in large_copies(lines)]
    assert all("10240" in line for line in once) and len(once) <= 3, once
    assert program.memory_analysis().temp_size_in_bytes < 0.2e9


@pytest.mark.parametrize("shape", [
    (47, 1140, BLOCK_SIZE, 1, 512), (47, 1140, BLOCK_SIZE, 1, 128),
    (8, 6988, BLOCK_SIZE, 1, 512), (16, 1559, BLOCK_SIZE, 8, 128)],
    ids=["glm_latent", "glm_rope", "longcat_latent", "mistral_keys"])
def test_reading_a_few_blocks_of_a_full_pool_copies_no_side(one_chip, shape):
    """``extract_kv`` reads a prompt's blocks out of a pool that fills the
    chip: the gather through the flat view has no temporary at any pool's
    shape, where ``x[:, idx]`` on the 47-layer latent side is given a
    copy of the whole side (3.5 GB: the check ran out of memory on the
    chip, PR 44)."""
    from production_stack_tpu.engine.core import _gather_blocks_flat

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    program = _gather_blocks_flat.lower(
        spec(shape, jnp.bfloat16), spec((11,), jnp.int32)).compile()
    assert program.memory_analysis().temp_size_in_bytes < 1 << 20
    if shape[0] == 47 and shape[-1] == 512:
        plain = jax.jit(lambda x, idx: x[:, idx]).lower(
            spec(shape, jnp.bfloat16), spec((11,), jnp.int32)).compile()
        assert plain.memory_analysis().temp_size_in_bytes > 3e9


def test_a_side_a_branch_hands_back_as_it_got_it_is_not_copied(one_chip):
    """PR 36's rule, held by ``decoder.by_layer`` and not by a family's
    memory (``tests/test_layer_loop.py``'s toy: each of its two operators
    returns the other's side of the pool as it got it): the scan compiled
    for the chip copies neither side, and the plain ``lax.cond`` copies
    one in every layer, which is what makes the reading worth something."""
    import test_layer_loop as toy
    from production_stack_tpu.models import decoder

    def plain(flags, layer, if_true, if_false, *operands):
        return jax.lax.cond(jnp.asarray(flags)[layer], if_true, if_false,
                            *operands)

    blocks = 8192  # a side of 0.1 GB: nothing the compiler keeps in VMEM

    def spec(x, blocks=None):
        shape = x.shape if blocks is None else x.shape[:1] + (
            blocks,) + x.shape[2:]
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=one_chip)

    def copies_of_a_side(choose):
        x, sides, params = toy._toy()
        text = jax.jit(lambda x, sides, params: decoder.scan_layers(
            toy._step(params, choose=choose),
            decoder.first_carry(x, sides, toy.COUNTED), toy.L),
            donate_argnums=(1,)).lower(
            spec(x), tuple(spec(side, blocks) for side in sides),
            jax.tree_util.tree_map(spec, params)).compile().as_text()
        side = rf"s32\[\d+,{blocks},{toy.ROWS},{toy.WIDTH}\]"
        return [line.strip()[:120] for line in text.splitlines()
                if re.search(rf"= {side}\S* copy(-start)?\(", line)]

    assert not copies_of_a_side(decoder.by_layer)
    assert copies_of_a_side(plain)


def _ouro_26b(tmp_path, monkeypatch, sharding):
    """(cfg, the weights' shapes, ``spec``, the pool's sides) of
    ``ouro-2.6b`` as the benchmark serves it: all 48 layers, 4 passes,
    192 page layers, a pool of 78 blocks of 96 MiB."""
    from production_stack_tpu.models import ouro

    cfg = _benchmark_config(tmp_path, "ouro-2.6b")
    assert (cfg.num_layers, cfg.loop_passes, cfg.num_kv_heads) == (48, 4, 16)
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    att.TRACED_PATHS.clear()

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda: ouro.init_params(cfg, jax.random.key(0))))
    pages = spec((192, 78, BLOCK_SIZE, 16, 128), jnp.bfloat16)
    return cfg, params, spec, (pages, pages)


def _copies_of_the_stack_or_the_pool(text):
    """Lines of the optimised HLO that copy a 48-layer leaf or a side of
    the 192-layer pool."""
    return [line.strip()[:120] for line in text.splitlines()
            if re.search(r"= \(?bf16\[(48,\d{4},\d{4}|192,78,64,16,128)\]"
                         r"\S* copy(-start)?\(", line)]


@pytest.mark.parametrize("mode,rows,width,tables", [
    ("decode", 8, 1, 32), ("prefill", 1, 512, 8),
    ("prefill_cached", 1, 256, 32), ("prefill_cached", 1, 1024, 32)])
def test_ouro_programs_compile_at_the_published_sizes(
        one_chip, monkeypatch, tmp_path, mode, rows, width, tables):
    """``ouro-2.6b`` as the benchmark serves it, nothing cut: the tree is
    the 5.34 GB the configuration states; each forward program compiles
    for the v5e with the Pallas kernel of its mode at a query group of
    one, holds ONE body of the layer (two ``while``s: the passes around
    the layers) however many passes run, copies neither a leaf of the
    48-layer stack nor a side of the 192-layer pool, and keeps its
    temporaries a single pass's (megabytes beside a 4.9 GB stack)."""
    from production_stack_tpu.models import ouro

    cfg, params, spec, pages = _ouro_26b(tmp_path, monkeypatch, one_chip)
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 5.34e9 - 1) < 0.005
    last = mode != "decode"
    program = jax.jit(
        lambda p, kv, tok, pos, slot, bt, cl, sl: ouro.apply(
            p, cfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
            last_token=jnp.maximum(sl - 1, 0) if last else None,
            with_stats=True), donate_argnums=(1,)).lower(
        params, pages, spec((rows, width)), spec((rows, width)),
        spec((rows, width)), spec((rows, tables)), spec((rows,)),
        spec((rows,))).compile()
    text = program.as_text()
    assert ("pallas_paged_attention" in text) == (mode == "decode")
    assert ("pallas_prefill_attention" in text) == (mode == "prefill_cached")
    bodies = _while_bodies(text)
    assert len(bodies) == 2
    layer_loop = [lines for lines in bodies.values()
                  if not any(" while(" in line for line in lines)]
    assert len(layer_loop) == 1
    assert not _copies_of_the_stack_or_the_pool(text)
    assert program.memory_analysis().temp_size_in_bytes < (
        4e6 if mode == "decode" else 64e6)


def test_ouro_decode_burst_keeps_stack_and_pool_in_place(
        one_chip, monkeypatch, tmp_path):
    """The decode step nested as the engine nests it (a scan over the
    burst's 8 steps around the passes around the layers, the pool on all
    three carries): three ``while``s, the kernel in the innermost alone,
    no ``conditional``, no copy of a leaf or of a side, and no
    ``copy-start`` over 1 MB in any loop's body (GLM's lesson, PR 46:
    ``apply`` compiled alone does not show what the burst prefetches)."""
    from production_stack_tpu.models import ouro

    cfg, params, spec, pages = _ouro_26b(tmp_path, monkeypatch, one_chip)
    rows, steps, tables = 8, 8, 32

    def burst(p, kv, tokens, positions, slots, block_tables, contexts):
        def step(carry, step_slots):
            tokens, kv, s = carry
            logits, kv, stats = ouro.apply(
                p, cfg, tokens[:, None], (positions + s)[:, None], kv,
                step_slots[:, None], block_tables, contexts + s,
                jnp.ones_like(contexts), mode="decode", with_stats=True)
            sampled = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (sampled, kv, s + 1), (sampled, stats)

        (_, kv, _), (out, stats) = jax.lax.scan(
            step, (tokens, kv, jnp.int32(0)), slots.T)
        return out.T, kv, stats.sum(axis=0)

    program = jax.jit(burst, donate_argnums=(1,)).lower(
        params, pages, spec((rows,)), spec((rows,)), spec((rows, steps)),
        spec((rows, tables)), spec((rows,))).compile()
    text = program.as_text()
    bodies = _while_bodies(text)
    assert len(bodies) == 3
    with_kernel = [lines for lines in bodies.values()
                   if any("pallas_paged_attention" in line for line in lines)
                   and not any(" while(" in line for line in lines)]
    assert len(with_kernel) == 1
    assert " conditional(" not in text
    assert not _copies_of_the_stack_or_the_pool(text)
    large = [line.strip()[:100] for lines in bodies.values()
             for line in lines
             if " copy-start(" in line and _bytes_copied(line) > 1 << 20]
    assert not large, large
    assert program.memory_analysis().temp_size_in_bytes < 4e6


def test_lfm2_decode_burst_selects_once_and_reduces_once_a_step(
        one_chip, monkeypatch, tmp_path):
    """``lfm2-24b-a2b-l10``'s ``decode_k8`` at the cell's ``[32, 65536]``
    logits, nested as the engine nests it (engine/core.py::
    _make_multi_decode: what the burst holds constant built before the
    scan, ``apply(mode="decode")`` and the sampling tail in its body).
    The step loop's own body selects from no more than the 64 chosen
    groups' 8,192 candidates a row (``sampling.exact_top_k``: on the
    chip a ``TopK`` of 64 over all 65,536 took 460 us of a 3.19 ms
    forward, PERF.md section 6, PR 49) and sorts nothing of the
    vocabulary's width (a consumer's slice merged into a top-k's own
    hides the pair the compiler makes its ``TopK`` of, and it sorts the
    row); it makes ONE float32 ``[32, 65536]`` (the shaped logits: no
    log-softmax written out) beside the re-tiled view of it the groups'
    maxima are read from, ONE int32 one (the penalty counts' scatter,
    the only scatter left) and no 8-bit one (the structured mask is
    unpacked before the loop, and the compiler does not sink the unpack
    back into it). Until PR 49 the body held two ``TopK``s over the whole
    row, three scatters, the unpack with its transposing copy and seven
    float32 ``[32, 65536]`` results."""
    from production_stack_tpu.engine import sampling
    from production_stack_tpu.models import lfm2

    cfg, params, spec, pool = _lfm2_24b(tmp_path, monkeypatch, one_chip)
    rows, steps, tables, vocab = 32, 8, 64, cfg.vocab_size
    assert vocab == 65536

    def burst(p, kv, counts, tokens0, positions0, slot_mat, block_tables,
              context0, temperature, top_k, top_p, seed_base, presence,
              frequency, min_tokens, out_len0, bias_ids, bias_vals,
              stop_ids, stop_valid, mask_bits, mask_on):
        with jax.named_scope("sample"):
            terms = sampling.burst_terms(
                vocab, bias_ids, bias_vals, stop_ids, stop_valid,
                mask_bits, mask_on)

        def body(carry, step_slots):
            tokens, kv, counts, s = carry
            logits, kv, stats = lfm2.apply(
                p, cfg, tokens[:, None], (positions0 + s)[:, None], kv,
                step_slots[:, None], block_tables, context0 + s,
                jnp.ones_like(context0), mode="decode", with_stats=True)
            with jax.named_scope("sample"):
                shaped = sampling.shape_logits(
                    sampling.apply_penalties(
                        logits[:, 0], counts, frequency, presence),
                    terms, (out_len0 + s) < min_tokens, 2)
                keys = sampling.make_rng_keys(0, 0, seed_base + s)
                sampled, lp, top_lp, top_ids = sampling.sample_with_logprobs(
                    shaped, keys, temperature, top_k, top_p, max_top_k=64)
                live = (step_slots >= 0).astype(jnp.int32)
                counts = counts.at[jnp.arange(rows), sampled].add(live)
            return ((sampled, kv, counts, s + 1),
                    (sampled, lp, top_lp, top_ids, stats))

        (_, kv, counts, _), outs = jax.lax.scan(
            body, (tokens0, kv, counts, jnp.int32(0)), slot_mat.T)
        return outs, kv, counts

    f32 = jnp.float32
    text = jax.jit(burst, donate_argnums=(1, 2)).lower(
        params, pool, spec((rows, vocab)), spec((rows,)),
        spec((rows,)), spec((rows, steps)), spec((rows, tables)),
        spec((rows,)), spec((rows,), f32), spec((rows,)), spec((rows,), f32),
        spec((rows,)), spec((rows,), f32), spec((rows,), f32), spec((rows,)),
        spec((rows,)), spec((rows, sampling.MAX_LOGIT_BIAS)),
        spec((rows, sampling.MAX_LOGIT_BIAS), f32),
        spec((rows, sampling.MAX_STOP_IDS)),
        spec((rows, sampling.MAX_STOP_IDS), f32),
        spec((rows, vocab // 8), jnp.uint8),
        spec((rows,), jnp.bool_)).compile().as_text()
    step_loops = [lines for lines in _while_bodies(text).values()
                  if any("sample_with_logprobs" in line for line in lines)
                  and any(" while(" in line for line in lines)]
    assert len(step_loops) == 1
    # the selections: every ``TopK`` of the program reads candidates
    read = re.findall(r'custom-call\(%([\w.\-]+)\), custom_call_target="TopK"',
                      text)
    widths = [int(re.search(
        rf"%{re.escape(name)} = f32\[{rows},(\d+)\]", text).group(1))
        for name in read]
    assert widths and max(widths) <= 64 * sampling.TOP_K_GROUP, widths
    made = {}  # dtype -> the body's own results of rows x vocab elements
    for line in step_loops[0]:
        if " sort(" in line:  # the groups' maxima (512 a row) at the widest
            sorted_dims = re.findall(r"\[\d+,(\d+)\]", line.split(" sort(")[0])
            assert max(int(d) for d in sorted_dims) <= 512, line[:160]
        found = re.match(
            r"\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]+)\]\S* ([\w\-]+)\(",
            line)
        if not found:
            continue
        name, dtype, dims, op = found.groups()
        dims = [int(d) for d in dims.split(",")]
        # a prefetch into VMEM or an alias reads an array, it makes none
        if (np.prod(dims) == rows * vocab
                and op not in ("copy-start", "copy-done", "bitcast",
                               "get-tuple-element")):
            made.setdefault(dtype, []).append(f"{name} {op}")
    assert sorted(made) == ["f32", "s32"], made
    assert len(made["s32"]) == 1, made
    assert [m.split()[1] for m in made["f32"]] in (
        ["fusion"], ["fusion", "copy"]), made
    scatters = [line for line in step_loops[0]
                if re.search(r'op_name="[^"]*/scatter-add"', line)
                and "65536" in line.split(" = ")[1].split(" ")[0]]
    assert len(scatters) == 1 and " s32[" in scatters[0], scatters


@pytest.mark.parametrize("tables", [64, 256])
@pytest.mark.parametrize("window", [None, 4096])
def test_decode_kernel_compiles_at_seven_query_heads_a_kv_head(one_chip,
                                                               window, tables):
    """SmallThinker-21BA3B's decode rows (PR 51): 32 of them, 28 query
    heads over 4 kv heads of 128 (a query group of 7), 64-token pages
    whose four rows are a bf16 tile of their own (the layout LFM2's
    packed rows have), under tables of 64 blocks and of the 256 that
    ``--max-model-len 16384`` brings, with and without the 4,096-token
    window's front bound."""
    B, H, KVH, D = 32, 28, 4, 128

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = _pages(one_chip, KVH, D, False)
    bound = {} if window is None else {"window": window}
    text = _compile_with_kernel(
        lambda q, k, v, bt, cl, layer: pallas_paged_attention(
            q, k, v, bt, cl, layer, scale=D ** -0.5, **bound),
        spec((B, H, D), jnp.bfloat16), pages, pages,
        spec((B, tables)), spec((B,)), spec(()))
    assert re.search(r"bf16\[4,256,64,4,128\]\{4,3,2,1,0:T\(4,128\)\(2,1\)\}",
                     text)


def _smallthinker_21b(tmp_path, monkeypatch, sharding, blocks=5120):
    """(cfg, the weights' shapes, ``spec``, the pool's sides) of
    ``smallthinker-21b-a3b-l8`` as the benchmark serves it, with the
    kernels the chip takes; a pool of 5,120 blocks is the ~330k tokens
    (5.4 GB) the chip holds beside the weights."""
    from production_stack_tpu.models import smallthinker

    cfg = _benchmark_config(tmp_path, "smallthinker-21b-a3b-l8")
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
            cfg.num_experts, cfg.experts_per_token, cfg.sliding_window) == (
        8, 28, 4, 64, 6, 4096)
    monkeypatch.setattr(att, "_use_pallas", lambda: True)
    monkeypatch.setattr(gmm, "_platform", lambda: "tpu")
    att.TRACED_PATHS.clear()

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree_util.tree_map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(
            lambda: smallthinker.init_params(cfg, jax.random.key(0))))
    pages = spec((8, blocks, BLOCK_SIZE, 4, 128), jnp.bfloat16)
    return cfg, params, spec, (pages, pages)


@pytest.mark.parametrize("mode,rows,width,tables", [
    ("decode", 32, 1, 64), ("decode", 32, 1, 256),
    ("prefill", 1, 1024, 16), ("prefill_cached", 1, 1024, 256)])
def test_smallthinker_programs_compile_at_the_configurations_widths(
        one_chip, monkeypatch, tmp_path, mode, rows, width, tables):
    """``smallthinker-21b-a3b-l8`` as the benchmark serves it (PR 51): the
    tree is the 7.93 GB the configuration states; the three forward
    programs compile for the v5e under tables up to the 256 blocks of
    ``--max-model-len 16384``, with the attention kernel of their mode at
    four kv heads of 128 (both kinds of layer: two calls, one with the
    window) and the grouped-matmul kernel with its ``relu`` epilogue (no
    ``ragged_dot``, no logistic left under ``moe_experts``); no expert
    stack and no side of the pool is copied, and the temporaries stay far
    under what the pool leaves free."""
    from production_stack_tpu.models import smallthinker

    cfg, params, spec, pages = _smallthinker_21b(tmp_path, monkeypatch,
                                                 one_chip)
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert abs(weights / 7.93e9 - 1) < 0.005
    last = mode != "decode"
    program = jax.jit(
        lambda p, kv, tok, pos, slot, bt, cl, sl: smallthinker.apply(
            p, cfg, tok, pos, kv, slot, bt, cl, sl, mode=mode,
            last_token=jnp.maximum(sl - 1, 0) if last else None,
            with_stats=True), donate_argnums=(1,)).lower(
        params, pages, spec((rows, width)), spec((rows, width)),
        spec((rows, width)), spec((rows, tables)), spec((rows,)),
        spec((rows,))).compile()
    text = program.as_text()
    assert ("pallas_paged_attention" in text) == (mode == "decode")
    assert ("pallas_prefill_attention" in text) == (mode == "prefill_cached")
    # 32 rows x top 6 = 192 assignments: sorted by expert, not one tile
    _traced_the_grouped_matmul_kernel(one_tile=False)
    assert "pallas_grouped_matmul" in text and "ragged-dot" not in text
    copied = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= bf16\[(8,64,\d{3,4},\d{3,4}|512,\d{3,4},"
                           r"\d{3,4}|8,5120,64,4,128)\]\S* copy(-start)?\(",
                           line)]
    assert not copied, copied
    assert program.memory_analysis().temp_size_in_bytes < (
        0.1e9 if mode == "decode" else 0.6e9)
