"""The Llama tree's one ``wqkv`` leaf (PERF.md section 6, PR 30).

The reference here is the three-matrix form the leaf replaced: the three
draws of the init recipe (``keys[1..3]``) and a layer that multiplies by
each and reshapes to heads, run through the same ``apply``. The fused leaf
is a re-arrangement of those columns, so every comparison is between the
same numbers contracted over the same ``Hd``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import decoder, llama
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.quantize import quantize_tree
from production_stack_tpu.models.weights import load_checkpoint

BLOCK, NUM_BLOCKS, MAXB = 4, 16, 8
# H == KVH (G = 1) and H = 4 KVH; widths chosen so that no other matrix of
# a layer has a projection's shape (Hd 48, H * D 32, I 80).
HEADS = [(4, 4), (4, 1)]


def _cfg(heads, kv_heads, dtype="float32"):
    return ModelConfig(
        name="fused-qkv-test", arch="llama", vocab_size=64, hidden_size=48,
        num_layers=2, num_heads=heads, num_kv_heads=kv_heads, head_dim=8,
        intermediate_size=80, max_position=64, rope_theta=10000.0,
        dtype=dtype)


def _three_draws(cfg, rng):
    """wq, wk, wv as the init recipe draws them (and as
    chipbench/reference/llama.py redraws them): ten keys from the seed,
    normal / sqrt(fan_in), rounded to the served type."""
    keys = jax.random.split(rng, 10)
    L, Hd, D = cfg.num_layers, cfg.hidden_size, cfg.head_dim

    def draw(key, out):
        w = jax.random.normal(key, (L, Hd, out), jnp.float32)
        return (w / jnp.sqrt(Hd)).astype(cfg.jnp_dtype)

    return (draw(keys[1], cfg.num_heads * D),
            draw(keys[2], cfg.num_kv_heads * D),
            draw(keys[3], cfg.num_kv_heads * D))


def _unfused(params, cfg, rng):
    """The same tree with the three leaves in place of the fused one."""
    layers = {k: v for k, v in params["layers"].items()
              if not k.startswith("wqkv")}
    layers["wq"], layers["wk"], layers["wv"] = _three_draws(cfg, rng)
    return {**params, "layers": layers}


def _quantized_alone(draws):
    """wq, wk, wv each quantized by itself, as quantize_tree does any
    leaf (amax over Hd per output column): {name: int8}, {name: scale}.
    quantize_tree only takes leaves it knows, so they borrow three names."""
    borrowed = ("wo", "w_gate", "w_up")
    q = quantize_tree({"layers": dict(zip(borrowed, draws))},
                      "llama")["layers"]
    names = ("wq", "wk", "wv")
    return ({n: q[b] for n, b in zip(names, borrowed)},
            {n: q[b + "_scale"] for n, b in zip(names, borrowed)})


def _three_matmul_layer(cfg, mode, x, per_layer, kv, layer, batch):
    """The layer as it was with three leaves: one matmul each, reshaped to
    heads; everything after the projections is the served layer's code."""
    p, lora = per_layer
    B, T, _ = x.shape
    H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = llama.rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    q_flat, k_flat, v_flat = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    if lora is not None:
        q_flat = q_flat + llama._lora_delta(
            h, lora["wq_a"], lora["wq_b"], batch.lora_scaling,
            batch.adapter_ids)
        v_flat = v_flat + llama._lora_delta(
            h, lora["wv_a"], lora["wv_b"], batch.lora_scaling,
            batch.adapter_ids)
    q = llama.rope(q_flat.reshape(B, T, H, D), batch.positions,
                   cfg.rope_theta)
    k = llama.rope(k_flat.reshape(B, T, KVH, D), batch.positions,
                   cfg.rope_theta)
    attn, kv = decoder.attend(
        mode, q, k, v_flat.reshape(B, T, KVH, D), kv, layer, batch,
        scale=1.0 / (D ** 0.5))
    x = x + attn.reshape(B, T, H * D) @ p["wo"]
    h = llama.rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)).astype(h.dtype)
    return x + (gate * (h @ p["w_up"])) @ p["w_down"], kv


# The same skeleton, embed and head around the three-matrix layer: what a
# family is (models/registry.py::Family) is what a reference swaps.
_three_matmul_apply = functools.partial(
    decoder.apply,
    dataclasses.replace(llama.FAMILY, layer=_three_matmul_layer))


def _pages(cfg):
    shape = (cfg.num_layers, NUM_BLOCKS, BLOCK, cfg.num_kv_heads,
             cfg.head_dim)
    return jnp.zeros(shape, cfg.jnp_dtype), jnp.zeros(shape, cfg.jnp_dtype)


def _three_modes(params, cfg, adapter_ids=None, apply=llama.apply):
    """Logits of one batch in all three modes: a plain prefill of 8
    tokens a row, a cached prefill of 4 more over those pages, then a
    decode step."""
    B = 3
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, cfg.vocab_size, (B, 13)).astype(np.int32)
    bt = np.zeros((B, MAXB), np.int32)
    bt[:, :4] = 1 + np.arange(B * 4).reshape(B, 4)
    out, kv = {}, _pages(cfg)
    for mode, lo, hi in (("prefill", 0, 8), ("prefill_cached", 8, 12),
                         ("decode", 12, 13)):
        pos = np.tile(np.arange(lo, hi, dtype=np.int32), (B, 1))
        slots = np.take_along_axis(bt, pos // BLOCK, 1) * BLOCK + pos % BLOCK
        out[mode], kv = apply(
            params, cfg, jnp.asarray(tokens[:, lo:hi]), jnp.asarray(pos),
            kv, jnp.asarray(slots, jnp.int32), jnp.asarray(bt),
            jnp.full((B,), hi, jnp.int32), jnp.full((B,), hi - lo, jnp.int32),
            mode=mode, adapter_ids=adapter_ids)
    return {m: np.asarray(v, np.float32) for m, v in out.items()}


@pytest.mark.parametrize("heads,kv_heads", HEADS)
def test_init_leaf_is_the_three_draws_rearranged(heads, kv_heads):
    cfg = _cfg(heads, kv_heads, "bfloat16")
    rng = jax.random.key(3)
    layers = llama.init_params(cfg, rng)["layers"]
    assert not {"wq", "wk", "wv"} & set(layers)
    leaf = np.asarray(layers["wqkv"], np.float32)
    wq, wk, wv = (np.asarray(w, np.float32) for w in _three_draws(cfg, rng))
    D, G = cfg.head_dim, heads // kv_heads
    assert leaf.shape == (2, 48, (heads + 2 * kv_heads) * D)
    # The documented order, column block by column block: for each KV
    # head its G query heads, then its key, then its value.
    col = 0
    for kvh in range(kv_heads):
        for src, lo, n in ((wq, kvh * G * D, G * D), (wk, kvh * D, D),
                           (wv, kvh * D, D)):
            np.testing.assert_array_equal(
                leaf[..., col:col + n], src[..., lo:lo + n])
            col += n
    assert col == leaf.shape[-1]


@pytest.mark.parametrize("mode", ["prefill", "prefill_cached", "decode"])
@pytest.mark.parametrize("heads,kv_heads", HEADS)
def test_apply_matches_three_matmul_forward(heads, kv_heads, mode):
    cfg = _cfg(heads, kv_heads, "bfloat16")
    rng = jax.random.key(11)
    params = llama.init_params(cfg, rng)
    got = _three_modes(params, cfg)[mode]
    want = _three_modes(_unfused(params, cfg, rng), cfg,
                        apply=_three_matmul_apply)[mode]
    # Same columns, same contraction: at most one bf16 step (2 ** -8 of
    # a value) where a backend sums a wider matmul in another order.
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7)
    assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("heads,kv_heads", HEADS)
def test_int8_leaf_is_the_three_quantized_column_for_column(heads, kv_heads):
    cfg = _cfg(heads, kv_heads, "bfloat16")
    rng = jax.random.key(4)
    layers = quantize_tree(llama.init_params(cfg, rng), "llama")["layers"]
    assert layers["wqkv"].dtype == jnp.int8
    for suffix, alone in zip(("", "_scale"),
                             _quantized_alone(_three_draws(cfg, rng))):
        np.testing.assert_array_equal(
            np.asarray(layers["wqkv" + suffix]),
            np.asarray(llama.fuse_qkv(
                alone["wq"], alone["wk"], alone["wv"], kv_heads)))


@pytest.mark.parametrize("heads,kv_heads", HEADS)
def test_int8_apply_matches_three_matmul_forward(heads, kv_heads):
    cfg = _cfg(heads, kv_heads)
    rng = jax.random.key(6)
    params = llama.init_params(cfg, rng)
    got = _three_modes(quantize_tree(params, "llama"), cfg)

    # The three-matrix reference with every matrix dequantized by itself.
    unfused = _unfused(params, cfg, rng)
    ints, scales = _quantized_alone(_three_draws(cfg, rng))
    rest = quantize_tree(params, "llama")["layers"]
    for name in ("wo", "w_gate", "w_up", "w_down"):
        ints[name], scales[name] = rest[name], rest[name + "_scale"]
    deq = {**unfused["layers"],
           **{n: ints[n].astype(jnp.float32) * scales[n] for n in ints}}
    want = _three_modes({**unfused, "layers": deq}, cfg,
                        apply=_three_matmul_apply)
    for mode in want:
        np.testing.assert_allclose(got[mode], want[mode],
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("heads,kv_heads", HEADS)
def test_lora_deltas_land_on_q_and_v_only(heads, kv_heads):
    cfg = _cfg(heads, kv_heads)
    rng = jax.random.key(8)
    params = llama.init_params(cfg, rng, lora_slots=2, lora_rank=4)
    draw = np.random.default_rng(9)
    lora = {k: jnp.asarray(0.3 * draw.standard_normal(v.shape), v.dtype)
            .at[:, 0].set(0) for k, v in params["lora"].items()
            if k != "scaling"}
    lora["scaling"] = jnp.asarray([0.0, 2.0], jnp.float32)
    params = {**params, "lora": lora}
    ids = jnp.asarray([1, 0, 1], jnp.int32)  # the adapter on rows 0 and 2

    got = _three_modes(params, cfg, ids)
    base = _three_modes(params, cfg, jnp.zeros((3,), jnp.int32))
    want = _three_modes(_unfused(params, cfg, rng), cfg, ids,
                        apply=_three_matmul_apply)
    for mode in want:
        # q and v carry the delta exactly as the three-matrix layer adds
        # it, k none: any delta on k's columns would show here.
        np.testing.assert_allclose(got[mode], want[mode],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(got[mode][1], base[mode][1])
        assert np.abs(got[mode][0] - base[mode][0]).max() > 1e-2
        assert np.abs(got[mode][2] - base[mode][2]).max() > 1e-2


@pytest.mark.parametrize("heads,kv_heads", HEADS)
def test_loader_fills_the_fused_leaf_in_order(heads, kv_heads, tmp_path):
    from safetensors.numpy import save_file

    cfg = _cfg(heads, kv_heads)
    L, Hd, D, I = cfg.num_layers, cfg.hidden_size, cfg.head_dim, 80
    draw = np.random.default_rng(12)

    def w(*shape):
        return draw.standard_normal(shape).astype(np.float32)

    tensors = {"model.embed_tokens.weight": w(cfg.vocab_size, Hd),
               "model.norm.weight": w(Hd), "lm_head.weight": w(64, Hd)}
    for i in range(L):
        pre = f"model.layers.{i}."
        tensors.update({
            pre + "input_layernorm.weight": w(Hd),
            pre + "post_attention_layernorm.weight": w(Hd),
            pre + "self_attn.q_proj.weight": w(heads * D, Hd),
            pre + "self_attn.k_proj.weight": w(kv_heads * D, Hd),
            pre + "self_attn.v_proj.weight": w(kv_heads * D, Hd),
            pre + "self_attn.o_proj.weight": w(Hd, heads * D),
            pre + "mlp.gate_proj.weight": w(I, Hd),
            pre + "mlp.up_proj.weight": w(I, Hd),
            pre + "mlp.down_proj.weight": w(Hd, I),
        })
    save_file(tensors, str(tmp_path / "model.safetensors"))

    layers = load_checkpoint(cfg, str(tmp_path))["layers"]
    assert not {"wq", "wk", "wv"} & set(layers)
    G = heads // kv_heads
    leaf = np.asarray(layers["wqkv"])
    for i in range(L):
        q, k, v = (tensors[f"model.layers.{i}.self_attn.{n}_proj.weight"].T
                   for n in "qkv")
        for kvh in range(kv_heads):
            group = leaf[i, :, kvh * (G + 2) * D:(kvh + 1) * (G + 2) * D]
            np.testing.assert_array_equal(
                group[:, :G * D], q[:, kvh * G * D:(kvh + 1) * G * D])
            np.testing.assert_array_equal(
                group[:, G * D:(G + 1) * D], k[:, kvh * D:(kvh + 1) * D])
            np.testing.assert_array_equal(
                group[:, (G + 1) * D:], v[:, kvh * D:(kvh + 1) * D])

    # A checkpoint short of one of the three says which.
    del tensors["model.layers.1.self_attn.v_proj.weight"]
    save_file(tensors, str(tmp_path / "model.safetensors"))
    with pytest.raises(ValueError, match=r"layers\.v_proj\[1\]"):
        load_checkpoint(cfg, str(tmp_path))


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_shards_hold_whole_kv_groups(tp):
    from production_stack_tpu.parallel.mesh import build_mesh
    from production_stack_tpu.parallel.sharding import param_shardings

    if len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} devices")
    cfg = _cfg(8, 4)
    rng = jax.random.key(2)
    params = llama.init_params(cfg, rng)
    mesh = build_mesh(tensor_parallel_size=tp, data_parallel_size=1,
                      devices=jax.devices()[:tp])
    sharding = param_shardings(cfg, mesh, params)["layers"]["wqkv"]
    assert tuple(sharding.spec) == (None, None, "tp")
    leaf = jax.device_put(params["layers"]["wqkv"], sharding)

    wq, wk, wv = _three_draws(cfg, rng)
    D, G, per = cfg.head_dim, 2, cfg.num_kv_heads // tp
    shards = sorted(leaf.addressable_shards, key=lambda s: s.index[2].start)
    assert len(shards) == tp
    for s, shard in enumerate(shards):
        # Shard s is the fused leaf of KV heads [s * per, (s + 1) * per)
        # alone: their query heads, keys and values, nobody else's.
        q_lo, kv_lo = s * per * G * D, s * per * D
        np.testing.assert_array_equal(
            np.asarray(shard.data),
            np.asarray(llama.fuse_qkv(
                wq[..., q_lo:q_lo + per * G * D],
                wk[..., kv_lo:kv_lo + per * D],
                wv[..., kv_lo:kv_lo + per * D], per)))


@pytest.mark.parametrize("heads,kv_heads", HEADS)
def test_layer_body_has_one_projection_dot_from_the_fused_leaf(heads,
                                                               kv_heads):
    """The property itself, without a chip: in the layer scan's body one
    ``dot_general`` takes ``h`` to all of q, k and v, its weight operand
    is the scan's slice of ``wqkv``, and no dot has a single projection's
    shape. (That the TPU compiler then reads that slice in place is held
    at real widths by tests/test_chip_compile.py.)"""
    cfg = _cfg(heads, kv_heads)
    params = llama.init_params(cfg, jax.random.key(0))
    B = 3
    kv = _pages(cfg)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    jaxpr = jax.make_jaxpr(
        lambda p, kv: llama.apply(
            p, cfg, i32(B, 1), i32(B, 1), kv, i32(B, 1), i32(B, MAXB),
            jnp.ones((B,), jnp.int32), jnp.ones((B,), jnp.int32),
            mode="decode"))(params, kv)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    scan = scans[0]
    body = scan.params["jaxpr"].jaxpr
    # The scan's per-layer operands, in the flattening order of
    # params["layers"]: which body input is the wqkv slice.
    xs_names = sorted(params["layers"])
    n_xs = len(xs_names)
    xs_vars = body.invars[len(body.invars) - n_xs:]
    wqkv_var = xs_vars[xs_names.index("wqkv")]
    Hd, D = cfg.hidden_size, cfg.head_dim
    fused_shape = (Hd, (heads + 2 * kv_heads) * D)
    assert tuple(wqkv_var.aval.shape) == fused_shape

    def dots(jaxpr_):
        for eqn in jaxpr_.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    rhs_shapes = [tuple(e.invars[1].aval.shape) for e in dots(body)]
    assert rhs_shapes.count(fused_shape) == 1
    assert (Hd, heads * D) not in rhs_shapes
    assert (Hd, kv_heads * D) not in rhs_shapes
    fused = [e for e in dots(body)
             if tuple(e.invars[1].aval.shape) == fused_shape]
    assert fused[0].invars[1] is wqkv_var
