"""Weight-only int8 quantization (models/quantize.py): logits closeness
vs the bf16 path, serving e2e with int8 weights, numpy/jnp quantizer
equivalence, and byte accounting (the point: an 8 B model in ~half the
HBM — BASELINE's model class on a 16 GB chip)."""

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import get_model_config
from production_stack_tpu.models.llama import apply, init_params
from production_stack_tpu.models.quantize import (
    quantize_loaded,
    quantize_tree,
)


def _forward(params, cfg, token_ids):
    B, T = token_ids.shape
    nb = 8
    kv = (jnp.zeros((cfg.num_layers, nb, 8, cfg.num_kv_heads, cfg.head_dim),
                    cfg.jnp_dtype),
          jnp.zeros((cfg.num_layers, nb, 8, cfg.num_kv_heads, cfg.head_dim),
                    cfg.jnp_dtype))
    positions = jnp.tile(jnp.arange(T)[None, :], (B, 1))
    slot_mapping = jnp.full((B, T), -1, jnp.int64)
    block_tables = jnp.zeros((B, 4), jnp.int32)
    lens = jnp.full((B,), T, jnp.int32)
    logits, _ = apply(params, cfg, token_ids, positions, kv, slot_mapping,
                      block_tables, lens, lens, mode="prefill")
    return np.asarray(logits, np.float32)


def test_int8_logits_close_to_bf16():
    cfg = get_model_config("tiny-llama")
    params = init_params(cfg, jax.random.key(0))
    qparams = jax.jit(lambda p: quantize_tree(p, "llama"))(params)

    assert qparams["layers"]["wqkv"].dtype == jnp.int8
    # embed / lm_head stay bf16 by default (head/embedding quantization
    # disproportionately hurts output quality for ~no HBM win).
    assert qparams["embed"].dtype == cfg.jnp_dtype
    assert "embed_scale" not in qparams
    assert qparams["layers"]["wqkv_scale"].shape == (
        cfg.num_layers, 1,
        (cfg.num_heads + 2 * cfg.num_kv_heads) * cfg.head_dim)
    q_all = jax.jit(
        lambda p: quantize_tree(p, "llama", quantize_embeddings=True)
    )(params)
    assert q_all["embed"].dtype == jnp.int8

    ids = jnp.asarray([[1, 7, 42, 99, 200, 3, 5, 17]], jnp.int32)
    ref = _forward(params, cfg, ids)
    got = _forward(qparams, cfg, ids)

    # Per-channel int8 keeps the output distribution close: high cosine
    # similarity and small relative error on the final-token logits.
    r, g = ref[0, -1], got[0, -1]
    cos = float(np.dot(r, g) / (np.linalg.norm(r) * np.linalg.norm(g)))
    rel = float(np.linalg.norm(r - g) / np.linalg.norm(r))
    assert cos > 0.99, cos
    assert rel < 0.12, rel


def test_quantize_loaded_matches_quantize_tree():
    cfg = get_model_config("tiny-llama")
    params = init_params(cfg, jax.random.key(1))
    host = jax.tree_util.tree_map(
        lambda x: np.asarray(x, np.float32), params)

    q_dev = jax.jit(
        lambda p: quantize_tree(p, "llama", quantize_embeddings=True)
    )(params)
    q_host = quantize_loaded(host, "llama", quantize_embeddings=True)

    # XLA's fused division can differ from numpy by a ULP, flipping
    # round-to-nearest at exact ties on a tiny fraction of weights —
    # allow |diff| <= 1 on <0.1% of entries, scales must match tightly.
    for dev, hostq in ((q_dev["layers"]["wqkv"], q_host["layers"]["wqkv"]),
                       (q_dev["embed"], q_host["embed"])):
        diff = np.abs(np.asarray(dev, np.int32)
                      - np.asarray(hostq, np.int32))
        assert diff.max() <= 1
        assert (diff != 0).mean() < 1e-3
    np.testing.assert_allclose(
        np.asarray(q_dev["layers"]["wqkv_scale"]),
        q_host["layers"]["wqkv_scale"], rtol=1e-6)


def test_engine_serves_with_int8_and_halves_weight_bytes():
    import threading

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.core import EngineCore
    from production_stack_tpu.engine.sampling import SamplingParams

    def run(quantization):
        core = EngineCore(EngineConfig(
            model="tiny-llama", max_model_len=128, max_num_seqs=2,
            block_size=8, num_blocks=64, max_loras=2,
            quantization=quantization))
        try:
            core.start()
            done = threading.Event()
            toks = []

            def cb(t, f):
                if t is not None:
                    toks.append(int(t))
                if f is not None:
                    done.set()

            core.add_request("q", list(range(1, 12)), SamplingParams(
                max_tokens=6, temperature=0.0, ignore_eos=True), cb)
            assert done.wait(120)
            big_bytes = sum(
                leaf.nbytes for leaf in
                jax.tree_util.tree_leaves(core.params["layers"]))
            return toks, big_bytes, core.params["layers"]["wqkv"].dtype
        finally:
            core.stop()

    toks_bf16, bytes_bf16, dt_bf16 = run(None)
    toks_int8, bytes_int8, dt_int8 = run("int8")
    assert dt_bf16 == jnp.bfloat16
    assert dt_int8 == jnp.int8
    assert len(toks_int8) == 6
    # int8 layer stack (weights + f32 scales) well under the bf16 bytes.
    assert bytes_int8 < 0.75 * bytes_bf16
    # LoRA hot-swap still works on the quantized base.


def test_quantization_validation():
    import pytest

    from production_stack_tpu.engine.config import EngineConfig

    with pytest.raises(ValueError):
        EngineConfig(model="tiny-llama", quantization="fp4")
    with pytest.raises(ValueError):
        from production_stack_tpu.engine.core import EngineCore

        EngineCore(EngineConfig(model="tiny-opt", num_blocks=32,
                                quantization="int8"))


def test_no_bf16_full_weight_leaf_live_after_int8_init():
    """Residual-HBM regression (llama8b headroom): after a quantized
    init, no full-weight bf16 staging buffer — host or device — may stay
    reachable. Runs in a subprocess because jax.live_arrays() is
    process-global (other tests' bf16 engines would false-positive)."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        from production_stack_tpu.engine.config import EngineConfig
        from production_stack_tpu.engine.core import EngineCore

        core = EngineCore(EngineConfig(
            model="tiny-llama", max_model_len=128, max_num_seqs=2,
            block_size=8, num_blocks=64, max_loras=0,
            quantization="int8", quantize_embeddings=True))
        core.start()
        try:
            cfg = core.model_config
            # Smallest full-weight leaf in bf16: the stacked wo stack.
            threshold = (cfg.num_layers * cfg.hidden_size
                         * cfg.num_heads * cfg.head_dim * 2)
            leaves = jax.tree_util.tree_leaves(core.params)
            big_bf16 = [l for l in leaves if l.dtype == jnp.bfloat16
                        and l.nbytes >= threshold]
            assert not big_bf16, [l.shape for l in big_bf16]
            owned = {id(x) for x in leaves}
            owned |= {id(x) for x in jax.tree_util.tree_leaves(core.kv)}
            stray = [x for x in jax.live_arrays()
                     if x.dtype == jnp.bfloat16 and x.nbytes >= threshold
                     and id(x) not in owned]
            assert not stray, [(x.shape, x.nbytes) for x in stray]
        finally:
            core.stop()
        print("NO_BF16_WEIGHT_LEAF_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=540)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "NO_BF16_WEIGHT_LEAF_OK" in out.stdout
