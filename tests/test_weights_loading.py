"""Checkpoint loading: HF weights -> engine pytrees, validated two ways —
leaf-level mapping checks and full logits parity against transformers'
eager reference implementation on the same tiny random checkpoint."""

import threading

import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.models import build_model, get_model_config
from production_stack_tpu.models.llama import fuse_qkv
from production_stack_tpu.models.weights import has_checkpoint, load_checkpoint


@pytest.fixture(scope="module")
def llama_ckpt(tmp_path_factory):
    """Save a tiny random HF Llama checkpoint to disk."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False,
    )
    model = LlamaForCausalLM(cfg)
    model.eval()
    path = tmp_path_factory.mktemp("llama-ckpt")
    model.save_pretrained(path, safe_serialization=True)
    return str(path), model


def test_has_checkpoint(llama_ckpt, tmp_path):
    path, _ = llama_ckpt
    assert has_checkpoint(path)
    assert not has_checkpoint(str(tmp_path))


def test_llama_leaf_mapping(llama_ckpt):
    path, hf_model = llama_ckpt
    cfg = get_model_config(path).replace(dtype="float32")
    params = load_checkpoint(cfg, path)
    sd = hf_model.state_dict()
    np.testing.assert_allclose(
        np.asarray(params["embed"]),
        sd["model.embed_tokens.weight"].numpy(), atol=1e-6)
    # Projections are transposed into x @ W layout; layer leaves stacked;
    # q/k/v joined into the fused leaf, columns grouped by KV head.
    attn = "model.layers.1.self_attn."
    np.testing.assert_allclose(
        np.asarray(params["layers"]["wqkv"][1]),
        fuse_qkv(*(sd[attn + f"{n}_proj.weight"].numpy().T for n in "qkv"),
                 cfg.num_kv_heads), atol=1e-6)
    assert "wq" not in params["layers"]
    np.testing.assert_allclose(
        np.asarray(params["layers"]["w_down"][0]),
        sd["model.layers.0.mlp.down_proj.weight"].numpy().T, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(params["lm_head"]),
        sd["lm_head.weight"].numpy().T, atol=1e-6)


def test_llama_logits_parity_with_transformers(llama_ckpt):
    """Full-model prefill logits must match HF eager attention."""
    import jax.numpy as jnp
    import torch

    path, hf_model = llama_ckpt
    cfg = get_model_config(path).replace(dtype="float32")
    _, apply = build_model(cfg)
    params = load_checkpoint(cfg, path)

    T = 12
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, T))

    with torch.no_grad():
        hf_logits = hf_model(
            torch.asarray(tokens, dtype=torch.long)
        ).logits.numpy()

    bs, NB, maxb = 4, 16, 8
    kv_shape = (cfg.num_layers, NB, bs, cfg.num_kv_heads, cfg.head_dim)
    kv = (jnp.zeros(kv_shape, jnp.float32), jnp.zeros(kv_shape, jnp.float32))
    positions = np.arange(T)[None, :].astype(np.int32)
    slot_mapping = positions.astype(np.int64)
    block_tables = np.arange(maxb)[None, :].astype(np.int32)
    logits, _ = apply(
        params, cfg, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions),
        kv, jnp.asarray(slot_mapping), jnp.asarray(block_tables),
        jnp.asarray([T], jnp.int32), jnp.asarray([T], jnp.int32),
        mode="prefill",
    )
    ours = np.asarray(logits)[:, :T]
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


def test_engine_serves_checkpoint_greedy_matches_hf(llama_ckpt):
    """End-to-end: the engine with loaded weights greedy-decodes the same
    continuation transformers generates."""
    import torch

    path, hf_model = llama_ckpt
    prompt = [3, 14, 15, 92, 65, 35, 89, 79]
    n_new = 8
    with torch.no_grad():
        hf_out = hf_model.generate(
            torch.asarray([prompt], dtype=torch.long),
            max_new_tokens=n_new, do_sample=False,
        )[0][len(prompt):].tolist()

    core = EngineCore(EngineConfig(
        model=path, dtype="float32", max_model_len=128, max_num_seqs=2,
        block_size=8, num_blocks=64, max_loras=0,
    ))
    core.start()
    try:
        done = threading.Event()
        out = []

        def on_token(tok, finish):
            if tok is not None:
                out.append(tok)
            if finish is not None:
                done.set()

        core.add_request(
            "r", prompt,
            SamplingParams(temperature=0.0, max_tokens=n_new,
                           ignore_eos=True),
            on_token,
        )
        assert done.wait(timeout=120)
    finally:
        core.stop()
    assert out == hf_out


def test_opt_logits_parity_with_transformers(tmp_path):
    """OPT (learned positions, LayerNorm, attention biases) must match HF."""
    import jax.numpy as jnp
    import torch
    from transformers import OPTConfig, OPTForCausalLM

    torch.manual_seed(1)
    hf_cfg = OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        do_layer_norm_before=True, word_embed_proj_dim=64,
    )
    hf_model = OPTForCausalLM(hf_cfg)
    hf_model.eval()
    path = str(tmp_path / "opt-ckpt")
    hf_model.save_pretrained(path, safe_serialization=True)

    cfg = get_model_config(path).replace(dtype="float32")
    _, apply = build_model(cfg)
    params = load_checkpoint(cfg, path)

    T = 10
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, T))
    with torch.no_grad():
        hf_logits = hf_model(
            torch.asarray(tokens, dtype=torch.long)
        ).logits.numpy()

    bs, NB, maxb = 4, 16, 8
    kv_shape = (cfg.num_layers, NB, bs, cfg.num_kv_heads, cfg.head_dim)
    kv = (jnp.zeros(kv_shape, jnp.float32), jnp.zeros(kv_shape, jnp.float32))
    positions = np.arange(T)[None, :].astype(np.int32)
    logits, _ = apply(
        params, cfg, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions),
        kv, jnp.asarray(positions.astype(np.int64)),
        jnp.asarray(np.arange(maxb)[None, :].astype(np.int32)),
        jnp.asarray([T], jnp.int32), jnp.asarray([T], jnp.int32),
        mode="prefill",
    )
    np.testing.assert_allclose(
        np.asarray(logits)[:, :T], hf_logits, rtol=2e-4, atol=2e-4)


def test_embeddings_parity_with_transformers(llama_ckpt):
    """/v1/embeddings vectors = mean-pooled post-norm hidden states."""
    import torch

    path, hf_model = llama_ckpt
    prompt = [5, 9, 22, 87, 54, 33]
    with torch.no_grad():
        hidden = hf_model.model(
            torch.asarray([prompt], dtype=torch.long)
        ).last_hidden_state[0].numpy()
    ref = hidden.mean(axis=0)
    ref = ref / np.linalg.norm(ref)

    core = EngineCore(EngineConfig(
        model=path, dtype="float32", max_model_len=128, max_num_seqs=2,
        block_size=8, num_blocks=32, max_loras=0,
    ))
    try:
        ours = np.asarray(core.embed(prompt), np.float32)
    finally:
        core.stop()
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_mixtral_logits_parity_with_transformers(tmp_path):
    """MoE: expert weights, router, and top-k weighting must match HF."""
    import jax.numpy as jnp
    import torch
    from transformers import MixtralConfig, MixtralForCausalLM

    torch.manual_seed(2)
    hf_cfg = MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=128, rope_theta=10000.0,
    )
    hf_model = MixtralForCausalLM(hf_cfg)
    hf_model.eval()
    path = str(tmp_path / "mixtral-ckpt")
    hf_model.save_pretrained(path, safe_serialization=True)

    cfg = get_model_config(path).replace(dtype="float32")
    assert cfg.arch == "mixtral" and cfg.num_experts == 4
    _, apply = build_model(cfg)
    params = load_checkpoint(cfg, path)

    T = 9
    rng = np.random.default_rng(6)
    tokens = rng.integers(0, cfg.vocab_size, size=(1, T))
    with torch.no_grad():
        hf_logits = hf_model(
            torch.asarray(tokens, dtype=torch.long)
        ).logits.numpy()

    bs, NB, maxb = 4, 16, 8
    kv_shape = (cfg.num_layers, NB, bs, cfg.num_kv_heads, cfg.head_dim)
    kv = (jnp.zeros(kv_shape, jnp.float32), jnp.zeros(kv_shape, jnp.float32))
    positions = np.arange(T)[None, :].astype(np.int32)
    logits, _ = apply(
        params, cfg, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions),
        kv, jnp.asarray(positions.astype(np.int64)),
        jnp.asarray(np.arange(maxb)[None, :].astype(np.int32)),
        jnp.asarray([T], jnp.int32), jnp.asarray([T], jnp.int32),
        mode="prefill",
    )
    np.testing.assert_allclose(
        np.asarray(logits)[:, :T], hf_logits, rtol=5e-4, atol=5e-4)


def test_missing_tensor_fails_loudly(tmp_path):
    """A checkpoint missing layers must raise, not serve garbage."""
    import numpy as np_
    from safetensors.numpy import save_file

    cfg = get_model_config("tiny-llama")
    save_file(
        {"model.embed_tokens.weight":
         np_.zeros((cfg.vocab_size, cfg.hidden_size), np_.float32)},
        str(tmp_path / "model.safetensors"),
    )
    with pytest.raises(ValueError, match="missing tensors"):
        load_checkpoint(cfg, str(tmp_path))
