"""Checkpoint loading: HuggingFace safetensors/torch weights -> the
engine's parameter pytrees.

The reference stack mounts HF weights into PVCs and lets vLLM load them
(``helm/values.yaml`` pvcStorage + modelURL); here the engine loads them
natively. Layer leaves are stacked on a leading axis (the models run one
``lax.scan`` over layers), and projection matrices are transposed from
HF's ``[out, in]`` to our ``x @ W`` ``[in, out]`` layout. The llama
loader joins each layer's ``q_proj``/``k_proj``/``v_proj`` on the host
into the one ``wqkv`` leaf the model reads (models/llama.py::fuse_qkv),
so the three never sit beside it on the device.

Entry point: :func:`load_checkpoint` — returns a params pytree matching
``init_params`` of the target architecture, or raises with the list of
unmapped tensors so partial/foreign checkpoints fail loudly instead of
serving garbage.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.llama import fuse_qkv
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)


def _iter_checkpoint_tensors(path: str):
    """Yield (name, np.ndarray) from all safetensors / torch shards."""
    st_files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    if st_files:
        from safetensors import safe_open

        for f in st_files:
            with safe_open(f, framework="np") as sf:
                for name in sf.keys():
                    yield name, sf.get_tensor(name)
        return
    bin_files = sorted(glob.glob(os.path.join(path, "pytorch_model*.bin")))
    if not bin_files:
        raise FileNotFoundError(
            f"no *.safetensors or pytorch_model*.bin under {path}")
    import torch

    for f in bin_files:
        state = torch.load(f, map_location="cpu", weights_only=True)
        for name, tensor in state.items():
            yield name, tensor.to(torch.float32).numpy()


def _to_dtype(arr: np.ndarray, dtype) -> jnp.ndarray:
    return jnp.asarray(arr).astype(dtype)


# --------------------------------------------------------------------- #
# Llama family (llama / mistral)
# --------------------------------------------------------------------- #

def _load_llama(cfg: ModelConfig, path: str) -> Dict:
    L = cfg.num_layers
    dtype = cfg.jnp_dtype
    per_layer: Dict[str, List] = {
        k: [None] * L for k in (
            "attn_norm", "wqkv", "wo",
            "mlp_norm", "w_gate", "w_up", "w_down",
        )
    }
    # q/k/v of a layer wait here, on the host, until all three are read.
    qkv_parts: List[Dict[str, np.ndarray]] = [{} for _ in range(L)]
    top: Dict[str, jnp.ndarray] = {}
    unmapped = []

    layer_map = {
        "input_layernorm.weight": ("attn_norm", False),
        "self_attn.q_proj.weight": ("q", True),
        "self_attn.k_proj.weight": ("k", True),
        "self_attn.v_proj.weight": ("v", True),
        "self_attn.o_proj.weight": ("wo", True),
        "post_attention_layernorm.weight": ("mlp_norm", False),
        "mlp.gate_proj.weight": ("w_gate", True),
        "mlp.up_proj.weight": ("w_up", True),
        "mlp.down_proj.weight": ("w_down", True),
    }

    for name, arr in _iter_checkpoint_tensors(path):
        if name in ("model.embed_tokens.weight",):
            top["embed"] = _to_dtype(arr, dtype)
        elif name in ("model.norm.weight",):
            top["final_norm"] = _to_dtype(arr, dtype)
        elif name == "lm_head.weight":
            top["lm_head"] = _to_dtype(arr.T, dtype)
        elif name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_str, leaf = rest.split(".", 1)
            i = int(idx_str)
            entry = layer_map.get(leaf)
            if entry is None or i >= L:
                unmapped.append(name)
                continue
            key, transpose = entry
            if transpose:
                arr = arr.T
            if key in ("q", "k", "v"):
                parts = qkv_parts[i]
                parts[key] = arr
                if len(parts) == 3:
                    per_layer["wqkv"][i] = _to_dtype(
                        fuse_qkv(parts.pop("q"), parts.pop("k"),
                                 parts.pop("v"), cfg.num_kv_heads), dtype)
                continue
            per_layer[key][i] = _to_dtype(arr, dtype)
        elif name.endswith("rotary_emb.inv_freq"):
            continue  # computed, not a parameter
        else:
            unmapped.append(name)

    missing = [
        f"layers.{k}[{i}]" for k, v in per_layer.items() if k != "wqkv"
        for i, leaf in enumerate(v) if leaf is None
    ] + [
        f"layers.{k}_proj[{i}]" for i, parts in enumerate(qkv_parts)
        if per_layer["wqkv"][i] is None for k in "qkv" if k not in parts
    ]
    for req_key in ("embed", "final_norm"):
        if req_key not in top:
            missing.append(req_key)
    if missing:
        raise ValueError(
            f"checkpoint at {path} is missing tensors: {missing[:8]}"
            + (f" (+{len(missing) - 8} more)" if len(missing) > 8 else ""))
    if unmapped:
        logger.warning("checkpoint: %d unmapped tensors (e.g. %s)",
                       len(unmapped), unmapped[:3])

    params: Dict = {
        "embed": top["embed"],
        "final_norm": top["final_norm"],
        "layers": {k: jnp.stack(v) for k, v in per_layer.items()},
    }
    if cfg.tie_word_embeddings or "lm_head" not in top:
        pass  # apply() falls back to embed.T
    else:
        params["lm_head"] = top["lm_head"]
    return params


# --------------------------------------------------------------------- #
# OPT
# --------------------------------------------------------------------- #

def _load_opt(cfg: ModelConfig, path: str) -> Dict:
    L = cfg.num_layers
    dtype = cfg.jnp_dtype
    keys = ("ln1_w", "ln1_b", "wq", "wq_b", "wk", "wk_b", "wv", "wv_b",
            "wo", "wo_b", "ln2_w", "ln2_b", "fc1", "fc1_b", "fc2", "fc2_b")
    per_layer: Dict[str, List] = {k: [None] * L for k in keys}
    top: Dict[str, jnp.ndarray] = {}
    unmapped = []

    layer_map = {
        "self_attn_layer_norm.weight": ("ln1_w", False),
        "self_attn_layer_norm.bias": ("ln1_b", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.q_proj.bias": ("wq_b", False),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.k_proj.bias": ("wk_b", False),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.v_proj.bias": ("wv_b", False),
        "self_attn.out_proj.weight": ("wo", True),
        "self_attn.out_proj.bias": ("wo_b", False),
        "final_layer_norm.weight": ("ln2_w", False),
        "final_layer_norm.bias": ("ln2_b", False),
        "fc1.weight": ("fc1", True),
        "fc1.bias": ("fc1_b", False),
        "fc2.weight": ("fc2", True),
        "fc2.bias": ("fc2_b", False),
    }

    prefix = "model.decoder."
    for name, arr in _iter_checkpoint_tensors(path):
        short = name[len(prefix):] if name.startswith(prefix) else name
        if short == "embed_tokens.weight":
            top["embed"] = _to_dtype(arr, dtype)
        elif short == "embed_positions.weight":
            top["pos_embed"] = _to_dtype(arr, dtype)
        elif short in ("final_layer_norm.weight",):
            top["final_ln_w"] = _to_dtype(arr, dtype)
        elif short in ("final_layer_norm.bias",):
            top["final_ln_b"] = _to_dtype(arr, dtype)
        elif short == "lm_head.weight" or name == "lm_head.weight":
            continue  # OPT ties lm_head to embeddings
        elif short.startswith("layers."):
            rest = short[len("layers."):]
            idx_str, leaf = rest.split(".", 1)
            i = int(idx_str)
            entry = layer_map.get(leaf)
            if entry is None or i >= L:
                unmapped.append(name)
                continue
            key, transpose = entry
            per_layer[key][i] = _to_dtype(
                arr.T if transpose else arr, dtype)
        else:
            unmapped.append(name)

    missing = [
        f"layers.{k}[{i}]" for k, v in per_layer.items()
        for i, leaf in enumerate(v) if leaf is None
    ]
    for req_key in ("embed", "pos_embed", "final_ln_w", "final_ln_b"):
        if req_key not in top:
            missing.append(req_key)
    if missing:
        raise ValueError(
            f"checkpoint at {path} is missing tensors: {missing[:8]}"
            + (f" (+{len(missing) - 8} more)" if len(missing) > 8 else ""))
    if unmapped:
        logger.warning("checkpoint: %d unmapped tensors (e.g. %s)",
                       len(unmapped), unmapped[:3])

    return {
        "embed": top["embed"],
        "pos_embed": top["pos_embed"],
        "final_ln_w": top["final_ln_w"],
        "final_ln_b": top["final_ln_b"],
        "layers": {k: jnp.stack(v) for k, v in per_layer.items()},
    }


# --------------------------------------------------------------------- #
# Mixtral (MoE)
# --------------------------------------------------------------------- #

def _load_mixtral(cfg: ModelConfig, path: str) -> Dict:
    L, E = cfg.num_layers, cfg.num_experts
    dtype = cfg.jnp_dtype
    per_layer: Dict[str, List] = {
        k: [None] * L for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                "mlp_norm", "router")
    }
    experts: Dict[str, List] = {
        k: [[None] * E for _ in range(L)]
        for k in ("w_gate", "w_up", "w_down")
    }
    top: Dict[str, jnp.ndarray] = {}
    unmapped = []

    layer_map = {
        "input_layernorm.weight": ("attn_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "post_attention_layernorm.weight": ("mlp_norm", False),
        "block_sparse_moe.gate.weight": ("router", True),
    }
    expert_map = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}

    for name, arr in _iter_checkpoint_tensors(path):
        if name == "model.embed_tokens.weight":
            top["embed"] = _to_dtype(arr, dtype)
        elif name == "model.norm.weight":
            top["final_norm"] = _to_dtype(arr, dtype)
        elif name == "lm_head.weight":
            top["lm_head"] = _to_dtype(arr.T, dtype)
        elif name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_str, leaf = rest.split(".", 1)
            i = int(idx_str)
            if leaf.startswith("block_sparse_moe.experts."):
                parts = leaf.split(".")
                e = int(parts[2])
                w = expert_map.get(parts[3])
                if w is None or i >= L or e >= E:
                    unmapped.append(name)
                    continue
                experts[w][i][e] = _to_dtype(arr.T, dtype)
                continue
            entry = layer_map.get(leaf)
            if entry is None or i >= L:
                unmapped.append(name)
                continue
            key, transpose = entry
            per_layer[key][i] = _to_dtype(
                arr.T if transpose else arr, dtype)
        else:
            unmapped.append(name)

    missing = [
        f"layers.{k}[{i}]" for k, v in per_layer.items()
        for i, leaf in enumerate(v) if leaf is None
    ] + [
        f"experts.{k}[{i}][{e}]" for k, le in experts.items()
        for i, row in enumerate(le) for e, leaf in enumerate(row)
        if leaf is None
    ]
    for req_key in ("embed", "final_norm", "lm_head"):
        if req_key not in top:
            missing.append(req_key)
    if missing:
        raise ValueError(
            f"checkpoint at {path} is missing tensors: {missing[:8]}"
            + (f" (+{len(missing) - 8} more)" if len(missing) > 8 else ""))
    if unmapped:
        logger.warning("checkpoint: %d unmapped tensors (e.g. %s)",
                       len(unmapped), unmapped[:3])

    layers = {k: jnp.stack(v) for k, v in per_layer.items()}
    for k, le in experts.items():
        layers[k] = jnp.stack([jnp.stack(row) for row in le])  # [L, E, ...]
    params = {
        "embed": top["embed"],
        "final_norm": top["final_norm"],
        "layers": layers,
    }
    if "lm_head" in top:
        params["lm_head"] = top["lm_head"]
    return params


def load_checkpoint(cfg: ModelConfig, path: str) -> Dict:
    """Load HF weights at ``path`` into the arch's parameter pytree."""
    loader = {"llama": _load_llama, "opt": _load_opt,
              "mixtral": _load_mixtral}[cfg.arch]
    logger.info("Loading %s checkpoint from %s", cfg.arch, path)
    return loader(cfg, path)


def load_whisper_checkpoint(cfg, path: str) -> Dict:
    """HF WhisperForConditionalGeneration safetensors -> the param tree of
    :mod:`production_stack_tpu.models.whisper` (reference serves Whisper via
    vLLM images; ``src/vllm_router/services/request_service/request.py:513-689``).

    torch Linear weights are [out, in] and our layout is ``x @ W`` =
    [in, out], so every projection transposes; conv1d weights go
    [out, in, k] -> [k, in, out] (WIO); k_proj carries no bias in Whisper.
    """
    dt = jnp.dtype(cfg.dtype)
    sd = {name: arr for name, arr in _iter_checkpoint_tensors(path)}

    def t(name):  # [out, in] -> [in, out]
        return _to_dtype(np.ascontiguousarray(sd[name].T), dt)

    def raw(name):
        return _to_dtype(sd[name], dt)

    def conv(name):  # [out, in, k] -> [k, in, out]
        return _to_dtype(
            np.ascontiguousarray(sd[name].transpose(2, 1, 0)), dt)

    def block(prefix: str, cross: bool) -> Dict:
        p = {
            "ln1_g": raw(f"{prefix}.self_attn_layer_norm.weight"),
            "ln1_b": raw(f"{prefix}.self_attn_layer_norm.bias"),
            "q": t(f"{prefix}.self_attn.q_proj.weight"),
            "q_b": raw(f"{prefix}.self_attn.q_proj.bias"),
            "k": t(f"{prefix}.self_attn.k_proj.weight"),
            "v": t(f"{prefix}.self_attn.v_proj.weight"),
            "v_b": raw(f"{prefix}.self_attn.v_proj.bias"),
            "o": t(f"{prefix}.self_attn.out_proj.weight"),
            "o_b": raw(f"{prefix}.self_attn.out_proj.bias"),
            "ln2_g": raw(f"{prefix}.final_layer_norm.weight"),
            "ln2_b": raw(f"{prefix}.final_layer_norm.bias"),
            "fc1": t(f"{prefix}.fc1.weight"),
            "fc1_b": raw(f"{prefix}.fc1.bias"),
            "fc2": t(f"{prefix}.fc2.weight"),
            "fc2_b": raw(f"{prefix}.fc2.bias"),
        }
        if cross:
            p.update({
                "lnx_g": raw(f"{prefix}.encoder_attn_layer_norm.weight"),
                "lnx_b": raw(f"{prefix}.encoder_attn_layer_norm.bias"),
                "xq": t(f"{prefix}.encoder_attn.q_proj.weight"),
                "xq_b": raw(f"{prefix}.encoder_attn.q_proj.bias"),
                "xk": t(f"{prefix}.encoder_attn.k_proj.weight"),
                "xv": t(f"{prefix}.encoder_attn.v_proj.weight"),
                "xv_b": raw(f"{prefix}.encoder_attn.v_proj.bias"),
                "xo": t(f"{prefix}.encoder_attn.out_proj.weight"),
                "xo_b": raw(f"{prefix}.encoder_attn.out_proj.bias"),
            })
        return p

    logger.info("Loading whisper checkpoint from %s", path)
    return {
        "conv1": conv("model.encoder.conv1.weight"),
        "conv1_b": raw("model.encoder.conv1.bias"),
        "conv2": conv("model.encoder.conv2.weight"),
        "conv2_b": raw("model.encoder.conv2.bias"),
        "enc_pos": raw("model.encoder.embed_positions.weight"),
        "enc_blocks": [
            block(f"model.encoder.layers.{i}", cross=False)
            for i in range(cfg.encoder_layers)
        ],
        "enc_ln_g": raw("model.encoder.layer_norm.weight"),
        "enc_ln_b": raw("model.encoder.layer_norm.bias"),
        "tok_emb": raw("model.decoder.embed_tokens.weight"),
        "dec_pos": raw("model.decoder.embed_positions.weight"),
        "dec_blocks": [
            block(f"model.decoder.layers.{i}", cross=True)
            for i in range(cfg.decoder_layers)
        ],
        "dec_ln_g": raw("model.decoder.layer_norm.weight"),
        "dec_ln_b": raw("model.decoder.layer_norm.bias"),
    }


def has_checkpoint(path: str) -> bool:
    return os.path.isdir(path) and (
        bool(glob.glob(os.path.join(path, "*.safetensors")))
        or bool(glob.glob(os.path.join(path, "pytorch_model*.bin")))
    )
