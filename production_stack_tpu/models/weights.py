"""Checkpoint loading: HuggingFace safetensors/torch weights -> the
engine's parameter pytrees.

The reference stack mounts HF weights into PVCs and lets vLLM load them
(``helm/values.yaml`` pvcStorage + modelURL); here the engine loads them
natively. This module holds the file formats; which tensor becomes which
leaf is each family's own loader (models/registry.py::Family.load), beside
the ``init_params`` whose tree it fills. Layer leaves are stacked on a
leading axis (the models run one ``lax.scan`` over layers), and projection
matrices are transposed from HF's ``[out, in]`` to our ``x @ W``
``[in, out]`` layout.

Entry point: :func:`load_checkpoint` — returns a params pytree matching
``init_params`` of the target architecture, or raises with the list of
unmapped tensors so partial/foreign checkpoints fail loudly instead of
serving garbage.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.registry import get_family
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


def report_incomplete(path: str, missing: List[str],
                      unmapped: List[str]) -> None:
    """Every family's loader ends here: a checkpoint that lacks a tensor
    of the tree fails loudly, one that carries more is named and served."""
    if missing:
        raise ValueError(
            f"checkpoint at {path} is missing tensors: {missing[:8]}"
            + (f" (+{len(missing) - 8} more)" if len(missing) > 8 else ""))
    if unmapped:
        logger.warning("checkpoint: %d unmapped tensors (e.g. %s)",
                       len(unmapped), unmapped[:3])


def load_checkpoint(cfg: ModelConfig, path: str) -> Dict:
    """Load HF weights at ``path`` into the arch's parameter pytree."""
    load = get_family(cfg.arch).load
    if load is None:
        raise NotImplementedError(
            f"no checkpoint loader for the {cfg.arch} family yet: a "
            "directory with config.json alone is served with random "
            "weights from --seed")
    logger.info("Loading %s checkpoint from %s", cfg.arch, path)
    return load(cfg, path)


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
