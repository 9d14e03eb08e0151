"""Mixtral-style sparse-MoE decoder (BASELINE config 5: Mixtral-8x7B).

Llama's attention half (models/llama.py::attention_half, the fused
``wqkv`` leaf included) + a top-k routed expert MLP: the expert layer of
models/moe.py with every expert held here (assignments sorted by expert,
one grouped matmul per projection, each token computing exactly its
top-k experts: no capacity, no dropped token, and not all E experts for
every token as the dense einsum this replaced did). No LoRA slots, no
pipeline stages, no int8 weights yet: the record at the foot of the file
says so.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from production_stack_tpu.models import decoder, llama, moe
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.registry import Family
from production_stack_tpu.models.weights import _to_dtype, report_incomplete


def init_params(cfg: ModelConfig, rng: jax.Array, **_unused) -> Dict:
    dtype = cfg.jnp_dtype
    H, KVH, D, Hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.hidden_size
    I, L, V, E = cfg.intermediate_size, cfg.num_layers, cfg.vocab_size, cfg.num_experts
    keys = jax.random.split(rng, 12)

    def stack(key, shape, fan_in):
        return (
            jax.random.normal(key, (L,) + shape, jnp.float32) / jnp.sqrt(fan_in)
        ).astype(dtype)

    return {
        "embed": (0.02 * jax.random.normal(keys[0], (V, Hd), jnp.float32)).astype(dtype),
        "layers": {
            "attn_norm": jnp.ones((L, Hd), dtype),
            # Drawn as three matrices, served as one leaf (llama.fuse_qkv).
            "wqkv": llama.fuse_qkv(
                stack(keys[1], (Hd, H * D), Hd),
                stack(keys[2], (Hd, KVH * D), Hd),
                stack(keys[3], (Hd, KVH * D), Hd), KVH),
            "wo": stack(keys[4], (H * D, Hd), H * D),
            "mlp_norm": jnp.ones((L, Hd), dtype),
            "router": stack(keys[5], (Hd, E), Hd),
            "w_gate": stack(keys[6], (E, Hd, I), Hd),
            "w_up": stack(keys[7], (E, Hd, I), Hd),
            "w_down": stack(keys[8], (E, I, Hd), I),
        },
        "final_norm": jnp.ones((Hd,), dtype),
        "lm_head": (
            jax.random.normal(keys[9], (Hd, V), jnp.float32) / jnp.sqrt(Hd)
        ).astype(dtype),
    }


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _layer(cfg: ModelConfig, mode: str, x, per_layer, kv, layer, batch):
    """``p`` holds the layer's slice of every leaf but the experts', which
    are the whole stacks ``[L, E, ...]`` (``Family.whole_leaves``): the
    expert layer hands them to its grouped matmuls with ``layer``."""
    p, _no_lora = per_layer
    x, kv = llama.attention_half(cfg, mode, x, p, None, kv, layer, batch)
    with jax.named_scope("mlp"):
        h = llama.rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        routed, _stats = moe.expert_layer(
            h, p, k=cfg.experts_per_token, at=layer)
        x = x + routed
    return x, kv


def load_checkpoint(cfg: ModelConfig, path: str) -> Dict:
    """An HF Mixtral checkpoint is Llama's with a router and E experts
    where the MLP was: ``block_sparse_moe.experts.<e>.w1 / w3 / w2``
    become ``w_gate`` / ``w_up`` / ``w_down`` ``[L, E, ...]``."""
    L, E = cfg.num_layers, cfg.num_experts
    experts: Dict[str, List] = {
        k: [[None] * E for _ in range(L)]
        for k in ("w_gate", "w_up", "w_down")
    }
    expert_map = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}

    def expert_leaf(i: int, leaf: str, arr) -> bool:
        if not leaf.startswith("block_sparse_moe.experts."):
            return False
        parts = leaf.split(".")
        e, w = int(parts[2]), expert_map.get(parts[3])
        if w is None or e >= E:
            return False
        experts[w][i][e] = _to_dtype(arr.T, cfg.jnp_dtype)
        return True

    params = llama.load_checkpoint(
        cfg, path,
        mlp_leaves={"block_sparse_moe.gate.weight": ("router", True)},
        other_leaf=expert_leaf, head_required=True)
    report_incomplete(path, [
        f"experts.{k}[{i}][{e}]" for k, le in experts.items()
        for i, row in enumerate(le) for e, leaf in enumerate(row)
        if leaf is None], [])
    for k, le in experts.items():
        params["layers"][k] = jnp.stack(
            [jnp.stack(row) for row in le])  # [L, E, ...]
    return params


FAMILY = Family(
    model_types=("mixtral",),
    init_params=init_params,
    embed=llama.FAMILY.embed,  # no lora leaf in this tree: x alone
    layer=_layer,
    head=llama.project_out,
    load=load_checkpoint,
    whole_leaves=EXPERT_LEAVES,
    specs={
        **llama.ATTN_SPECS,
        ("layers", "router"): P(None, None, None),
        # Experts shard across the tp axis (expert parallelism on the
        # same mesh).
        ("layers", "w_gate"): P(None, "tp", None, None),
        ("layers", "w_up"): P(None, "tp", None, None),
        ("layers", "w_down"): P(None, "tp", None, None),
    },
)

apply = functools.partial(decoder.apply, FAMILY)
