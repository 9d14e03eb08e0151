"""Sharding rules: where every tensor lives on the mesh.

Megatron-style tensor parallelism expressed as NamedSharding specs — XLA
GSPMD inserts the all-reduces over ICI (this replaces the NCCL collectives
inside the reference's vLLM engines):

- attention qkv projections: column-parallel on the head dimension
  (Llama's one ``wqkv`` leaf has its columns grouped by KV head,
  models/llama.py::fuse_qkv, so a shard of its last axis holds whole
  groups: a KV head with its query heads, like the KV pages' shard);
  ``wo``: row-parallel (all-reduce after).
- MLP up/gate: column-parallel on intermediate; down: row-parallel.
- MoE experts: sharded on the expert axis (``ep`` == ``tp`` axis here).
- KV pages: sharded on the kv-head axis, so paged attention is fully local
  to each chip (queries for a chip's heads only touch that chip's pages).
- embeddings/lm_head: vocab-sharded lm_head, replicated input embedding.
- LoRA slot tensors follow their base projections.

When a dimension does not divide the tp size the leaf falls back to
replicated (correct, just not distributed) — this keeps tiny test models
runnable on any mesh.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.models.config import ModelConfig

# Per-arch leaf -> PartitionSpec templates. Leading axis of "layers" leaves is
# the stacked layer axis (never sharded). Axis name "tp" is substituted.
_LLAMA_SPECS = {
    ("embed",): P(None, None),
    ("final_norm",): P(None),
    ("lm_head",): P(None, "tp"),
    ("layers", "attn_norm"): P(None, None),
    ("layers", "mlp_norm"): P(None, None),
    ("layers", "wqkv"): P(None, None, "tp"),
    ("layers", "wo"): P(None, "tp", None),
    ("layers", "w_gate"): P(None, None, "tp"),
    ("layers", "w_up"): P(None, None, "tp"),
    ("layers", "w_down"): P(None, "tp", None),
    ("lora", "wq_a"): P(None, None, None, None),
    ("lora", "wq_b"): P(None, None, None, "tp"),
    ("lora", "wv_a"): P(None, None, None, None),
    ("lora", "wv_b"): P(None, None, None, "tp"),
    ("lora", "scaling"): P(None),
}

_OPT_SPECS = {
    ("embed",): P(None, None),
    ("pos_embed",): P(None, None),
    ("final_ln_w",): P(None),
    ("final_ln_b",): P(None),
    ("layers", "ln1_w"): P(None, None),
    ("layers", "ln1_b"): P(None, None),
    ("layers", "ln2_w"): P(None, None),
    ("layers", "ln2_b"): P(None, None),
    ("layers", "wq"): P(None, None, "tp"),
    ("layers", "wq_b"): P(None, "tp"),
    ("layers", "wk"): P(None, None, "tp"),
    ("layers", "wk_b"): P(None, "tp"),
    ("layers", "wv"): P(None, None, "tp"),
    ("layers", "wv_b"): P(None, "tp"),
    ("layers", "wo"): P(None, "tp", None),
    ("layers", "wo_b"): P(None, None),
    ("layers", "fc1"): P(None, None, "tp"),
    ("layers", "fc1_b"): P(None, "tp"),
    ("layers", "fc2"): P(None, "tp", None),
    ("layers", "fc2_b"): P(None, None),
}

_MIXTRAL_SPECS = {
    ("embed",): P(None, None),
    ("final_norm",): P(None),
    ("lm_head",): P(None, "tp"),
    ("layers", "attn_norm"): P(None, None),
    ("layers", "mlp_norm"): P(None, None),
    ("layers", "wq"): P(None, None, "tp"),
    ("layers", "wk"): P(None, None, "tp"),
    ("layers", "wv"): P(None, None, "tp"),
    ("layers", "wo"): P(None, "tp", None),
    ("layers", "router"): P(None, None, None),
    # Experts shard across the tp axis (expert parallelism on the same mesh).
    ("layers", "w_gate"): P(None, "tp", None, None),
    ("layers", "w_up"): P(None, "tp", None, None),
    ("layers", "w_down"): P(None, "tp", None, None),
}


def _specs_for(arch: str) -> Dict:
    return {
        "llama": _LLAMA_SPECS, "opt": _OPT_SPECS, "mixtral": _MIXTRAL_SPECS
    }[arch]


def _divisible(shape, spec: P, mesh: Mesh) -> bool:
    for dim, axis in zip(shape, spec):
        if axis is None:
            continue
        size = mesh.shape[axis] if isinstance(axis, str) else 1
        if dim % size != 0:
            return False
    return True


def _with_pp(key, spec: P, leaf_shape, cfg: ModelConfig, mesh: Mesh) -> P:
    """Pipeline parallelism: layer-stacked leaves additionally shard their
    leading (layer) axis over the ``pp`` mesh axis, so each stage holds only
    its own layers' weights (the memory point of PP)."""
    pp = mesh.shape.get("pp", 1)
    if (
        pp > 1
        and key[0] in ("layers", "lora")
        and len(leaf_shape) == len(spec)
        and len(leaf_shape) >= 2  # excludes ("lora","scaling"): [S] per-slot
        and spec[0] is None
        and leaf_shape[0] == cfg.num_layers
        and cfg.num_layers % pp == 0
    ):
        return P(*(("pp",) + tuple(spec)[1:]))
    return spec


def param_shardings(
    cfg: ModelConfig, mesh: Mesh, params_shape: Any
) -> Any:
    """NamedShardings matching a params pytree's structure.

    ``params_shape`` may be the params themselves or their ShapeDtypeStructs.
    """
    specs = _specs_for(cfg.arch)
    replicated = NamedSharding(mesh, P())

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    out = []
    for path, leaf in flat:
        key = tuple(
            p.key if hasattr(p, "key") else p.idx for p in path
        )
        if key and isinstance(key[-1], str) and key[-1].endswith("_scale"):
            # int8 quantization scales (models/quantize.py) keep their
            # base weight's ndim with singleton reduced dims, so the base
            # spec applies; _divisible falls back to replicated when the
            # sharded dim collapsed to 1 (scales are tiny either way).
            key = key[:-1] + (key[-1][: -len("_scale")],)
        spec = specs.get(key)
        if spec is not None:
            spec = _with_pp(key, spec, leaf.shape, cfg, mesh)
        if spec is not None and _divisible(leaf.shape, spec, mesh):
            out.append(NamedSharding(mesh, spec))
        else:
            out.append(replicated)
    return jax.tree_util.tree_unflatten(treedef, out)


def kv_pages_sharding(cfg: ModelConfig, mesh: Mesh) -> NamedSharding:
    """KV pages [L, NB, bs, KVH, D]: shard the kv-head axis on tp, and the
    layer axis on pp (each pipeline stage's HBM holds only its own layers'
    pages)."""
    tp = mesh.shape.get("tp", 1)
    pp = mesh.shape.get("pp", 1)
    layer_axis = "pp" if pp > 1 and cfg.num_layers % pp == 0 else None
    if cfg.num_kv_heads % tp == 0 and tp > 1:
        return NamedSharding(mesh, P(layer_axis, None, None, "tp", None))
    if layer_axis:
        return NamedSharding(mesh, P(layer_axis, None, None, None, None))
    return NamedSharding(mesh, P())


def kv_block_sharding(cfg: ModelConfig, mesh: Mesh) -> NamedSharding:
    """ONE block [L, bs, KVH, D] — the pool spec minus the NB axis.
    Per-host offload staging slices blocks out of the pool and later
    reassembles them from locally-staged shards
    (``make_array_from_callback``); the spec must mirror
    :func:`kv_pages_sharding` exactly or the reassembled block would
    re-shard through a collective."""
    tp = mesh.shape.get("tp", 1)
    pp = mesh.shape.get("pp", 1)
    layer_axis = "pp" if pp > 1 and cfg.num_layers % pp == 0 else None
    if cfg.num_kv_heads % tp == 0 and tp > 1:
        return NamedSharding(mesh, P(layer_axis, None, "tp", None))
    if layer_axis:
        return NamedSharding(mesh, P(layer_axis, None, None, None))
    return NamedSharding(mesh, P())


def kv_scale_sharding(cfg: ModelConfig, mesh: Mesh) -> NamedSharding:
    """Int8 KV scale array [L, NB, bs*KVH]: layer axis on pp (alongside
    its pages), replicated over tp. The flat token-major last dim
    interleaves kv heads per token, so a tp head split is inexpressible —
    and not worth expressing: scales are ~0.8% of the pool's bytes."""
    pp = mesh.shape.get("pp", 1)
    layer_axis = "pp" if pp > 1 and cfg.num_layers % pp == 0 else None
    if layer_axis:
        return NamedSharding(mesh, P(layer_axis, None, None))
    return NamedSharding(mesh, P())


def kv_scale_block_sharding(cfg: ModelConfig, mesh: Mesh) -> NamedSharding:
    """ONE block's scales [L, bs*KVH] — :func:`kv_scale_sharding` minus
    the NB axis (mirrors kv_block_sharding's relationship to the pool)."""
    pp = mesh.shape.get("pp", 1)
    layer_axis = "pp" if pp > 1 and cfg.num_layers % pp == 0 else None
    if layer_axis:
        return NamedSharding(mesh, P(layer_axis, None))
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Replicated host-built batch metadata (tokens, tables, lens)."""
    return NamedSharding(mesh, P())
