"""Sharding rules: where every tensor lives on the mesh.

Megatron-style tensor parallelism expressed as NamedSharding specs — XLA
GSPMD inserts the all-reduces over ICI (this replaces the NCCL collectives
inside the reference's vLLM engines):

- each family's leaf -> PartitionSpec table stands beside the
  ``init_params`` that fixes its shapes (models/registry.py::Family.specs:
  qkv and MLP up/gate column-parallel, ``wo`` and down row-parallel,
  experts on the expert axis, the head vocab-sharded); this module holds
  the rules that apply a table to a mesh;
- KV pages: sharded on the kv-head axis, so paged attention is fully local
  to each chip (queries for a chip's heads only touch that chip's pages).

When a dimension does not divide the tp size the leaf falls back to
replicated (correct, just not distributed) — this keeps tiny test models
runnable on any mesh.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.models.registry import get_family


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
    specs = get_family(cfg.arch).specs
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


def place_checkpoint(cfg: ModelConfig, mesh: Mesh, params: Dict,
                     loaded: Dict, shardings: Any) -> Dict:
    """``params`` with a checkpoint's host leaves put in their places on
    the mesh. Leaves the checkpoint doesn't carry (LoRA slots) keep their
    init values, except the random head of a family that ties it: a
    checkpoint without ``lm_head`` reads ``embed.T``."""
    from production_stack_tpu.parallel.multihost import put_global

    replicated = NamedSharding(mesh, P())

    def merge(dst: dict, src: dict, shard: dict) -> None:
        for key, val in src.items():
            if isinstance(val, dict):
                merge(dst.setdefault(key, {}), val, shard.get(key, {}))
            else:
                # put_global: each process contributes its local shards
                # (device_put cannot target non-addressable devices of a
                # multi-host mesh; every process loads the same
                # checkpoint from its own disk).
                dst[key] = put_global(val, shard.get(key, replicated))

    params = dict(params, layers=dict(params["layers"]))
    merge(params, loaded, shardings)
    if get_family(cfg.arch).head_may_tie and "lm_head" not in loaded:
        params.pop("lm_head", None)
        params.pop("lm_head_scale", None)
    return params


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
