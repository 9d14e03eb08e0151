"""Model registry: one record per family, and ``arch`` -> its module.

What a family *is* is said once, in the :class:`Family` its module
defines beside the ``init_params`` that fixes its tree; the engine, the
sharding rules, the checkpoint loader and the quantiser ask the record
and never the family's name. A new family is its own module, one line of
``ARCH_MODULES`` and a preset if it wants one (docs/engine.md, "Adding a
model family").
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Mapping, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from production_stack_tpu.models.config import ModelConfig

# arch -> module, imported on first use (an engine pays for one family).
ARCH_MODULES = {
    "llama": "production_stack_tpu.models.llama",
    "opt": "production_stack_tpu.models.opt",
    "mixtral": "production_stack_tpu.models.mixtral",
    "laguna": "production_stack_tpu.models.laguna",
    "lfm2": "production_stack_tpu.models.lfm2",
    "longcat": "production_stack_tpu.models.longcat",
    "glm4_moe_lite": "production_stack_tpu.models.glm4_moe_lite",
    "ouro": "production_stack_tpu.models.ouro",
    "smallthinker": "production_stack_tpu.models.smallthinker",
}


@dataclasses.dataclass(frozen=True)
class Family:
    """``embed``, ``layer`` and ``head`` are the three parts
    models/decoder.py::apply (and the pipeline stages of
    parallel/pp_serving.py) run:

    - ``embed(params, cfg, token_ids, positions, adapter_ids)`` ->
      ``(x [B, T, Hd], lora_layers, lora_scaling, adapter_ids)``, the
      last three ``None`` for a tree without LoRA slots;
    - ``layer(cfg, mode, x, (layer_params, lora), kv, l, batch)`` ->
      ``(x, kv)``: one layer on its un-stacked leaves, attention through
      ``decoder.attend`` (None for a family whose layers are of several
      kinds: it brings ``loop``, and has no pipeline stages yet);
    - ``head(params, cfg, x, output_hidden)`` -> logits, or the normed
      hidden states.
    """

    # Hugging Face ``model_type`` strings whose checkpoints this family
    # serves exactly (models/config.py refuses any no family claims).
    model_types: Tuple[str, ...]
    init_params: Callable  # (cfg, rng, **lora_kwargs) -> params
    embed: Callable
    head: Callable
    # leaf path -> PartitionSpec template, "tp" substituted; the leading
    # axis of a "layers" leaf is the stacked layer axis
    # (parallel/sharding.py applies the rules).
    specs: Mapping[Tuple[str, ...], object]
    layer: Callable | None = None
    # (cfg, path) -> the tree of an HF checkpoint directory, in
    # ``init_params``' layout (file formats: models/weights.py). None: no
    # loader yet; a directory with ``config.json`` alone is served with
    # random weights from ``--seed``.
    load: Callable | None = None
    # ``layers`` leaves that weight-only int8 takes; empty = unsupported.
    quant_keys: Tuple[str, ...] = ()
    lora: bool = False  # init_params takes lora_slots / lora_rank
    pipeline: bool = False  # its layers run as stages over a pp axis
    # A checkpoint without ``lm_head`` ties the head to ``embed``: the
    # random head of the init is dropped and ``head`` reads ``embed.T``.
    head_may_tie: bool = False
    # What the family's layers are, where they are of several kinds and
    # one stack cannot hold them: ``loop(cfg, mode, x, params, kv_pages,
    # batch)`` -> ``(x, kv_pages, counts)`` steps them through
    # ``decoder.scan_layers`` (docs/engine.md, "Layers of several
    # kinds"). None: that scan over ``params["layers"]`` with ``layer``.
    loop: Callable | None = None
    # ``layers`` leaves that ``layer`` is handed whole, as the stack
    # ``[L, ...]``, beside the layer's own slice of every other leaf: the
    # operands of a kernel that takes the layer's index itself (the
    # expert layer's grouped matmuls, models/moe.py), which a slice of
    # the stack would be copied out for in every forward.
    whole_leaves: Tuple[str, ...] = ()
    # Per-layer lists its ``config.json`` must hold, each at least
    # ``num_hidden_layers`` long (models/config.py reads their first
    # ``num_hidden_layers`` entries, and refuses a file without them).
    per_layer_keys: Tuple[str, ...] = ()
    # What ``loop`` counts of one forward: ``apply(..., with_stats=True)``
    # returns it as a third value, an int32 vector with one entry per
    # name, which the step programs sum over their forwards and the step
    # record carries under these names (engine/core.py, obs/steps.py).
    stats: Tuple[str, ...] = ()
    # ``(config.json as a dict, num_hidden_layers) -> ModelConfig fields``:
    # what the family reads of its own keys, laid over the common ones
    # (models/config.py names no family's; called for any family that
    # has one, with or without ``per_layer_keys``).
    config_fields: Callable | None = None
    # ``(cfg) -> int``: how many page layers a token's cache has, where
    # that is not one a layer: fewer (only some layers hold keys and
    # values) or more (a stack applied several times keeps a page layer
    # for every pass of every layer, models/ouro.py). The pool's pages are
    # ``[that many, NB, bs, ...]`` and the family numbers them itself.
    # None: one a layer.
    page_layers: Callable | None = None
    # ``(cfg) -> int``: how many times one forward runs (and so reads)
    # the stack ``params["layers"]``. None: once. What a forward reads of
    # the weights is :func:`forward_weight_bytes`, which the step
    # recorder's roofline model counts (obs/steps.py).
    layer_passes: Callable | None = None
    # ``(cfg) -> (layers, rows, width)`` of a state per cache block that
    # rides beside the pages as the pool's third side ``[layers, NB, rows,
    # width]`` (models/decoder.py::read_block_state; docs/engine.md):
    # ``loop`` then takes and returns ``(k, v, state)``. A block's entry
    # is the state after the last token written into it, so a full
    # block's is the state at its boundary and a prefix hit brings pages
    # and state together. None: pages only. Surfaces that move pages
    # (engine/core.py) move the state or are refused at start-up:
    # speculation, host offload, the cache server, int8 weights, LoRA
    # slots, pipeline stages and a mesh of several devices are not
    # taught it yet.
    block_state: Callable | None = None
    # ``(cfg) -> ((rows, width), (rows, width))``: what a token keeps on
    # the two sides of a page, for a family whose pages are not grouped
    # keys and values of ``num_kv_heads x head_dim`` each: a latent cache
    # keeps one normed latent row on the first side and one rotated key,
    # shared by every head, on the second, of unequal widths (models/
    # longcat.py, docs/engine.md). The pool's sides are then ``[page
    # layers, NB, bs, rows, lanes]`` each with its own ``lanes`` (the
    # width rounded up to whole 128-lane tiles, zeros beyond it), the
    # family attends through ``decoder.attend_latent``, and every surface
    # that speaks pages speaks ``[..., bs, rows, width]`` per side. None:
    # keys and values of the grouped heads. Surfaces that move page bytes
    # and are not taught two shapes are refused at start-up
    # (engine/core.py::_refuse_what_the_page_sides_are_not_taught).
    page_sides: Callable | None = None

    def __post_init__(self):
        if (self.layer is None) == (self.loop is None) or (
                self.pipeline and self.layer is None):
            raise ValueError(
                "a family brings ``layer`` (layers of one kind: the shared "
                "scan steps it, and pipeline stages run it) or ``loop`` "
                "(layers of several kinds, and no pipeline stages yet)")


def replicated(*paths_and_ranks) -> dict:
    """``specs`` of a family without tensor-parallel rules yet: every
    ``(leaf path, rank)`` replicated over a mesh."""
    return {path: P(*[None] * rank) for path, rank in paths_and_ranks}


def page_sides(cfg: ModelConfig):
    """((rows, width), (rows, width)) of the two sides of a token's page
    where the family says so (``Family.page_sides``), else None."""
    sides = get_family(cfg.arch).page_sides
    return None if sides is None else tuple(map(tuple, sides(cfg)))


def page_layers(cfg: ModelConfig) -> int:
    """Layers of ``cfg``'s model that hold KV pages."""
    held = get_family(cfg.arch).page_layers
    return cfg.num_layers if held is None else held(cfg)


def forward_weight_bytes(cfg: ModelConfig, params) -> int:
    """Bytes of weights one forward reads from ``params`` (arrays or
    shapes): every leaf once, the stack ``params["layers"]`` as many
    times as the family runs it (``Family.layer_passes``)."""
    def held(tree) -> int:
        return sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree_util.tree_leaves(tree))

    passes = get_family(cfg.arch).layer_passes
    again = 0 if passes is None else passes(cfg) - 1
    return held(params) + again * held(params.get("layers", {}))


def block_state_shape(cfg: ModelConfig) -> Tuple[int, int, int] | None:
    """(layers, rows, width) of the state a cache block holds beside its
    pages, None for a family without one."""
    state = get_family(cfg.arch).block_state
    return None if state is None else state(cfg)


def _module(arch: str):
    try:
        return importlib.import_module(ARCH_MODULES[arch])
    except KeyError:
        raise ValueError(f"Unknown arch {arch!r}") from None


def get_family(arch: str) -> Family:
    return _module(arch).FAMILY


def arch_of_model_type(model_type: str) -> str:
    """The ``arch`` whose family answers to a checkpoint's ``model_type``."""
    known = {t: arch for arch in ARCH_MODULES
             for t in get_family(arch).model_types}
    if model_type not in known:
        raise ValueError(
            f"Unknown model_type {model_type!r}: no model family serves "
            f"it (known: {sorted(known)})")
    return known[model_type]


def build_model(cfg: ModelConfig) -> Tuple[Callable, Callable]:
    """Return (init_params(cfg, rng) -> params, apply(params, cfg, ...))."""
    mod = _module(cfg.arch)
    return mod.init_params, mod.apply
