"""Model architecture configs and the named-model preset table.

Model weights cannot be downloaded in this environment (zero egress), so
named models resolve to architecture presets; weights come from a local
checkpoint directory when available (orbax/safetensors) or random
initialization otherwise. The preset table covers the model families the
reference stack's example configs exercise (BASELINE.json configs:
opt-125m, Llama-3-8B, Llama-3-70B, Mixtral-8x7B).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional, Tuple

import jax.numpy as jnp

FULL_ATTENTION = "full_attention"
SLIDING_ATTENTION = "sliding_attention"
SHORT_CONV = "conv"
# The two attention kinds again for a layer that takes NO positional
# encoding (NoPE: its queries and keys go unrotated; smallthinker). A
# kind names both of a layer's switches, the window and the rotation.
NOPE_FULL_ATTENTION = "full_attention_nope"
NOPE_SLIDING_ATTENTION = "sliding_attention_nope"


@dataclasses.dataclass(frozen=True)
class RopeParams:
    """One rotary block of a config's ``rope_parameters``: ``default`` or
    ``yarn`` (Hugging Face's ``_compute_yarn_parameters``), over the first
    ``partial_rotary_factor`` of each head's dims."""

    rope_type: str = "default"
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny-llama"
    arch: str = "llama"  # a key of models/registry.py::ARCH_MODULES
    vocab_size: int = 32000
    hidden_size: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    max_position: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # OPT-specific
    do_layer_norm_before: bool = True
    # MoE (mixtral, laguna): ``num_experts`` is the count held HERE.
    num_experts: int = 0
    experts_per_token: int = 2
    # Layers of several kinds (laguna). The per-layer tuples are as long
    # as ``num_layers``; empty = every layer full attention, ``num_heads``.
    layer_types: Tuple[str, ...] = ()
    heads_per_layer: Tuple[int, ...] = ()
    sliding_window: int = 0  # of the ``sliding_attention`` layers
    # A kind's rotary block; None for a kind that takes no rotation.
    rope_by_kind: Tuple[Tuple[str, Optional[RopeParams]], ...] = ()
    moe_intermediate_size: int = 0  # a routed expert's width
    shared_expert_size: int = 0  # 0 = no shared expert
    routed_scaling: float = 1.0
    dense_layers: int = 0  # leading layers whose MLP is dense
    # The deployment: ``chips_per_layer`` chips share each layer, each
    # holds ``num_experts`` of the ``num_experts * chips_per_layer`` the
    # router scores, and this one holds block ``layer_share``.
    chips_per_layer: int = 1
    layer_share: int = 0
    # The router (models/moe.py::route): ``softmax`` over all scores or a
    # ``sigmoid`` of each; with ``router_bias`` a per-expert float32 bias
    # joins the score for the selection alone (lfm2).
    router_scoring: str = "softmax"
    router_bias: bool = False
    # Gated short convolution (lfm2's ``conv`` layers): the kernel's
    # length; a layer's state is its last ``conv_kernel - 1`` inputs.
    conv_kernel: int = 0
    # Latent attention (MLA: longcat, glm4_moe_lite): the ranks of the
    # query's and the cache's low-rank projections, a head's widths
    # without and with rotary embedding and its value's, and whether each
    # latent is multiplied by sqrt(hidden_size / its rank) after its norm.
    # A token's page keeps the ``kv_lora_rank`` latent and the one rotated
    # key.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # Zero-compute experts: router outputs behind the experts with
    # weights that give the layer's input back (models/moe.py).
    zero_experts: int = 0
    # A stack applied several times (ouro): how many passes a forward
    # makes over the same layers, and the exit gate's threshold (1.0:
    # every token takes every pass; the programs serve no other).
    loop_passes: int = 1
    early_exit_threshold: float = 1.0
    dtype: str = "bfloat16"

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def published_experts(self) -> int:
        """The router's width: the experts of the whole layer."""
        return self.num_experts * self.chips_per_layer

    def layer_kind(self, layer: int) -> str:
        return self.layer_types[layer] if self.layer_types else FULL_ATTENTION

    def layer_heads(self, layer: int) -> int:
        return (self.heads_per_layer[layer] if self.heads_per_layer
                else self.num_heads)

    def window_of(self, kind: str) -> Optional[int]:
        """The static window bound ``decoder.attend`` takes for a layer
        of this kind: None where it sees its whole context."""
        return (self.sliding_window
                if kind in (SLIDING_ATTENTION, NOPE_SLIDING_ATTENTION)
                and self.sliding_window > 0 else None)

    def rope_of(self, kind: str) -> Optional[RopeParams]:
        """The rotary block of a layer of this kind; None where the kind
        takes no positional encoding (nothing is rotated: not a rotation
        by zero angles, which would still multiply)."""
        return dict(self.rope_by_kind).get(
            kind, RopeParams(rope_theta=self.rope_theta))

    def replace(self, **kwargs) -> "ModelConfig":
        return dataclasses.replace(self, **kwargs)


# Architecture presets. Sizes follow the public model cards.
_PRESETS = {
    "tiny-llama": ModelConfig(
        name="tiny-llama", arch="llama", vocab_size=512, hidden_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
        intermediate_size=256, max_position=2048, rope_theta=10000.0,
    ),
    "tiny-mixtral": ModelConfig(
        name="tiny-mixtral", arch="mixtral", vocab_size=512, hidden_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
        intermediate_size=256, max_position=2048, rope_theta=10000.0,
        num_experts=4, experts_per_token=2,
    ),
    # Both layer kinds with their own head counts and rotary blocks, a
    # window shorter than the tests' contexts, a dense first layer, a
    # shared expert, the second of two shares of 8 experts.
    "tiny-laguna": ModelConfig(
        name="tiny-laguna", arch="laguna", vocab_size=512, hidden_size=128,
        num_layers=6, num_heads=4, num_kv_heads=2, head_dim=32,
        intermediate_size=256, max_position=2048, rms_norm_eps=1e-6,
        num_experts=4, experts_per_token=3, moe_intermediate_size=64,
        shared_expert_size=64, routed_scaling=2.5, dense_layers=1,
        chips_per_layer=2, layer_share=1, sliding_window=24,
        layer_types=(FULL_ATTENTION, SLIDING_ATTENTION,
                     SLIDING_ATTENTION) * 2,
        heads_per_layer=(4, 6, 6) * 2,
        rope_by_kind=(
            (FULL_ATTENTION, RopeParams(
                rope_type="yarn", rope_theta=500000.0,
                partial_rotary_factor=0.5, factor=8.0,
                original_max_position_embeddings=256,
                attention_factor=1.2079441541679836)),
            (SLIDING_ATTENTION, RopeParams(rope_theta=10000.0))),
    ),
    # Both operators (short convolutions 3:1 with attention layers whose
    # eight 64-wide kv heads take the pool's packed rows), two dense
    # layers, then sigmoid-routed experts with a selection bias, tied head.
    "tiny-lfm2": ModelConfig(
        name="tiny-lfm2", arch="lfm2", vocab_size=512, hidden_size=128,
        num_layers=6, num_heads=16, num_kv_heads=8, head_dim=64,
        intermediate_size=256, max_position=2048, rope_theta=1000000.0,
        num_experts=8, experts_per_token=2, moe_intermediate_size=64,
        dense_layers=2, router_scoring="sigmoid", router_bias=True,
        conv_kernel=3, tie_word_embeddings=True,
        layer_types=(SHORT_CONV, SHORT_CONV, FULL_ATTENTION, SHORT_CONV,
                     SHORT_CONV, FULL_ATTENTION),
    ),
    # Two layers of two latent-attention sublayers (a 128-wide latent and
    # a 16-wide rotated key a token), the second of two shares of 8
    # experts, 4 zero-compute experts behind them, top 3 not renormalised.
    "tiny-longcat": ModelConfig(
        name="tiny-longcat", arch="longcat", vocab_size=512,
        hidden_size=128, num_layers=2, num_heads=8, num_kv_heads=1,
        head_dim=48, intermediate_size=256, max_position=2048,
        rope_theta=10000000.0, num_experts=4, experts_per_token=3,
        moe_intermediate_size=64, routed_scaling=6.0, chips_per_layer=2,
        layer_share=1, router_bias=True, q_lora_rank=64, kv_lora_rank=128,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        mla_scale_q_lora=True, mla_scale_kv_lora=True, zero_experts=4,
    ),
    # Four layers of one latent-attention sublayer each, five heads (not a
    # multiple of the kernels' eight) whose values are wider than their
    # keys' nope part, a dense first layer, then the first of two shares
    # of 8 sigmoid-routed experts with a selection bias beside a shared one.
    "tiny-glm4-moe-lite": ModelConfig(
        name="tiny-glm4-moe-lite", arch="glm4_moe_lite", vocab_size=512,
        hidden_size=128, num_layers=4, num_heads=5, num_kv_heads=1,
        head_dim=40, intermediate_size=256, max_position=2048,
        rope_theta=1000000.0, num_experts=4, experts_per_token=3,
        moe_intermediate_size=64, shared_expert_size=64, routed_scaling=1.8,
        dense_layers=1, chips_per_layer=2, layer_share=0,
        router_scoring="sigmoid", router_bias=True, q_lora_rank=48,
        kv_lora_rank=128, qk_nope_head_dim=24, qk_rope_head_dim=16,
        v_head_dim=32,
    ),
    # Two periods of SmallThinker's layout at a window shorter than the
    # tests' contexts: a NoPE full layer, then rotary window layers; 8
    # ReGLU experts top 3 routed from the layer's input, 7 query heads a
    # kv head as the published 28 / 4.
    "tiny-smallthinker": ModelConfig(
        name="tiny-smallthinker", arch="smallthinker", vocab_size=512,
        hidden_size=128, num_layers=4, num_heads=14, num_kv_heads=2,
        head_dim=32, intermediate_size=0, max_position=2048,
        rope_theta=1500000.0, rms_norm_eps=1e-6, num_experts=8,
        experts_per_token=3, moe_intermediate_size=64, sliding_window=24,
        layer_types=(NOPE_FULL_ATTENTION, SLIDING_ATTENTION) * 2,
        rope_by_kind=((NOPE_FULL_ATTENTION, None),),
    ),
    # Three sandwich-normed layers run twice over the same weights, a
    # query group of one (as Ouro's), a page layer for every pass.
    "tiny-ouro": ModelConfig(
        name="tiny-ouro", arch="ouro", vocab_size=512, hidden_size=128,
        num_layers=3, num_heads=4, num_kv_heads=4, head_dim=32,
        intermediate_size=256, max_position=2048, rope_theta=1000000.0,
        rms_norm_eps=1e-6, loop_passes=2,
    ),
    "tiny-opt": ModelConfig(
        name="tiny-opt", arch="opt", vocab_size=512, hidden_size=128,
        num_layers=2, num_heads=4, num_kv_heads=4, head_dim=32,
        intermediate_size=512, max_position=2048,
    ),
    "facebook/opt-125m": ModelConfig(
        name="facebook/opt-125m", arch="opt", vocab_size=50272,
        hidden_size=768, num_layers=12, num_heads=12, num_kv_heads=12,
        head_dim=64, intermediate_size=3072, max_position=2048,
    ),
    # ~0.9B Llama-family preset sized to fit one v5e chip with KV headroom:
    # the flagship architecture class (GQA 16q/8kv, head_dim 128) at a scale
    # a single-chip bench can serve.
    "tpu-llama-1b": ModelConfig(
        name="tpu-llama-1b", arch="llama", vocab_size=32000,
        hidden_size=2048, num_layers=16, num_heads=16, num_kv_heads=8,
        head_dim=128, intermediate_size=7168, max_position=8192,
        rope_theta=500000.0,
    ),
    # ~3.2B Llama-family preset (Llama-3.2-3B card dimensions): the largest
    # Llama-class architecture that fits a single 16 GB v5e chip in bf16
    # with KV headroom (weights ~6.4 GB).
    "tpu-llama-3b": ModelConfig(
        name="tpu-llama-3b", arch="llama", vocab_size=128256,
        hidden_size=3072, num_layers=28, num_heads=24, num_kv_heads=8,
        head_dim=128, intermediate_size=8192, max_position=8192,
        rope_theta=500000.0,
    ),
    "meta-llama/Llama-3-8B": ModelConfig(
        name="meta-llama/Llama-3-8B", arch="llama", vocab_size=128256,
        hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, intermediate_size=14336, max_position=8192,
        rope_theta=500000.0,
    ),
    "meta-llama/Llama-3-70B": ModelConfig(
        name="meta-llama/Llama-3-70B", arch="llama", vocab_size=128256,
        hidden_size=8192, num_layers=80, num_heads=64, num_kv_heads=8,
        head_dim=128, intermediate_size=28672, max_position=8192,
        rope_theta=500000.0,
    ),
    "mistralai/Mistral-7B-v0.1": ModelConfig(
        name="mistralai/Mistral-7B-v0.1", arch="llama", vocab_size=32000,
        hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, intermediate_size=14336, max_position=8192,
        rope_theta=10000.0,
    ),
    "mistralai/Mixtral-8x7B-v0.1": ModelConfig(
        name="mistralai/Mixtral-8x7B-v0.1", arch="mixtral", vocab_size=32000,
        hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, intermediate_size=14336, max_position=8192,
        rope_theta=1000000.0, num_experts=8, experts_per_token=2,
    ),
}

_ALIASES = {
    "meta-llama/Meta-Llama-3-8B": "meta-llama/Llama-3-8B",
    "meta-llama/Meta-Llama-3-8B-Instruct": "meta-llama/Llama-3-8B",
    "meta-llama/Llama-3.1-8B-Instruct": "meta-llama/Llama-3-8B",
    "meta-llama/Meta-Llama-3-70B": "meta-llama/Llama-3-70B",
    "mistralai/Mixtral-8x7B-Instruct-v0.1": "mistralai/Mixtral-8x7B-v0.1",
}


def _from_hf_config_json(path: str, name: str) -> ModelConfig:
    """Build a ModelConfig from a local HuggingFace config.json."""
    with open(path) as f:
        cfg = json.load(f)
    # Outside input: a checkpoint no family claims (q/k/v biases, a
    # window, another norm) would be served wrong as Llama, so it raises.
    from production_stack_tpu.models.registry import arch_of_model_type

    arch = arch_of_model_type(cfg.get("model_type", "llama"))
    heads = cfg.get("num_attention_heads", 32)
    hidden = cfg.get("hidden_size", 4096)
    layers = cfg.get("num_hidden_layers", cfg.get("num_layers", 32))
    fields = dict(
        name=name,
        arch=arch,
        vocab_size=cfg.get("vocab_size", 32000),
        hidden_size=hidden,
        num_layers=layers,
        num_heads=heads,
        num_kv_heads=cfg.get("num_key_value_heads", heads),
        # some configs carry an explicit null head_dim
        head_dim=cfg.get("head_dim") or hidden // heads,
        intermediate_size=cfg.get("intermediate_size", cfg.get("ffn_dim", 4 * hidden)),
        max_position=cfg.get("max_position_embeddings", 8192),
        rope_theta=cfg.get("rope_theta", 10000.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        do_layer_norm_before=cfg.get("do_layer_norm_before", True),
        num_experts=cfg.get("num_local_experts", cfg.get("num_experts", 0)),
        experts_per_token=cfg.get("num_experts_per_tok", 2),
    )
    # What the family reads of its own keys goes over the common ones.
    fields.update(_layer_kind_keys(cfg, arch, layers))
    return ModelConfig(**fields)


def rope_params(block: dict) -> RopeParams:
    kinds = {f.name: f.type for f in dataclasses.fields(RopeParams)}
    return RopeParams(**{
        k: float(v) if kinds[k] in ("float", "Optional[float]") else v
        for k, v in block.items() if k in kinds})


def _layer_kind_keys(cfg: dict, arch: str, layers: int) -> dict:
    """The ``ModelConfig`` fields a family reads of its own keys
    (``Family.config_fields``; {} for a family without one). Per-layer
    lists are read for their first ``num_hidden_layers`` entries (a cut
    in layers keeps the lists at their published length). A family that
    needs them (``Family.per_layer_keys``) refuses a file without them."""
    from production_stack_tpu.models.registry import get_family

    family = get_family(arch)
    if family.config_fields is None:
        return {}
    for key in family.per_layer_keys:
        held = cfg.get(key)
        if not isinstance(held, list) or len(held) < layers:
            raise ValueError(
                f"a {cfg.get('model_type')!r} config.json needs the "
                f"per-layer list {key!r} with at least num_hidden_layers "
                f"= {layers} entries")
    return family.config_fields(cfg, layers)


def get_model_config(model: str) -> ModelConfig:
    """Resolve a model name or local path to an architecture config."""
    if os.path.isdir(model) and os.path.exists(os.path.join(model, "config.json")):
        return _from_hf_config_json(os.path.join(model, "config.json"), model)
    key = _ALIASES.get(model, model)
    if key in _PRESETS:
        return _PRESETS[key]
    raise ValueError(
        f"Unknown model {model!r}; known presets: {sorted(_PRESETS)} "
        f"(or pass a local checkpoint directory with config.json)"
    )
