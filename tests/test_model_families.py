"""The seam between a model family and everything that serves it
(models/registry.py::Family, models/decoder.py; docs/engine.md, "Adding a
model family"). Every case runs on every registered family, so a new one
gets them by its line in ``ARCH_MODULES`` and a tiny checkpoint below.
"""

import json

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.models import build_model, decoder, get_model_config
from production_stack_tpu.models.quantize import quantize_loaded, quantize_tree
from production_stack_tpu.models.registry import (
    ARCH_MODULES,
    arch_of_model_type,
    get_family,
)
from production_stack_tpu.models.weights import load_checkpoint
from production_stack_tpu.parallel.mesh import build_mesh
from production_stack_tpu.parallel.pp_serving import make_pp_apply

ARCHS = sorted(ARCH_MODULES)
PRESET = {"llama": "tiny-llama", "opt": "tiny-opt", "mixtral": "tiny-mixtral",
          "laguna": "tiny-laguna", "lfm2": "tiny-lfm2",
          "longcat": "tiny-longcat",
          "glm4_moe_lite": "tiny-glm4-moe-lite", "ouro": "tiny-ouro",
          "smallthinker": "tiny-smallthinker"}
# What a family's config.json must hold beside the sizes every family
# reads (``Family.per_layer_keys``: lists, one entry a layer or more).
REQUIRED_KEYS = {"laguna": {
    "head_dim": 8, "num_key_value_heads": 2, "num_experts": 4,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "layer_types": ["full_attention", "sliding_attention"] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 3,
    "num_attention_heads_per_layer": [4, 6] * 2, "sliding_window": 8},
    "lfm2": {
    "num_key_value_heads": 2, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "num_dense_layers": 1, "conv_L_cache": 3,
    "use_expert_bias": True,
    "layer_types": ["conv", "full_attention"] * 2},
    "longcat": {
    "num_layers": 2, "ffn_hidden_size": 48, "expert_ffn_hidden_size": 16,
    "q_lora_rank": 24, "kv_lora_rank": 128, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "n_routed_experts": 4,
    "zero_expert_num": 2, "moe_topk": 2, "routed_scaling_factor": 6},
    "glm4_moe_lite": {
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "q_lora_rank": 24, "kv_lora_rank": 128, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "first_k_dense_replace": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1.8, "num_nextn_predict_layers": 1},
    "ouro": {
    "intermediate_size": 48, "num_key_value_heads": 4, "total_ut_steps": 2,
    "early_exit_threshold": 1},
    "smallthinker": {
    "head_dim": 8, "num_key_value_heads": 2, "moe_ffn_hidden_size": 16,
    "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 2,
    "rope_layout": [0, 1], "sliding_window_layout": [0, 1],
    "sliding_window_size": 8}}
SIZES = dict(vocab_size=64, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=4, max_position_embeddings=64)


def _hf_model(arch):
    """A tiny random Hugging Face model of the family's own kind (a new
    family brings its line)."""
    import transformers as tf

    if arch in ("laguna", "lfm2", "longcat", "glm4_moe_lite", "ouro",
                "smallthinker"):
        return None  # no class of it here (or no loader yet), no checkpoint
    return {
        "llama": lambda: tf.LlamaForCausalLM(tf.LlamaConfig(
            **SIZES, intermediate_size=48, num_key_value_heads=2)),
        "mixtral": lambda: tf.MixtralForCausalLM(tf.MixtralConfig(
            **SIZES, intermediate_size=48, num_key_value_heads=2,
            num_local_experts=4, num_experts_per_tok=2)),
        "opt": lambda: tf.OPTForCausalLM(tf.OPTConfig(
            **SIZES, ffn_dim=48, word_embed_proj_dim=32)),
    }[arch]()


@pytest.fixture(scope="module", params=ARCHS)
def arch(request):
    return request.param


@pytest.fixture(scope="module")
def checkpoint(arch, tmp_path_factory):
    path = tmp_path_factory.mktemp(f"{arch}-ckpt")
    model = _hf_model(arch)
    if model is None:  # a directory with config.json alone
        (path / "config.json").write_text(json.dumps(
            {"model_type": get_family(arch).model_types[0], **SIZES,
             **REQUIRED_KEYS[arch]}))
    else:
        model.save_pretrained(path, safe_serialization=True)
    return str(path)


def _leaves(tree):
    return {tuple(k.key for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _engine(arch, **kwargs):
    return EngineCore(EngineConfig(
        model=PRESET[arch], max_model_len=64, max_num_seqs=2, block_size=8,
        num_blocks=16, **kwargs))


def test_tree_specs_and_loader_name_the_same_leaves(arch, checkpoint):
    family = get_family(arch)
    cfg = get_model_config(checkpoint)
    assert cfg.arch == arch
    lora = {"lora_slots": 2, "lora_rank": 4} if family.lora else {}
    tree = _leaves(jax.eval_shape(
        lambda: family.init_params(cfg, jax.random.key(0), **lora)))
    # every leaf has its rule; a rule may name a leaf this config lacks
    # (a shared expert it does not have)
    assert tree <= set(family.specs)
    assert tree == set(family.specs) or family.loop is not None
    if _hf_model(arch) is None:
        with pytest.raises(NotImplementedError, match="no checkpoint loader"):
            load_checkpoint(cfg, checkpoint)
        return
    # A checkpoint carries everything but the adapters' slots.
    loaded = _leaves(load_checkpoint(cfg, checkpoint))
    assert loaded == {leaf for leaf in tree if leaf[0] != "lora"}


def test_apply_is_the_shared_apply_bound_to_the_family(arch):
    module = __import__(ARCH_MODULES[arch], fromlist=["apply"])
    assert module.apply.func is decoder.apply
    assert module.apply.args == (module.FAMILY,) and not module.apply.keywords
    cfg = get_model_config(PRESET[arch])
    assert build_model(cfg) == (module.init_params, module.apply)


def test_int8_takes_the_keys_the_record_names(arch):
    family = get_family(arch)
    cfg = get_model_config(PRESET[arch])
    params = family.init_params(cfg, jax.random.key(0))
    if not family.quant_keys:
        refusal = "int8 quantization is supported for the llama family"
        for quantize in (quantize_tree, quantize_loaded):
            with pytest.raises(ValueError, match=refusal):
                quantize(params, arch)
        with pytest.raises(ValueError, match=refusal):
            _engine(arch, quantization="int8")
        return
    layers = quantize_tree(params, arch)["layers"]
    int8 = {k for k, v in layers.items() if v.dtype == np.int8}
    assert int8 == set(family.quant_keys)
    assert {k + "_scale" for k in int8} == set(layers) - set(params["layers"])


def test_lora_slots_only_where_the_record_takes_them(arch):
    family = get_family(arch)
    core = _engine(arch, max_loras=2, max_lora_rank=4)
    assert ("lora" in core.params) == family.lora
    if family.lora:
        assert core.params["lora"]["scaling"].shape == (2,)


def test_pipeline_stages_only_where_the_record_takes_them(arch):
    family = get_family(arch)
    if family.pipeline:
        mesh = build_mesh(tensor_parallel_size=1, data_parallel_size=1,
                          pipeline_parallel_size=2,
                          devices=jax.devices()[:2])
        assert callable(make_pp_apply(mesh, family, microbatches=2))
        return
    with pytest.raises(ValueError, match="pipeline_parallel_size > 1 is "
                       "supported for the Llama family"):
        _engine(arch, pipeline_parallel_size=2)


def test_its_model_types_resolve_to_it_and_to_no_other(arch, tmp_path):
    family = get_family(arch)
    assert family.model_types
    for model_type in family.model_types:
        assert arch_of_model_type(model_type) == arch
        (tmp_path / "config.json").write_text(
            json.dumps({"model_type": model_type, "hidden_size": 32,
                        "num_attention_heads": 4, "num_hidden_layers": 2,
                        **REQUIRED_KEYS.get(arch, {})}))
        assert get_model_config(str(tmp_path)).arch == arch
    others = [t for a in ARCHS if a != arch
              for t in get_family(a).model_types]
    assert not set(family.model_types) & set(others)


def test_unknown_model_type_raises_and_names_the_known_ones(tmp_path):
    """Outside input: served as Llama, a checkpoint no family claims
    would drop its q/k/v biases or its window without a word."""
    (tmp_path / "config.json").write_text(
        json.dumps({"model_type": "qwen2", "hidden_size": 32}))
    with pytest.raises(ValueError, match="qwen2") as refused:
        get_model_config(str(tmp_path))
    for arch in ARCHS:
        for model_type in get_family(arch).model_types:
            assert repr(model_type) in str(refused.value)


def test_unknown_arch_raises():
    cfg = get_model_config("tiny-llama").replace(arch="nope")
    with pytest.raises(ValueError, match="Unknown arch 'nope'"):
        build_model(cfg)
