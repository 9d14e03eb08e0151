"""Plain float32 reference of the Laguna decoder block, for one chip's
share of a layer.

Straight ``jax.numpy``: full causal attention over the whole sequence
(banded in the sliding layers), no cache, no kernels, no grouped matmul,
every matmul at ``highest`` precision. It takes nothing the program made:
the weights are drawn here from the seed by this file's own copy of the
program's init recipe (``models/laguna.py::init_params``: 24 keys split
from the seed; a leaf is stacked over the layers that have it, so layer
``n`` of a stack is elements ``n * size ..`` of the key's normal array;
normal / sqrt(fan_in), rounded to the served dtype), one layer and one
expert at a time. The counter-based generator and the small helpers are
``chipbench/reference/llama.py``'s (a reference file, not the program).

The equations, for layer ``l`` of kind ``layer_types[l]`` with ``H_l =
num_attention_heads_per_layer[l]`` query heads (per-layer lists are read
for their first ``num_hidden_layers`` entries):

- ``h = rmsnorm(x)``; ``q = h Wq``, ``k = h Wk``, ``v = h Wv``;
- rotary: full layers rotate the first ``partial_rotary_factor`` of each
  head's dims with YaRN frequencies (Hugging Face's
  ``_compute_yarn_parameters``), cos and sin times ``attention_factor``;
  sliding layers rotate every dim with the plain frequencies;
- causal attention, scale ``1/sqrt(head_dim)``; in a sliding layer query
  ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``;
- head ``n``'s output times ``sigmoid(h Wg)[n]``, then ``Wo``, residual;
- MLP: a leading dense SwiGLU where ``mlp_layer_types[l]`` is ``dense``;
  else ``softmax_f32(h Wr)`` over all published experts, top
  ``num_experts_per_tok``, renormalised to sum 1, times
  ``moe_routed_scaling_factor``, applied to each expert's output, plus
  the shared expert.

One chip of ``chips_per_layer``: the router scores all ``num_experts x
chips_per_layer`` experts and this file computes those of block
``layer_share`` (``num_experts`` of them) and the ``vocab_size`` rows held
here; what the other chips' experts would add is left out, here as in the
program, and that partial sum goes on to the next layer.

``assumed`` (the config names the mechanism, not its function; each is
marked at its line): (a) the per-head gate is a sigmoid of a linear map
of the layer's normed input, applied to the attention output before
``Wo``; (b) the router's scores are a softmax; (c) ``hidden_act`` is
``silu``.

``activations`` names a lower-precision type to which every activation is
rounded on its way between operations: a control, not the reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.llama import (
    HIGHEST,
    _mm,
    _rounded,
    normal_rows,
    rms_norm,
    seed_key,
    split,
)

KINDS = ("full_attention", "sliding_attention")  # the program's sorted order
# Experts computed at once: 8 x 3 matrices of 3072 x 1024 in float32 are
# 300 MB, which fits beside a serving engine.
EXPERT_BLOCK = 8


def _stacked(key, index, shape, fan_in, dtype):
    """Entry ``index`` of the leaf ``[n, *shape]`` that ``key`` draws."""
    size = math.prod(shape)
    w = normal_rows(key, jnp.uint32(index) * jnp.uint32(size), size)
    return (w.reshape(shape) / jnp.sqrt(jnp.float32(fan_in))
            ).astype(dtype).astype(jnp.float32)


def inverse_frequencies(block: dict, head_dim: int):
    """(inverse frequencies of the rotated dims, cos/sin factor) of one
    ``rope_parameters`` block."""
    dim = int(head_dim * block.get("partial_rotary_factor", 1))
    theta = float(block["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if block.get("rope_type", "default") == "default":
        return plain.astype(np.float32), 1.0
    if block["rope_type"] != "yarn":
        raise ValueError(f"no reference for rope_type {block['rope_type']!r}")
    factor = float(block["factor"])
    original = block["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(block.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(block.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    blended = plain / factor * ramp + plain * (1.0 - ramp)
    attention_factor = block.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return blended.astype(np.float32), float(attention_factor)


def rope(x, block: dict):
    """x [S, T, heads, D]: the first rotated dims in the half-split
    layout, the rest as they are."""
    inv_freq, factor = inverse_frequencies(block, x.shape[-1])
    half = inv_freq.shape[0]
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(angles) * factor)[None, :, None]
    sin = (jnp.sin(angles) * factor)[None, :, None]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _swiglu(h, w_gate, w_up, w_down, r):
    # assumed (c): hidden_act is silu
    return _mm(r(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up)), w_down)


@functools.partial(jax.jit, static_argnames=(
    "kind", "heads", "at", "dims", "rope_block", "window", "activations"))
def _attention(keys, x, lens, *, kind, heads, at, dims, rope_block, window,
               activations):
    """x + gated attention; also (k, v) as the cache holds them."""
    hidden, kv_heads, head_dim, eps, dtype = dims
    S, T, _ = x.shape
    base = 2 + 5 * KINDS.index(kind)
    mat = functools.partial(_stacked, index=at, dtype=jnp.dtype(dtype))
    q_out, kv_out = heads * head_dim, kv_heads * head_dim
    wq = mat(keys[base], shape=(hidden, q_out), fan_in=hidden)
    wk = mat(keys[base + 1], shape=(hidden, kv_out), fan_in=hidden)
    wv = mat(keys[base + 2], shape=(hidden, kv_out), fan_in=hidden)
    wg = mat(keys[base + 3], shape=(hidden, heads), fan_in=hidden)
    wo = mat(keys[base + 4], shape=(q_out, hidden), fan_in=q_out)
    r = functools.partial(_rounded, activations=activations)
    block = dict(rope_block)
    h = r(rms_norm(x, eps))  # the norm weights are initialised to one
    q = r(rope(_mm(h, wq).reshape(S, T, heads, head_dim), block))
    k = r(rope(_mm(h, wk).reshape(S, T, kv_heads, head_dim), block))
    v = r(_mm(h, wv).reshape(S, T, kv_heads, head_dim))
    # assumed (a): a sigmoid of a linear map of the normed input, per head
    gate = jax.nn.sigmoid(_mm(h, wg))
    group = heads // kv_heads
    t = jnp.arange(T)
    seen = t[None, :] <= t[:, None]
    if window:
        seen = seen & (t[:, None] - t[None, :] < window)

    def one(args):  # a sequence at a time: the scores are [heads, T, T]
        q1, k1, v1, n = args
        qg = q1.reshape(T, kv_heads, group, head_dim)
        scores = jnp.einsum("tkgd,ukd->kgtu", qg, k1,
                            precision=HIGHEST) / math.sqrt(head_dim)
        mask = seen & (t[None, :] < n)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("kgtu,ukd->tkgd", jax.nn.softmax(scores, -1), v1,
                          precision=HIGHEST).reshape(T, heads, head_dim)

    attn = jax.lax.map(one, (q, k, v, lens))
    attn = r(attn * gate[..., None]).reshape(S, T, q_out)
    return r(x + _mm(attn, wo)), k, v


@functools.partial(jax.jit, static_argnames=("at", "dims", "activations"))
def _dense_mlp(keys, x, *, at, dims, activations):
    hidden, inter, eps, dtype = dims
    mat = functools.partial(_stacked, index=at, dtype=jnp.dtype(dtype))
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, eps))
    return r(x + _swiglu(
        h, mat(keys[12], shape=(hidden, inter), fan_in=hidden),
        mat(keys[13], shape=(hidden, inter), fan_in=hidden),
        mat(keys[14], shape=(inter, hidden), fan_in=inter), r))


@functools.partial(jax.jit, static_argnames=("at", "dims", "activations"))
def _sparse_mlp(keys, x, *, at, dims, activations):
    """x + the held experts' weighted outputs + the shared expert."""
    (hidden, width, shared, published, held, share, top_k, scaling, eps,
     dtype) = dims
    S, T, _ = x.shape
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, eps)).reshape(S * T, hidden)
    router = _stacked(keys[15], at, (hidden, published), hidden, dt)
    # assumed (b): the scores are a softmax over all published experts
    scores = jax.nn.softmax(_mm(h, router), axis=-1)
    top, chosen = jax.lax.top_k(scores, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * scaling
    weights = jnp.zeros_like(scores).at[
        jnp.arange(S * T)[:, None], chosen].set(top)
    mine = weights[:, share * held:(share + 1) * held]  # [N, held]

    step = math.gcd(held, EXPERT_BLOCK)

    def block(total, first):
        for e in range(step):
            index = at * held + first + e
            out = _swiglu(
                h, _stacked(keys[16], index, (hidden, width), hidden, dt),
                _stacked(keys[17], index, (hidden, width), hidden, dt),
                _stacked(keys[18], index, (width, hidden), width, dt), r)
            w = jax.lax.dynamic_slice_in_dim(mine, first + e, 1, axis=1)
            total = total + w * out
        return total, None

    routed, _ = jax.lax.scan(
        block, jnp.zeros_like(h), jnp.arange(0, held, step, dtype=jnp.int32))
    out = r(routed)
    if shared:
        out = out + _swiglu(
            h, _stacked(keys[19], at, (hidden, shared), hidden, dt),
            _stacked(keys[20], at, (hidden, shared), hidden, dt),
            _stacked(keys[21], at, (shared, hidden), shared, dt), r)
    return r(x + out.reshape(S, T, hidden))


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _embed(key, tokens, *, vocab, hidden, dtype):
    table = (0.02 * normal_rows(key, jnp.uint32(0), vocab * hidden)
             .reshape(vocab, hidden)).astype(jnp.dtype(dtype))
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "eps", "dtype", "activations"))
def _logprobs(key, x, *, vocab, eps, dtype, activations=None):
    hidden = x.shape[-1]
    head = _stacked(key, 0, (hidden, vocab), hidden, jnp.dtype(dtype))
    h = _rounded(rms_norm(x, eps), activations)
    return jax.nn.log_softmax(_mm(h, head), axis=-1)


def _frozen(block: dict):
    return tuple(sorted(block.items()))


def forward(hf: dict, seed: int, tokens, lens, *, keep_from: int,
            quantization=None, dtype="bfloat16", kv_layers=(0,),
            activations=None):
    """Log-probabilities [S, T - keep_from, rows held] of the next token
    after each position from ``keep_from`` on, and {layer: (k, v)} of the
    listed layers, for right-padded ``tokens`` [S, T] of lengths ``lens``.
    ``hf`` holds the sizes under their published keys, the cut ones at
    what is held here."""
    if quantization is not None:
        raise ValueError(f"no reference for quantization {quantization!r}")
    if hf.get("tie_word_embeddings"):
        raise ValueError("the reference has no tied-head path")
    if hf.get("moe_router_logit_softcapping") or hf.get(
            "moe_apply_router_weight_on_input") or not hf.get(
            "norm_topk_prob", True):
        raise ValueError("the reference has the published router only: no "
                         "soft-capping, weights on the output, renormalised")
    layers = hf["num_hidden_layers"]
    hidden, head_dim = hf["hidden_size"], hf["head_dim"]
    kv_heads, eps = hf["num_key_value_heads"], float(hf["rms_norm_eps"])
    kinds = hf["layer_types"][:layers]
    heads = hf["num_attention_heads_per_layer"][:layers]
    mlp_kinds = hf["mlp_layer_types"][:layers]
    chips, share = hf.get("chips_per_layer", 1), hf.get("layer_share", 0)
    held = hf["num_experts"]
    vocab = hf["vocab_size"]
    keys = split(seed_key(seed), 24)
    tokens = jnp.asarray(tokens, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _rounded(_embed(keys[0], tokens, vocab=vocab, hidden=hidden,
                            dtype=dtype), activations)
        kept = {}
        for layer in range(layers):
            kind = kinds[layer]
            x, k, v = _attention(
                keys, x, lens, kind=kind, heads=heads[layer],
                at=kinds[:layer].count(kind),
                dims=(hidden, kv_heads, head_dim, eps, dtype),
                rope_block=_frozen(hf["rope_parameters"][kind]),
                window=(hf["sliding_window"]
                        if kind == "sliding_attention" else 0),
                activations=activations)
            if layer in kv_layers:
                kept[layer] = (np.asarray(k), np.asarray(v))
            if mlp_kinds[layer] == "dense":
                x = _dense_mlp(
                    keys, x, at=mlp_kinds[:layer].count("dense"),
                    dims=(hidden, hf["intermediate_size"], eps, dtype),
                    activations=activations)
            else:
                x = _sparse_mlp(
                    keys, x, at=mlp_kinds[:layer].count("sparse"),
                    dims=(hidden, hf["moe_intermediate_size"],
                          hf.get("shared_expert_intermediate_size", 0),
                          held * chips, held, share,
                          hf["num_experts_per_tok"],
                          float(hf.get("moe_routed_scaling_factor", 1.0)),
                          eps, dtype),
                    activations=activations)
        logp = _logprobs(keys[1], x[:, keep_from:], vocab=vocab, eps=eps,
                         dtype=dtype, activations=activations)
    return np.asarray(logp), kept
