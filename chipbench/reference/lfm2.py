"""Plain float32 reference of the LFM2-MoE decoder block.

Straight ``jax.numpy``: the convolution is shifted adds over the whole
sequence, attention is full and causal over the whole sequence, no cache,
no state, no kernels, no grouped matmul, every matmul at ``highest``
precision. It takes nothing the program made: the weights are drawn here
from the seed by this file's own copy of the program's init recipe
(``models/lfm2.py::init_params``: 20 keys split from the seed; a leaf is
stacked over the layers that have it, so layer ``n`` of a stack is
elements ``n * size ..`` of the key's normal array; normal / sqrt(fan_in)
rounded to the served dtype; the q/k norm weights ``1 + 0.1 normal``
rounded likewise, the router's bias ``0.1 normal`` in float32), one layer
and one block of experts at a time. The counter-based generator and the
small helpers are ``chipbench/reference/llama.py``'s (a reference file,
not the program).

The equations (RMSNorm with ``norm_eps``, no biases), for layer ``l`` of
``layer_types[l]`` (read for its first ``num_hidden_layers`` entries):
``x = x + op_l(rmsnorm(x))``, then ``x = x + ffn_l(rmsnorm(x))``; after the
last layer ``rmsnorm(x)`` and the embedding transposed as the head.

- ``conv``: ``[B, C, X] = split3(h W_in)``; ``u = B * X``; ``c_t = sum_j
  w_j u_{t - (K - 1) + j}`` per channel with ``K = conv_L_cache`` taps and
  ``u`` before the sequence's start 0; ``op = (C * c) W_out``.
- ``full_attention``: ``q = h Wq``, ``k = h Wk``, ``v = h Wv``; ``q`` and
  ``k`` RMS-normed over each head's dims with a learned weight before the
  rotation; RoPE over all of a head's dims, half-split layout; causal,
  scale ``1 / sqrt(head_dim)``; ``op = concat(heads) Wo``.
- ``ffn_l``, ``l < num_dense_layers``: ``(silu(h Wg) * (h Wu)) Wd``.
- ``ffn_l`` after them: ``s = sigmoid_f32(h Wr)``; the experts are the top
  ``num_experts_per_tok`` of ``s + b``; ``w = s[experts] / (sum + 1e-6)``
  times ``routed_scaling_factor``; ``sum_e w_e SwiGLU_e(h)``.

``assumed`` (what the config does not spell; each is marked at its line):
(a) the head is tied to the embedding; (b) the activation is silu; (c) the
three chunks of ``W_in``'s output are ``B, C, X`` in this order; (d) the
bias ``b`` is drawn from the seed with a spread of 0.1; (e) ``q`` and ``k``
are RMS-normed per head before the rotation, and the final norm sits
before the head.

``kv[n]`` is the keys and values of the ``n``-th layer that has them (the
program's pages hold those layers only, in that order).

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
    rope,
    seed_key,
    split,
)

ROUTER_EPS = 1e-6
SPREAD = 0.1  # of the router's bias and of the q/k norm weights around 1
# Sequences computed at once: three of ~716 tokens beside a serving
# engine; and experts at once: 8 x 3 matrices of 2048 x 1536 in float32
# are 300 MB.
SEQUENCE_BLOCK = 1
EXPERT_BLOCK = 8


def _draw(key, index, shape):
    """Entry ``index`` of the normal array ``[n, *shape]`` of ``key``."""
    size = math.prod(shape)
    return normal_rows(key, jnp.uint32(index) * jnp.uint32(size),
                       size).reshape(shape)


def _stacked(key, index, shape, fan_in, dtype):
    return (_draw(key, index, shape) / jnp.sqrt(jnp.float32(fan_in))
            ).astype(dtype).astype(jnp.float32)


def _swiglu(h, w_gate, w_up, w_down, r):
    # assumed (b): the activation is silu
    return _mm(r(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up)), w_down)


@functools.partial(jax.jit, static_argnames=("at", "dims", "activations"))
def _short_conv(keys, x, *, at, dims, activations):
    hidden, taps, eps, dtype = dims
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)
    w_in = _stacked(keys[2], at, (hidden, 3 * hidden), hidden, dt)
    w_conv = _stacked(keys[3], at, (hidden, taps), taps, dt)
    w_out = _stacked(keys[4], at, (hidden, hidden), hidden, dt)
    h = r(rms_norm(x, eps))  # the norm weights are initialised to one
    # assumed (c): the chunks are B, C, X in this order
    gate_in, gate_out, value = jnp.split(_mm(h, w_in), 3, axis=-1)
    u = r(gate_in * value)
    T = u.shape[1]
    # c_t = sum_j w_j u_{t - (K-1) + j}: shifted adds, zeros before 0
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + T] * w_conv[:, j] for j in range(taps))
    return r(x + _mm(r(gate_out * conv), w_out))


@functools.partial(jax.jit, static_argnames=("at", "dims", "activations"))
def _attention(keys, x, lens, *, at, dims, activations):
    """x + attention; also (k, v) as the cache holds them."""
    hidden, heads, kv_heads, head_dim, theta, eps, dtype = dims
    S, T, _ = x.shape
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)
    q_out, kv_out = heads * head_dim, kv_heads * head_dim
    wq = _stacked(keys[5], at, (hidden, q_out), hidden, dt)
    wk = _stacked(keys[6], at, (hidden, kv_out), hidden, dt)
    wv = _stacked(keys[7], at, (hidden, kv_out), hidden, dt)
    wo = _stacked(keys[8], at, (q_out, hidden), q_out, dt)
    q_norm, k_norm = ((1.0 + SPREAD * _draw(key, at, (head_dim,))
                       ).astype(dt).astype(jnp.float32)
                      for key in (keys[9], keys[10]))
    h = r(rms_norm(x, eps))
    positions = jnp.broadcast_to(jnp.arange(T), (S, T))
    # assumed (e): normed per head, with a learned weight, BEFORE the
    # rotation
    q = rms_norm(_mm(h, wq).reshape(S, T, heads, head_dim), eps) * q_norm
    k = rms_norm(_mm(h, wk).reshape(S, T, kv_heads, head_dim), eps) * k_norm
    q = r(rope(r(q), positions, theta))
    k = r(rope(r(k), positions, theta))
    v = r(_mm(h, wv).reshape(S, T, kv_heads, head_dim))
    group = heads // kv_heads
    t = jnp.arange(T)
    seen = t[None, :] <= t[:, None]

    def one(args):  # a sequence at a time: the scores are [heads, T, T]
        q1, k1, v1, n = args
        qg = q1.reshape(T, kv_heads, group, head_dim)
        scores = jnp.einsum("tkgd,ukd->kgtu", qg, k1,
                            precision=HIGHEST) / math.sqrt(head_dim)
        mask = seen & (t[None, :] < n)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("kgtu,ukd->tkgd", jax.nn.softmax(scores, -1), v1,
                          precision=HIGHEST).reshape(T, heads, head_dim)

    attn = r(jax.lax.map(one, (q, k, v, lens))).reshape(S, T, q_out)
    return r(x + _mm(attn, wo)), k, v


@functools.partial(jax.jit, static_argnames=("at", "dims", "activations"))
def _dense_mlp(keys, x, *, at, dims, activations):
    hidden, inter, eps, dtype = dims
    mat = functools.partial(_stacked, index=at, dtype=jnp.dtype(dtype))
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, eps))
    return r(x + _swiglu(
        h, mat(keys[11], shape=(hidden, inter), fan_in=hidden),
        mat(keys[12], shape=(hidden, inter), fan_in=hidden),
        mat(keys[13], shape=(inter, hidden), fan_in=inter), r))


@functools.partial(jax.jit, static_argnames=("at", "dims", "activations"))
def _sparse_mlp(keys, x, *, at, dims, activations):
    """x + the experts' weighted outputs."""
    hidden, width, experts, top_k, scaling, biased, eps, dtype = dims
    S, T, _ = x.shape
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, eps)).reshape(S * T, hidden)
    router = _stacked(keys[14], at, (hidden, experts), hidden, dt)
    scores = jax.nn.sigmoid(_mm(h, router))
    # assumed (d): the selection bias is drawn from the seed, spread 0.1.
    # It decides which experts, never their weights.
    bias = SPREAD * _draw(keys[18], at, (experts,)) if biased else 0.0
    _, chosen = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + ROUTER_EPS) * scaling
    weights = jnp.zeros_like(scores).at[
        jnp.arange(S * T)[:, None], chosen].set(top)

    step = math.gcd(experts, EXPERT_BLOCK)

    def block(total, first):
        for e in range(step):
            index = at * experts + first + e
            out = _swiglu(
                h, _stacked(keys[15], index, (hidden, width), hidden, dt),
                _stacked(keys[16], index, (hidden, width), hidden, dt),
                _stacked(keys[17], index, (width, hidden), width, dt), r)
            w = jax.lax.dynamic_slice_in_dim(weights, first + e, 1, axis=1)
            total = total + w * out
        return total, None

    routed, _ = jax.lax.scan(
        block, jnp.zeros_like(h),
        jnp.arange(0, experts, step, dtype=jnp.int32))
    return r(x + r(routed).reshape(S, T, hidden))


def _table(key, vocab, hidden, dtype):
    return (0.02 * normal_rows(key, jnp.uint32(0), vocab * hidden)
            .reshape(vocab, hidden)).astype(jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _embed(key, tokens, *, vocab, hidden, dtype):
    return _table(key, vocab, hidden, dtype)[tokens].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "eps", "dtype", "activations"))
def _logprobs(key, x, *, vocab, eps, dtype, activations=None):
    # assumed (a): the head is the embedding transposed; (e): behind the
    # final norm
    table = _table(key, vocab, x.shape[-1], dtype).astype(jnp.float32)
    h = _rounded(rms_norm(x, eps), activations)
    return jax.nn.log_softmax(_mm(h, table.T), axis=-1)


def _forward_block(hf, keys, tokens, lens, keep_from, dtype, kv_layers,
                   activations):
    layers = hf["num_hidden_layers"]
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    kv_heads = hf["num_key_value_heads"]
    head_dim = hf.get("head_dim") or hidden // heads
    eps, vocab = float(hf.get("norm_eps", 1e-5)), hf["vocab_size"]
    kinds = hf["layer_types"][:layers]
    dense = min(hf.get("num_dense_layers", 0), layers)
    rope_block = hf.get("rope_parameters") or {}
    theta = float(rope_block.get("rope_theta", 1000000.0))
    x = _rounded(_embed(keys[0], tokens, vocab=vocab, hidden=hidden,
                        dtype=dtype), activations)
    kept = {}
    for layer in range(layers):
        at = kinds[:layer].count(kinds[layer])
        if kinds[layer] == "conv":
            x = _short_conv(
                keys, x, at=at,
                dims=(hidden, int(hf.get("conv_L_cache", 3)), eps, dtype),
                activations=activations)
        else:
            x, k, v = _attention(
                keys, x, lens, at=at,
                dims=(hidden, heads, kv_heads, head_dim, theta, eps, dtype),
                activations=activations)
            if at in kv_layers:
                kept[at] = (np.asarray(k), np.asarray(v))
        if layer < dense:
            x = _dense_mlp(keys, x, at=layer,
                           dims=(hidden, hf["intermediate_size"], eps, dtype),
                           activations=activations)
        else:
            x = _sparse_mlp(
                keys, x, at=layer - dense,
                dims=(hidden, hf["moe_intermediate_size"], hf["num_experts"],
                      hf["num_experts_per_tok"],
                      float(hf.get("routed_scaling_factor", 1.0)),
                      bool(hf.get("use_expert_bias", False)), eps, dtype),
                activations=activations)
    logp = _logprobs(keys[0], x[:, keep_from:], vocab=vocab, eps=eps,
                     dtype=dtype, activations=activations)
    return np.asarray(logp), kept


def forward(hf: dict, seed: int, tokens, lens, *, keep_from: int,
            quantization=None, dtype="bfloat16", kv_layers=(0,),
            activations=None, sequence_block: int = SEQUENCE_BLOCK):
    """Log-probabilities [S, T - keep_from, vocab] of the next token after
    each position from ``keep_from`` on, and {n: (k, v)} of the listed
    ones of the layers that have keys and values (counted among those
    alone), for right-padded ``tokens`` [S, T] of lengths ``lens``; in
    blocks of ``sequence_block`` sequences, each a whole forward."""
    if quantization is not None:
        raise ValueError(f"no reference for quantization {quantization!r}")
    if not hf.get("tie_word_embeddings", True):
        raise ValueError("the reference has the tied head only")
    if hf.get("conv_bias") or not hf.get("norm_topk_prob", True):
        raise ValueError("the reference has the published block only: no "
                         "convolution bias, weights renormalised")
    keys = split(seed_key(seed), 20)
    tokens = jnp.asarray(tokens, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    logps, kept = [], {}
    with jax.default_matmul_precision("highest"):
        for first in range(0, tokens.shape[0], sequence_block):
            rows = slice(first, first + sequence_block)
            logp, kv = _forward_block(hf, keys, tokens[rows], lens[rows],
                                      keep_from, dtype, tuple(kv_layers),
                                      activations)
            logps.append(logp)
            for n, sides in kv.items():
                kept.setdefault(n, []).append(sides)
    return np.concatenate(logps), {
        n: tuple(np.concatenate(side) for side in zip(*blocks))
        for n, blocks in kept.items()}
