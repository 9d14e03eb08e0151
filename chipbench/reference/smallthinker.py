"""Plain float32 reference of the SmallThinker decoder (``model_type:
smallthinker``; PowerInfer/SmallThinker-21BA3B-Instruct).

Straight ``jax.numpy``: full causal attention over each whole sequence
(banded in the window layers, unrotated in the NoPE layers), every expert
applied densely to the tokens, no cache, no kernels, no grouped matmul,
every matmul at ``highest`` precision. It takes nothing the program made:
the weights are drawn here from the seed by this file's own copy of the
program's init recipe (``models/smallthinker.py::init_params``: 12 keys
split from the seed; a leaf is stacked over the layers, so layer ``n`` of
a stack is elements ``n * size ..`` of the key's normal array; normal /
sqrt(fan_in), rounded to the served dtype), one layer and one expert at
a time. The counter-based generator and the small helpers are
``chipbench/reference/llama.py``'s (a reference file, not the program).

The equations, for layer ``l`` (per-layer lists are read for their first
``num_hidden_layers`` entries)::

    h = RMS(x)                                   (norm weights are ones)
    p = softmax over the top k of (h Wr), k = moe_num_active_primary_experts
    q, k, v = h Wq, h Wk, h Wv
    rope_layout[l] == 1: rotate q, k over all head_dim dims, plain
        frequencies, theta rope_theta; == 0: no positional encoding
    causal attention, scale 1/sqrt(head_dim); sliding_window_layout[l]
        == 1: query i sees key j iff 0 <= i - j < sliding_window_size
    x = x + attn Wo
    m = RMS(x)
    x = x + sum_e p_e (relu(m Wgate_e) * (m Wup_e)) Wdown_e
    log_softmax(RMS(x) Whead)

``assumed`` (each marked at its line): (a) the router reads the layer's
normed input ``h``, before attention, and its weights reach the experts
on the post-attention ``m``; (b) the gate's activation is ``relu``
(ReGLU), computed densely; (c) primary experts only, no shared expert;
(e) no QK norm and no bias.

**Sized for contexts of 6k and a vocabulary of 152k beside a serving
engine** (the check's prompts pass the 4,096-token window): the whole
forward runs a sequence at a time at the sequence's own length (no
padding is computed), attention in blocks of ``QUERY_BLOCK`` queries, so
the scores are ``[heads, 256, T]``; the embedding draws only the rows
the tokens name; the head is drawn ``HEAD_BLOCK`` columns at a time,
each block's logits go to the host, and the log-softmax over all columns
is taken there in float32 (the log-probabilities, 1.5 GB at the check's
sizes, never sit on the device). ``_normal_at`` is ``reference/llama.py``'s
``normal_rows`` for any flat indices, and the one generator here.

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
    rms_norm,
    rope,
    seed_key,
    split,
    threefry2x32,
)

KEYS = 12
EMBED, HEAD, WQ, WK, WV, WO, ROUTER, GATE, UP, DOWN = range(10)
QUERY_BLOCK = 256  # queries attended at once
HEAD_BLOCK = 16384  # columns of the head drawn at once
HEAD_ROWS = 2048  # positions whose logits are made at once


def _normal_at(key, index):
    """``normal_rows`` at the flat indices ``index`` (uint32, any shape):
    the elements of the standard-normal array this key generates."""
    b0, b1 = threefry2x32(key[0], key[1], jnp.zeros_like(index), index)
    bits = ((b0 ^ b1) >> np.uint32(9)) | np.uint32(0x3F800000)
    unit = jax.lax.bitcast_convert_type(bits, jnp.float32) - 1.0
    low = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jnp.maximum(low, unit * (np.float32(1.0) - low) + low)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


def _stacked(key, index, shape, fan_in, dtype):
    """Entry ``index`` of the leaf ``[n, *shape]`` that ``key`` draws."""
    size = math.prod(shape)
    w = _normal_at(key, jnp.asarray(index, jnp.uint32) * jnp.uint32(size)
                   + jnp.arange(size, dtype=jnp.uint32))
    return (w.reshape(shape) / jnp.sqrt(jnp.float32(fan_in))
            ).astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("hidden", "dtype"))
def _embed(key, tokens, *, hidden, dtype):
    """The rows ``tokens`` name of the table ``0.02 normal [vocab,
    hidden]``, and no other row of it."""
    index = (tokens.astype(jnp.uint32)[..., None] * jnp.uint32(hidden)
             + jnp.arange(hidden, dtype=jnp.uint32))
    return (0.02 * _normal_at(key, index)).astype(
        jnp.dtype(dtype)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _project(keys, layer, x, *, dims, activations):
    """(router weights [S, T, experts], q, k, v) of the layer's input."""
    (hidden, heads, kv_heads, head_dim, experts, top_k, rotate, theta, eps,
     dtype) = dims
    S, T, _ = x.shape
    mat = functools.partial(_stacked, index=layer, dtype=jnp.dtype(dtype))
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, eps))  # the norm weights are initialised to one
    # assumed (a): the router reads the normed INPUT of the layer, ahead
    # of attention; softmax over the selected logits.
    logits = _mm(h, mat(keys[ROUTER], shape=(hidden, experts),
                        fan_in=hidden))
    top, chosen = jax.lax.top_k(logits, top_k)
    weights = jnp.zeros_like(logits).at[
        jnp.arange(S)[:, None, None], jnp.arange(T)[None, :, None],
        chosen].set(jax.nn.softmax(top, axis=-1))
    q_out, kv_out = heads * head_dim, kv_heads * head_dim
    # assumed (e): no bias, no norm on queries or keys.
    q = _mm(h, mat(keys[WQ], shape=(hidden, q_out), fan_in=hidden)
            ).reshape(S, T, heads, head_dim)
    k = _mm(h, mat(keys[WK], shape=(hidden, kv_out), fan_in=hidden)
            ).reshape(S, T, kv_heads, head_dim)
    v = _mm(h, mat(keys[WV], shape=(hidden, kv_out), fan_in=hidden)
            ).reshape(S, T, kv_heads, head_dim)
    if rotate:  # rope_layout 1; a NoPE layer rotates nothing
        positions = jnp.broadcast_to(jnp.arange(T), (S, T))
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    return weights, r(q), r(k), r(v)


@functools.partial(jax.jit, static_argnames=("window",))
def _attend_block(q, k, v, first, *, window):
    """Attention of the queries ``q [B, heads, D]`` at positions ``first
    ..`` of ONE sequence over its keys and values ``[T, kv_heads, D]``."""
    B, heads, head_dim = q.shape
    T, kv_heads, _ = k.shape
    qg = q.reshape(B, kv_heads, heads // kv_heads, head_dim)
    scores = jnp.einsum("tkgd,ukd->kgtu", qg, k,
                        precision=HIGHEST) / math.sqrt(head_dim)
    i = first + jnp.arange(B)[:, None]
    j = jnp.arange(T)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    return jnp.einsum("kgtu,ukd->tkgd", jax.nn.softmax(scores, -1), v,
                      precision=HIGHEST).reshape(B, heads, head_dim)


def _attention(q, k, v, window):
    """[1, T, heads, D] of one sequence, ``QUERY_BLOCK`` queries at a
    time."""
    T = q.shape[1]
    return jnp.concatenate([
        _attend_block(q[0, first:first + QUERY_BLOCK], k[0], v[0],
                      jnp.int32(first), window=window)
        for first in range(0, T, QUERY_BLOCK)], axis=0)[None]


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _close_attention(keys, layer, x, attn, *, dims, activations):
    hidden, q_out, eps, dtype = dims
    r = functools.partial(_rounded, activations=activations)
    wo = _stacked(keys[WO], layer, (q_out, hidden), q_out, jnp.dtype(dtype))
    x = r(x + _mm(r(attn).reshape(attn.shape[:2] + (q_out,)), wo))
    return x, r(rms_norm(x, eps))


@functools.partial(jax.jit, static_argnames=("dims", "activations"))
def _experts(keys, layer, x, m, weights, *, dims, activations):
    """x + every expert's output on ``m`` under the router's ``weights``
    (zero for an expert a token did not select)."""
    hidden, width, experts, dtype = dims
    dt = jnp.dtype(dtype)
    r = functools.partial(_rounded, activations=activations)

    def one(total, e):
        index = layer * jnp.uint32(experts) + e
        # assumed (b): ReGLU, relu on the gate, every column computed.
        act = r(jax.nn.relu(_mm(m, _stacked(keys[GATE], index,
                                            (hidden, width), hidden, dt)))
                * _mm(m, _stacked(keys[UP], index, (hidden, width), hidden,
                                  dt)))
        out = _mm(act, _stacked(keys[DOWN], index, (width, hidden), width,
                                dt))
        w = jax.lax.dynamic_slice_in_dim(weights, e, 1, axis=-1)
        return total + w * out, None

    # assumed (c): primary experts only, no shared expert.
    routed, _ = jax.lax.scan(one, jnp.zeros_like(m),
                             jnp.arange(experts, dtype=jnp.uint32))
    return r(x + r(routed))


@functools.partial(jax.jit,
                   static_argnames=("vocab", "first", "width", "dtype"))
def _logits(key, h, *, vocab, first, width, dtype):
    """``h [N, hidden]`` times columns ``first .. first + width`` of the
    head ``[hidden, vocab]``, drawn here and nowhere else."""
    hidden = h.shape[-1]
    index = (jnp.arange(hidden, dtype=jnp.uint32)[:, None]
             * jnp.uint32(vocab)
             + jnp.arange(first, first + width, dtype=jnp.uint32)[None, :])
    head = (_normal_at(key, index) / jnp.sqrt(jnp.float32(hidden))
            ).astype(jnp.dtype(dtype)).astype(jnp.float32)
    return _mm(h, head)


def _logprobs(key, x, *, vocab, eps, dtype, activations):
    """Log-probabilities ``[S, T, vocab]`` (numpy, float32) of the states
    ``x``: logits by blocks of columns and of positions on the device,
    the log-softmax over all columns on the host."""
    S, T, hidden = x.shape
    h = _rounded(rms_norm(x, eps), activations).reshape(S * T, hidden)
    out = np.empty((S * T, vocab), np.float32)
    for row in range(0, S * T, HEAD_ROWS):
        rows = h[row:row + HEAD_ROWS]
        for first in range(0, vocab, HEAD_BLOCK):
            width = min(HEAD_BLOCK, vocab - first)
            out[row:row + HEAD_ROWS, first:first + width] = np.asarray(
                _logits(key, rows, vocab=vocab, first=first, width=width,
                        dtype=dtype))
    for row in range(0, S * T, HEAD_ROWS):  # in place, a block at a time
        block = out[row:row + HEAD_ROWS]
        top = block.max(axis=-1, keepdims=True)
        block -= top
        block -= np.log(np.exp(block, dtype=np.float32).sum(
            axis=-1, keepdims=True, dtype=np.float32))
    return out.reshape(S, T, vocab)


def forward(hf: dict, seed: int, tokens, lens, *, keep_from: int,
            quantization=None, dtype="bfloat16", kv_layers=(0,),
            activations=None):
    """Log-probabilities [S, T - keep_from, vocab] of the next token
    after each position from ``keep_from`` on, and {layer: (k, v)} of the
    listed layers (keys as the cache holds them: rotated in a rotary
    layer, as projected in a NoPE layer), for right-padded ``tokens``
    [S, T] of lengths ``lens``. ``hf`` holds the sizes under their
    published keys."""
    if quantization is not None:
        raise ValueError(f"no reference for quantization {quantization!r}")
    if hf.get("tie_word_embeddings") or hf.get("rope_scaling"):
        raise ValueError("the reference has the published model only: an "
                         "untied head, no rope_scaling")
    if not (hf.get("moe_primary_router_apply_softmax", True)
            and hf.get("norm_topk_prob", True)):
        raise ValueError("the reference has the published router only: a "
                         "softmax, renormalised over the selected experts")
    layers = hf["num_hidden_layers"]
    hidden, head_dim = hf["hidden_size"], hf["head_dim"]
    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    eps, vocab = float(hf["rms_norm_eps"]), hf["vocab_size"]
    experts = hf["moe_num_primary_experts"]
    windowed = hf["sliding_window_layout"][:layers]
    rotated = hf["rope_layout"][:layers]
    keys = split(seed_key(seed), KEYS)
    tokens = np.asarray(tokens, np.int32)
    S, T = tokens.shape
    logp = np.zeros((S, T - keep_from, vocab), np.float32)
    kept = {layer: tuple(np.zeros((S, T, kv_heads, head_dim), np.float32)
                         for _ in "kv") for layer in kv_layers}
    with jax.default_matmul_precision("highest"):
        for s, n in enumerate(int(n) for n in lens):
            # One sequence, at its own length: what lies past it is
            # padding, which nothing reads.
            x = _rounded(_embed(keys[EMBED], jnp.asarray(tokens[s:s + 1, :n]),
                                hidden=hidden, dtype=dtype), activations)
            for layer in range(layers):
                weights, q, k, v = _project(
                    keys, jnp.uint32(layer), x,
                    dims=(hidden, heads, kv_heads, head_dim, experts,
                          hf["moe_num_active_primary_experts"],
                          bool(rotated[layer]), float(hf["rope_theta"]),
                          eps, dtype),
                    activations=activations)
                if layer in kept:
                    kept[layer][0][s, :n] = np.asarray(k[0])
                    kept[layer][1][s, :n] = np.asarray(v[0])
                attn = _attention(
                    q, k, v,
                    hf["sliding_window_size"] if windowed[layer] else 0)
                x, m = _close_attention(
                    keys, jnp.uint32(layer), x, attn,
                    dims=(hidden, heads * head_dim, eps, dtype),
                    activations=activations)
                x = _experts(keys, jnp.uint32(layer), x, m, weights,
                             dims=(hidden, hf["moe_ffn_hidden_size"],
                                   experts, dtype),
                             activations=activations)
            if n > keep_from:
                logp[s, :n - keep_from] = _logprobs(
                    keys[HEAD], x[:, keep_from:], vocab=vocab, eps=eps,
                    dtype=dtype, activations=activations)[0]
    return logp, kept
