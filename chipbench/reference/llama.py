"""Plain float32 reference of the Llama/Mistral decoder block.

Straight ``jax.numpy``: full causal attention over the whole sequence, no
cache, no kernels, no batching tricks, every matmul at ``highest``
precision. It takes nothing the program made. The weights are drawn here
from the seed with this file's own copy of the counter-based generator
(Threefry-2x32, Salmon et al. 2011) and of the program's init recipe
(``models/llama.py::init_params``: ten keys split from the seed, normal /
sqrt(fan_in), rounded to the served dtype), one layer at a time, so a
32-layer model never has to exist twice on the device. With
``quantization="int8"`` each layer matrix is quantised here per output
channel (symmetric, 127 levels) and dequantised to float32: the same
int8 weights the configuration states, but none of the program's arrays.

Departures from the published Mistral description: none in the block
(RMSNorm, rotary embedding in the half-split layout, grouped-query
attention, SwiGLU). The published ``sliding_window`` is not applied: the
configurations keep every context within it (see their ``assumed``).

``activations`` names a lower-precision type (``float8_e4m3fn``) to which
every activation is rounded on its way between operations: not the
reference any more but a control, the reference computed one precision
step below the bf16 the configuration states (``chipbench.control``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))
_QMAX = 127.0


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """Threefry-2x32, 20 rounds: the two output words for key (k0, k1)
    and counter words (c0, c1), all uint32."""
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
    x0, x1 = c0 + ks[0], c1 + ks[1]
    for i in range(5):
        for r in _ROT[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def seed_key(seed: int):
    """The generator's key words for ``seed`` (a non-negative integer)."""
    return (jnp.uint32((seed >> 32) & 0xFFFFFFFF),
            jnp.uint32(seed & 0xFFFFFFFF))


def split(key, n: int):
    """``n`` sub-keys of ``key``: the words at counters 0..n-1."""
    lo = jnp.arange(n, dtype=jnp.uint32)
    b0, b1 = threefry2x32(key[0], key[1], jnp.zeros_like(lo), lo)
    return [(b0[i], b1[i]) for i in range(n)]


def normal_rows(key, offset, n: int):
    """Elements ``offset .. offset+n`` (flat index) of the standard-normal
    array this key generates: 32 random bits per element from the
    counter, 23 of them as the mantissa of a float in [1, 2), mapped to
    (-1, 1) and through the inverse error function."""
    lo = offset.astype(jnp.uint32) + jnp.arange(n, dtype=jnp.uint32)
    b0, b1 = threefry2x32(key[0], key[1], jnp.zeros_like(lo), lo)
    bits = ((b0 ^ b1) >> np.uint32(9)) | np.uint32(0x3F800000)
    unit = jax.lax.bitcast_convert_type(bits, jnp.float32) - 1.0
    low = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jnp.maximum(low, unit * (np.float32(1.0) - low) + low)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


def _matrix(key, layer, shape, fan_in, dtype):
    n = shape[0] * shape[1]
    w = normal_rows(key, layer * n, n).reshape(shape)
    return (w / jnp.sqrt(jnp.float32(fan_in))).astype(dtype)


def _served(w, quantization):
    """The float32 value of a layer matrix as the configuration serves
    it: the rounded weight itself, or its int8 quantisation (one scale
    per output channel) dequantised."""
    w = w.astype(jnp.float32)
    if quantization is None:
        return w
    if quantization != "int8":
        raise ValueError(f"no reference for quantization {quantization!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True),
                        1e-8) / _QMAX
    return jnp.clip(jnp.round(w / scale), -_QMAX, _QMAX) * scale


def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, positions, theta):
    """Rotary embedding, half-split layout; x is [S, T, heads, D]."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rounded(x, activations):
    """x as it is after a stay in the ``activations`` type (None: as it
    is)."""
    if activations is None:
        return x
    return x.astype(jnp.dtype(activations)).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("dims", "quantization", "activations"))
def _layer(keys, layer, x, lens, *, dims, quantization, activations=None):
    """One decoder layer on x [S, T, hidden]; returns (x, k, v) with the
    keys after the rotary embedding, as the cache holds them."""
    hidden, heads, kv_heads, head_dim, inter, theta, eps, dtype = dims
    S, T, _ = x.shape
    mat = functools.partial(_matrix, layer=layer, dtype=jnp.dtype(dtype))
    q_out, kv_out = heads * head_dim, kv_heads * head_dim
    wq = _served(mat(keys[1], shape=(hidden, q_out), fan_in=hidden),
                 quantization)
    wk = _served(mat(keys[2], shape=(hidden, kv_out), fan_in=hidden),
                 quantization)
    wv = _served(mat(keys[3], shape=(hidden, kv_out), fan_in=hidden),
                 quantization)
    wo = _served(mat(keys[4], shape=(q_out, hidden), fan_in=q_out),
                 quantization)
    positions = jnp.broadcast_to(jnp.arange(T), (S, T))
    r = functools.partial(_rounded, activations=activations)
    h = r(rms_norm(x, eps))  # the norm weights are initialised to one
    q = r(rope(_mm(h, wq).reshape(S, T, heads, head_dim), positions, theta))
    k = r(rope(_mm(h, wk).reshape(S, T, kv_heads, head_dim), positions,
               theta))
    v = r(_mm(h, wv).reshape(S, T, kv_heads, head_dim))
    group = heads // kv_heads
    qg = q.reshape(S, T, kv_heads, group, head_dim)
    scores = jnp.einsum("stkgd,sukd->skgtu", qg, k,
                        precision=HIGHEST) / math.sqrt(head_dim)
    t = jnp.arange(T)
    mask = (t[None, :] <= t[:, None])[None] & (
        t[None, None, :] < lens[:, None, None])
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    attn = r(jnp.einsum("skgtu,sukd->stkgd", jax.nn.softmax(scores, -1), v,
                        precision=HIGHEST).reshape(S, T, q_out))
    x = r(x + _mm(attn, wo))
    w_gate = _served(mat(keys[5], shape=(hidden, inter), fan_in=hidden),
                     quantization)
    w_up = _served(mat(keys[6], shape=(hidden, inter), fan_in=hidden),
                   quantization)
    h = r(rms_norm(x, eps))
    act = r(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up))
    del w_gate, w_up
    w_down = _served(mat(keys[7], shape=(inter, hidden), fan_in=inter),
                     quantization)
    return r(x + _mm(act, w_down)), k, v


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "dtype"))
def _embed(key, tokens, *, vocab, hidden, dtype):
    table = (0.02 * normal_rows(key, jnp.uint32(0), vocab * hidden)
             .reshape(vocab, hidden)).astype(jnp.dtype(dtype))
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("vocab", "eps", "dtype", "activations"))
def _logprobs(key, x, *, vocab, eps, dtype, activations=None):
    hidden = x.shape[-1]
    head = _matrix(key, jnp.uint32(0), (hidden, vocab), hidden,
                   jnp.dtype(dtype)).astype(jnp.float32)
    h = _rounded(rms_norm(x, eps), activations)
    return jax.nn.log_softmax(_mm(h, head), axis=-1)


def forward(hf: dict, seed: int, tokens, lens, *, keep_from: int,
            quantization=None, dtype="bfloat16", kv_layers=(0,),
            activations=None):
    """Log-probabilities [S, T - keep_from, vocab] of the next token
    after each position from ``keep_from`` on, and {layer: (k, v)} of the
    listed layers, for right-padded ``tokens`` [S, T] of lengths
    ``lens``. ``hf`` holds the sizes under their published (HuggingFace)
    keys."""
    if hf.get("tie_word_embeddings"):
        raise ValueError("the reference has no tied-head path")
    hidden, heads = hf["hidden_size"], hf["num_attention_heads"]
    dims = (hidden, heads, hf.get("num_key_value_heads", heads),
            hf.get("head_dim") or hidden // heads, hf["intermediate_size"],
            float(hf.get("rope_theta", 10000.0)),
            float(hf.get("rms_norm_eps", 1e-5)), dtype)
    vocab = hf["vocab_size"]
    keys = split(seed_key(seed), 10)
    tokens, lens = jnp.asarray(tokens, jnp.int32), jnp.asarray(lens,
                                                               jnp.int32)
    x = _rounded(_embed(keys[0], tokens, vocab=vocab, hidden=hidden,
                        dtype=dtype), activations)
    kept = {}
    for layer in range(hf["num_hidden_layers"]):
        x, k, v = _layer(keys, jnp.uint32(layer), x, lens, dims=dims,
                         quantization=quantization, activations=activations)
        if layer in kv_layers:
            kept[layer] = (np.asarray(k), np.asarray(v))
    logp = _logprobs(keys[8], x[:, keep_from:], vocab=vocab, eps=dims[6],
                     dtype=dtype, activations=activations)
    return np.asarray(logp), kept
