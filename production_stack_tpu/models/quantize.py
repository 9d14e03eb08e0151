"""Weight-only int8 quantization (per-output-channel, symmetric).

Serves the BASELINE model class on one 16 GB chip: an 8 B-parameter model
is ~16 GB in bf16 (does not fit next to KV + workspace) but ~9 GB with
int8 layer weights (embed/lm_head stay bf16 by default — quantizing them
disproportionately hurts output quality for ~1 GB more;
``quantize_embeddings=True`` reclaims it when HBM is the binding
constraint). The compute path stays bf16 on the MXU — each weight is
stored as ``int8`` plus a per-output-channel ``float32`` scale, and the
dequant (`w.astype(bf16) * scale`) fuses into the matmul's operand read
under XLA, so the HBM weight traffic (the decode bottleneck) halves too.

The reference reaches this class through vLLM's quantization support in
its CUDA images (``--quantization`` engine args in
``helm/templates/deployment-vllm-multi.yaml`` extraArgs); this is the
TPU-native equivalent at the engine layer.

Two entry points with matching semantics (identical up to one-ULP
rounding-tie flips between XLA's and numpy's division):
- :func:`quantize_tree` — traceable (jax.numpy); used inside the jitted
  init so a random-init 8 B model NEVER materializes fully in bf16 on
  device (each leaf quantizes as it is created, peak = one bf16 leaf).
- :func:`quantize_loaded` — numpy; used on host-loaded checkpoints so
  the device transfer ships int8, not bf16.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from production_stack_tpu.models.registry import get_family

# Symmetric int8 range. 127 (not 128) keeps the scale exact for the max.
_QMAX = 127.0


def _quantize_jnp(w, reduce_axis: int):
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=reduce_axis,
                   keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / _QMAX
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def _quantize_np(w: np.ndarray, reduce_axis: int):
    w32 = np.asarray(w, np.float32)
    amax = np.max(np.abs(w32), axis=reduce_axis, keepdims=True)
    scale = np.maximum(amax, 1e-8) / _QMAX
    q = np.clip(np.round(w32 / scale), -_QMAX, _QMAX).astype(np.int8)
    return q, scale.astype(np.float32)


def _apply_tree(params: Dict, arch: str, quant,
                quantize_embeddings: bool) -> Dict:
    """Only the leaves ``params`` carries, of those the family's record
    lets int8 take (a host-loaded checkpoint may hold fewer than the
    init's tree)."""
    keys = get_family(arch).quant_keys
    if not keys:
        raise ValueError(
            f"int8 quantization is supported for the llama family "
            f"(got arch {arch!r})")
    out = dict(params)

    def take(tree: Dict, name: str, reduce_axis: int) -> None:
        if name in tree:
            tree[name], tree[name + "_scale"] = quant(
                tree[name], reduce_axis)

    if "layers" in params:
        out["layers"] = dict(params["layers"])
        for name in keys:
            # [L, in, out] -> int8 [L, in, out] + scale [L, 1, out]
            take(out["layers"], name, -2)
    # embed / lm_head stay bf16 by default: quantizing them hurts output
    # quality disproportionately (standard weight-only recipes exclude
    # them) while saving only ~1 GB of an 8 B model's bytes — the HBM win
    # is nearly unchanged without them.
    if quantize_embeddings:
        # embed [V, Hd]: per-ROW scales [V, 1] — correct for both the
        # lookup (dequant the gathered rows) and the tied head
        # (x @ embed.T scales per output/vocab channel).
        take(out, "embed", -1)
        take(out, "lm_head", -2)  # [Hd, V] -> scale [1, V]
    return out


def quantize_tree(params: Dict, arch: str, *,
                  quantize_embeddings: bool = False) -> Dict:
    """Traceable int8 quantization of a params pytree (use inside jit)."""
    return _apply_tree(params, arch, _quantize_jnp, quantize_embeddings)


def quantize_loaded(loaded: Dict, arch: str, *,
                    quantize_embeddings: bool = False) -> Dict:
    """Numpy twin of :func:`quantize_tree` for host-loaded checkpoints."""
    return _apply_tree(loaded, arch, _quantize_np, quantize_embeddings)
