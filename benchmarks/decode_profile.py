#!/usr/bin/env python3
"""Decode-burst component profile: where does the burst time go?

BENCH_DISPATCH_r04 established the engine is on-chip bound and the
256-token burst runs well above the HBM floor. This script decomposes the
burst by ABLATION — recompiling the fused multi-step decode program with
individual components removed (monkeypatched to cheap identities) and
differencing the pipelined steady-state times:

  full            the engine's real burst (baseline)
  nosample        sampling+penalties+logprobs replaced by argmax feedback
  noattn          paged attention replaced by a zeros passthrough
  nowrite         KV page scatter replaced by identity
  noattn_nowrite  both removed -> pure matmul chain + sampling
  xla_attn        pallas kernel swapped for the XLA gather fallback

plus standalone microbenches (pallas kernel at serving shapes over L
layers; the sampling chain alone in a K-step scan) and a context sweep
(the attention term scales with ctx; weights/sampling do not).

All programs run at the flagship serving shape: tpu-llama-1b, B=16, K=16
decode steps, 64-wide block table, ctx ~3000, scattered page ids.

Writes ONE JSON line (redirect to BENCH_DECODE_PROFILE_r{N}.json).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))

from benchmarks._profile_common import (  # noqa: E402
    hbm_bytes_per_s,
    build_engine,
    install_params_holder,
    params_bytes,
    pipelined_seconds,
)

MODEL = os.environ.get("PROFILE_MODEL", "tpu-llama-1b")
CTX = int(os.environ.get("PROFILE_CTX", "3000"))
REPS = int(os.environ.get("PROFILE_REPS", "8"))


def _engine(num_blocks=900):
    return build_engine(MODEL, num_blocks=num_blocks)


def _burst_args(core, ctx, rng):
    import numpy as np

    from production_stack_tpu.engine.sampling import (
        MAX_LOGIT_BIAS,
        MAX_STOP_IDS,
    )

    cfg = core.config
    B, K, maxb = cfg.max_num_seqs, cfg.decode_steps, 64
    # Scattered (realistic) page ids: each sequence's live pages land
    # anywhere in the pool, like they do after eviction/reuse churn.
    bt = rng.integers(0, core.num_blocks, size=(B, maxb)).astype(np.int32)
    return (core.params, core.kv, core._token_counts,
            np.ones((B,), bool), np.zeros((B, K), np.int32),
            np.zeros((B,), np.int32), np.zeros((B,), np.int32),
            np.ones((B,), bool), np.full((B,), ctx, np.int32),
            np.full((B, K), -1, np.int64),
            bt,
            np.full((B,), ctx, np.int32), np.zeros((B,), np.int32),
            np.zeros((B,), np.float32), np.zeros((B,), np.int32),
            np.ones((B,), np.float32), np.zeros((B,), np.int64),
            np.zeros((B,), np.float32), np.zeros((B,), np.float32),
            np.zeros((B,), np.int32), np.zeros((B,), np.int32),
            np.zeros((B, MAX_LOGIT_BIAS), np.int32),
            np.zeros((B, MAX_LOGIT_BIAS), np.float32),
            np.zeros((B, MAX_STOP_IDS), np.int32),
            np.zeros((B, MAX_STOP_IDS), np.float32))


def _time_burst(core, fn, ctx, reps=REPS):
    """Pipelined steady-state seconds per burst."""
    import numpy as np

    rng = np.random.default_rng(0)
    args = _burst_args(core, ctx, rng)

    def run():
        outs, core.kv, core._token_counts = fn(
            args[0], core.kv, core._token_counts, *args[3:])
        return outs

    return pipelined_seconds(run, lambda outs: np.asarray(outs[0]),
                             reps=reps)


def _fresh_decode_fn(core, K=16):
    """Build (don't cache) the fused decode program with CURRENT globals,
    so monkeypatched components get traced in."""
    return core._make_multi_decode(K)


def _ablate(core, *, attn=None, write=None, sample=False):
    """Context manager-free patcher: returns (fn, restore_callback)."""
    import jax.numpy as jnp

    from production_stack_tpu.engine import core as core_mod
    from production_stack_tpu.models import decoder

    saved = {}
    if attn is not None:
        saved[("decoder", "paged_decode_attention")] = (
            decoder.paged_decode_attention)
        decoder.paged_decode_attention = attn
    if write is not None:
        saved[("decoder", "write_kv_pages")] = decoder.write_kv_pages
        decoder.write_kv_pages = write
    if sample:
        saved[("core", "sample_with_logprobs")] = (
            core_mod.sample_with_logprobs)
        core_mod.sample_with_logprobs = (
            lambda logits, keys, t, k, p, max_top_k=64: (
                jnp.argmax(logits, axis=-1),
                jnp.zeros(logits.shape[0], jnp.float32),
                jnp.zeros((logits.shape[0], 8), jnp.float32),
                jnp.zeros((logits.shape[0], 8), jnp.int32)))

    def restore():
        for (mod, name), v in saved.items():
            setattr(decoder if mod == "decoder" else core_mod, name, v)

    return restore


def _bench_kernel_standalone(core, ctx, reps=REPS):
    """The pallas kernel alone, called L times (one per layer) per rep,
    at exact serving shapes with scattered tables."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mc = core.model_config
    B, maxb = core.config.max_num_seqs, 64
    k_pages, v_pages = core.kv
    rng = np.random.default_rng(1)
    bt = jnp.asarray(
        rng.integers(0, core.num_blocks, size=(B, maxb)), jnp.int32)
    cl = jnp.full((B,), ctx, jnp.int32)
    q = jnp.asarray(
        rng.standard_normal((B, mc.num_heads, mc.head_dim)), mc.jnp_dtype)
    from production_stack_tpu.ops.pallas_paged_attention import (
        pallas_paged_attention,
    )
    scale = 1.0 / (mc.head_dim ** 0.5)

    @jax.jit
    def all_layers(q, k_pages, v_pages, bt, cl):
        def body(acc, l):
            o = pallas_paged_attention(
                q, k_pages, v_pages, bt, cl, l, scale=scale)
            return acc + o.astype(jnp.float32), None
        out, _ = jax.lax.scan(
            body, jnp.zeros(q.shape, jnp.float32),
            jnp.arange(mc.num_layers))
        return out

    return pipelined_seconds(
        lambda: all_layers(q, k_pages, v_pages, bt, cl),
        np.asarray, reps=reps)


def _bench_sampling_standalone(core, K=16, reps=REPS):
    """The full per-step logits pipeline (penalties + bias + top-k sample
    + logprob outputs) in a K-step scan, no model forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.engine.sampling import (
        make_rng_keys,
        sample_with_logprobs,
    )

    B, V = core.config.max_num_seqs, core.model_config.vocab_size
    rng = np.random.default_rng(2)
    logits0 = jnp.asarray(rng.standard_normal((B, V)), jnp.float32)
    counts0 = jnp.zeros((B, V), jnp.int32)
    temp = jnp.ones((B,), jnp.float32)
    topk = jnp.zeros((B,), jnp.int32)
    topp = jnp.ones((B,), jnp.float32)
    fp = jnp.zeros((B,), jnp.float32)
    pp = jnp.zeros((B,), jnp.float32)

    @jax.jit
    def chain(logits, counts):
        def body(carry, s):
            counts, acc = carry
            penalized = (logits - fp[:, None] * counts
                         - pp[:, None] * (counts > 0))
            keys = make_rng_keys(0, 0, jnp.zeros((B,), jnp.int64) + s)
            sampled, lp, top_lp, top_ids = sample_with_logprobs(
                penalized, keys, temp, topk, topp)
            counts = counts.at[jnp.arange(B), sampled].add(1)
            return (counts, acc + sampled), None
        (counts, acc), _ = jax.lax.scan(
            body, (counts0, jnp.zeros((B,), jnp.int32)),
            jnp.arange(K))
        return acc

    return pipelined_seconds(
        lambda: chain(logits0, counts0), np.asarray, reps=reps)


def main() -> None:
    import jax
    import jax.numpy as jnp

    backend = jax.devices()[0].platform
    core = _engine()
    mc = core.model_config
    B, K = core.config.max_num_seqs, core.config.decode_steps

    results = {}

    # Baseline: the cached engine program (same as serving uses).
    fn_full = core._multi_decode_fn(K)
    results["full_s"] = _time_burst(core, fn_full, CTX)

    # Context sweep on the SAME program (attention term scales, rest
    # doesn't).
    results["full_ctx512_s"] = _time_burst(core, fn_full, 512)
    results["full_ctx1024_s"] = _time_burst(core, fn_full, 1024)

    # Ablations (fresh programs, patched globals).
    def zero_attn(q, k_pages, v_pages, bt, cl, layer, *, scale):
        return jnp.zeros_like(q)

    def id_write(k_pages, v_pages, k, v, slots, layer):
        return k_pages, v_pages

    restore = _ablate(core, sample=True)
    results["nosample_s"] = _time_burst(core, _fresh_decode_fn(core), CTX)
    restore()

    restore = _ablate(core, attn=zero_attn)
    results["noattn_s"] = _time_burst(core, _fresh_decode_fn(core), CTX)
    restore()

    restore = _ablate(core, write=id_write)
    results["nowrite_s"] = _time_burst(core, _fresh_decode_fn(core), CTX)
    restore()

    restore = _ablate(core, attn=zero_attn, write=id_write, sample=True)
    results["bare_matmul_s"] = _time_burst(
        core, _fresh_decode_fn(core), CTX)
    restore()

    # XLA fallback attention instead of the pallas kernel.
    os.environ["TPU_STACK_FORCE_XLA_ATTENTION"] = "1"
    results["xla_attn_s"] = _time_burst(core, _fresh_decode_fn(core), CTX)
    del os.environ["TPU_STACK_FORCE_XLA_ATTENTION"]

    # Standalone microbenches.
    kernel_all_layers = _bench_kernel_standalone(core, CTX)
    sampling_chain = _bench_sampling_standalone(core, K)
    results["kernel_Llayers_1step_s"] = kernel_all_layers
    results["sampling_chain_Ksteps_s"] = sampling_chain

    core.stop()

    # Derived per-burst component estimates.
    full = results["full_s"]
    comp = {
        "sampling_est_s": round(full - results["nosample_s"], 4),
        "attention_est_s": round(full - results["noattn_s"], 4),
        "pagewrite_est_s": round(full - results["nowrite_s"], 4),
        "bare_matmul_s": round(results["bare_matmul_s"], 4),
        "kernel_standalone_per_burst_s": round(kernel_all_layers * K, 4),
        "sampling_standalone_per_burst_s": round(sampling_chain, 4),
    }

    # Floors at this shape.
    pbytes = params_bytes(core_params_holder[0])
    kv_bytes_step = (CTX * B * mc.num_kv_heads * mc.head_dim * 2 * 2
                     * mc.num_layers)
    peak = hbm_bytes_per_s()
    floors = gap = None  # no device peak (the CPU): no floor to compare
    if peak is not None:
        floors = {
            "weights_read_per_burst_s": round(K * pbytes / peak, 4),
            "kv_read_per_burst_s": round(K * kv_bytes_step / peak, 4),
        }
        floors["combined_floor_s"] = round(
            floors["weights_read_per_burst_s"]
            + floors["kv_read_per_burst_s"], 4)
        gap = round(full / floors["combined_floor_s"], 2)

    out = {
        "metric": "decode_profile",
        "backend": backend,
        "model": MODEL,
        "B": B, "K": K, "ctx": CTX,
        **{k: round(v, 4) for k, v in results.items()},
        "components": comp,
        "floors": floors,
        "gap_vs_combined_floor": gap,
    }
    print(json.dumps(out))


core_params_holder = []

if __name__ == "__main__":
    # Stash params for the floor calc before main() frees the core.
    core_params_holder = install_params_holder()
    main()
