#!/usr/bin/env python3
"""Prefill decomposition profile: where does a 2048-token chunk go?

The chunked-prefill serving path runs the cached-prefill program per
chunk: matmuls over the chunk, a KV page scatter of the fresh keys, and
context attention over everything written so far. This script decomposes
that per-chunk time by ABLATION — recompiling the forward with
individual components replaced by cheap identities and differencing the
pipelined steady-state times (same timing rule as decode_profile.py;
shared scaffolding in benchmarks/_profile_common.py):

  full         the engine's cached-prefill program (attends over HBM pages)
  noattn       both prefill attention variants -> zeros passthrough
  nowrite      KV page scatter -> identity (isolates layout/copy cost)
  bare_matmul  both removed -> the pure matmul chain + fused sampling

Derived per chunk: attention_est = full - noattn, copy_est = full -
nowrite, matmul_est = bare_matmul. The chunk-position sweep shows the
context-attention term growing with how deep into the prompt the chunk
lands, while matmuls and copies stay flat.

Two r18 legs ride along as new top-level artifact keys:

  kernel_ab       flash cached-prefill kernel vs XLA gather path —
                  interpret-mode parity errors (bf16 + int8 pages), the
                  per-chunk attention+copy byte model for each dispatch
                  path, and the total prefill KV-read byte drop; on a
                  TPU backend both paths are additionally wall-timed
                  via TPU_STACK_FORCE_XLA_ATTENTION.
  fused_dispatch  the same mixed prefill+decode workload through
                  --fused-step off/on engines: dispatch counts, fused
                  step records, stream equality.

--hermetic runs tiny-llama at a small chunk so CI can smoke the schema
on CPU in seconds. Writes ONE JSON line (redirect to
BENCH_PREFILL_PROFILE_r{N}.json).
"""

from __future__ import annotations

import argparse
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

core_params_holder = []


def _chunk_args(core, chunk, offset, rng):
    """Call args for the cached-prefill program: one row, ``chunk`` new
    tokens landing at prompt position ``offset``, REAL slot ids (the
    scatter must execute — the nowrite ablation measures it)."""
    import numpy as np

    from production_stack_tpu.engine.sampling import (
        MAX_LOGIT_BIAS,
        MAX_STOP_IDS,
    )

    bs = core.config.block_size
    total = offset + chunk
    nblocks = (total + bs - 1) // bs
    maxb = 4
    while maxb < nblocks:
        maxb *= 2
    maxb = min(maxb, core.config.max_blocks_per_seq)
    # Scattered (realistic) page ids, like the pool looks after churn.
    pages = rng.permutation(core.num_blocks)[:nblocks].astype(np.int32)
    bt = np.zeros((1, maxb), np.int32)
    bt[0, :nblocks] = pages
    pos = np.arange(offset, total, dtype=np.int32)
    slots = (pages[pos // bs].astype(np.int64) * bs + pos % bs)
    return (
        np.zeros((1, chunk), np.int32),          # token ids
        pos[None, :],                            # positions
        slots[None, :],                          # slot mapping (real)
        bt,                                      # block tables
        np.asarray([total], np.int32),           # context lens
        np.asarray([chunk], np.int32),           # seq lens
        np.zeros((1,), np.int32),                # adapter ids
        np.zeros((1,), np.float32),              # temperature
        np.zeros((1,), np.int32),                # top_k
        np.ones((1,), np.float32),               # top_p
        np.zeros((1,), np.int64),                # seq seeds
        np.ones((1,), np.int64),                 # steps
        np.zeros((1,), bool),                    # suppress_eos
        np.zeros((1, MAX_LOGIT_BIAS), np.int32),
        np.zeros((1, MAX_LOGIT_BIAS), np.float32),
        np.zeros((1, MAX_STOP_IDS), np.int32),
        np.zeros((1, MAX_STOP_IDS), np.float32),
        np.zeros((1, core._mask_row_bytes), np.uint8),
        np.zeros((1,), bool),                    # mask on
    )


def _time_chunk(core, fn, chunk, offset, reps):
    import numpy as np

    rng = np.random.default_rng(offset + 3)
    args = _chunk_args(core, chunk, offset, rng)

    def run():
        outs, core.kv = fn(core.params, core.kv, *args)
        return outs

    return pipelined_seconds(run, lambda outs: np.asarray(outs[0]),
                             reps=reps)


def _ablate(*, attn=False, write=False):
    """Patch the attention entry points where the models call them
    (models/decoder.py::attend, every family's); returns a restore
    callback. Fresh programs built afterwards trace the patched ops."""
    import jax.numpy as jnp

    from production_stack_tpu.models import decoder

    saved = {}

    def zero_prefill_attn(q, k, v, *, scale, seq_lens):
        return jnp.zeros_like(q)

    def zero_context_attn(q, k_pages, v_pages, block_tables, positions,
                          context_lens, layer, *, scale,
                          k_new=None, v_new=None, suffix_lens=None):
        return jnp.zeros_like(q)

    def id_write(k_pages, v_pages, k, v, slots, layer):
        return k_pages, v_pages

    if attn:
        saved["prefill_attention"] = decoder.prefill_attention
        saved["context_prefill_attention"] = decoder.context_prefill_attention
        decoder.prefill_attention = zero_prefill_attn
        decoder.context_prefill_attention = zero_context_attn
    if write:
        saved["write_kv_pages"] = decoder.write_kv_pages
        decoder.write_kv_pages = id_write

    def restore():
        for name, v in saved.items():
            setattr(decoder, name, v)

    return restore


def _bench_run_meta() -> dict:
    """Provenance stamp borrowed from bench.py's ``_run_meta`` (loaded
    by path — bench.py lives at the repo root, outside the package)."""
    import importlib.util

    try:
        spec = importlib.util.spec_from_file_location(
            "bench_mod", os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod._run_meta()
    except Exception:  # noqa: BLE001 - provenance is best-effort
        return {"schema": 1}


def _kernel_parity(quantized: bool, seed: int = 0) -> float:
    """Interpret-mode max-abs-err of the flash cached-prefill kernel vs
    the XLA gather reference on a small ragged GQA shape (CPU-safe; the
    same parity the unit tests pin, surfaced in the artifact so a
    regression shows up in the committed numbers too)."""
    import numpy as np

    from production_stack_tpu.ops.attention import (
        context_prefill_attention,
        quantize_kv,
    )
    from production_stack_tpu.ops.pallas_prefill_attention import (
        pallas_prefill_attention,
    )

    B, T, KVH, group, D, L = 2, 8, 8, 2, 128, 1
    bs = 16 if quantized else 8  # int8 tile gate needs bs*KVH % 128 == 0
    MAXB, layer = 4, 0
    NB, S = B * MAXB + 8, MAXB * bs
    rng = np.random.default_rng(seed)
    prefix = np.asarray([0, min(S - T, 2 * bs + 3)], np.int32)
    total = prefix + T
    tables = rng.permutation(NB)[:B * MAXB].reshape(B, MAXB).astype(np.int32)
    ctx = rng.standard_normal((B, S, KVH, D)).astype(np.float32)
    if quantized:
        kq, ks = quantize_kv(np.asarray(ctx))
        kq, ks = np.asarray(kq), np.asarray(ks)
        ctx = np.asarray(kq, np.float32) * ks[..., None]  # what pages hold
        k_pages = np.zeros((L, NB, bs, KVH, D), np.int8)
        # The pool's scale layout is FLAT [L, NB, bs*KVH] (128-lane tile).
        k_scales = np.ones((L, NB, bs * KVH), np.float32)
        for b in range(B):
            for j in range(MAXB):
                k_pages[layer, tables[b, j]] = kq[b, j * bs:(j + 1) * bs]
                k_scales[layer, tables[b, j]] = \
                    ks[b, j * bs:(j + 1) * bs].reshape(-1)
        kp = (k_pages, k_scales)
        vp = (k_pages.copy(), k_scales.copy())
    else:
        k_pages = np.zeros((L, NB, bs, KVH, D), np.float32)
        for b in range(B):
            for j in range(MAXB):
                k_pages[layer, tables[b, j]] = ctx[b, j * bs:(j + 1) * bs]
        kp, vp = k_pages, k_pages.copy()
    positions = prefix[:, None] + np.arange(T, dtype=np.int32)[None, :]
    q = rng.standard_normal((B, T, KVH * group, D)).astype(np.float32)
    fresh = np.take_along_axis(ctx, positions[:, :, None, None], axis=1)
    suffix = np.full((B,), T, np.int32)
    ref = np.asarray(context_prefill_attention(
        q, kp, vp, tables, positions, total, layer, scale=0.09))
    got = np.asarray(pallas_prefill_attention(
        q, kp, vp, tables, positions, total, layer, fresh, fresh.copy(),
        suffix, scale=0.09, interpret=True))
    return float(np.max(np.abs(got - ref)))


def _kernel_ab_leg(core, chunk: int, rows: list, reps: int) -> dict:
    """Flash-vs-gather A/B: interpret-mode parity plus the per-chunk
    attention+copy HBM byte model for each dispatch path. The gather
    path re-reads the FULL context (prefix + fresh chunk) from the page
    pool every chunk; the flash kernel streams only the live prefix
    pages and attends the fresh chunk from VMEM. On a TPU backend the
    two paths are additionally wall-timed via the
    TPU_STACK_FORCE_XLA_ATTENTION override."""
    import numpy as np

    from production_stack_tpu.ops.attention import attention_path

    mc = core.model_config
    cfg = core.config
    quantized = cfg.kv_cache_dtype == "int8"
    tok_bytes = {
        "bf16": mc.num_kv_heads * mc.head_dim * 2 * mc.num_layers * 2,
        "int8": mc.num_kv_heads * mc.head_dim * 2 * mc.num_layers * 1,
    }

    per_chunk = []
    for row in rows:
        o, ctx_len = row["offset"], row["context"]
        entry = {"offset": o,
                 "kv_read_tokens_xla": ctx_len,   # full-context regather
                 "kv_read_tokens_flash": o}       # live prefix pages only
        comp = row["components"]
        measured = row["full_s"]
        # Attention+copy share of the measured chunk: the XLA leg is the
        # direct ablation estimate; the flash leg scales the attention
        # term by its KV-read byte ratio (the copy term — the fresh-KV
        # page scatter — is identical on both paths).
        xla_share = (comp["attention_est_s"] + comp["copy_est_s"]) / measured
        ratio = o / ctx_len if ctx_len else 0.0
        flash_share = (comp["attention_est_s"] * ratio
                       + comp["copy_est_s"]) / measured
        entry["attn_copy_share_xla"] = round(xla_share, 6)
        entry["attn_copy_share_flash_est"] = round(flash_share, 6)
        per_chunk.append(entry)

    read_xla = sum(r["kv_read_tokens_xla"] for r in per_chunk)
    read_flash = sum(r["kv_read_tokens_flash"] for r in per_chunk)
    drop = 1.0 - (read_flash / read_xla) if read_xla else 0.0

    leg = {
        "path_configured": attention_path(
            cfg.block_size, mc.num_kv_heads, mc.head_dim, quantized),
        "interpret_parity": {
            "bf16_max_abs_err": round(_kernel_parity(False), 8),
            "int8_max_abs_err": round(_kernel_parity(True), 8),
        },
        "per_chunk": per_chunk,
        "kv_read_bytes_xla_int8": read_xla * tok_bytes["int8"],
        "kv_read_bytes_flash_int8": read_flash * tok_bytes["int8"],
        "kv_read_bytes_bf16": {
            "xla": read_xla * tok_bytes["bf16"],
            "flash": read_flash * tok_bytes["bf16"],
        },
        "kv_read_bytes_drop_pct": round(100.0 * drop, 2),
    }

    import jax

    if jax.devices()[0].platform == "tpu" and \
            leg["path_configured"] == "pallas":
        # Wall-time both dispatch paths on the real chunk shapes.
        timed = []
        for row in rows:
            o = row["offset"]
            os.environ["TPU_STACK_FORCE_XLA_ATTENTION"] = "1"
            try:
                fn = core._make_forward("prefill_cached")
                t_xla = _time_chunk(core, fn, chunk, o, reps)
            finally:
                os.environ.pop("TPU_STACK_FORCE_XLA_ATTENTION", None)
            t_flash = _time_chunk(core, core._prefill_cached_fn, chunk, o,
                                  reps)
            timed.append({"offset": o, "flash_s": round(t_flash, 6),
                          "xla_s": round(t_xla, 6)})
        leg["timed"] = timed
    return leg


def _fused_dispatch_leg() -> dict:
    """Fused-vs-alternating dispatch A/B: the SAME mixed
    prefill+decode workload through two engines that differ only in
    --fused-step, counting device dispatches. The workload is the
    fused step's home turf — one long-decoding sequence with long
    prompts arriving MID-decode, so every arrival's prefill chunks
    overlap running bursts and each overlapped (prefill, decode) pair
    collapses from two dispatches to one. Hermetic shape (tiny model,
    tiny pages) so the schema smoke exercises it on CPU; the
    dispatch-count delta is shape-independent."""
    import queue
    import time as _time

    import jax

    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.core import EngineCore
    from production_stack_tpu.engine.sampling import SamplingParams

    anchor = list(range(7, 19))                    # decodes for 48 tokens
    arrivals = [list(range(1, 60)), list(range(101, 140))]  # chunked
    out = {"workload": {
        "anchor_prompt": len(anchor), "anchor_max_tokens": 48,
        "arrival_prompts": [len(p) for p in arrivals],
        "arrival_max_tokens": 8,
    }}
    streams = {}
    for label, fused in (("alternating", False), ("fused", True)):
        eng = EngineCore(EngineConfig(
            model="tiny-llama", max_model_len=128, max_num_seqs=4,
            block_size=4, num_blocks=96, min_prefill_bucket=16,
            max_loras=0, enable_chunked_prefill=True,
            max_num_batched_tokens=32, fused_step=fused,
        ), devices=jax.devices()[:1])
        eng.start()
        try:
            queues = {"anchor": queue.Queue()}
            eng.add_request(
                "anchor", list(anchor),
                SamplingParams(max_tokens=48, temperature=0.0,
                               ignore_eos=True),
                lambda t, f, q=queues["anchor"]: q.put((t, f)))
            # Wait until the anchor is demonstrably decoding, then land
            # the long prompts: their chunks overlap its bursts.
            first = queues["anchor"].get(timeout=120)
            for i, prompt in enumerate(arrivals):
                q = queue.Queue()
                queues[f"r{i}"] = q
                eng.add_request(
                    f"r{i}", list(prompt),
                    SamplingParams(max_tokens=8, temperature=0.0,
                                   ignore_eos=True),
                    lambda t, f, q=q: q.put((t, f)))
            results = {"anchor": [first]}
            for rid, q in queues.items():
                tokens = results.get(rid, [])
                if tokens and tokens[0][1] is not None:
                    results[rid] = ([tokens[0][0]], tokens[0][1])
                    continue
                tokens = [t for t, _f in tokens if t is not None]
                deadline = _time.time() + 300
                while _time.time() < deadline:
                    try:
                        token, finish = q.get(timeout=10)
                    except queue.Empty:
                        continue
                    if token is not None:
                        tokens.append(token)
                    if finish is not None:
                        results[rid] = (tokens, finish)
                        break
                else:
                    raise TimeoutError(rid)
            streams[label] = results
            s = eng.stats()
            out[label] = {
                "dispatch_count_total": s["dispatch_count_total"],
                "fused_steps_total": s["fused_steps_total"],
                "step_kinds": {
                    k: v["count"]
                    for k, v in s["step_kind_stats"].items() if v["count"]},
            }
        finally:
            eng.stop()
    out["streams_equal"] = streams["alternating"] == streams["fused"]
    out["dispatches_saved"] = (out["alternating"]["dispatch_count_total"]
                               - out["fused"]["dispatch_count_total"])
    # Per overlapped pair the program count is structural: one fused
    # dispatch where alternating issues two.
    out["dispatches_per_pair"] = {"alternating": 2, "fused": 1}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hermetic", action="store_true",
                    help="tiny-llama, small chunk — CPU schema smoke")
    ap.add_argument("--model", default=os.environ.get(
        "PROFILE_MODEL", "tpu-llama-1b"))
    ap.add_argument("--chunk", type=int, default=int(os.environ.get(
        "PROFILE_CHUNK", "2048")))
    ap.add_argument("--reps", type=int, default=int(os.environ.get(
        "PROFILE_REPS", "8")))
    args = ap.parse_args(argv)

    if args.hermetic:
        args.model, args.chunk, args.reps = "tiny-llama", 128, 2
        max_model_len, num_blocks = 512, 64
        offsets = [0, args.chunk]
    else:
        max_model_len, num_blocks = 8192, 900
        offsets = [0, args.chunk, 2 * args.chunk, 3 * args.chunk]

    import jax

    backend = jax.devices()[0].platform
    global core_params_holder
    core_params_holder = install_params_holder()
    core = build_engine(args.model, max_model_len=max_model_len,
                        max_num_seqs=1, decode_steps=1,
                        num_blocks=num_blocks)
    mc = core.model_config

    chunks = []
    # One fresh program per ablation (compiled once, reused across the
    # offset sweep — offsets change only array VALUES at fixed shapes...
    # except the block-table width, which recompiles per width; that is
    # the same cost serving pays and stays outside the timed region).
    variants = {}
    variants["full_s"] = core._prefill_cached_fn
    restore = _ablate(attn=True)
    variants["noattn_s"] = core._make_forward("prefill_cached")
    restore()
    restore = _ablate(write=True)
    variants["nowrite_s"] = core._make_forward("prefill_cached")
    restore()
    restore = _ablate(attn=True, write=True)
    variants["bare_matmul_s"] = core._make_forward("prefill_cached")
    restore()

    for offset in offsets:
        row = {"offset": offset, "context": offset + args.chunk}
        for name, fn in variants.items():
            row[name] = round(
                _time_chunk(core, fn, args.chunk, offset, args.reps), 6)
        row["components"] = {
            "attention_est_s": round(row["full_s"] - row["noattn_s"], 6),
            "copy_est_s": round(row["full_s"] - row["nowrite_s"], 6),
            "matmul_est_s": round(row["bare_matmul_s"], 6),
        }
        chunks.append(row)

    kernel_ab = _kernel_ab_leg(core, args.chunk, chunks, args.reps)

    core.stop()

    fused_dispatch = _fused_dispatch_leg()

    # Roofline floors per chunk at this shape.
    pbytes = params_bytes(core_params_holder[0])
    kv_token_bytes = (mc.num_kv_heads * mc.head_dim * 2
                      * mc.num_layers
                      * (1 if core.config.kv_cache_dtype == "int8" else 2))
    peak = hbm_bytes_per_s()
    floors = None  # no device peak (the CPU): no floor to compare with
    if peak is not None:
        floors = {
            "weights_read_per_chunk_s": round(pbytes / peak, 6),
            "kv_write_per_chunk_s": round(
                args.chunk * kv_token_bytes / peak, 6),
        }

    out = {
        "metric": "prefill_profile",
        "backend": backend,
        "model": args.model,
        "hermetic": bool(args.hermetic),
        "chunk": args.chunk,
        "reps": args.reps,
        "chunks": chunks,
        "floors": floors,
        # r18 legs: NEW top-level keys (the r11 drift check pins the
        # chunks[].components key set).
        "kernel_ab": kernel_ab,
        "fused_dispatch": fused_dispatch,
    }
    out["meta"] = _bench_run_meta()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
