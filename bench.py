"""Full-stack benchmark: multi-round QA through router + TPU engine.

Reproduces the reference's headline harness at the reference's workload
shape (``benchmarks/multi-round-qa/run_single.sh:11-41``: 15 users x 20
rounds, 1000-token shared system prompt, long per-user chat history,
100-token answers, QPS-paced arrivals) through the real router (static
discovery, session routing) to a real in-process engine on the available
accelerator.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": "tok/s", "vs_baseline": N, ...}``

``vs_baseline`` compares against the recorded number for the same config
in ``bench_baselines.json`` (prior-round measurements on this hardware);
``null`` when no prior number exists — never a fabricated 1.0.

Configs (BENCH_CONFIG):
  flagship  tpu-llama-1b, reference shape w/ history scaled to the chip
  llama3b   tpu-llama-3b (largest Llama-class fitting one v5e chip in bf16)
  llama8b   meta-llama/Llama-3-8B at int8 (the BASELINE model class)
  opt       facebook/opt-125m smoke config (BASELINE config 1)
Every knob is still individually overridable via BENCH_* env vars.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _env_float(name: str, default: float) -> float:
    return float(os.environ.get(name, default))


# ---- workload configs ---------------------------------------------------- #
# Reference shape: NUM_USERS=15 NUM_ROUNDS=20 SYSTEM_PROMPT=1000
# CHAT_HISTORY=20000 ANSWER_LEN=100 (run_single.sh). The bench must
# finish inside one chip call, so per-config history is scaled down
# while keeping the shape (long shared prefix + long per-user history +
# short questions); BENCH_USER_HISTORY_TOKENS restores the full 20000.
_CONFIGS = {
    "flagship": dict(model="tpu-llama-1b", users=15, rounds=20,
                     answer_tokens=100, sys_prompt_tokens=1000,
                     history_tokens=2000, max_model_len=8192,
                     max_num_seqs=16),
    # Big models prefill in 2048-token chunks (half the chunk barriers /
    # readback syncs of the default 1024 on 3k-token first-round prompts;
    # attention memory still O(chunk x ctx)).
    "llama3b": dict(model="tpu-llama-3b", users=15, rounds=8,
                    answer_tokens=100, sys_prompt_tokens=1000,
                    history_tokens=2000, max_model_len=8192,
                    max_num_seqs=16, prefill_chunk=2048),
    # THE BASELINE model class: Llama-3-8B. bf16 weights (~16 GB) cannot
    # fit a 16 GB chip; int8 weight-only quantization (~8 GB +
    # per-channel scales, models/quantize.py) makes the headline model
    # servable on one v5e.
    # Pool pinned explicitly: int8 weights (~8.5 GB) + pool sit within
    # ~1 GB of the chip's usable HBM, and the auto-sizer's 0.7 margin
    # lands on the edge depending on residual allocator state.
    # quantize_embeddings: random-init bench weights make head quality
    # moot, and the ~1 GB embed/lm_head saving is what keeps the pool
    # off the OOM edge (real checkpoints on roomier chips should prefer
    # the bf16-head default). prefill_batch=1: grouped prefill programs
    # add activation/compile footprint that an 8 B model within ~1 GB of
    # the 16 GB chip cannot afford (measured with round 5's 4-wide cached
    # programs: all three attempts OOM'd at warmup with them on).
    "llama8b": dict(model="meta-llama/Llama-3-8B", users=15, rounds=6,
                    answer_tokens=100, sys_prompt_tokens=1000,
                    history_tokens=2000, max_model_len=8192,
                    max_num_seqs=16, quantization="int8",
                    quantize_embeddings=True, prefill_batch=1,
                    prefill_chunk=1024, num_blocks=440),
    # OPT's (12 kv-heads, 64 head_dim) pages tile-pad 2.7x AND the page
    # scatter materializes a padded pool copy as an HLO temp (no lane
    # merge at head_dim 64), so the pool is sized explicitly: 768 blocks
    # = 49k tokens, 16 seqs x 2k ctx + headroom.
    "opt": dict(model="facebook/opt-125m", users=15, rounds=6,
                answer_tokens=100, sys_prompt_tokens=400,
                history_tokens=400, max_model_len=2048,
                max_num_seqs=16, num_blocks=768),
    # BASELINE config 3: prefix/KV-aware routing + host-RAM KV offload
    # (the LMCache CPU-offload topology, values-07/09 equivalent).
    "kvaware": dict(model="tpu-llama-1b", users=15, rounds=10,
                    answer_tokens=100, sys_prompt_tokens=1000,
                    history_tokens=2000, max_model_len=8192,
                    max_num_seqs=16, routing="kvaware",
                    kv_offload_gb=4.0),
    # BASELINE config 4 at dev-chip scale: two engines (prefill + decode
    # units) behind the two-phase disaggregated-prefill flow; the KV
    # handoff rides the /kv/pull path negotiation.
    "disagg": dict(model="tpu-llama-1b", users=15, rounds=6,
                   answer_tokens=100, sys_prompt_tokens=1000,
                   history_tokens=2000, max_model_len=8192,
                   max_num_seqs=16, routing="disaggregated_prefill",
                   engines=2, num_blocks=800),
    # BASELINE config 5's LoRA leg at dev-chip scale: flagship engine
    # with adapter slots compiled in; half the users request a hot-swapped
    # adapter (engine-local delta weights, per-adapter KV namespaces).
    "lora": dict(model="tpu-llama-1b", users=15, rounds=8,
                 answer_tokens=100, sys_prompt_tokens=1000,
                 history_tokens=2000, max_model_len=8192,
                 max_num_seqs=16, max_loras=4, lora_users=7),
}

CONFIG_KEY = os.environ.get("BENCH_CONFIG", "flagship")
_cfg = _CONFIGS.get(CONFIG_KEY, _CONFIGS["flagship"])

MODEL = os.environ.get("BENCH_MODEL", _cfg["model"])
USERS = _env_int("BENCH_USERS", _cfg["users"])
ROUNDS = _env_int("BENCH_ROUNDS", _cfg["rounds"])
ANSWER_TOKENS = _env_int("BENCH_ANSWER_TOKENS", _cfg["answer_tokens"])
SYS_PROMPT_TOKENS = _env_int(
    "BENCH_SYS_PROMPT_TOKENS", _cfg["sys_prompt_tokens"])
HISTORY_TOKENS = _env_int(
    "BENCH_USER_HISTORY_TOKENS", _cfg["history_tokens"])
MAX_NUM_SEQS = _env_int("BENCH_MAX_NUM_SEQS", _cfg["max_num_seqs"])
MAX_MODEL_LEN = _env_int("BENCH_MAX_MODEL_LEN", _cfg["max_model_len"])
# New-user arrival rate (users/s), the reference's --qps pacing knob.
QPS = _env_float("BENCH_QPS", 1.0)
# LoRA leg (config "lora"): this many users request the hot-swapped
# adapter instead of the base model.
LORA_USERS = _env_int("BENCH_LORA_USERS", _cfg.get("lora_users", 0))
ADAPTER_NAME = "bench-adapter"
# Soft wall-clock budget for the traffic phase: users stop STARTING new
# rounds after this many seconds (in-flight rounds finish), mirroring the
# reference's --time per-point cap. 0 = no cap.
TIME_LIMIT = _env_float("BENCH_TIME_LIMIT", 480.0)
# Chunked prefill A/B knobs (the tail-latency tentpole): BENCH_CHUNKED=1
# turns the budgeted scheduler on; BENCH_MAX_NUM_BATCHED_TOKENS overrides
# the per-step budget (0 = derive from the prefill chunk size).
CHUNKED = _env_int("BENCH_CHUNKED", int(_cfg.get("chunked", 0)))
MAX_NUM_BATCHED_TOKENS = _env_int(
    "BENCH_MAX_NUM_BATCHED_TOKENS",
    int(_cfg.get("max_num_batched_tokens", 0)))
# Scripted arrival storm: BENCH_STORM_USERS long-prompt one-shot requests
# fired together BENCH_STORM_AT seconds into the traffic phase. Storm
# requests are excluded from throughput/TTFT/gap stats — the stall they
# cause is measured on the steady streams' max inter-token gap.
STORM_USERS = _env_int("BENCH_STORM_USERS", 0)
STORM_AT = _env_float("BENCH_STORM_AT", 10.0)
STORM_PROMPT_TOKENS = _env_int("BENCH_STORM_PROMPT_TOKENS", 4000)
# Speculative-decoding knobs: BENCH_SPEC sets --speculative-num-tokens
# (0 = off). BENCH_REPETITIVE=1 swaps the incompressible prompt text for
# highly repetitive text AND pins greedy answers to one token via
# logit_bias so the generation itself is draftable (the prompt-lookup
# best case even on random bench weights). BENCH_SPEC_AB=1
# runs the whole bench twice — spec off, then spec on at BENCH_SPEC
# (default 4) — and writes BENCH_SPEC_OUT (default BENCH_SPEC.json) with
# tokens/s + acceptance rate for both legs.
SPEC = _env_int("BENCH_SPEC", int(_cfg.get("spec", 0)))
REPETITIVE = _env_int("BENCH_REPETITIVE", 0)
SPEC_AB = _env_int("BENCH_SPEC_AB", 0)
SPEC_OUT = os.environ.get("BENCH_SPEC_OUT", "BENCH_SPEC.json")
# Int8 KV cache A/B: BENCH_KV_QUANT=1 runs the whole bench twice —
# --kv-cache-dtype bf16, then int8 — and writes BENCH_KV_QUANT_OUT
# (default BENCH_KV_QUANT.json) with tok/s, decode time, KV bytes per
# token, and pool capacity (blocks) for both legs.
KV_QUANT = _env_int("BENCH_KV_QUANT", 0)
KV_QUANT_OUT = os.environ.get("BENCH_KV_QUANT_OUT", "BENCH_KV_QUANT.json")
# Multi-tenant QoS noisy-neighbor A/B: BENCH_QOS=1 runs the hermetic
# two-tenant harness (production_stack_tpu/testing/qos_ab.py — fake
# contention engine, no TPU, no jax import) in three legs: unloaded,
# batch flood with QoS on, batch flood with QoS off. Writes
# BENCH_QOS_OUT (default BENCH_QOS.json) with interactive p99 TTFT for
# all legs. Acceptance: QoS-on p99 TTFT within 1.5x unloaded.
QOS = _env_int("BENCH_QOS", 0)
QOS_OUT = os.environ.get("BENCH_QOS_OUT", "BENCH_QOS.json")
QOS_FLOOD = _env_int("BENCH_QOS_FLOOD", 16)
QOS_INTERACTIVE_REQS = _env_int("BENCH_QOS_INTERACTIVE_REQS", 6)
QOS_TTFT = _env_float("BENCH_QOS_TTFT", 0.3)
QOS_PREFILL_CHUNKS = _env_int("BENCH_QOS_PREFILL_CHUNKS", 8)
# Chaos failover A/B: BENCH_CHAOS=1 runs the hermetic fault-tolerance
# harness (production_stack_tpu/testing/chaos_ab.py — 3 fake replicas,
# real router, no TPU, no jax import): mid-storm one replica is killed
# and another hung before first byte, with router fault tolerance ON
# then OFF. Writes BENCH_CHAOS_OUT (default BENCH_CHAOS_r09.json) with
# completion rate + p99 for both legs. Acceptance: ON completes >= 99%
# with p99 bounded near the TTFT deadline; OFF is the failure baseline.
# A third leg (BENCH_CHAOS_KILL9, default on) kill -9's a claim-holding
# replica with the fleet cache on and the breaker disabled: the KV claim
# lease alone must sweep the corpse and stop stale-holder /kv/pulls
# within one lease window.
CHAOS = _env_int("BENCH_CHAOS", 0)
CHAOS_OUT = os.environ.get("BENCH_CHAOS_OUT", "BENCH_CHAOS_r09.json")
CHAOS_KILL9 = _env_int("BENCH_CHAOS_KILL9", 1)
CHAOS_TOTAL = _env_int("BENCH_CHAOS_TOTAL", 120)
CHAOS_CONCURRENCY = _env_int("BENCH_CHAOS_CONCURRENCY", 12)
CHAOS_AFTER = _env_int("BENCH_CHAOS_AFTER", 30)
CHAOS_CLIENT_TIMEOUT = _env_float("BENCH_CHAOS_CLIENT_TIMEOUT", 8.0)
CHAOS_TTFT_DEADLINE = _env_float("BENCH_CHAOS_TTFT_DEADLINE", 2.0)
# Fleet prefix-cache A/B: BENCH_FLEET=1 runs the hermetic cross-replica
# pull A/B (testing/fleet_ab.py) — repeat-prompt traffic round-robined
# across 3 fake replicas, global prefix cache ON then OFF. Writes
# BENCH_FLEET_OUT (default BENCH_FLEET_r09.json) with the reuse-TTFT
# speedup and the cross-replica pull hit-rate.
FLEET = _env_int("BENCH_FLEET", 0)
FLEET_OUT = os.environ.get("BENCH_FLEET_OUT", "BENCH_FLEET_r09.json")
FLEET_USERS = _env_int("BENCH_FLEET_USERS", 10)
FLEET_ROUNDS = _env_int("BENCH_FLEET_ROUNDS", 3)
FLEET_CONCURRENCY = _env_int("BENCH_FLEET_CONCURRENCY", 4)
FLEET_TTFT = _env_float("BENCH_FLEET_TTFT", 0.2)
# KV pull-economics A/B: BENCH_KV_ECON=1 runs the hermetic crossover
# sweep (testing/kv_economics_ab.py) — shared-prefix groups of several
# lengths through the real router at a range of --fleet-min-match-chars
# thresholds, against 3 fake replicas with a parameterized
# transfer-latency model. Writes BENCH_KV_ECON_OUT (default
# BENCH_KV_ECON_r15.json) with the measured pull-vs-recompute crossover
# and whether the ledger-fed advisor's recommendation lands inside the
# empirically-optimal threshold band.
KV_ECON = _env_int("BENCH_KV_ECON", 0)
KV_ECON_OUT = os.environ.get("BENCH_KV_ECON_OUT", "BENCH_KV_ECON_r15.json")
KV_ECON_REUSE = _env_int("BENCH_KV_ECON_REUSE", 2)
KV_ECON_PULL_BASE = _env_float("BENCH_KV_ECON_PULL_BASE", 0.12)
KV_ECON_S_PER_BYTE = _env_float("BENCH_KV_ECON_S_PER_BYTE", 1e-6)
# Structured-output A/B: BENCH_STRUCTURED=1 runs the conformance +
# mask-overhead harness (testing/structured_ab.py) — the 30-case corpus
# through the real router to fake engines on both request surfaces,
# then masked-vs-unmasked greedy tokens/s on the real CPU engine
# (decode_steps=1 both legs). Writes BENCH_STRUCTURED_OUT (default
# BENCH_STRUCTURED_r10.json) with the overhead percentage.
STRUCTURED = _env_int("BENCH_STRUCTURED", 0)
STRUCTURED_OUT = os.environ.get("BENCH_STRUCTURED_OUT",
                                "BENCH_STRUCTURED_r10.json")
STRUCTURED_REQS = _env_int("BENCH_STRUCTURED_REQS", 8)
STRUCTURED_MAX_TOKENS = _env_int("BENCH_STRUCTURED_MAX_TOKENS", 32)
STRUCTURED_REPEATS = _env_int("BENCH_STRUCTURED_REPEATS", 3)
# Draft-model speculation A/B: BENCH_SPEC_DRAFT=1 runs the
# testing/spec_draft_ab.py harness on the real CPU engine — prompt
# lookup vs a draft model on non-repetitive text (where lookup drafts
# nothing), then the structured composition: the same
# grammar-constrained JSON traffic with no speculation, with the
# drafter FSM-ablated, and with the token FSM threaded into the
# drafter. Writes BENCH_SPEC_DRAFT_OUT (default BENCH_SPEC_DRAFT_r20.json).
# Acceptance: draft-model tokens-per-forward >= 1.3x prompt lookup on
# the non-repetitive leg, structured+drafter beats structured-alone AND
# drafter-alone, 0 failed requests every leg.
SPEC_DRAFT = _env_int("BENCH_SPEC_DRAFT", 0)
SPEC_DRAFT_OUT = os.environ.get("BENCH_SPEC_DRAFT_OUT",
                                "BENCH_SPEC_DRAFT_r20.json")
SPEC_DRAFT_MAX_TOKENS = _env_int("BENCH_SPEC_DRAFT_MAX_TOKENS", 32)
SPEC_DRAFT_K = _env_int("BENCH_SPEC_DRAFT_K", 4)
# LoRA adapter-plane A/B: BENCH_LORA=1 runs the hermetic noisy-neighbor
# harness (testing/lora_ab.py) — 4 adapters + base across 3 fake
# replicas with 2 adapter slots each, adapter-affinity pinning ON then
# OFF. Writes BENCH_LORA_OUT (default BENCH_LORA_r19.json) with hit
# rate, loads/evictions, and adapter p99 TTFT for both legs.
# Acceptance: affinity-on has the higher hit rate and lower p99 TTFT at
# equal offered load, with 0 failed requests in both legs.
LORA = _env_int("BENCH_LORA", 0)
LORA_OUT = os.environ.get("BENCH_LORA_OUT", "BENCH_LORA_r19.json")
LORA_ADAPTERS = _env_int("BENCH_LORA_ADAPTERS", 4)
LORA_ROUNDS = _env_int("BENCH_LORA_ROUNDS", 3)
LORA_PER_ADAPTER = _env_int("BENCH_LORA_PER_ADAPTER", 3)
LORA_LOAD_DELAY = _env_float("BENCH_LORA_LOAD_DELAY", 0.15)
LORA_TTFT = _env_float("BENCH_LORA_TTFT", 0.02)
# Router saturation harness: BENCH_SATURATION=1 steps rungs of
# closed-loop users (BENCH_SATURATION_STEPS, comma-separated counts)
# against BENCH_SATURATION_REPLICAS fake replicas through the real
# router running a real --slo-config, until goodput falls below
# BENCH_SATURATION_COLLAPSE (production_stack_tpu/testing/
# saturation.py — no TPU, no jax import). Writes BENCH_SATURATION_OUT
# (default BENCH_SATURATION_r13.json) with the RPS ceiling, the
# goodput-vs-load curve, per-rung outcome-classifier deltas (which must
# reconcile with the offered totals), and router_overhead_p99 at the
# knee.
SATURATION = _env_int("BENCH_SATURATION", 0)
SATURATION_OUT = os.environ.get("BENCH_SATURATION_OUT",
                                "BENCH_SATURATION_r13.json")
SATURATION_STEPS = os.environ.get("BENCH_SATURATION_STEPS",
                                  "100,500,1000,2500,5000,10000")
SATURATION_REQS_PER_USER = _env_int("BENCH_SATURATION_REQS_PER_USER", 2)
SATURATION_REPLICAS = _env_int("BENCH_SATURATION_REPLICAS", 4)
SATURATION_COLLAPSE = _env_float("BENCH_SATURATION_COLLAPSE", 0.9)
# Workers A/B: BENCH_SATURATION_WORKERS=1 runs the saturation ladder
# twice — --router-workers 1 vs --router-workers N (legs from
# BENCH_SATURATION_WORKERS_LEGS) — with the router as a real pre-fork
# subprocess, per-worker loop-lag p99 and outcome reconciliation read
# over the /debug/workers federation plane. Writes
# BENCH_SATURATION_WORKERS_OUT (default BENCH_SATURATION_r16.json).
SATURATION_WORKERS = _env_int("BENCH_SATURATION_WORKERS", 0)
SATURATION_WORKERS_OUT = os.environ.get("BENCH_SATURATION_WORKERS_OUT",
                                        "BENCH_SATURATION_r16.json")
SATURATION_WORKERS_STEPS = os.environ.get("BENCH_SATURATION_WORKERS_STEPS",
                                          "100,500,1000,2500")
SATURATION_WORKERS_LEGS = os.environ.get("BENCH_SATURATION_WORKERS_LEGS",
                                         "1,4")
# Relay A/B: BENCH_SATURATION_RELAY=1 runs the saturation ladder three
# times — relay off, relay on (both --router-workers 1), and
# --router-workers N + relay — each a real pre-fork subprocess. Per-rung
# outcome reconciliation, per-worker streaming_relay/relay_feed on-loop
# seconds, and pump counters come over the /debug/workers + /metrics
# federation planes. Writes BENCH_SATURATION_RELAY_OUT (default
# BENCH_SATURATION_r17.json).
SATURATION_RELAY = _env_int("BENCH_SATURATION_RELAY", 0)
SATURATION_RELAY_OUT = os.environ.get("BENCH_SATURATION_RELAY_OUT",
                                      "BENCH_SATURATION_r17.json")
# The relay ladder tops out at the old 1000-user knee: with paced
# 32-token streams, deeper rungs are bound by the closed-loop harness
# itself (TTFT ~= users/rps for both legs), not the router.
SATURATION_RELAY_STEPS = os.environ.get("BENCH_SATURATION_RELAY_STEPS",
                                        "100,250,500,1000")
SATURATION_RELAY_REQS = _env_int("BENCH_SATURATION_RELAY_REQS", 3)
SATURATION_RELAY_WORKERS = _env_int("BENCH_SATURATION_RELAY_WORKERS", 4)
SATURATION_RELAY_PUMPS = _env_int("BENCH_SATURATION_RELAY_PUMPS", 2)
SATURATION_RELAY_MAX_TOKENS = _env_int("BENCH_SATURATION_RELAY_MAX_TOKENS",
                                       32)
SATURATION_RELAY_TOKS = _env_float("BENCH_SATURATION_RELAY_TOKS", 200.0)
# --cold-repeat N: N fully cold serves, each in its own subprocess (no
# warm jit caches, no reused pools — the cold-start number operators
# actually see on a fresh replica). The artifact is rewritten and
# fsynced after EVERY iteration, so a crash mid-run keeps the
# completed ones.
COLD_OUT = os.environ.get("BENCH_COLD_OUT", "BENCH_COLD_r09.json")


def _load_baseline() -> float:
    """Prior recorded tok/s for this config on this hardware, or 0."""
    override = os.environ.get("BENCH_BASELINE_TOKS")
    if override:
        return float(override)
    try:
        with open(os.path.join(REPO, "bench_baselines.json")) as f:
            table = json.load(f)
        return float(table.get(CONFIG_KEY, {}).get("gen_tok_s", 0))
    except (OSError, ValueError):
        return 0.0


BASELINE_TOKS = _load_baseline()


def _run_meta() -> dict:
    """Provenance stamped into every BENCH_*.json artifact (the ``meta``
    key): enough to tie a number to a commit, interpreter, and knob set
    months later."""
    import platform
    import subprocess
    from datetime import datetime, timezone

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO, capture_output=True,
            text=True, timeout=10).stdout.strip() or None
    except Exception:  # noqa: BLE001 - provenance is best-effort
        sha = None
    return {
        "schema": 1,
        "git_sha": sha,
        "timestamp_utc": datetime.now(timezone.utc).isoformat(
            timespec="seconds"),
        "python": platform.python_version(),
        "platform": platform.platform(),
        # Only truthful when jax actually loaded: the hermetic branches
        # (QoS/chaos/fleet/saturation) never import it.
        "jax": getattr(sys.modules.get("jax"), "__version__", None),
        "bench_config": CONFIG_KEY,
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith("BENCH_")},
    }


def _write_artifact(path: str, result: dict,
                    worker_topology=None) -> None:
    """Write a BENCH_*.json artifact with the run-metadata stamp.

    ``worker_topology`` (saturation artifacts) records which processes
    produced the numbers: a list of legs, each ``{"workers": N,
    "members": [{"worker", "pid", "port"}, ...]}``. An in-process
    single-loop run is one leg of one member (this pid)."""
    meta = result.setdefault("meta", _run_meta())
    if worker_topology is not None:
        meta["worker_topology"] = worker_topology
    with open(os.path.join(REPO, path), "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")


async def _start_site(app):
    from aiohttp import web

    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}"


def _make_prompt(tokens: int, tag: str) -> str:
    """~`tokens` engine tokens of unique, incompressible text.

    Preset models tokenize byte-level (engine/tokenizer.py ByteTokenizer:
    1 token per UTF-8 byte), so emit exactly `tokens` ASCII chars; with a
    real HF tokenizer the same text is a comparable-or-smaller token count.
    """
    if REPETITIVE:
        # Prompt-lookup best case: the text is one phrase repeated, so
        # the n-gram index finds a continuation for almost every tail.
        phrase = f"repeat {tag[:4]} the same words again and again. "
        return (phrase * (tokens // len(phrase) + 1))[:tokens]
    rng = random.Random(tag)
    alphabet = "abcdefghijklmnopqrstuvwxyz "
    return "".join(rng.choice(alphabet) for _ in range(tokens))


def _turn_tokens(m: dict) -> int:
    # content bytes + chat-template framing ("<|role|>\n...\n")
    return len(m["content"].encode()) + 16


def _trim_history(history, token_budget: int):
    """Client-side context-window management: drop the oldest non-system
    turns until the request fits the budget, mirroring the reference
    harness's maxModelLen-sized workloads."""
    while len(history) > 2 and \
            sum(_turn_tokens(m) for m in history) > token_budget:
        # history[0] is the system prompt; drop the oldest turn pair
        # after it (the per-user history primer goes first).
        del history[1:3]
    return history


async def _drive(router_url: str):
    import aiohttp

    sys_prompt = _make_prompt(SYS_PROMPT_TOKENS, "ctx")
    ttfts = []
    latencies = []
    max_itgs = []  # per-steady-request max inter-token gap (decode stall)
    tokens_done = 0
    prompt_tokens_sent = 0
    failures = 0
    storm_done = [0]
    rounds_done = 0
    t_deadline = [None]
    t_start_box = [None]

    async def one_user(session, uid: int):
        nonlocal tokens_done, failures, rounds_done, prompt_tokens_sent
        # Arrival pacing: user uid enters the system at ~uid/QPS seconds
        # (jittered), the reference's qps knob.
        if QPS > 0:
            await asyncio.sleep(uid / QPS * random.uniform(0.8, 1.2))
        history = [
            {"role": "system", "content": sys_prompt},
            {"role": "user",
             "content": "my notes so far: "
                        + _make_prompt(HISTORY_TOKENS, f"h{uid}_")},
            {"role": "assistant", "content": "noted."},
        ]
        for rnd in range(ROUNDS):
            if t_deadline[0] is not None and time.perf_counter() > t_deadline[0]:
                return
            history.append({
                "role": "user",
                "content": f"user{uid} round{rnd} "
                           + _make_prompt(100, f"q{uid}_{rnd}_"),
            })
            _trim_history(
                history, MAX_MODEL_LEN - ANSWER_TOKENS - 256)
            prompt_tokens_sent += sum(_turn_tokens(m) for m in history)
            t0 = time.perf_counter()
            first = None
            last_tok = None
            max_gap = 0.0
            answer = []
            model = ADAPTER_NAME if uid < LORA_USERS else MODEL
            body = {
                "model": model, "messages": history,
                "max_tokens": ANSWER_TOKENS, "stream": True,
                "temperature": 0.0, "ignore_eos": True,
            }
            if REPETITIVE:
                # Pin greedy output to one token: the generation echoes
                # itself, so prompt-lookup drafts always accept — the
                # speculation best case, independent of model weights.
                body["logit_bias"] = {"104": 100.0}
            try:
                async with session.post(
                    router_url + "/v1/chat/completions",
                    json=body,
                    headers={"x-user-id": str(uid)},
                    timeout=aiohttp.ClientTimeout(total=900),
                ) as resp:
                    if resp.status != 200:
                        failures += 1
                        history.pop()
                        continue
                    finish = None
                    async for line in resp.content:
                        line = line.decode().strip()
                        if not line.startswith("data: "):
                            continue
                        data = line[len("data: "):]
                        if data == "[DONE]":
                            break
                        chunk = json.loads(data)
                        choice = chunk["choices"][0]
                        if choice.get("finish_reason"):
                            finish = choice["finish_reason"]
                        content = choice.get("delta", {}).get("content")
                        if content:
                            now = time.perf_counter()
                            if first is None:
                                first = now
                            else:
                                max_gap = max(max_gap, now - last_tok)
                            last_tok = now
                            answer.append(content)
            except Exception:  # noqa: BLE001 - count and continue
                failures += 1
                history.pop()
                continue
            if first is None or finish == "error":
                # Stream finished without content (engine-side error
                # finish): a FAILED round — counting it as served once
                # produced a nonsense 749 tok/s row from an engine that
                # was ResourceExhausted the whole time.
                failures += 1
                history.pop()
                continue
            ttfts.append(first - t0)
            latencies.append(time.perf_counter() - t0)
            if max_gap > 0:
                max_itgs.append(max_gap)
            tokens_done += ANSWER_TOKENS
            rounds_done += 1
            history.append({"role": "assistant", "content": "".join(answer)})

    async def storm(session):
        """Scripted arrival storm: STORM_USERS long cold prompts land at
        once, STORM_AT seconds into the traffic phase. Each is one
        non-streaming short-answer request (pure prefill pressure)."""
        if STORM_USERS <= 0:
            return
        while t_start_box[0] is None:
            await asyncio.sleep(0.05)
        await asyncio.sleep(STORM_AT)

        async def one_storm(i: int):
            try:
                async with session.post(
                    router_url + "/v1/chat/completions",
                    json={
                        "model": MODEL,
                        "messages": [{
                            "role": "user",
                            "content": _make_prompt(
                                STORM_PROMPT_TOKENS, f"storm{i}_"),
                        }],
                        "max_tokens": 4, "temperature": 0.0,
                        "ignore_eos": True,
                    },
                    headers={"x-user-id": f"storm{i}"},
                    timeout=aiohttp.ClientTimeout(total=900),
                ) as resp:
                    await resp.read()
                    if resp.status == 200:
                        storm_done[0] += 1
            except Exception:  # noqa: BLE001 - storm failures are counted
                pass

        await asyncio.gather(*[one_storm(i) for i in range(STORM_USERS)])

    async with aiohttp.ClientSession() as session:
        # Warmup: trigger prefill-bucket + decode compiles before timing
        # (the reference runs warmup_single.sh first for the same reason).
        warm = [
            {"role": "system", "content": sys_prompt},
            {"role": "user", "content": _make_prompt(256, "w")},
        ]
        for _ in range(2):
            async with session.post(
                router_url + "/v1/chat/completions",
                json={"model": MODEL, "messages": warm, "max_tokens": 4,
                      "temperature": 0.0, "ignore_eos": True},
                timeout=aiohttp.ClientTimeout(total=900),
            ) as resp:
                await resp.read()
        t_start = time.perf_counter()
        t_start_box[0] = t_start
        if TIME_LIMIT > 0:
            t_deadline[0] = t_start + TIME_LIMIT
        await asyncio.gather(
            *[one_user(session, u) for u in range(USERS)],
            storm(session))
        elapsed = time.perf_counter() - t_start
    return (tokens_done, elapsed, ttfts, latencies, failures,
            rounds_done, prompt_tokens_sent, max_itgs, storm_done[0])


async def _main(spec_tokens: int = SPEC,
                kv_cache_dtype: str = "bf16") -> dict:
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.server import (
        EngineServer,
        run_engine_server,
    )
    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.router.parser import build_parser

    routing = _cfg.get("routing", "session")
    n_engines = int(_cfg.get("engines", 1))
    config = EngineConfig(
        model=MODEL,
        max_model_len=MAX_MODEL_LEN,
        max_num_seqs=MAX_NUM_SEQS,
        max_loras=int(_cfg.get("max_loras", 0)),
        decode_steps=_env_int("BENCH_DECODE_STEPS", 16),
        kv_offload_bytes=int(
            float(_cfg.get("kv_offload_gb", 0)) * 1e9),
        # Multi-engine configs size pools explicitly: the capacity
        # fallback can't see the sibling engine's HBM footprint.
        num_blocks=(_env_int("BENCH_NUM_BLOCKS", 0)
                    or _cfg.get("num_blocks")),
        quantization=_cfg.get("quantization"),
        quantize_embeddings=bool(_cfg.get("quantize_embeddings", False)),
        prefill_chunk_size=_env_int(
            "BENCH_PREFILL_CHUNK", _cfg.get("prefill_chunk", 1024)),
        # Same-bucket plain-prefill groups. BENCH_PREFILL_BATCH=1 skips
        # their warmup variants (CI's CPU smoke does: parity is covered
        # by tests/test_prefill_batch.py, and 7 extra 1B-model compiles
        # on a 1-core runner are minutes).
        prefill_batch=_env_int(
            "BENCH_PREFILL_BATCH", _cfg.get("prefill_batch", 4)),
        enable_chunked_prefill=bool(CHUNKED),
        max_num_batched_tokens=MAX_NUM_BATCHED_TOKENS,
        speculative_num_tokens=spec_tokens,
        kv_cache_dtype=kv_cache_dtype,
    )
    # Each engine is handed its device: without it every engine takes
    # jax.devices()[0]. On one chip the units of a multi-engine config
    # (disagg) share it by design; on a host with more they spread out.
    import jax

    chips = jax.devices()
    servers = [EngineServer(config, warmup=True,
                            devices=[chips[i % len(chips)]])
               for i in range(n_engines)]
    runners, engine_urls = [], []
    for server in servers:
        runner = await run_engine_server(server, "127.0.0.1", 0)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        runners.append(runner)
        engine_urls.append(f"http://127.0.0.1:{port}")

    if LORA_USERS > 0:
        # The adapter is a served model on the same backend (the engine
        # resolves the name to its LoRA slot; no alias rewrite, which
        # would strip the adapter name from the forwarded body). A failed
        # load would silently 404 the adapter users and publish a number
        # measuring only the base traffic — fail fast instead.
        assert servers[0].core.load_lora_adapter(ADAPTER_NAME, rank=8), \
            "adapter load failed (max_loras=0 or no free slot?)"

    args = build_parser().parse_args([])
    args.static_backends = ",".join(engine_urls)
    args.static_models = ",".join([MODEL] * n_engines)
    if LORA_USERS > 0:
        args.static_backends += "," + engine_urls[0]
        args.static_models += "," + ADAPTER_NAME
    args.routing_logic = routing
    args.session_key = "x-user-id"
    args.engine_stats_interval = 5
    # Hold the whole run in the trace ring so router_overhead_p99 below
    # is computed over every request, not the newest 512.
    args.trace_buffer = max(4096, USERS * ROUNDS + STORM_USERS)
    if routing == "disaggregated_prefill":
        args.static_model_labels = "prefill-unit,decode-unit"
        args.prefill_model_labels = "prefill-unit"
        args.decode_model_labels = "decode-unit"
    router_app = build_app(args)
    router_runner, router_url = await _start_site(router_app)
    if routing == "kvaware":
        # Engines report prefix admissions to the router's KV controller
        # (registration is lazy, so wiring after router start is fine).
        for server, url in zip(servers, engine_urls):
            server.kv_controller_url = router_url
            server.advertise_url = url

    try:
        (tokens, elapsed, ttfts, latencies, failures, rounds_done,
         prompt_tokens, max_itgs, storm_done) = await _drive(router_url)
        core_stats = servers[0].core.stats()
        if n_engines > 1:
            # Aggregate across units: the prefill engine does the real
            # prefill compute, the decode unit's injected-KV prompts count
            # as cached — only the sum is an honest pair-level hit rate.
            for server in servers[1:]:
                s = server.core.stats()
                for key in ("prompt_tokens_total", "cached_tokens_total",
                            "generation_tokens_total", "prefix_cache_hits",
                            "prefix_cache_queries", "num_preempted_total",
                            "prefill_time_total", "decode_time_total",
                            "flush_time_total", "prefill_count",
                            "decode_burst_count", "dispatch_count_total",
                            "dispatch_enqueue_s",
                            "decode_forward_steps_total",
                            "spec_proposed_tokens_total",
                            "spec_accepted_tokens_total",
                            "spec_disabled_requests_total"):
                    core_stats[key] += s[key]
    finally:
        await router_runner.cleanup()
        for runner in runners:
            await runner.cleanup()
        for server in servers:
            server.core.stop()

    tok_s = tokens / elapsed if elapsed > 0 else 0.0
    # Router overhead clock: per-request in-router time minus upstream
    # engine time, read from the in-process trace recorder ring.
    _overheads = sorted(
        router_app["state"].trace_recorder.root_attribute_values(
            "overhead_s"))
    router_overhead_p99 = (
        round(_overheads[
            min(len(_overheads) - 1,
                max(0, -(-99 * len(_overheads) // 100) - 1))], 6)
        if _overheads else None)
    result = {
        "metric": f"multi_round_qa_gen_throughput({MODEL})",
        "value": round(tok_s, 2),
        "unit": "tok/s",
        "vs_baseline": (
            round(tok_s / BASELINE_TOKS, 3) if BASELINE_TOKS else None
        ),
        "config": CONFIG_KEY,
        "p50_ttft_s": round(statistics.median(ttfts), 4) if ttfts else None,
        "p99_ttft_s": (
            # ceil-based index: with few samples this picks the LARGEST
            # (int()-1 picked the smallest at n=2, reporting p99 < p50).
            round(sorted(ttfts)[
                min(len(ttfts) - 1,
                    max(0, -(-99 * len(ttfts) // 100) - 1))], 4)
            if ttfts else None
        ),
        "p50_latency_s": (
            round(statistics.median(latencies), 4) if latencies else None
        ),
        "prompt_tok_s": round(prompt_tokens / elapsed, 1) if elapsed else 0,
        "requests": len(latencies),
        "rounds_done": rounds_done,
        "rounds_target": USERS * ROUNDS,
        "failures": failures,
        "users": USERS,
        "rounds": ROUNDS,
        "answer_tokens": ANSWER_TOKENS,
        "sys_prompt_tokens": SYS_PROMPT_TOKENS,
        "history_tokens": HISTORY_TOKENS,
        "elapsed_s": round(elapsed, 1),
        # Engine-side accounting: how much prefill the prefix cache skipped,
        # and whether block pressure caused preemption churn.
        "engine_prompt_tokens": core_stats["prompt_tokens_total"],
        "engine_cached_tokens": core_stats["cached_tokens_total"],
        "engine_prefix_hit_rate": round(
            core_stats["prefix_cache_hits"]
            / max(core_stats["prefix_cache_queries"], 1), 4),
        "engine_preemptions": core_stats["num_preempted_total"],
        "engine_num_blocks": core_stats["num_blocks"],
        "engine_prefill_s": core_stats["prefill_time_total"],
        "engine_decode_s": core_stats["decode_time_total"],
        "engine_flush_s": core_stats["flush_time_total"],
        "engine_prefills": core_stats["prefill_count"],
        "engine_prefill_groups": core_stats.get("prefill_group_count", 0),
        "engine_prefill_group_rows": core_stats.get(
            "prefill_group_rows", 0),
        "engine_bursts": core_stats["decode_burst_count"],
        "engine_dispatches": core_stats["dispatch_count_total"],
        "engine_dispatch_enqueue_s": core_stats["dispatch_enqueue_s"],
        # Arrival-storm A/B (chunked-prefill acceptance): the max gap
        # between consecutive streamed tokens on a steady user is the
        # decode stall a storm prefill induced.
        "chunked": bool(CHUNKED),
        "max_itg_s": round(max(max_itgs), 4) if max_itgs else None,
        "itg_p99_s": (
            round(sorted(max_itgs)[
                min(len(max_itgs) - 1,
                    max(0, -(-99 * len(max_itgs) // 100) - 1))], 4)
            if max_itgs else None
        ),
        "storm_users": STORM_USERS,
        "storm_done": storm_done,
        "router_overhead_p99": router_overhead_p99,
        "engine_prefill_chunks": core_stats.get("prefill_chunks_total", 0),
        "engine_deferred_prefill_tokens": core_stats.get(
            "deferred_prefill_tokens_total", 0),
        # Speculative decoding A/B surface: the engine-side win is
        # generated tokens per model forward (1.0 = plain decode).
        "speculative_num_tokens": spec_tokens,
        "repetitive": bool(REPETITIVE),
        "engine_forward_steps": core_stats.get(
            "decode_forward_steps_total", 0),
        "tokens_per_forward": round(
            core_stats["generation_tokens_total"]
            / max(core_stats.get("decode_forward_steps_total", 0), 1), 3),
        "engine_spec_proposed": core_stats.get(
            "spec_proposed_tokens_total", 0),
        "engine_spec_accepted": core_stats.get(
            "spec_accepted_tokens_total", 0),
        "engine_spec_acceptance_rate": (
            round(core_stats.get("spec_accepted_tokens_total", 0)
                  / core_stats["spec_proposed_tokens_total"], 4)
            if core_stats.get("spec_proposed_tokens_total") else None),
        "engine_spec_disabled": core_stats.get(
            "spec_disabled_requests_total", 0),
        # Int8 KV cache A/B surface: per-token KV storage cost and the
        # pool size that bought (engine_num_blocks above).
        "kv_cache_dtype": kv_cache_dtype,
        "engine_kv_bytes_per_token": core_stats.get(
            "kv_cache_bytes_per_token", 0),
        "backend": None,  # filled below
    }
    return result


def _run_scenario(factory, name: str, partial_out=None, partials=None):
    """Run one bench scenario (an async ``_main`` leg), retrying ONCE
    with backoff on transient connection errors (local socket hiccups /
    slow engine startup on shared dev hosts). When ``partials`` is given,
    the completed leg is flushed to ``partial_out`` immediately so a
    crash later in an A/B still leaves the finished legs on disk."""
    import aiohttp

    transient = (aiohttp.ClientConnectionError, ConnectionError,
                 OSError, asyncio.TimeoutError)
    try:
        result = asyncio.run(factory())
    except transient as e:
        print(f"scenario {name}: transient {type(e).__name__}: {e}; "
              f"retrying once after backoff", file=sys.stderr)
        time.sleep(10)
        result = asyncio.run(factory())
    if partials is not None and partial_out is not None:
        partials[name] = result
        _write_artifact(partial_out,
                        {"partial": True, "scenarios": partials})
    return result


def _qos_main() -> None:
    """BENCH_QOS=1: the noisy-neighbor A/B. Fully hermetic (fake
    engines), so this branch never imports jax or touches a device."""
    import tempfile

    from production_stack_tpu.testing.qos_ab import (
        run_qos_ab,
        write_tenants_file,
    )

    with tempfile.TemporaryDirectory() as tmp:
        tenants = write_tenants_file(os.path.join(tmp, "tenants.json"))
        result = asyncio.run(run_qos_ab(
            tenants, flood=QOS_FLOOD,
            interactive_requests=QOS_INTERACTIVE_REQS,
            ttft_s=QOS_TTFT, prefill_chunks=QOS_PREFILL_CHUNKS))
    result["backend"] = "fake"
    _write_artifact(QOS_OUT, result)
    print(json.dumps(result))


def _chaos_main() -> None:
    """BENCH_CHAOS=1: the failover A/B. Fully hermetic (fake engines),
    so this branch never imports jax or touches a device."""
    from production_stack_tpu.testing.chaos_ab import run_chaos_ab

    result = asyncio.run(run_chaos_ab(
        total=CHAOS_TOTAL, concurrency=CHAOS_CONCURRENCY,
        chaos_after=CHAOS_AFTER, client_timeout_s=CHAOS_CLIENT_TIMEOUT,
        ttft_deadline_s=CHAOS_TTFT_DEADLINE,
        include_kill9=bool(CHAOS_KILL9)))
    result["backend"] = "fake"
    _write_artifact(CHAOS_OUT, result)
    print(json.dumps(result))


def _fleet_main() -> None:
    """BENCH_FLEET=1: the cross-replica prefix-cache A/B. Fully hermetic
    (fake engines), so this branch never imports jax or touches a device."""
    from production_stack_tpu.testing.fleet_ab import run_fleet_ab

    result = asyncio.run(run_fleet_ab(
        users=FLEET_USERS, rounds=FLEET_ROUNDS,
        concurrency=FLEET_CONCURRENCY, engine_ttft=FLEET_TTFT))
    result["backend"] = "fake"
    _write_artifact(FLEET_OUT, result)
    print(json.dumps(result))


def _lora_main() -> None:
    """BENCH_LORA=1: the adapter-affinity noisy-neighbor A/B. Fully
    hermetic (fake engines), so this branch never imports jax or touches
    a device. Per-request router INFO logging is squelched — the churn
    leg logs every eviction and the lines drown the result."""
    import logging

    from production_stack_tpu.testing.lora_ab import run_lora_ab

    logging.getLogger(
        "production_stack_tpu.router.request_service"
    ).setLevel(logging.WARNING)
    result = asyncio.run(run_lora_ab(
        adapters=LORA_ADAPTERS, rounds=LORA_ROUNDS,
        per_adapter=LORA_PER_ADAPTER, load_delay_s=LORA_LOAD_DELAY,
        engine_ttft=LORA_TTFT))
    result["backend"] = "fake"
    _write_artifact(LORA_OUT, result)
    print(json.dumps(result))


def _kv_econ_main() -> None:
    """BENCH_KV_ECON=1: the KV pull-economics crossover sweep. Fully
    hermetic (fake engines), so this branch never imports jax or touches
    a device. Per-request router INFO logging is squelched — the sweep
    is ~75 sequential timed requests and the lines drown the result."""
    import logging

    from production_stack_tpu.testing.kv_economics_ab import run_kv_econ_ab

    for name in ("production_stack_tpu.router.request_service",
                 "production_stack_tpu.kv.fleet"):
        logging.getLogger(name).setLevel(logging.WARNING)
    result = asyncio.run(run_kv_econ_ab(
        reuse_per_group=KV_ECON_REUSE, pull_base_s=KV_ECON_PULL_BASE,
        s_per_byte=KV_ECON_S_PER_BYTE))
    result["backend"] = "fake"
    _write_artifact(KV_ECON_OUT, result)
    print(json.dumps({k: v for k, v in result.items() if k != "legs"}))


def _structured_main() -> None:
    """BENCH_STRUCTURED=1: corpus conformance (router + fake engines)
    plus the mask-overhead A/B on the real CPU engine."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from production_stack_tpu.testing.structured_ab import run_structured_ab

    result = run_structured_ab(
        n_requests=STRUCTURED_REQS, max_tokens=STRUCTURED_MAX_TOKENS,
        repeats=STRUCTURED_REPEATS)
    result["backend"] = "fake+cpu-engine"
    _write_artifact(STRUCTURED_OUT, result)
    print(json.dumps(result))


def _spec_draft_main() -> None:
    """BENCH_SPEC_DRAFT=1: draft-model speculation A/B on the real CPU
    engine (tiny zoo models, one device)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from production_stack_tpu.testing.spec_draft_ab import run_spec_draft_ab

    result = run_spec_draft_ab(max_tokens=SPEC_DRAFT_MAX_TOKENS,
                               spec_tokens=SPEC_DRAFT_K)
    result["backend"] = "cpu-engine"
    _write_artifact(SPEC_DRAFT_OUT, result)
    print(json.dumps(result))


def _saturation_main() -> None:
    """BENCH_SATURATION=1: the router saturation harness. Fully hermetic
    (fake engines), so this branch never imports jax or touches a
    device. Per-request router INFO logging is squelched — the top rung
    alone is 20k+ requests."""
    import logging

    from production_stack_tpu.testing.saturation import run_saturation

    logging.getLogger(
        "production_stack_tpu.router.request_service"
    ).setLevel(logging.WARNING)
    steps = tuple(int(s) for s in SATURATION_STEPS.split(",") if s.strip())
    result = asyncio.run(run_saturation(
        steps=steps, requests_per_user=SATURATION_REQS_PER_USER,
        replicas=SATURATION_REPLICAS,
        collapse_threshold=SATURATION_COLLAPSE))
    result["backend"] = "fake"
    _write_artifact(SATURATION_OUT, result, worker_topology=[
        {"workers": 1,
         "members": [{"worker": 0, "pid": os.getpid(), "port": None}]},
    ])
    print(json.dumps({k: v for k, v in result.items() if k != "rungs"}))


def _saturation_workers_main() -> None:
    """BENCH_SATURATION_WORKERS=1: the 1-vs-N-worker saturation A/B.
    Fully hermetic — fake engines in this process, the router as a
    ``--router-workers`` subprocess — so this branch never imports jax
    or touches a device."""
    from production_stack_tpu.testing.saturation import (
        run_saturation_workers_ab,
    )

    steps = tuple(int(s) for s in
                  SATURATION_WORKERS_STEPS.split(",") if s.strip())
    legs = tuple(int(s) for s in
                 SATURATION_WORKERS_LEGS.split(",") if s.strip())
    result = asyncio.run(run_saturation_workers_ab(
        steps=steps, requests_per_user=SATURATION_REQS_PER_USER,
        replicas=SATURATION_REPLICAS, worker_legs=legs,
        collapse_threshold=SATURATION_COLLAPSE))
    result["backend"] = "fake"
    _write_artifact(SATURATION_WORKERS_OUT, result, worker_topology=[
        {"workers": leg["workers"], "members": leg["worker_topology"]}
        for leg in result["legs"]
    ])
    print(json.dumps({k: v for k, v in result.items() if k != "legs"}))


def _saturation_relay_main() -> None:
    """BENCH_SATURATION_RELAY=1: the relay-off-vs-on saturation A/B
    plus the workers+relay composition leg. Fully hermetic — fake
    engines in this process, the router as a subprocess — so this
    branch never imports jax or touches a device."""
    from production_stack_tpu.testing.saturation import (
        run_saturation_relay_ab,
    )

    steps = tuple(int(s) for s in
                  SATURATION_RELAY_STEPS.split(",") if s.strip())
    result = asyncio.run(run_saturation_relay_ab(
        steps=steps, requests_per_user=SATURATION_RELAY_REQS,
        replicas=SATURATION_REPLICAS,
        relay_pump_threads=SATURATION_RELAY_PUMPS,
        multi_workers=SATURATION_RELAY_WORKERS,
        max_tokens=SATURATION_RELAY_MAX_TOKENS,
        engine_tokens_per_sec=SATURATION_RELAY_TOKS,
        collapse_threshold=SATURATION_COLLAPSE))
    result["backend"] = "fake"
    _write_artifact(SATURATION_RELAY_OUT, result, worker_topology=[
        {"workers": leg["workers"], "relay": leg["relay"],
         "members": leg["worker_topology"]}
        for leg in result["legs"]
    ])
    print(json.dumps({k: v for k, v in result.items() if k != "legs"}))


def _cold_repeat_main(n: int, cpu: bool) -> None:
    """--cold-repeat N: run the configured scenario N times, each in an
    isolated subprocess so every serve is fully cold (fresh interpreter,
    fresh jit, fresh KV pool). Per-iteration results are flushed to
    COLD_OUT as they land. The children need the chip one after the
    other, so this parent must never touch JAX: main() calls it before
    anything imports jax."""
    import subprocess

    out_path = os.path.join(REPO, COLD_OUT)
    iters: list = []
    summary: dict = {}
    for i in range(n):
        cmd = [sys.executable, os.path.abspath(__file__)]
        if cpu:
            cmd.append("--cpu")
        t0 = time.time()
        proc = subprocess.run(cmd, capture_output=True, text=True)
        wall = round(time.time() - t0, 2)
        parsed = None
        # The child prints ONE JSON line last; partial-progress lines
        # may precede it, so scan from the end.
        for line in reversed((proc.stdout or "").strip().splitlines()):
            try:
                parsed = json.loads(line)
                break
            except ValueError:
                continue
        iters.append({
            "iteration": i,
            "wall_s": wall,
            "returncode": proc.returncode,
            "result": parsed,
            "stderr_tail": ((proc.stderr or "")[-2000:]
                            if proc.returncode else None),
        })
        values = [it["result"]["value"] for it in iters
                  if it["result"] and it["result"].get("value") is not None]
        summary = {
            "meta": _run_meta(),
            "metric": "cold_serve_repeat",
            "unit": (iters[0]["result"] or {}).get("unit"),
            "value": (statistics.median(values) if values else None),
            "iterations_done": len(iters),
            "iterations_total": n,
            "values": values,
            "wall_s_per_iteration": [it["wall_s"] for it in iters],
            "iterations": iters,
        }
        with open(out_path, "w") as f:
            json.dump(summary, f, indent=2)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        print(json.dumps({"cold_iteration": i, "wall_s": wall,
                          "value": (parsed or {}).get("value"),
                          "returncode": proc.returncode}), flush=True)
    print(json.dumps(summary))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true",
                        help="force CPU backend (for smoke testing)")
    parser.add_argument("--cold-repeat", type=int, default=0, metavar="N",
                        help="run the scenario N times, each in an "
                             "isolated subprocess (fully cold serve); "
                             "per-iteration results flushed to "
                             "BENCH_COLD_OUT")
    args = parser.parse_args()
    if args.cold_repeat > 0:
        _cold_repeat_main(args.cold_repeat, args.cpu)
        return
    if QOS:
        _qos_main()
        return
    if CHAOS:
        _chaos_main()
        return
    if FLEET:
        _fleet_main()
        return
    if KV_ECON:
        _kv_econ_main()
        return
    if LORA:
        _lora_main()
        return
    if STRUCTURED:
        _structured_main()
        return
    if SPEC_DRAFT:
        _spec_draft_main()
        return
    if SATURATION:
        _saturation_main()
        return
    if SATURATION_WORKERS:
        _saturation_workers_main()
        return
    if SATURATION_RELAY:
        _saturation_relay_main()
        return
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    import jax

    if SPEC_AB:
        # Spec-on vs spec-off A/B on the same workload (run
        # BENCH_REPETITIVE=1 for the prompt-lookup best case). Both
        # legs run in this process back to back; the JSON artifact
        # carries both so the speedup is attributable.
        partials = {}
        off = _run_scenario(lambda: _main(0), "spec_off",
                            SPEC_OUT, partials)
        on = _run_scenario(lambda: _main(SPEC or 4), "spec_on",
                           SPEC_OUT, partials)
        for leg in (off, on):
            leg["backend"] = jax.devices()[0].platform
        result = {
            "metric": f"spec_decode_ab({MODEL})",
            "value": on["value"],
            "unit": "tok/s",
            "vs_baseline": (
                round(on["value"] / off["value"], 3)
                if off["value"] else None),
            "config": CONFIG_KEY,
            "spec_off_tok_s": off["value"],
            "spec_on_tok_s": on["value"],
            "spec_off_tokens_per_forward": off["tokens_per_forward"],
            "spec_on_tokens_per_forward": on["tokens_per_forward"],
            "acceptance_rate": on["engine_spec_acceptance_rate"],
            "spec_disabled_requests": on["engine_spec_disabled"],
            "repetitive": bool(REPETITIVE),
            "spec_off": off,
            "spec_on": on,
        }
        _write_artifact(SPEC_OUT, result)
        print(json.dumps(result))
        return
    if KV_QUANT:
        # Int8 KV cache A/B: same workload, bf16 pages vs int8
        # pages + per-token scales. Token-level greedy agreement is
        # covered by tests/test_kv_quant.py; the A/B surfaces
        # throughput, decode time, per-token KV bytes, and the
        # capacity win (blocks at equal HBM budget when the pool is
        # auto-sized).
        partials = {}
        bf16 = _run_scenario(lambda: _main(SPEC, "bf16"), "kv_bf16",
                             KV_QUANT_OUT, partials)
        int8 = _run_scenario(lambda: _main(SPEC, "int8"), "kv_int8",
                             KV_QUANT_OUT, partials)
        for leg in (bf16, int8):
            leg["backend"] = jax.devices()[0].platform
        result = {
            "metric": f"kv_quant_ab({MODEL})",
            "value": int8["value"],
            "unit": "tok/s",
            "vs_baseline": (
                round(int8["value"] / bf16["value"], 3)
                if bf16["value"] else None),
            "config": CONFIG_KEY,
            "bf16_tok_s": bf16["value"],
            "int8_tok_s": int8["value"],
            "bf16_kv_bytes_per_token":
                bf16["engine_kv_bytes_per_token"],
            "int8_kv_bytes_per_token":
                int8["engine_kv_bytes_per_token"],
            "bf16_num_blocks": bf16["engine_num_blocks"],
            "int8_num_blocks": int8["engine_num_blocks"],
            "bf16_decode_s": bf16["engine_decode_s"],
            "int8_decode_s": int8["engine_decode_s"],
            "bf16_p50_ttft_s": bf16["p50_ttft_s"],
            "int8_p50_ttft_s": int8["p50_ttft_s"],
            "kv_bf16": bf16,
            "kv_int8": int8,
        }
        _write_artifact(KV_QUANT_OUT, result)
        print(json.dumps(result))
        return
    # Init OOM from residual runtime HBM (llama8b near the ceiling,
    # ROADMAP item 3) is now absorbed IN-PROCESS by the engine's
    # pool-shrink ladder (engine/core.py _alloc_kv_with_shrink) plus
    # --hbm-headroom-reserve; the fresh-process re-exec workaround
    # that used to live here is gone.
    result = _run_scenario(lambda: _main(), "single")
    result["backend"] = jax.devices()[0].platform
    print(json.dumps(result))


if __name__ == "__main__":
    main()
