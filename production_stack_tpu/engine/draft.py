"""Draft-model proposer for speculative decoding.

A second, much smaller model from the zoo (``--speculative-draft-model``,
e.g. ``tpu-llama-1b`` drafting for ``Llama-3-8B``) loaded alongside the
target on the SAME mesh. It owns its own parameters, its own bf16 KV
page pool, and its own compiled greedy draft programs; the target
engine's verify program, burst selection, acceptance rule, rollback and
multihost op replay are untouched — the drafter only changes where the
draft tokens in :meth:`EngineCore._propose_spec_drafts` come from, so
streams stay byte-identical to plain decode by the same argument that
covers prompt lookup.

Two compiled programs, both bounded (the compile-budget contract):

* ``forward_fn`` — a batched cached-prefill forward ([B, bucket] rows at
  a FIXED full-width block table) returning the greedy next token per
  row. One XLA variant per warmed prefill bucket. It serves both the
  KV catch-up (feeding tokens the drafter has not seen — the whole
  prompt right after prefill, usually just the last verified token in
  steady state) and the per-token FSM-constrained draft steps, which
  are span-1 rows through the smallest bucket.
* ``scan_fn`` — a K-2-step greedy decode scan (argmax feedback) that
  extends the first drafted token to the full draft width in one
  dispatch when no row needs FSM masking. One variant total.

The page pool is sized for the worst case up front
(``max_blocks_per_seq * max_num_seqs`` blocks — a drafted sequence never
needs more than ``max_model_len - 1`` positions) and carved out BEFORE
the target's pool is auto-sized, so the drafter spends the headroom
reserve and never competes with target KV capacity.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.kvcache import KVCacheManager
from production_stack_tpu.engine.sampling import apply_fsm_mask
from production_stack_tpu.models import build_model, get_model_config
from production_stack_tpu.models.registry import page_layers
from production_stack_tpu.ops.attention import shard_paged_kernels
from production_stack_tpu.parallel.sharding import (
    kv_pages_sharding,
    param_shardings,
    place_checkpoint,
)
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)


class DraftModel:
    """Device state + compiled programs + host page bookkeeping for the
    draft model. Followers construct it too (same config on every
    process) and replay the leader's ``draft_forward`` / ``draft_scan``
    ops against their local shards; only the leader maintains the
    host-side page tables and ``computed`` frontiers."""

    def __init__(self, config, mesh, repl_sharding, target_model_config):
        self.config = config
        self.name = config.speculative_draft_model
        self.mesh = mesh
        self._repl = repl_sharding
        mc = get_model_config(self.name)
        if config.dtype:
            mc = mc.replace(dtype=config.dtype)
        if mc.vocab_size != target_model_config.vocab_size:
            raise ValueError(
                f"speculative_draft_model {self.name!r} has vocab "
                f"{mc.vocab_size}, target has {target_model_config.vocab_size}"
                " — draft tokens must be target tokens")
        self.model_config = mc

        # -- parameters (sharded over the shared mesh; no LoRA slots —
        # the drafter proposes for every adapter, verify applies them) --
        init_fn, self._apply = build_model(mc)
        if mesh.size > 1:  # as the target's forward: engine/core.py
            from production_stack_tpu.ops.pallas_grouped_matmul import (
                on_devices,
            )

            self._apply = on_devices(self._apply, mesh.size)
        rng = jax.random.key(config.seed)
        shapes = jax.eval_shape(lambda: init_fn(mc, rng))
        self._param_shardings = param_shardings(mc, mesh, shapes)
        self.params = jax.jit(
            lambda: init_fn(mc, rng),
            out_shardings=self._param_shardings)()
        self._maybe_load_checkpoint()

        # -- KV pages (always bf16-family, never quantized: the pool is
        # tiny next to the target's and draft logits feed only argmax) --
        self.num_blocks = (
            config.max_blocks_per_seq * config.max_num_seqs + 1)
        self._kv_sharding = kv_pages_sharding(mc, mesh)
        self._apply, _ = shard_paged_kernels(self._apply, self._kv_sharding)
        kv_shape = (page_layers(mc), self.num_blocks, config.block_size,
                    mc.num_kv_heads, mc.head_dim)

        def _zeros():
            z = jnp.zeros(kv_shape, mc.jnp_dtype)
            return z, jnp.zeros(kv_shape, mc.jnp_dtype)

        self.kv = jax.jit(
            _zeros,
            out_shardings=(self._kv_sharding, self._kv_sharding))()

        # Prefix caching OFF: draft pages are throwaway scratch keyed to
        # the live request; sharing them across requests would tie page
        # lifetime to the hash chain instead of the request.
        self.kv_mgr = KVCacheManager(
            self.num_blocks, config.block_size,
            enable_prefix_caching=False,
            namespace=f"draft|{self.name}")
        # request_id -> tokens the drafter's KV covers (positions
        # 0..computed-1 written; leader only).
        self.computed: Dict[str, int] = {}

        self.forward_fn = self._make_forward()
        self.scan_fn = (
            self._make_scan() if config.speculative_num_tokens > 2
            else None)

    # -- setup ------------------------------------------------------------
    def _maybe_load_checkpoint(self) -> None:
        from production_stack_tpu.models.weights import (
            has_checkpoint,
            load_checkpoint,
        )

        if not has_checkpoint(self.name):
            return
        loaded = load_checkpoint(self.model_config, self.name)
        self.params = place_checkpoint(
            self.model_config, self.mesh, self.params, loaded,
            self._param_shardings)

    # -- compiled programs -------------------------------------------------
    def _make_forward(self):
        apply = self._apply
        mc = self.model_config

        def fwd(params, kv, token_ids, positions, slot_mapping,
                block_tables, context_lens, seq_lens, mask_bits, mask_on):
            last_idx = jnp.maximum(seq_lens - 1, 0)
            logits, kv = apply(
                params, mc, token_ids, positions, kv, slot_mapping,
                block_tables, context_lens, seq_lens,
                mode="prefill_cached", adapter_ids=None,
                last_token=last_idx,
            )
            with jax.named_scope("sample"):
                shaped = apply_fsm_mask(logits[:, 0], mask_bits, mask_on)
                return (jnp.argmax(shaped, axis=-1).astype(jnp.int32), kv)

        fwd.__name__ = "draft_forward"  # its name in traces and records
        return jax.jit(
            fwd, donate_argnums=(1,),
            out_shardings=(self._repl,
                           (self._kv_sharding, self._kv_sharding)))

    def _make_scan(self):
        apply = self._apply
        mc = self.model_config
        S = self.config.speculative_num_tokens - 2

        def fwd(params, kv, token0, positions0, slot_mat, block_tables,
                context0):
            def body(carry, step_slots):
                tokens, kv, s = carry
                logits, kv = apply(
                    params, mc, tokens[:, None], (positions0 + s)[:, None],
                    kv, step_slots[:, None], block_tables, context0 + s,
                    jnp.ones_like(context0), mode="decode",
                    adapter_ids=None,
                )
                with jax.named_scope("sample"):
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return (nxt, kv, s + 1), nxt

            (_, kv, _), out = jax.lax.scan(
                body, (token0, kv, jnp.int32(0)), slot_mat.T, length=S)
            return out.T, kv

        fwd.__name__ = f"draft_scan_k{S}"
        return jax.jit(
            fwd, donate_argnums=(1,),
            out_shardings=(self._repl,
                           (self._kv_sharding, self._kv_sharding)))

    # -- host bookkeeping (leader only) -----------------------------------
    def buckets(self):
        """The warmed catch-up span buckets — same pruning as the
        target's prefill warmup so both stay within one bounded set."""
        cfg = self.config
        buckets = cfg.prefill_buckets()
        if cfg.prefill_chunk_size:
            buckets = [
                b for b in buckets
                if b <= cfg.bucket_for(
                    min(cfg.prefill_chunk_size, cfg.max_model_len))
            ]
        return buckets

    def ensure_capacity(self, rid: str, total: int) -> bool:
        """Grow the draft page table for ``rid`` to cover ``total``
        tokens (worst case for the coming burst). False on pool
        exhaustion — the caller skips speculation for this burst."""
        seq = self.kv_mgr.seqs.get(rid)
        if seq is None:
            res = self.kv_mgr.allocate_prompt(rid, [0] * max(total, 1))
            if res is None:
                return False
            # Prefix caching is off, so no allocator state references
            # these blocks; zero the registration frontier (it advances
            # over full blocks even with caching disabled) so
            # rollback_tokens can release rejected draft-position pages.
            self.kv_mgr.seqs[rid].num_registered = 0
            self.computed[rid] = 0
            return True
        while seq.num_tokens < total:
            if not self.kv_mgr.append_token(rid, 0):
                return False
        return True

    def truncate(self, rid: str, keep: int) -> None:
        """Roll the draft table back to ``keep`` tokens after a verify
        outcome (rejected draft positions release their pages, exactly
        like the target-side rollback)."""
        seq = self.kv_mgr.seqs.get(rid)
        if seq is None:
            return
        if seq.num_tokens > keep:
            self.kv_mgr.rollback_tokens(rid, seq.num_tokens - keep)
        if self.computed.get(rid, 0) > keep:
            self.computed[rid] = keep

    def release(self, rid: str) -> None:
        """Target-KV free hook: the request is gone (finish / preempt /
        abort / drain) — drop its draft pages and frontier."""
        self.kv_mgr.free(rid)
        self.computed.pop(rid, None)

    def block_table(self, rid: str):
        return self.kv_mgr.block_table(rid)

    # -- warmup ------------------------------------------------------------
    def warmup(self, mask_row_bytes: int) -> int:
        """Precompile the draft programs: one forward variant per
        catch-up bucket plus the one scan. Returns the variant count
        (``warmup_variants["draft"]``). Dummy slots are -1 so no real
        page is written."""
        cfg = self.config
        B = cfg.max_num_seqs
        maxb = cfg.max_blocks_per_seq
        n = 0
        for bucket in self.buckets():
            _, self.kv = self.forward_fn(
                self.params, self.kv,
                np.zeros((B, bucket), np.int32),
                np.tile(np.arange(bucket, dtype=np.int32), (B, 1)),
                np.full((B, bucket), -1, np.int64),
                np.zeros((B, maxb), np.int32),
                np.full((B,), min(bucket, 2), np.int32),
                np.full((B,), min(bucket, 2), np.int32),
                np.zeros((B, mask_row_bytes), np.uint8),
                np.zeros((B,), bool),
            )
            n += 1
        if self.scan_fn is not None:
            S = cfg.speculative_num_tokens - 2
            _, self.kv = self.scan_fn(
                self.params, self.kv,
                np.zeros((B,), np.int32),
                np.zeros((B,), np.int32),
                np.full((B, S), -1, np.int64),
                np.zeros((B, maxb), np.int32),
                np.ones((B,), np.int32),
            )
            n += 1
        return n
