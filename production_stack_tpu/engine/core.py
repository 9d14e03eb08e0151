"""Engine core: model execution + continuous batching on the TPU mesh.

Owns the sharded parameters, the paged KV cache in HBM, the two compiled
programs (bucketed prefill, fixed-width decode), on-device sampling, the
scheduler, and the background engine thread that drives them. The OpenAI
server (:mod:`production_stack_tpu.engine.server`) talks to this class only.

This is the stack's replacement for the vLLM engine process the reference
launches in each pod (``helm/templates/deployment-vllm-multi.yaml:108-199``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.kvcache import KVCacheManager
from production_stack_tpu.engine.sampling import (
    MAX_LOGIT_BIAS,
    MAX_STOP_IDS,
    SamplingParams,
    accepted_prefix_len,
    allowed_tokens,
    apply_penalties,
    burst_terms,
    make_rng_keys,
    sample_with_logprobs,
    shape_logits,
)
from production_stack_tpu.engine.scheduler import (
    EngineRequest,
    RunningSeq,
    Scheduler,
    SpecState,
)
from production_stack_tpu.engine.tokenizer import build_tokenizer
from production_stack_tpu.obs.steps import StepRecorder, device_hbm_bytes_per_s
from production_stack_tpu.structured.api import compile_char_dfa
from production_stack_tpu.structured.tokenfsm import (
    FSMState,
    StructuredCache,
    mask_row_bytes,
)
from production_stack_tpu.models import build_model, get_model_config, registry
from production_stack_tpu.models.registry import (
    block_state_shape,
    page_sides,
    get_family,
)
from production_stack_tpu.parallel import multihost
from production_stack_tpu.parallel.mesh import build_mesh
from production_stack_tpu.parallel.sharding import (
    kv_block_sharding,
    kv_pages_sharding,
    kv_scale_block_sharding,
    kv_scale_sharding,
    param_shardings,
    place_checkpoint,
)
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)


@dataclasses.dataclass
class _StagedParam:
    """One sleeping parameter: this process's shards (keyed by shard
    index) plus what's needed to rebuild the global array on wake."""
    shards: dict
    shape: tuple
    sharding: object
    dtype: object


def kv_page_dims(model_config, kv_cache_dtype: str = "bf16",
                 packed: bool = True):
    """(layers that hold pages, rows, lanes) of one token's page: the
    family's own count of layers with keys and values
    (``Family.page_layers``; every layer for most) and what a page keeps
    of a token per layer, ``(KVH, D)`` or narrow heads side by side in
    128-lane rows (``ops.attention.packed_page_dims``; never for int8
    pages, nor with ``packed`` off: a pool sharded over kv heads). Of a
    family whose two sides differ (``Family.page_sides``), the first
    side's: :func:`kv_page_sides` has both."""
    layers, first, _ = kv_page_sides(model_config, kv_cache_dtype, packed)
    return (layers,) + first


def kv_page_sides(model_config, kv_cache_dtype: str = "bf16",
                  packed: bool = True):
    """(layers that hold pages, (rows, lanes) of the pool's first side,
    (rows, lanes) of its second). The two are the same for grouped keys
    and values. A family that says what its sides are
    (``Family.page_sides``: a latent and a rotated key) gets each at its
    own width rounded up to whole 128-lane tiles: the lanes beyond the
    width hold zeros, and a one-row side ``[.., bs, 1, lanes]`` is laid
    out by the device as ``bs x lanes`` tiles, with no padding of its
    own."""
    from production_stack_tpu.models.registry import page_layers
    from production_stack_tpu.ops.attention import packed_page_dims

    mc = model_config
    own = page_sides(mc)
    if own is not None:
        return (page_layers(mc),) + tuple(
            (rows, -(-width // 128) * 128) for rows, width in own)
    dims = (packed_page_dims(mc.num_kv_heads, mc.head_dim,
                             kv_cache_dtype == "int8")
            if packed else (mc.num_kv_heads, mc.head_dim))
    return page_layers(mc), dims, dims


def kv_bytes_per_block(model_config, block_size: int,
                       kv_cache_dtype: str = "bf16") -> int:
    """Per-block HBM bytes INCLUDING XLA's tile padding. When a page's
    rows are lane-aligned (a multiple of 128 wide: llama-family 8x128,
    or eight 64-wide heads side by side as 4x128) the trailing dims
    occupy exactly their unpadded size. Otherwise the minor dim pads to
    128 and the kv-head dim to the sublane granularity — e.g. OPT's
    (12, 64) stores as (16, 128), a 2.7x expansion that OOMed compile
    when the pool was sized from unpadded bytes.

    ``int8`` stores one byte per K/V element (sublane granularity 32
    when head_dim needs lane padding) plus the per-slot per-kv-head f32
    scale rows, whose flat [bs*KVH] minor dim pads to the 128-lane tile
    — ~1.94x the blocks of bf16 at an equal HBM budget for llama-family
    shapes.

    A family with a state per block (``Family.block_state``) adds its
    ``layers x rows x width`` values in the model's dtype. A family with
    its own page sides (``Family.page_sides``) holds each side at its
    stored lanes (:func:`kv_page_sides`): 512 + 128 lanes a token for a
    512-wide latent and a 64-wide rotated key, 1,280 bytes in bf16 where
    the values alone are 1,152."""
    mc = model_config
    layers, (kvh, d), second = kv_page_sides(mc, kv_cache_dtype)
    itemsize = jnp.dtype(mc.dtype).itemsize
    state = block_state_shape(mc)
    state_bytes = 0
    if state is not None:
        rows = -(-state[1] // 8) * 8 if state[2] % 128 else state[1]
        state_bytes = state[0] * rows * (-(-state[2] // 128) * 128) * itemsize
    if page_sides(mc) is not None:
        return state_bytes + layers * block_size * itemsize * sum(
            rows * lanes for rows, lanes in ((kvh, d), second))
    if kv_cache_dtype == "int8":
        if d % 128 != 0:
            d = -(-d // 128) * 128
            kvh = -(-kvh // 32) * 32
        scale_lanes = -(-(block_size * mc.num_kv_heads) // 128) * 128
        return state_bytes + layers * (
            2 * block_size * kvh * d + 2 * scale_lanes * 4)
    if d % 128 != 0:
        d = -(-d // 128) * 128
        sublane = 16 if itemsize == 2 else 8
        kvh = -(-kvh // sublane) * sublane
    return state_bytes + layers * 2 * block_size * kvh * d * itemsize


# -- KV pool leaf helpers --------------------------------------------------
# Each of the pool's k/v leaves is a bare [L, NB, bs, KVH, D] array (bf16)
# or a (data, scales) tuple (int8; scales [L, NB, bs*KVH] f32 — see
# ops/attention.quantize_kv). Block payloads mirror that minus the NB axis.
# These helpers keep every slice/stack/transfer site one code path.

def _kv_set(pages, bid, new):
    """Scatter one block (scalar bid) or a batch of blocks (bid array)
    into a pool leaf."""
    if isinstance(pages, tuple):
        data, scales = pages
        nd, ns = new
        return (data.at[:, bid].set(nd.astype(data.dtype)),
                scales.at[:, bid].set(ns.astype(scales.dtype)))
    return pages.at[:, bid].set(new.astype(pages.dtype))


@jax.jit
def _gather_blocks_flat(x, idx):
    """``x[:, idx]`` of a side ``[L, NB, ...]`` (or of each leaf of one)
    for block ids ``idx [n]``, as ONE program through the flat
    ``[L * NB, ...]`` view, as the step programs read pages (inside one
    program a reshape of leading dims is no copy; on its own it is one,
    of the whole side). For ``extract_kv``, which reads a prompt's blocks
    beside a full pool: the TPU compiler gives ``x[:, idx]`` on a
    47-layer latent side a copy of the whole side as a temporary (3.5 GB:
    the check ran out of memory on the chip, PERF.md section 6, PR 44),
    and this gather none at any of the pools' shapes
    (tests/test_chip_compile.py)."""
    def leaf(e):
        L, NB = e.shape[:2]
        rows = (jnp.arange(L, dtype=jnp.int32)[:, None] * NB
                + idx.astype(jnp.int32)[None, :]).reshape(-1)
        return e.reshape((L * NB,) + e.shape[2:])[rows].reshape(
            (L, idx.shape[0]) + e.shape[2:])

    return jax.tree.map(leaf, x)


def _kv_leaf_index(x, idx):
    """``x[:, idx]`` over a leaf (the block axis is axis 1 for both the
    pages and the scale layouts)."""
    if isinstance(x, tuple):
        return tuple(e[:, idx] for e in x)
    return x[:, idx]


def _kv_leaf_np(x):
    if isinstance(x, (tuple, list)):
        return tuple(np.asarray(e) for e in x)
    return np.asarray(x)


def _kv_leaf_jnp(x):
    if isinstance(x, (tuple, list)):
        return tuple(jnp.asarray(e) for e in x)
    return jnp.asarray(x)


def _kv_leaf_get(x):
    """device_get a leaf to host numpy."""
    if isinstance(x, tuple):
        return tuple(np.asarray(jax.device_get(e)) for e in x)
    return np.asarray(jax.device_get(x))


def _kv_leaf_swap01(x):
    if isinstance(x, tuple):
        return tuple(e.swapaxes(0, 1) for e in x)
    return x.swapaxes(0, 1)


def _kv_leaf_stack(parts, axis):
    """np.stack per-block payloads along ``axis`` (tuple-aware)."""
    if isinstance(parts[0], (tuple, list)):
        return tuple(
            np.stack([p[i] for p in parts], axis=axis)
            for i in range(len(parts[0])))
    return np.stack(parts, axis=axis)


def _flatten_kv_payload(head, k, v):
    """Op-channel wire order for write_block/write_blocks: int8 tuple
    payloads ship flattened as [head, kd, ks, vd, vs] (the channel
    carries flat numpy lists); bf16 stays [head, k, v]."""
    if isinstance(k, (tuple, list)):
        return [head, k[0], k[1], v[0], v[1]]
    return [head, k, v]


def _regroup_kv_payload(arrays):
    """Inverse of :func:`_flatten_kv_payload` (by payload length)."""
    if len(arrays) == 5:
        head, kd, ks, vd, vs = arrays
        return head, (kd, ks), (vd, vs)
    head, k, v = arrays
    return head, k, v


class _FusedPlaceholder:
    """Result slot for an op diverted into a fused-step capture. Filled
    when the fused dispatch (or the degraded per-op drain) executes;
    ``error`` carries a dispatch failure to the deferred readback that
    would otherwise wait on a value that will never arrive."""

    __slots__ = ("value", "error", "ready")

    def __init__(self):
        self.value = None
        self.error = None
        self.ready = False


def _unwrap_fused(x):
    """Resolve a possibly-placeholder dispatch result (raises the
    captured dispatch error, if any)."""
    if isinstance(x, _FusedPlaceholder):
        if x.error is not None:
            raise x.error
        return x.value
    return x


class _StatsTap:
    """A step program of a family that counts (``Family.stats``), called
    like the program of one that does not: its last output, the counts
    summed over its ``forwards`` forwards (a device array), goes to
    ``sink`` and the rest is returned. Everything else about the jitted
    program (``__name__``, ``lower``, ``_cache_size``) is the program's."""

    def __init__(self, program, sink, forwards: int, prefix: str):
        self._program, self._sink = program, sink
        self._forwards, self._prefix = forwards, prefix

    def __call__(self, *args):
        *out, stats = self._program(*args)
        self._sink((self._prefix, self._forwards, stats))
        return tuple(out)

    def __getattr__(self, name):
        return getattr(self._program, name)


class EngineCore:
    def __init__(
        self,
        config: EngineConfig,
        devices: Optional[list] = None,
    ):
        self.config = config
        self.model_config = get_model_config(config.model)
        # Latched by unrecoverable faults (multi-host op-channel break):
        # /health reports 503 so probes restart the pod, and the engine
        # loop stops stepping.
        self.fatal_error: Optional[str] = None
        if config.dtype:
            self.model_config = self.model_config.replace(dtype=config.dtype)
        self.tokenizer = build_tokenizer(
            config.model, self.model_config.vocab_size,
            chat_template_path=config.chat_template,
        )

        # Multi-host: every process joins one jax.distributed job, the
        # mesh spans the GLOBAL device set, and followers replay the
        # leader's dispatches (see parallel/multihost.py; the reference
        # spans hosts with KubeRay — ref helm/templates/ray-cluster.yaml).
        self._mh = multihost.maybe_context()

        # Without ``devices`` the mesh is the FIRST tp*dp*pp of
        # jax.devices(): a second engine in the same process that is not
        # handed its own lands on the same chips as the first.
        all_devices = list(devices if devices is not None else jax.devices())
        pp = max(config.pipeline_parallel_size, 1)
        tp = max(config.tensor_parallel_size, 1)
        if self._mh is not None and config.data_parallel_size <= 1:
            # Multi-host: the mesh MUST cover every process (a program
            # whose mesh excludes a process cannot be executed by it), so
            # dp auto-fills the whole global device set.
            dp = len(all_devices) // (tp * pp)
        else:
            dp = max(config.data_parallel_size, 1)
        n_needed = tp * dp * pp
        if self._mh is not None and n_needed != len(all_devices):
            raise ValueError(
                f"multi-host mesh tp={tp} x pp={pp} x dp={dp} covers "
                f"{n_needed} devices but the job has {len(all_devices)}; "
                f"size the parallelism to the whole slice")
        self.mesh = build_mesh(
            tensor_parallel_size=tp,
            data_parallel_size=dp,
            pipeline_parallel_size=pp,
            devices=all_devices[:n_needed],
        )
        from jax.sharding import NamedSharding, PartitionSpec

        # Replicated-on-the-mesh sharding for host-read outputs and small
        # device state: in multi-host SPMD every output the leader reads
        # back (sampled tokens, logprobs) must be fully replicated, or
        # device_get would need shards this process cannot address.
        self._repl = NamedSharding(self.mesh, PartitionSpec())

        self._init_fn, self._apply = build_model(self.model_config)
        if self.mesh.size > 1:
            # The compiler cannot partition a pallas_call: a program
            # that spans devices keeps ragged_dot for its expert layers.
            from production_stack_tpu.ops.pallas_grouped_matmul import (
                on_devices,
            )

            self._apply = on_devices(self._apply, self.mesh.size)
        family = get_family(self.model_config.arch)
        # (layers, rows, width) of the state a cache block holds beside
        # its pages (Family.block_state), None for most families.
        self.block_state_shape = block_state_shape(self.model_config)
        # (layers, rows, lanes) of this engine's pages; a pool sharded
        # over kv heads keeps one head a row.
        layers_held, *self.page_side_dims = kv_page_sides(
            self.model_config, config.kv_cache_dtype,
            packed=self.mesh.shape.get("tp", 1) == 1)
        self.page_dims = (layers_held,) + self.page_side_dims[0]
        # ``page_side_dims``: both sides' (rows, lanes); what a token
        # keeps on each where the family says so (Family.page_sides: the
        # pool's lanes are those widths in whole tiles), else None.
        self.own_page_sides = page_sides(self.model_config)
        # What the family's forward counts (models/registry.py::
        # Family.stats; the expert layer's assignment counts): the step
        # programs return the sums beside their tokens, and the step that
        # finds them ready carries them in its record.
        self._stat_names = family.stats
        self._stats_pending: collections.deque = collections.deque(
            maxlen=256)
        self.family_stats_total = {name: 0 for name in family.stats}
        if pp > 1:
            # Stage-sharded serving: swap the layer stack for the GPipe
            # pipeline over the pp mesh axis. Same signature, so prefill /
            # cached prefill / fused decode bursts / embeddings all run on
            # top of it unchanged.
            from production_stack_tpu.parallel.pp_serving import make_pp_apply

            if not family.pipeline:
                raise ValueError(
                    "pipeline_parallel_size > 1 is supported for the Llama "
                    f"family (model arch {self.model_config.arch!r})"
                )
            if self.model_config.num_layers % pp != 0:
                raise ValueError(
                    f"num_layers {self.model_config.num_layers} is not "
                    f"divisible by pipeline_parallel_size {pp}"
                )
            self._apply = make_pp_apply(
                self.mesh, family, microbatches=config.pp_microbatches or pp
            )

        self._refuse_what_the_block_state_is_not_taught()
        self._refuse_what_the_page_sides_are_not_taught()

        # -- parameters (sharded over the mesh) ----------------------------
        lora_kwargs = {}
        if family.lora and config.max_loras > 0:
            lora_kwargs = {
                "lora_slots": config.max_loras,
                "lora_rank": config.max_lora_rank,
            }
        rng = jax.random.key(config.seed)
        if config.quantization and not family.quant_keys:
            raise ValueError(
                "int8 quantization is supported for the llama family "
                f"(model arch {self.model_config.arch!r})")

        def _init():
            p = self._init_fn(self.model_config, rng, **lora_kwargs)
            if config.quantization == "int8":
                # Quantize INSIDE the init program: each bf16 leaf is
                # freed as soon as its int8 twin exists, so an 8 B model
                # never materializes fully in bf16 on device.
                from production_stack_tpu.models.quantize import (
                    quantize_tree,
                )

                p = quantize_tree(
                    p, self.model_config.arch,
                    quantize_embeddings=config.quantize_embeddings)
            return p

        shapes = jax.eval_shape(_init)
        self._param_shardings = param_shardings(
            self.model_config, self.mesh, shapes
        )
        self.params = jax.jit(_init, out_shardings=self._param_shardings)()
        self._maybe_load_checkpoint()

        # -- draft model (speculative decoding proposer) -------------------
        # Built BEFORE the target's KV pool is sized: the drafter's
        # params + fixed worst-case page pool come out of free HBM (the
        # headroom reserve in sized deployments), so _auto_num_blocks
        # naturally excludes them and the target pool never shrinks to
        # accommodate drafts mid-flight. Every process constructs it
        # (followers replay draft ops against their local shards).
        self._draft = None
        if config.speculative_draft_model:
            from production_stack_tpu.engine.draft import DraftModel

            self._draft = DraftModel(
                config, self.mesh, self._repl, self.model_config)

        # -- KV pages ------------------------------------------------------
        if self._mh is not None and not self._mh.is_leader:
            # The pool size is a host-side decision that must agree across
            # processes (it fixes the global KV array shape): followers
            # take the leader's figure instead of auto-sizing from their
            # own memory stats.
            op = self._mh.channel.recv()
            assert op[0] == "cfg", op
            self.num_blocks = int(op[1]["num_blocks"])
        else:
            self.num_blocks = config.num_blocks or self._auto_num_blocks()
            if self._mh is not None:
                self._mh.channel.send(
                    ("cfg", {"num_blocks": self.num_blocks}, []))
        # Per-LEAF shardings: a bare NamedSharding for bf16 pools, a
        # (pages, scales) tuple for int8 — matching the pool's leaf
        # structure exactly. The (k, v) pair variant below is spelled
        # out because with tuple leaves a single sharding is no longer a
        # broadcastable out_shardings prefix (it would pair the page
        # spec with the 3-dim scale array).
        pages_sh = kv_pages_sharding(self.model_config, self.mesh)
        block_sh = kv_block_sharding(self.model_config, self.mesh)
        if config.kv_cache_dtype == "int8":
            self._kv_sharding = (
                pages_sh, kv_scale_sharding(self.model_config, self.mesh))
            self._block_sharding = (
                block_sh,
                kv_scale_block_sharding(self.model_config, self.mesh))
        else:
            self._kv_sharding = pages_sh
            self._block_sharding = block_sh
        # The pool: (k, v), and a family's state per block as a third
        # side beside them (models/registry.py::Family.block_state).
        self._kv_pair_sharding = (
            (self._kv_sharding, self._kv_sharding)
            + ((self._repl,) if self.block_state_shape else ()))
        # A pool sharded over kv heads: the paged kernels cannot be
        # partitioned by the compiler, so the dispatchers run them per
        # shard (ops/attention.py::kv_head_sharding) — or take the
        # reference where a shard's head count fails the tile gate.
        from production_stack_tpu.ops.attention import shard_paged_kernels

        self._apply, self._kv_shards = shard_paged_kernels(
            self._apply, pages_sh)
        # HBM headroom left on this device AFTER the pool: exported as
        # tpu:hbm_headroom_bytes so near-OOM deployments (llama8b-int8
        # on 16 GB) are visible before they flip to ResourceExhausted
        # (VERDICT r4 weak #6). Computed after the allocation so a
        # pool-shrink ladder rung is reflected in the exported figure.
        self.hbm_headroom_bytes: Optional[int] = None
        self.pool_shrink_retries_total = 0
        free_before = self.free_hbm_before_pool = self._free_hbm_bytes()
        self.kv = self._alloc_kv_with_shrink()
        if free_before is not None:
            mc_ = self.model_config
            tp_ = self.mesh.shape.get("tp", 1)
            pp_ = self.mesh.shape.get("pp", 1)
            shard_factor = (
                (tp_ if tp_ > 1 and mc_.num_kv_heads % tp_ == 0 else 1)
                * (pp_ if pp_ > 1 and self.page_dims[0] % pp_ == 0 else 1))
            pool_per_device = (
                self.num_blocks * self._kv_bytes_per_block()
                // shard_factor)
            self.hbm_headroom_bytes = max(free_before - pool_per_device, 0)
        # Replicated block gather (disagg extract): every process runs
        # the same gather; the replicated output is host-readable from
        # any of them. (A bare _repl per (k, v) component is a valid
        # out_shardings prefix even for int8 tuple leaves — it
        # broadcasts over the subtree.)
        self._gather_blocks_fn = jax.jit(
            lambda kv, idx: tuple(_kv_leaf_index(side, idx) for side in kv),
            out_shardings=(self._repl,) * len(self._kv_pair_sharding))
        self.kv_mgr = KVCacheManager(
            self.num_blocks, config.block_size, config.enable_prefix_caching,
            namespace=config.model,
        )
        if self._draft is not None:
            # Every teardown path (finish / preempt / abort / drain)
            # frees target KV through kv_mgr.free — piggyback the
            # drafter's page + frontier cleanup on it.
            self.kv_mgr.on_free = self._draft.release
        self.scheduler = Scheduler(
            self.kv_mgr, config.max_num_seqs, config.max_model_len,
            chunked_prefill=config.chunked_prefill_enabled,
            chunk_tokens=config.chunk_tokens(),
            token_budget=config.token_budget,
            max_consecutive_prefills=config.max_consecutive_prefills,
            # Multi-row chunk steps ride the batched-prefill program, which
            # warmup only compiles when prefill_batch > 1.
            max_prefill_rows=(
                config.prefill_batch if config.prefill_batch > 1 else 1),
            fused_step=config.fused_step,
        )

        # -- KV offload tier (LMCache-equivalent, SURVEY §7 step 4) --------
        self.offload = None
        self._pending_offload: "list[tuple[int, int]]" = []
        if config.kv_offload_bytes > 0 or config.kv_remote_url:
            from production_stack_tpu.kv.offload import HostKVStore

            self.offload = HostKVStore(
                max(config.kv_offload_bytes, 0), config.kv_remote_url
            )
            self.kv_mgr.external_lookup = self.offload.contains
        # Eviction fan-out: offload spill (when configured) plus an
        # external listener (the server's KV-controller evict reporting —
        # closes the reference's LMCache worker->controller channel).
        # Fired under the engine locks: listeners must not block.
        self.prefix_evict_listener: Optional[
            Callable[[int, int], None]] = None
        # Eviction accounting for the anti-entropy layer: dispatches vs.
        # listener failures. A listener that throws (or a report the
        # server later loses to a timeout) leaves the controller trie
        # claiming chunks this engine no longer serves — the drift the
        # periodic resync digest exists to detect and heal.
        self.prefix_evicts_total = 0
        self.evict_listener_errors_total = 0

        def _dispatch_evict(prefix_hash: int, bid: int) -> None:
            if self.offload is not None:
                # Spilled to the host/remote tier: the prefix is STILL
                # servable here (external_lookup restores it), so don't
                # retract the controller claim — that would defeat the
                # offload tier exactly when it wins. Claims for chains the
                # second tier later drops age out via the admit TTL.
                self._offload_block(prefix_hash, bid)
                return
            self.prefix_evicts_total += 1
            listener = self.prefix_evict_listener
            if listener is not None:
                try:
                    listener(prefix_hash, bid)
                except Exception:  # noqa: BLE001 - never break the allocator
                    self.evict_listener_errors_total += 1

        self.kv_mgr.allocator.on_evict = _dispatch_evict

        # -- compiled programs --------------------------------------------
        self._prefill_fn = self._make_forward("prefill")
        self._prefill_cached_fn = self._make_forward("prefill_cached")
        self._set_counts_row_fn = self._make_set_counts_row()
        self._init_first_token_feed()
        # Decode always runs the fused burst program (K == decode_steps).
        self._multi_decode_fns: Dict[int, Callable] = {}
        # Speculative verify program (prompt-lookup decoding): one jit fn
        # for the configured verify width; XLA lowers one variant per
        # block-table bucket, mirroring the decode variants.
        self._spec_verify_fns: Dict[int, Callable] = {}
        self._embed_fns: Dict[int, Callable] = {}
        self._write_block_fn = self._make_write_block()
        self._write_blocks_fn = self._make_write_blocks()

        # -- LoRA slot registry -------------------------------------------
        self.lora_slots: Dict[str, int] = {}  # adapter name -> slot (1-based)

        # -- counters (exported via /metrics) ------------------------------
        self.prompt_tokens_total = 0
        self.cached_tokens_total = 0  # prompt tokens skipped via prefix cache
        # Token positions the prefill programs computed on: spans padded to
        # their bucket (and the chunked step plan's rows to
        # [prefill_batch, chunk]).
        self.prefill_padded_tokens_total = 0
        self.kv_fetch_tokens_total = 0  # copied by the decode kernel
        # The share of the page layers that belong to layers with a
        # window (0 for a model without one): what ``kv_window_dead_tokens``
        # weighs a token past the window by.
        mc = self.model_config
        self._window_layer_share = sum(
            mc.window_of(mc.layer_kind(l)) is not None
            for l in range(mc.num_layers)) / max(self.page_dims[0], 1)
        # Prefill rows that began from a block's state, and block entries
        # written (a family with Family.block_state; 0 otherwise).
        self.state_restores_total = 0
        self.state_blocks_written_total = 0
        self.generation_tokens_total = 0
        self.requests_finished_total = 0
        self.step_count = 0
        # Plain-prefill groups dispatched / prompts they carried (whether
        # same-rung prompts met in the queue: _do_prefill_group).
        self.prefill_group_count = 0
        self.prefill_group_rows = 0
        # Chunked prefill: chunks dispatched, prompt tokens deferred to a
        # later step by the per-step budget, and the last chunked step's
        # batched-token count (utilization of --max-num-batched-tokens).
        self.prefill_chunks_total = 0
        self.deferred_prefill_tokens_total = 0
        self.last_step_batched_tokens = 0
        # Mid-prefill sequences evicted by extend-time OOM (distinct from
        # scheduler-level preemptions, which have their own counter).
        self.prefill_chunk_requeues_total = 0
        # Fused step program: prefill-span + decode-burst pairs executed
        # as ONE dispatch (scheduler action "fused"); and cached-prefill
        # dispatches by attention path — "pallas" when the flash prefix
        # kernel's trace-time tile gate admits the page shape, "xla" for
        # the gather reference (exported as
        # tpu:prefill_attention_dispatch_total{path=...}).
        self.fused_steps_total = 0
        self.prefill_attention_dispatch_total = {"pallas": 0, "xla": 0}
        # Step programs of a model with an expert layer, by the path that
        # ops/pallas_grouped_matmul.py::grouped_matmul_path names at their
        # tokens; exported as tpu:expert_matmul_dispatch_total{path=...}.
        self.expert_matmul_dispatch_total = dict.fromkeys(
            ("pallas", "pallas_one_tile", "xla"), 0)
        # Decode programs of a model with a latent cache, by the path
        # the absorbed attention over its pages takes: "pallas"
        # (ops/pallas_mla_decode.py) or "xla" (gather and einsum);
        # exported as tpu:latent_decode_dispatch_total{path=...}.
        self.latent_decode_dispatch_total = {"pallas": 0, "xla": 0}
        # Cached-prefill programs of such a model, by the form their
        # attention over the gathered latents takes at the program's
        # shapes (models/decoder.py::latent_prefill_form); exported as
        # tpu:latent_prefill_form_total{form=...}, and on the step's
        # record as latent_prefill_<form>.
        self.latent_prefill_form_total = {"absorbed": 0, "up_projected": 0}
        # While set, _dispatch diverts prefill/decode ops into this list
        # (each entry (name, static, arrays, placeholder)) instead of
        # executing them; _do_fused then issues them as one "fused" op.
        self._fused_capture: "Optional[list]" = None
        # Speculative decoding (prompt lookup): draft tokens sent to the
        # verify program / accepted by it, requests latched back to plain
        # decode by the adaptive fallback, verify bursts dispatched, and
        # the model-forward-step count behind them (a plain K-step burst
        # is K sequential forwards; a verify burst is ONE — generation
        # tokens per forward step is the speedup speculation buys).
        self.spec_proposed_tokens_total = 0
        self.spec_accepted_tokens_total = 0
        self.spec_disabled_requests_total = 0
        self.spec_verify_bursts_total = 0
        self.decode_forward_steps_total = 0
        # Per-proposer split of the proposed/accepted totals (exported
        # as the source label on tpu:spec_{proposed,accepted}_tokens_total)
        # and the drafter's own forward count — draft forwards are small-
        # model steps, so they are NOT in decode_forward_steps_total (the
        # tokens-per-TARGET-forward speedup metric).
        self.spec_proposed_by_source = {"ngram": 0, "draft_model": 0}
        self.spec_accepted_by_source = {"ngram": 0, "draft_model": 0}
        self.spec_draft_forward_steps_total = 0
        # Structured output: compiled token-FSM cache (LRU, knob-sized)
        # and the tpu:structured_* counters. The packed mask row width is
        # fixed by the padded vocab so every program shares one shape.
        self._structured_cache = StructuredCache(
            self.config.structured_cache_size)
        self._mask_row_bytes = mask_row_bytes(self.model_config.vocab_size)
        self.structured_requests_total = 0
        self.structured_violations_total = 0
        # Step flight recorder, and the one clock of the engine loop:
        # ``_steps`` times every step and its phases (the wall-clock
        # split in stats() is its per-kind and per-phase totals) whether
        # or not the recorder is on. With the recorder on
        # (``step_recorder``, the same object) each step also makes a
        # record (kind, batch composition, wall time and phases, roofline
        # HBM byte estimate) and the phases are annotated into profiler
        # traces. The step functions stash a pending info dict; _loop
        # completes it with the measured wall time.
        self._steps = StepRecorder(
            capacity=config.step_record_capacity,
            kv_token_bytes=(
                self._kv_bytes_per_block() // config.block_size),
            hbm_bytes_per_s=device_hbm_bytes_per_s(self._local_device()),
        )
        self.step_recorder: Optional[StepRecorder] = (
            self._steps if config.step_recorder else None)
        self._step_info: Optional[dict] = None
        # Warmup variant counts per program family (compile-budget
        # regression tests read this; also logged at the end of warmup).
        self.warmup_variants: Dict[str, int] = {}
        self.warmup_seconds = 0.0
        self._sleeping = False
        self._sleep_level = 1
        self._host_params = None

        # In-flight speculative decode burst: dispatched to the device but
        # not yet read back (see _do_decode pipelining).
        self._pending_burst: Optional[dict] = None
        # Prefills dispatched but whose first token is not yet read back
        # (deferred sync: see _do_prefill / _flush_pending_prefills).
        self._pending_prefills: "list[dict]" = []
        # Device-resident [B, K] tokens of the most recent burst — the
        # next burst's feedback source (kept per-process so multi-host
        # followers never need the leader to ship device state).
        self._last_burst_tokens = None

        # Per-slot output-token counts [B, V] (device-resident), the state
        # behind presence/frequency penalties: updated inside the fused
        # burst, row-reset in-burst for freshly prefilled slots. Small
        # (B x V x 4B; 2 MB at 16 x 32k) and never host-transferred.
        # Created THROUGH jit with an explicit mesh sharding: a plain
        # jnp.zeros would be committed to this process's default device
        # only, which cannot feed a computation over a multi-host mesh.
        _counts_shape = (config.max_num_seqs, self.model_config.vocab_size)
        self._token_counts = jax.jit(
            lambda: jnp.zeros(_counts_shape, jnp.int32),
            out_shardings=self._repl)()
        # What a burst with no burst before it feeds back: zeros placed
        # like a burst's own tokens, so that the first burst and warm-up
        # run the program every later burst runs (a host array here was a
        # second compiled variant per table width).
        self._no_burst_tokens = jax.jit(
            lambda: jnp.zeros(
                (config.max_num_seqs, max(config.decode_steps, 1)),
                jnp.int32),
            out_shardings=self._repl)()
        # Slots whose counts row must reset at the next burst (set when a
        # prefill lands in the slot; consumed by _do_decode).
        self._counts_reset: "set[int]" = set()

        # -- engine thread -------------------------------------------------
        self._lock = threading.Condition()
        # Held for the duration of each forward step; sleep()/wake_up() take
        # it before swapping params/kv so a mid-flight step never sees None.
        # Lock order: _step_lock before _lock.
        self._step_lock = threading.Lock()
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="engine-core"
        )

    # ------------------------------------------------------------------ #
    # setup helpers
    # ------------------------------------------------------------------ #
    def _maybe_load_checkpoint(self) -> None:
        """If the model points at a local HF checkpoint directory, replace
        the random-init leaves with the loaded weights (device_put with the
        leaf's mesh sharding). Leaves the checkpoint doesn't carry — LoRA
        slots — keep their init values."""
        from production_stack_tpu.models.weights import (
            has_checkpoint,
            load_checkpoint,
        )

        if not has_checkpoint(self.config.model):
            return
        loaded = load_checkpoint(self.model_config, self.config.model)
        if self.config.quantization == "int8":
            # Quantize on the host so the device transfer ships int8 (and
            # the merged leaves match the quantized init structure).
            from production_stack_tpu.models.quantize import quantize_loaded

            loaded = quantize_loaded(
                loaded, self.model_config.arch,
                quantize_embeddings=self.config.quantize_embeddings)

        self.params = place_checkpoint(
            self.model_config, self.mesh, self.params, loaded,
            self._param_shardings)
        # The host staging tree holds the FULL checkpoint (bf16 unless
        # quantize_loaded already shrank it) — on an 8B model that is
        # ~16 GB of host RAM pinned for the rest of the process if left
        # to the GC's leisure, and it shows up as "residual HBM" when
        # the runtime backs host buffers with device-adjacent memory.
        # Drop it eagerly, before warmup starts compiling.
        del loaded
        gc.collect()
        logger.info("Loaded checkpoint weights from %s", self.config.model)

    def _kv_bytes_per_block(self) -> int:
        """See module-level :func:`kv_bytes_per_block` (tests and the
        server's capacity gauge call that directly)."""
        return kv_bytes_per_block(
            self.model_config, self.config.block_size,
            self.config.kv_cache_dtype)

    def _local_device(self):
        """First ADDRESSABLE mesh device: in a multi-host job, device [0]
        may belong to another process and expose no stats here."""
        return next(
            (d for d in self.mesh.devices.flat
             if d.process_index == jax.process_index()),
            self.mesh.devices.flat[0])

    def _free_hbm_bytes(self) -> Optional[int]:
        """Free memory on this process's first mesh device, from the
        runtime's ``memory_stats()``. None where the platform keeps no
        such figure (the CPU test meshes: they get the minimal pool); a
        TPU that does not answer is an error, not a guess."""
        dev = self._local_device()
        stats = dev.memory_stats()
        if not stats:
            if dev.platform == "tpu":
                raise RuntimeError(
                    f"{dev} returned no memory_stats(): the KV pool cannot "
                    f"be sized; pass --num-blocks")
            return None
        return stats["bytes_limit"] - stats["bytes_in_use"]

    def _auto_num_blocks(self) -> int:
        """Size the KV pool from free device memory (hbm_utilization)."""
        free = self._free_hbm_bytes()
        if free is not None:
            # Pages shard over tp (kv-head axis) and pp (layer axis) ONLY
            # when the dims divide (kv_pages_sharding falls back to
            # replicated otherwise) — scale the budget by the factors that
            # actually engage, or a replicated pool would be sized x-fold
            # over per-device capacity and OOM HBM at startup.
            mc = self.model_config
            tp = self.mesh.shape.get("tp", 1)
            pp = self.mesh.shape.get("pp", 1)
            tp_factor = tp if tp > 1 and mc.num_kv_heads % tp == 0 else 1
            pp_factor = pp if pp > 1 and self.page_dims[0] % pp == 0 else 1
            # Explicit per-device headroom reserve comes off the top:
            # residual allocations that memory_stats misses (checkpoint
            # staging remnants, XLA autotuning scratch) repeatedly OOMed
            # llama8b at utilization budgets that looked safe on paper.
            free = max(free - self.config.hbm_headroom_reserve, 0)
            budget = free * self.config.hbm_utilization * tp_factor * pp_factor
            num = int(budget // self._kv_bytes_per_block())
        else:
            num = 0
        min_blocks = self.config.max_blocks_per_seq * 2
        num = max(num, min_blocks)
        # Cap by what max_num_seqs could ever use, plus prefix-cache headroom.
        cap = self.config.max_blocks_per_seq * (self.config.max_num_seqs * 4)
        return min(num, cap)

    def _alloc_kv(self):
        mc = self.model_config
        layers, rows, lanes = self.page_dims
        shape, v_shape = (
            (layers, self.num_blocks, self.config.block_size) + side
            for side in self.page_side_dims)
        state_shape = self.block_state_shape and (
            self.block_state_shape[0], self.num_blocks) + self.block_state_shape[1:]

        def with_state(k, v):
            # Zeros: a block's state before anything was written into it
            # is a sequence's before its first token.
            return (k, v) + ((jnp.zeros(state_shape, mc.jnp_dtype),)
                             if state_shape else ())

        if self.config.kv_cache_dtype == "int8":
            sshape = (layers, self.num_blocks,
                      self.config.block_size * mc.num_kv_heads)

            @functools.partial(
                jax.jit, out_shardings=self._kv_pair_sharding)
            def zeros_q():
                # Scales init to 1 (not 0): a never-written slot must
                # dequantize its zero int8 data to exact zeros without
                # a 0*0-vs-NaN hazard anywhere downstream.
                return with_state(
                    (jnp.zeros(shape, jnp.int8),
                     jnp.ones(sshape, jnp.float32)),
                    (jnp.zeros(shape, jnp.int8),
                     jnp.ones(sshape, jnp.float32)))

            return zeros_q()

        @functools.partial(jax.jit, out_shardings=self._kv_pair_sharding)
        def zeros():
            z = jnp.zeros(shape, mc.jnp_dtype)
            return with_state(z, jnp.zeros(v_shape, mc.jnp_dtype))

        return zeros()

    def _asked_to_move_pages(self) -> Dict[str, bool]:
        """flag -> whether this engine was asked for it, over the
        surfaces that move, roll back or split pages, which a family
        whose blocks hold more than grouped keys and values has to be
        taught one by one."""
        cfg = self.config
        return {
            "--speculative-num-tokens": cfg.speculative_num_tokens > 0,
            "--speculative-draft-model": bool(cfg.speculative_draft_model),
            "--kv-offload-bytes": cfg.kv_offload_bytes > 0,
            "--kv-remote-url": bool(cfg.kv_remote_url),
            "--tensor-parallel-size / a mesh of several devices":
                self.mesh.size > 1 or self._mh is not None,
        }

    def _refuse_what_the_block_state_is_not_taught(self) -> None:
        """A family whose blocks hold a state beside their pages
        (``Family.block_state``) is served by the paths that carry the
        state. Every other one that moves, rolls back or splits pages is
        refused here, by the flag that asks for it: none drops the state
        silently."""
        if not self.block_state_shape:
            return
        asked = self._asked_to_move_pages()
        refused = sorted(flag for flag, on in asked.items() if on)
        if refused:
            raise ValueError(
                f"model arch {self.model_config.arch!r} keeps a state per "
                f"cache block beside its pages, which {', '.join(refused)} "
                "would not carry: not supported for this family yet")

    def _refuse_what_the_page_sides_are_not_taught(self) -> None:
        """A family whose page has two sides of its own shapes
        (``Family.page_sides``: a latent cache) is served by the paths
        that move block ids, and by ``extract_kv`` / ``inject_kv_blocks``,
        which speak each side's own shape. Every other surface that moves
        page *bytes*, or reads a page as ``num_kv_heads x head_dim`` keys
        and values, is refused here by the flag that asks for it (the
        KV-transfer routes of the server answer 501; pipeline stages and
        int8 weights are refused beside this, by ``Family.pipeline`` and
        ``Family.quant_keys``)."""
        if self.own_page_sides is None:
            return
        asked = {**self._asked_to_move_pages(),
                 "--kv-cache-dtype int8":
                     self.config.kv_cache_dtype == "int8"}
        refused = sorted(flag for flag, on in asked.items() if on)
        if refused:
            raise ValueError(
                f"model arch {self.model_config.arch!r} keeps a latent and "
                "a rotated key per token, two page sides of unequal width, "
                f"which {', '.join(refused)} is not taught: not supported "
                "for this family yet")

    @staticmethod
    def _is_resource_exhausted(exc: BaseException) -> bool:
        """XLA surfaces device OOM as XlaRuntimeError with a
        RESOURCE_EXHAUSTED status string (no stable exception subclass
        across jaxlib versions — the same string-match bench.py used for
        its re-exec workaround, now handled in-process)."""
        return "RESOURCE_EXHAUSTED" in str(exc)

    def _alloc_kv_with_shrink(self):
        """KV-pool allocation with an OOM pool-shrink retry ladder.

        Auto-sizing works from free-HBM estimates that can miss residual
        allocations (checkpoint staging remnants, compiler workspaces),
        so the first allocation may land on ResourceExhausted even at a
        sane hbm_utilization. Instead of dying — and forcing the
        fresh-process relaunch bench.py used to do — shrink num_blocks
        by pool_shrink_step and retry, up to pool_shrink_retries rungs,
        never below the 2-sequence floor. Multihost replicas exchange
        num_blocks before allocation and must agree on array shapes, so
        the ladder only engages single-host; a multihost OOM still
        raises (the leader's figure is already committed to peers)."""
        cfg = self.config
        rungs = cfg.pool_shrink_retries if self._mh is None else 0
        min_blocks = cfg.max_blocks_per_seq * 2
        for rung in range(rungs + 1):
            try:
                return self._alloc_kv()
            except Exception as e:  # noqa: BLE001 - XlaRuntimeError
                if not self._is_resource_exhausted(e):
                    raise
                if rung >= rungs or self.num_blocks <= min_blocks:
                    logger.error(
                        "KV pool allocation RESOURCE_EXHAUSTED with no "
                        "shrink rungs left (num_blocks=%d, floor=%d)",
                        self.num_blocks, min_blocks)
                    raise
                shrunk = max(
                    int(self.num_blocks * (1.0 - cfg.pool_shrink_step)),
                    min_blocks)
                logger.warning(
                    "KV pool allocation RESOURCE_EXHAUSTED at %d blocks; "
                    "shrinking to %d (rung %d/%d)",
                    self.num_blocks, shrunk, rung + 1, rungs)
                self.num_blocks = shrunk
                self.pool_shrink_retries_total += 1
                gc.collect()  # drop the failed allocation's host refs

    def _stats_outputs(self):
        """(keyword for ``apply``, the out_shardings of what it adds to a
        step program's outputs): both empty for a family that counts
        nothing, whose programs are then what they always were."""
        if not self._stat_names:
            return {}, ()
        return {"with_stats": True}, (self._repl,)

    def _tap_stats(self, program, forwards: int, prefix: str = ""):
        if not self._stat_names:
            return program
        return _StatsTap(program, self._stats_pending.append, forwards,
                         prefix)

    def _note_block_state(self, first, lens) -> None:
        """What a prefill dispatch does with the blocks' state
        (``Family.block_state``), from its rows' first positions and
        lengths: ``state_rows``, its rows that hold a span;
        ``state_restores``, those of them that begin from a block's
        state (behind a prefix hit or an earlier chunk); and
        ``state_blocks_written``, the blocks whose entry they write. Into
        the step's record (summed over its dispatches) and the lifetime
        totals."""
        first, lens = np.asarray(first), np.asarray(lens)
        live = lens > 0
        bs = self.config.block_size
        restores = int((live & (first > 0)).sum())
        written = int(np.where(
            live, (first + lens - 1) // bs - first // bs + 1, 0).sum())
        self.state_restores_total += restores
        self.state_blocks_written_total += written
        self._steps.note_sum(state_rows=int(live.sum()),
                             state_restores=restores,
                             state_blocks_written=written)

    def _note_family_stats(self) -> None:
        """The counts of the step programs that have finished since the
        last step go into this step's record and into the lifetime
        totals: a decode burst's under the family's names beside
        ``stats_forwards`` (how many forwards they cover), a prefill
        program's under the same names behind ``prefill_``. A program
        still running keeps its counts for a later step: nothing here
        waits for the device, so a record carries the counts of the
        programs read back in its step, not of the one it dispatched."""
        noted: Dict[str, int] = {}
        while self._stats_pending and self._stats_pending[0][2].is_ready():
            prefix, forwards, stats = self._stats_pending.popleft()
            counted = dict(zip(self._stat_names,
                               (int(v) for v in np.asarray(stats))))
            for name, value in counted.items():
                self.family_stats_total[name] += value
            counted["stats_forwards"] = forwards
            for name, value in counted.items():
                noted[prefix + name] = noted.get(prefix + name, 0) + value
        if noted:
            self._steps.note(**noted)

    def _make_forward(self, mode: str):
        """Prefill program: forward + on-device sampling of the last real
        token's logits fused into ONE dispatch (the token is the only value
        the host ever reads back — fusing removes a logits round-trip and a
        separate sampling dispatch per prefill)."""
        apply = self._apply
        cfg = self.model_config
        max_top_k = self.config.max_top_k
        seed_static = self.config.seed
        with_stats, stats_sharding = self._stats_outputs()

        _eos = getattr(self.tokenizer, "eos_token_id", None)
        eos_id = int(_eos) if _eos is not None else -1  # 0 is a valid id

        def fwd(params, kv, token_ids, positions, slot_mapping,
                block_tables, context_lens, seq_lens, adapter_ids,
                temperature, top_k, top_p, seq_seeds, steps,
                suppress_eos, bias_ids, bias_vals, stop_ids, stop_valid,
                mask_bits, mask_on):
            # Prefill: only the last REAL token's logits are ever read,
            # so the model slices hidden states to that position before
            # the vocab projection (for 128k-vocab models the full
            # [B, T, V] f32 logits temp is multi-GB and its head GEMM is
            # pure waste).
            last_idx = (None if mode == "decode"
                        else jnp.maximum(seq_lens - 1, 0))
            logits, kv, *stats = apply(
                params, cfg, token_ids, positions, kv, slot_mapping,
                block_tables, context_lens, seq_lens,
                mode=mode, adapter_ids=adapter_ids, last_token=last_idx,
                **with_stats,
            )
            with jax.named_scope("sample"):
                last = logits[:, 0]
                # logit_bias, then (min_tokens: for the first token) EOS
                # and the stop ids masked, then the grammar's FSM mask
                # (all-off for unconstrained sequences).
                shaped = shape_logits(
                    last, burst_terms(
                        last.shape[1], bias_ids, bias_vals, stop_ids,
                        stop_valid, mask_bits, mask_on),
                    suppress_eos, eos_id)
                # per row, so a row of a group samples as it would alone
                keys = make_rng_keys(seed_static, steps, seq_seeds + steps)
                # Logprobs reflect the distribution actually sampled from
                # (logit_bias + min_tokens masking applied), matching
                # OpenAI/vLLM post-processor logprob semantics.
                return (sample_with_logprobs(
                    shaped, keys, temperature, top_k, top_p,
                    max_top_k=max_top_k), kv, *stats)

        # The program's name in a profiler trace (the XLA Modules line)
        # and in the step records: ``prefill`` or ``prefill_cached``.
        fwd.__name__ = mode
        # Sampled tokens / logprobs are read back on the host: pin them
        # fully replicated so device_get works from any process of a
        # multi-host mesh (and is a no-copy local read).
        return self._tap_stats(jax.jit(
            fwd, donate_argnums=(1,),
            out_shardings=((self._repl,) * 4, self._kv_pair_sharding)
            + stats_sharding), 1, "prefill_")

    def _make_multi_decode(self, K: int):
        """Fused K-step decode: forward + on-device sampling (keys derived
        on device) + next-token feedback run in one compiled lax.scan — one
        host round-trip (and one [B, K] token transfer) per K generated
        tokens, instead of a dispatch + logits sync per token. The
        serving-throughput analog of vLLM's multi-step scheduling, shaped
        for XLA. Per-sequence early exit is handled by the caller: steps a
        sequence cannot use carry slot id -1 (the page write drops) and
        their sampled tokens are discarded at emission."""
        apply = self._apply
        cfg = self.model_config
        max_top_k = self.config.max_top_k
        seed = self.config.seed
        K_max = max(self.config.decode_steps, 1)
        with_stats, stats_sharding = self._stats_outputs()

        _eos = getattr(self.tokenizer, "eos_token_id", None)
        eos_id = int(_eos) if _eos is not None else -1  # 0 is a valid id

        def fwd(params, kv, counts, reset_counts, tokens_prev, tok_idx,
                host_tokens, use_host, positions0, slot_mat, block_tables,
                context0, adapter_ids, temperature, top_k, top_p,
                seed_base, presence_penalty, frequency_penalty,
                min_tokens, out_len0, bias_ids, bias_vals,
                stop_ids, stop_valid, mask_bits, mask_on):
            # tokens_prev: [B, K] the PREVIOUS burst's sampled tokens (device
            # array — the feedback token never round-trips to the host, which
            # is what lets the engine dispatch burst N+1 before reading
            # burst N); tok_idx selects each sequence's last valid step;
            # host_tokens/use_host override rows for sequences that just
            # prefilled. Other args: [B] or [B, K] as before.
            with jax.named_scope("sample"):
                tokens0 = jnp.where(
                    use_host, host_tokens,
                    jnp.take_along_axis(
                        tokens_prev, tok_idx[:, None], 1)[:, 0],
                )
                # Freshly prefilled slots start a new output: zero their
                # penalty-count rows in-burst (no extra dispatch), then count
                # the slot's first output token (sampled during prefill, it
                # arrives here as tokens0) so penalties see it too.
                counts = jnp.where(reset_counts[:, None], 0, counts)
                B = tokens0.shape[0]
                counts = counts.at[jnp.arange(B), tokens0].add(
                    reset_counts.astype(jnp.int32))
                # logit_bias, the stop ids and the structured mask are
                # constant across the scan (the host advances a grammar's
                # automaton only at burst boundaries, so structured rows
                # are scheduled with allow=1 — steps past the first are
                # discarded at emission and their stale mask never
                # reaches a stream): their dense forms are built once
                # here, not in each step.
                terms = burst_terms(
                    counts.shape[1], bias_ids, bias_vals, stop_ids,
                    stop_valid, mask_bits, mask_on)

            def body(carry, step_slots):
                tokens, kv, counts, s = carry
                logits, kv, *stats = apply(
                    params, cfg, tokens[:, None], (positions0 + s)[:, None],
                    kv, step_slots[:, None], block_tables, context0 + s,
                    jnp.ones_like(context0), mode="decode",
                    adapter_ids=adapter_ids, **with_stats,
                )
                with jax.named_scope("sample"):
                    # OpenAI presence/frequency penalties over the slot's
                    # OUTPUT tokens, plus logit_bias, min_tokens EOS /
                    # stop-id masking and the structured mask. Logprobs
                    # are computed from these shaped logits (OpenAI/vLLM
                    # post-processor semantics).
                    penalized = shape_logits(
                        apply_penalties(logits[:, 0], counts,
                                        frequency_penalty, presence_penalty),
                        terms, (out_len0 + s) < min_tokens, eos_id)
                    keys = make_rng_keys(seed, 0, seed_base + s)
                    sampled, lp, top_lp, top_ids = sample_with_logprobs(
                        penalized, keys, temperature, top_k, top_p,
                        max_top_k=max_top_k)
                    # Only steps whose page slot is live count (masked
                    # speculative steps are discarded at emission).
                    live = (step_slots >= 0).astype(jnp.int32)
                    counts = counts.at[jnp.arange(B), sampled].add(live)
                return ((sampled, kv, counts, s + 1),
                        (sampled, lp, top_lp, top_ids, *stats))

            ((_, kv, counts, _),
             (out, lps, top_lps, top_idxs, *stats)) = jax.lax.scan(
                body, (tokens0, kv, counts, jnp.int32(0)), slot_mat.T,
                length=K,
            )
            # Feedback tokens are padded to the FULL decode_steps width so
            # tokens_prev keeps one static shape across adaptive burst
            # widths (decode_steps_pressure) — otherwise each (K_cur,
            # K_prev) pair would compile its own program.
            with jax.named_scope("sample"):
                out_fb = out
                if K < K_max:
                    out_fb = jnp.concatenate(
                        [out, jnp.zeros((K_max - K,) + out.shape[1:],
                                        out.dtype)], axis=0)
                # [K, B, ...] -> [B, K, ...]; the family's counts summed
                # over the burst's K forwards
                return ((out_fb.T, lps.T, top_lps.swapaxes(0, 1),
                         top_idxs.swapaxes(0, 1)), kv, counts,
                        *(st.sum(axis=0) for st in stats))

        fwd.__name__ = f"decode_k{K}"
        return self._tap_stats(jax.jit(
            fwd, donate_argnums=(1, 2),
            out_shardings=((self._repl,) * 4, self._kv_pair_sharding,
                           self._repl) + stats_sharding), K)

    def _multi_decode_fn(self, K: int):
        fn = self._multi_decode_fns.get(K)
        if fn is None:
            fn = self._make_multi_decode(K)
            self._multi_decode_fns[K] = fn
        return fn

    def _make_spec_verify(self, K: int):
        """Speculative verify: score K draft positions in ONE forward.

        Input row s carries [last_emitted, d1, .., d_{K-1}] at positions
        base-1 .. base+K-2; the cached-prefill path writes each token's
        KV page before attention, and the causal mask over the block
        table means position base-1+s attends exactly the pages the
        plain decode scan's step s would (its own just-written token
        included). Each position's logits then get the SAME per-step
        shaping and rng-key schedule as the decode scan (bias, min_tokens
        EOS/stop masking, make_rng_keys(seed, 0, seed_base + s)), so the
        sample at position s IS what plain decode would have emitted at
        that step given the same prefix — acceptance reduces to the
        longest prefix where sample == draft, and emitting the samples
        themselves keeps the stream identical to non-speculative
        decoding at ANY temperature (exact for greedy; for sampled
        requests the match holds through the shared rng schedule).

        Presence/frequency penalties need cross-step device counts that
        a single-pass verify cannot update mid-pass; requests using them
        are spec-ineligible (the scheduler never proposes for them), so
        this program omits the counts state entirely — for eligible rows
        the decode scan's penalty term is an exact zero subtraction.
        """
        apply = self._apply
        cfg = self.model_config
        max_top_k = self.config.max_top_k
        seed = self.config.seed

        _eos = getattr(self.tokenizer, "eos_token_id", None)
        eos_id = int(_eos) if _eos is not None else -1  # 0 is a valid id

        def fwd(params, kv, tokens, positions0, slot_mat, block_tables,
                context0, adapter_ids, temperature, top_k, top_p,
                seed_base, min_tokens, out_len0, bias_ids, bias_vals,
                stop_ids, stop_valid, mask_bits, mask_on):
            B = tokens.shape[0]
            positions = positions0[:, None] + jnp.arange(K)[None, :]
            logits, kv = apply(
                params, cfg, tokens, positions, kv, slot_mat,
                block_tables, context0 + K - 1,
                jnp.full((B,), K, jnp.int32),
                mode="prefill_cached", adapter_ids=adapter_ids,
            )
            with jax.named_scope("sample"):
                # Per-position logit shaping + sampling, identical to the
                # decode scan body (K is small — unrolled); what the
                # positions share is built once.
                shared = burst_terms(
                    logits.shape[-1], bias_ids, bias_vals, stop_ids,
                    stop_valid, mask_bits[:, 0], mask_on[:, 0])
                outs, lp_l, top_lp_l, top_id_l = [], [], [], []
                for s in range(K):
                    # Structured output: position s's mask is precomputed on
                    # the host from the FSM state AFTER drafts 0..s-1 —
                    # exactly the mask plain decode would apply at that step,
                    # so drafts that exit the language are rejected here by
                    # the same term (mask_bits [B, K, MB], mask_on [B, K]).
                    terms = shared if s == 0 else shared._replace(
                        allowed=allowed_tokens(mask_bits[:, s], mask_on[:, s],
                                               logits.shape[-1]))
                    penalized = shape_logits(
                        logits[:, s], terms, (out_len0 + s) < min_tokens,
                        eos_id)
                    keys = make_rng_keys(seed, 0, seed_base + s)
                    sampled, lp, top_lp, top_ids = sample_with_logprobs(
                        penalized, keys, temperature, top_k, top_p,
                        max_top_k=max_top_k)
                    outs.append(sampled)
                    lp_l.append(lp)
                    top_lp_l.append(top_lp)
                    top_id_l.append(top_ids)
                return (jnp.stack(outs, 1), jnp.stack(lp_l, 1),
                        jnp.stack(top_lp_l, 1), jnp.stack(top_id_l, 1)), kv

        fwd.__name__ = f"spec_verify_k{K}"
        return jax.jit(
            fwd, donate_argnums=(1,),
            out_shardings=((self._repl,) * 4, self._kv_pair_sharding))

    def _spec_verify_fn(self, K: int):
        fn = self._spec_verify_fns.get(K)
        if fn is None:
            fn = self._make_spec_verify(K)
            self._spec_verify_fns[K] = fn
        return fn

    def _make_write_block(self):
        """Jitted single-block page write (offload restore / KV inject)."""

        @functools.partial(
            jax.jit, donate_argnums=(0,),
            out_shardings=self._kv_pair_sharding)
        def write_block(kv, bid, k, v, *state):
            return tuple(_kv_set(side, bid, new)
                         for side, new in zip(kv, (k, v) + state))

        return write_block

    def _make_set_counts_row(self):
        """Jitted penalty-counts row install (preemption-resume path)."""

        @functools.partial(jax.jit, donate_argnums=(0,),
                           out_shardings=self._repl)
        def set_row(counts, slot, row):
            return counts.at[slot].set(row)

        return set_row

    def _init_first_token_feed(self) -> None:
        """The state of the hand-over from a prefill to the burst behind
        it (``_exec_op``, ``_do_decode``). Set up from ``__init__`` in one
        line, and defined here, behind the step programs: a line added
        above them renumbers every operation they trace, and the compile
        cache keys a Mosaic kernel by its body's source lines."""
        # Jitted hand-over of a prefill's sampled first tokens to the next
        # decode burst, on the device: ``sampled[r]`` fills the whole row
        # ``slots[r]`` of the [B, K] feedback array a burst reads its
        # input tokens from (any ``tok_idx`` finds it), and a row of the
        # prefill that takes no decode slot carries ``B``, past the last
        # row, and is dropped. Not donated: the burst whose tokens the
        # array holds may not have been read back yet.

        @functools.partial(jax.jit, out_shardings=self._repl)
        def feed_first_tokens(tokens_prev, sampled, slots):
            return tokens_prev.at[slots].set(
                sampled[:, None].astype(tokens_prev.dtype), mode="drop")

        self._feed_first_tokens_fn = feed_first_tokens
        # (slot, seq) of every row whose prefill wrote its first token
        # into row ``slot`` of the feedback array (_last_burst_tokens)
        # since the last burst was built: the next burst takes those
        # tokens on the device (_do_decode).
        self._first_on_device: "list[tuple]" = []
        # Prefilled rows by where their first decode burst took their
        # first token from: "device" (the prefill's sample, scattered
        # into the burst's feedback array behind the prefill program, so
        # the burst was built and enqueued while the prefill still ran)
        # or "host" (read back first: _feeds_first_token says which rows,
        # _do_decode which bursts; a row that left before any burst took
        # it counts here too). Exported as
        # tpu:first_token_feed_total{path=...}.
        self.first_token_feed_total = {"device": 0, "host": 0}

    def _make_write_blocks(self):
        """Jitted BATCHED page write: all transferred blocks land in one
        dispatch (k/v are [L, N, bs, KVH, D], bids [N]) — the disagg
        receive path's scatter; per-block writes would cost one dispatch
        per page. A family's block state rides along as ``state``
        ([layers, N, rows, width])."""

        @functools.partial(
            jax.jit, donate_argnums=(0,),
            out_shardings=self._kv_pair_sharding)
        def write_blocks(kv, bids, k, v, *state):
            return tuple(_kv_set(side, bids, new)
                         for side, new in zip(kv, (k, v) + state))

        return write_blocks

    # -- multi-host lockstep dispatch -------------------------------------
    # Every serving-time device dispatch funnels through _dispatch: on a
    # single host it just executes; in a multi-host job the leader first
    # streams the op (name, static params, numpy args) to the followers,
    # and every process then enqueues the SAME compiled program via
    # _exec_op — the SPMD replacement for the reference's Ray actor RPCs
    # (ref helm/templates/ray-cluster.yaml). Device-side state (params,
    # KV pages, penalty counts, the previous burst's feedback tokens)
    # stays process-local as addressable shards of the global arrays.

    def _dispatch(self, name: str, static: dict, arrays: list):
        cap = self._fused_capture
        if cap is not None:
            if name in ("prefill", "decode"):
                # Fused capture: divert the op; _do_fused issues the
                # whole pair as ONE "fused" dispatch.
                ph = _FusedPlaceholder()
                cap.append((name, static, arrays, ph))
                return ph
            # An op the fused program cannot carry (spec verify,
            # counts-row rebuild, KV offload/restore...) arrived
            # mid-capture. Device-op ORDER is the correctness contract,
            # so degrade: stop capturing, issue what was captured as
            # individual dispatches, then this op normally below.
            self._fused_capture = None
            self._drain_captured(cap)
        mh = self._mh
        # The loop's ``enqueue`` phase: how much engine-thread wall time
        # goes into ENQUEUEING programs (microseconds each on an attached
        # chip); its count and seconds are stats()'s dispatch_count_total
        # and dispatch_enqueue_s. Readback waits are the ``readback``
        # phase.
        with self._steps.phase("enqueue"):
            if mh is None:
                return self._exec_op(name, static, arrays)
            with mh.lock:  # (send, enqueue) must be atomic for op ordering
                try:
                    mh.channel.send((name, static, arrays))
                except OSError as e:
                    # A partial fan-out (one follower's socket dead,
                    # others fed) is NOT recoverable: surviving followers
                    # replay the op while the leader would skip it, and
                    # the job silently diverges/wedges at the next
                    # collective. Mirror the follower side's die-loudly
                    # policy: latch fatal (surfaced by /health as 503 so
                    # probes restart the pod) and refuse further work.
                    self.fatal_error = (
                        f"op-channel send failed ({e!r}); multi-host "
                        f"lockstep broken — restart the job")
                    logger.exception(
                        "Leader: op-channel send for %r failed; latching "
                        "fatal (lockstep cannot be resumed past a "
                        "partial fan-out)", name)
                    raise RuntimeError(self.fatal_error) from e
                return self._exec_op(name, static, arrays)

    def _drain_captured(self, cap: list) -> None:
        """Issue captured-but-unexecuted ops as individual dispatches, in
        capture order (the degraded path: capture aborted, or the fused
        dispatch itself failed). A failure poisons every remaining
        placeholder so deferred readbacks surface the error instead of
        waiting forever, then re-raises."""
        err = None
        for name, static, arrays, ph in cap:
            if ph.ready:
                continue
            if err is None:
                try:
                    ph.value = self._dispatch(name, static, arrays)
                except Exception as e:  # noqa: BLE001
                    err = e
                    ph.error = e
            else:
                ph.error = err
            ph.ready = True
        if err is not None:
            raise err

    def _abort_fused_capture(self) -> None:
        """Leave fused-capture mode and really execute anything already
        captured. Called by step paths that need host-visible results
        mid-step (spec drafting, structured masking) — fusion cannot
        carry those, and their builds read tokens the captured prefill
        has not produced yet."""
        cap = self._fused_capture
        if cap is None:
            return
        self._fused_capture = None
        self._drain_captured(cap)

    def _exec_op(self, name: str, static: dict, arrays: list):
        """The single source of truth for what each op does on-device;
        leader and followers both run exactly this."""
        if name == "prefill":
            fn = (self._prefill_cached_fn if static["cached"]
                  else self._prefill_fn)
            # arrays[0] is the [rows, bucket] token array: what the
            # program computes on, padding included.
            self.prefill_padded_tokens_total += arrays[0].size
            self._steps.note_program(fn.__name__,
                                     padded_tokens=arrays[0].size)
            self._count_expert_matmul_path(arrays[0].size)
            if self.block_state_shape:
                self._note_block_state(arrays[1][:, 0], arrays[5])
            if static["cached"] and self.own_page_sides is not None:
                self._count_latent_prefill_form(arrays[0].shape[1],
                                                arrays[3].shape[1])
            out, self.kv = fn(self.params, self.kv, *arrays)
            feed = static.get("feed")
            if feed is not None:
                # The rows' first tokens, into the feedback array the next
                # burst reads (``feed``: a decode slot a row of the
                # sample). Behind the prefill on the device's queue, and
                # so behind any burst still in flight, whose output that
                # array is: a slot's former owner, which such a burst may
                # still cover, is overwritten and not the other way round.
                prev = self._last_burst_tokens
                self._last_burst_tokens = self._feed_first_tokens_fn(
                    self._no_burst_tokens if prev is None else prev,
                    out[0], np.asarray(feed, np.int32))
            return out
        if name == "decode":
            K = static["K"]
            fn = self._multi_decode_fn(K)
            self._steps.note_program(fn.__name__)
            self._count_expert_matmul_path(arrays[0].shape[0])
            if self.own_page_sides is not None:
                self.latent_decode_dispatch_total[
                    self._latent_decode_path()] += 1
            # Feedback tokens always carry the FULL decode_steps width
            # (bursts pad their output) so adaptive widths share shapes.
            tokens_prev = (
                self._last_burst_tokens if static["use_prev"]
                else self._no_burst_tokens)
            outs, self.kv, self._token_counts = fn(
                self.params, self.kv, self._token_counts, arrays[0],
                tokens_prev, *arrays[1:])
            # The feedback tokens for the NEXT burst live on device on
            # every process (the host never sees them mid-pipeline).
            self._last_burst_tokens = outs[0]
            return outs
        if name == "fused":
            # One dispatch, several already-compiled programs back to
            # back: the constituent ops run through this same method, so
            # leader and followers replay identically and warmup needs
            # ZERO new variants for the fused path.
            outs = []
            off = 0
            for n_i, s_i, c_i in zip(static["names"], static["statics"],
                                     static["counts"]):
                outs.append(self._exec_op(n_i, s_i, arrays[off:off + c_i]))
                off += c_i
            return outs
        if name == "spec_verify":
            # Speculative verify burst. Does NOT touch _last_burst_tokens:
            # spec-mode bursts always flush before dispatching, so the
            # next burst feeds from host tokens, never from device
            # feedback (use_prev is False throughout spec mode).
            fn = self._spec_verify_fn(static["K"])
            self._steps.note_program(fn.__name__)
            outs, self.kv = fn(self.params, self.kv, *arrays)
            return outs
        if name == "draft_forward":
            # Draft-model catch-up / FSM-constrained draft step: runs
            # against the DRAFTER's params and pages — never compiles or
            # touches a target-model program.
            d = self._draft
            self._steps.note_program(d.forward_fn.__name__)
            out, d.kv = d.forward_fn(d.params, d.kv, *arrays)
            return out
        if name == "draft_scan":
            d = self._draft
            self._steps.note_program(d.scan_fn.__name__)
            out, d.kv = d.scan_fn(d.params, d.kv, *arrays)
            return out
        if name == "set_counts_row":
            self._token_counts = self._set_counts_row_fn(
                self._token_counts, *arrays)
            return None
        if name == "write_block":
            # int8 payloads arrive flattened over the op channel
            # ([bid, kd, ks, vd, vs]); regroup into (data, scales)
            # tuple leaves (single-host dispatch passes tuples through
            # untouched — _regroup_kv_payload is shape-stable there).
            self.kv = self._write_block_fn(
                self.kv, *_regroup_kv_payload(arrays))
            return None
        if name == "write_blocks":
            self.kv = self._write_blocks_fn(
                self.kv, *_regroup_kv_payload(arrays))
            return None
        if name == "embed":
            fn = self._embed_fn(static["bucket"])
            return fn(self.params, *arrays)
        if name == "lora_load":
            return self._lora_load_local(**static)
        if name == "lora_unload":
            return self._lora_unload_local(**static)
        if name == "gather_blocks":
            # Disagg extract: replicated gather of the selected pages so
            # ANY process (the leader) can host-read them.
            return self._gather_blocks_fn(self.kv, jnp.asarray(arrays[0]))
        if name == "offload_block":
            return self._offload_block_local(static["hash"],
                                             int(arrays[0]))
        if name == "restore_block":
            return self._restore_block_local(static["hash"],
                                             int(arrays[0]))
        if name == "sleep":
            return self._sleep_device()
        if name == "wake":
            return self._wake_device()
        raise ValueError(f"unknown multihost op {name!r}")

    def run_follower(self) -> None:
        """Mirror loop for follower processes (process_id > 0): replay the
        leader's op stream until it stops. The follower runs no scheduler,
        no HTTP surface — just the same sequence of XLA programs, each of
        which blocks at its collectives until all processes arrive."""
        assert self._mh is not None and not self._mh.is_leader
        logger.info("Follower %d/%d: entering mirror loop",
                    self._mh.process_id, self._mh.num_processes)
        while True:
            op = self._mh.channel.recv()
            if op[0] == "stop":
                logger.info("Follower: leader stopped, exiting")
                return
            try:
                self._exec_op(op[0], op[1], op[2])
            except Exception:  # noqa: BLE001
                # A failed replay is NOT safely resumable: ops donate
                # kv/_token_counts, so a host-local failure (per-host
                # OOM) can leave this process's buffers deleted while
                # the leader's mutation succeeded — continuing would
                # silently diverge lockstep. Die loudly instead: the
                # health endpoint goes down (probes restart the pod) and
                # the leader's next channel send surfaces the break.
                logger.exception(
                    "Follower: op %r failed — exiting (lockstep cannot "
                    "be resumed past a one-sided failure)", op[0])
                raise

    # -- KV offload / transfer helpers ------------------------------------
    def _offload_block(self, prefix_hash: int, bid: int) -> None:
        """Allocator eviction hook: queue a cached block for spill to host
        RAM. The hook can fire under ``self._lock`` (decode-path block
        accounting), so the actual device_get happens later in
        :meth:`_drain_offload`, after the lock is released but before any
        forward step overwrites the recycled pages."""
        if self.offload is None or self.kv is None:
            return
        self._pending_offload.append((prefix_hash, bid))

    def _drain_offload(self) -> None:
        """Copy queued evicted blocks to the host store (engine thread,
        under _step_lock, no _lock held). Multi-host: the spill is an op —
        every process stages ITS OWN addressable shards of the block into
        its local store (the stores stay in lockstep because puts/gets
        arrive in op order with identical shard sizes, so their LRU
        states are identical)."""
        if not self._pending_offload or self.kv is None:
            self._pending_offload.clear()
            return
        if self._mh is not None:
            if self.config.kv_remote_url:
                # Remote tier configured: the cache server stores WHOLE
                # blocks, so spill through ONE replicated gather for all
                # pending blocks (every process joins; only the leader
                # host-reads and owns the store — offload accounting is
                # leader-side host state, like the allocator's).
                if self._mh.is_leader:
                    bids = np.asarray(
                        [bid for _, bid in self._pending_offload],
                        np.int32)
                    out = self._dispatch("gather_blocks", {}, [bids])
                    k_all = _kv_leaf_get(out[0])
                    v_all = _kv_leaf_get(out[1])
                    for n, (prefix_hash, _) in enumerate(
                            self._pending_offload):
                        self.offload.put(prefix_hash,
                                         _kv_leaf_index(k_all, n),
                                         _kv_leaf_index(v_all, n))
            else:
                # Host-RAM tier only: every process stages its own
                # shards (no cross-host data movement).
                for prefix_hash, bid in self._pending_offload:
                    self._dispatch("offload_block", {"hash": prefix_hash},
                                   [np.int32(bid)])
            self._pending_offload.clear()
            return
        k_pages, v_pages = self.kv
        for prefix_hash, bid in self._pending_offload:
            k = _kv_leaf_get(_kv_leaf_index(k_pages, bid))
            v = _kv_leaf_get(_kv_leaf_index(v_pages, bid))
            self.offload.put(prefix_hash, k, v)
        self._pending_offload.clear()

    def _offload_block_local(self, prefix_hash: int, bid: int) -> None:
        """Per-process side of the multi-host spill: stage this process's
        shards of block ``bid``, keyed by shard index for exact
        reassembly in :meth:`_restore_block_local`."""
        if self.offload is None or self.kv is None:
            return

        def stage(leaf_block):
            if isinstance(leaf_block, tuple):
                return tuple(stage(e) for e in leaf_block)
            return {str(s.index): np.asarray(s.data)
                    for s in leaf_block.addressable_shards}

        k_pages, v_pages = self.kv
        k_sh = stage(_kv_leaf_index(k_pages, bid))
        v_sh = stage(_kv_leaf_index(v_pages, bid))
        self.offload.put(prefix_hash, k_sh, v_sh)

    def _restore_block_local(self, prefix_hash: int, bid: int) -> None:
        """Per-process side of the multi-host restore: reassemble the
        block from locally staged shards and join the global scatter."""
        entry = self.offload.get(prefix_hash) if self.offload else None
        if entry is None:
            # The leader checked contains() before dispatching and the
            # stores run in lockstep — a miss here means they diverged,
            # which is not resumable (the scatter below is collective).
            raise RuntimeError(
                f"offload store diverged: block {prefix_hash} missing "
                f"on process "
                f"{self._mh.process_id if self._mh else 0}")
        k_sh, v_sh = entry
        mc = self.model_config
        layers, rows, lanes = self.page_dims
        shape = (layers, self.config.block_size, rows, lanes)

        def unstage(sh_dict, shp, sharding):
            return jax.make_array_from_callback(
                shp, sharding, lambda idx: sh_dict[str(idx)])

        if isinstance(k_sh, tuple):
            sshape = (layers, self.config.block_size * mc.num_kv_heads)
            pg_sh, sc_sh = self._block_sharding
            k = (unstage(k_sh[0], shape, pg_sh),
                 unstage(k_sh[1], sshape, sc_sh))
            v = (unstage(v_sh[0], shape, pg_sh),
                 unstage(v_sh[1], sshape, sc_sh))
        else:
            k = unstage(k_sh, shape, self._block_sharding)
            v = unstage(v_sh, shape, self._block_sharding)
        self.kv = self._write_block_fn(self.kv, jnp.int32(bid), k, v)

    def _restore_blocks(self, restores) -> bool:
        """Copy offloaded pages back into HBM. Returns False on any miss."""
        if self._mh is not None:
            if self.offload is None:
                return False
            if self.config.kv_remote_url:
                # Whole-block leader store (see _drain_offload): fetch
                # every block host-side FIRST (fail before any
                # collective dispatch on a miss), then install them all
                # in one batched write_blocks op.
                entries = []
                for _, h in restores:
                    entry = self.offload.get(h)
                    if entry is None:
                        return False
                    entries.append(entry)
                self._dispatch(
                    "write_blocks", {},
                    _flatten_kv_payload(
                        np.asarray([bid for bid, _ in restores], np.int32),
                        _kv_leaf_stack([k for k, _ in entries], axis=1),
                        _kv_leaf_stack([v for _, v in entries], axis=1)))
                return True
            # contains() first: a miss must NOT turn into a collective
            # dispatch half the processes cannot serve.
            if not all(self.offload.contains(h) for _, h in restores):
                return False
            for bid, h in restores:
                self._dispatch("restore_block", {"hash": h},
                               [np.int32(bid)])
            return True
        for bid, h in restores:
            entry = self.offload.get(h) if self.offload is not None else None
            if entry is None:
                return False
            k, v = entry
            self._dispatch("write_block", {}, [np.int32(bid), k, v])
        return True

    def extract_kv(self, token_ids: List[int], adapter: str = ""):
        """Serialize the KV pages of the longest cached prefix of
        ``token_ids`` (disaggregated-prefill sender side; the NIXL-pipe
        replacement, SURVEY §2.3). Returns dict or None. In multi-host
        mode the gather is an op: every process joins a replicated
        page gather, so the leader can host-read the full blocks even
        though its own HBM holds only a shard (round 5 — unlocks
        BASELINE config 4 between multi-host units; ref
        examples/disaggregated_prefill/pd.yaml)."""
        from production_stack_tpu.engine.kvcache import BlockAllocator

        bs = self.config.block_size
        alloc = self.kv_mgr.allocator
        parent = self.kv_mgr.chain_root(adapter)
        hashes: List[int] = []
        bids: List[int] = []
        with self._step_lock:
            if self.kv is None:
                return None
            with self._lock:
                i = 0
                while i + bs <= len(token_ids):
                    h = BlockAllocator.chain_hash(
                        parent, tuple(token_ids[i : i + bs])
                    )
                    bid = alloc.prefix_map.get(h)
                    if bid is None:
                        break
                    hashes.append(h)
                    bids.append(bid)
                    parent = h
                    i += bs
            if not hashes:
                return None
            if self._mh is not None:
                # Collective replicated gather; leader reads locally.
                out = self._dispatch("gather_blocks", {},
                                     [np.asarray(bids, np.int32)])
                k = _kv_leaf_swap01(_kv_leaf_get(out[0]))
                v = _kv_leaf_swap01(_kv_leaf_get(out[1]))
            else:
                k_pages, v_pages, *state = self.kv
                idx = jnp.asarray(bids)
                # [L, N, bs, KVH, D] -> [N, L, bs, KVH, D] (per-block
                # payloads)
                k = _kv_leaf_swap01(
                    _kv_leaf_get(_gather_blocks_flat(k_pages, idx)))
                v = _kv_leaf_swap01(
                    _kv_leaf_get(_gather_blocks_flat(v_pages, idx)))
                state = [np.asarray(jax.device_get(
                    _gather_blocks_flat(side, idx))).swapaxes(0, 1)
                    for side in state]
        return {
            "hashes": hashes,
            "num_tokens": len(hashes) * bs,
            "k": self._one_head_a_row(k, 0),
            "v": self._one_head_a_row(v, 1),
            # [N, layers, rows, width]: the blocks' state, where the
            # family keeps one (Family.block_state).
            **({"state": state[0]} if state else {}),
        }

    def _one_head_a_row(self, side, which: int = 0):
        """A payload's pages in the logical layout every surface speaks,
        ``[..., bs, KVH, D]``, whatever rows the pool keeps them in
        (kv_page_dims); int8 payloads are never packed. Side ``which``
        of a family with its own page sides (``Family.page_sides``) is
        ``[..., bs, rows, width]``: the lanes beyond the width, zeros,
        stay behind."""
        if isinstance(side, tuple):
            return side
        if self.own_page_sides is not None:
            return side[..., :self.own_page_sides[which][1]]
        mc = self.model_config
        return side.reshape(
            side.shape[:-2] + (mc.num_kv_heads, mc.head_dim))

    def _as_pool_rows(self, side, which: int = 0):
        """The inverse: a logical payload in the pool's rows."""
        if isinstance(side, tuple):
            return side
        if self.own_page_sides is not None:
            lanes = self.page_side_dims[which][1]
            return jnp.pad(side, [(0, 0)] * (side.ndim - 1)
                           + [(0, lanes - side.shape[-1])])
        return side.reshape(side.shape[:-2] + self.page_dims[1:])

    def extract_kv_device(self, token_ids: List[int], adapter: str = ""):
        """Device-side variant of :meth:`extract_kv` for the transfer-pipe
        handoff: the gathered prefix pages STAY on device ([L, N, bs, KVH,
        D] arrays the KV device pipe offers for a peer pull) — no
        device_get, no host copy. Returns dict or None. Multi-host jobs
        fall back to the HTTP relay rung (extract_kv works there via the
        replicated gather op); the per-host device pipe fan-out awaits a
        runtime that implements jax.experimental.transfer."""
        if self._mh is not None:
            return None
        from production_stack_tpu.engine.kvcache import BlockAllocator

        bs = self.config.block_size
        alloc = self.kv_mgr.allocator
        parent = self.kv_mgr.chain_root(adapter)
        hashes: List[int] = []
        bids: List[int] = []
        with self._step_lock:
            if self.kv is None:
                return None
            with self._lock:
                i = 0
                while i + bs <= len(token_ids):
                    h = BlockAllocator.chain_hash(
                        parent, tuple(token_ids[i : i + bs])
                    )
                    bid = alloc.prefix_map.get(h)
                    if bid is None:
                        break
                    hashes.append(h)
                    bids.append(bid)
                    parent = h
                    i += bs
            if not hashes:
                return None
            k_pages, v_pages, *state = self.kv
            idx = jnp.asarray(bids)
            # Dispatched under _step_lock so the gather reads self.kv
            # before any later engine step donates the buffer.
            k = self._one_head_a_row(_gather_blocks_flat(k_pages, idx), 0)
            v = self._one_head_a_row(_gather_blocks_flat(v_pages, idx), 1)
            state = [_gather_blocks_flat(side, idx) for side in state]
        return {
            "hashes": hashes,
            "num_tokens": len(hashes) * bs,
            "k": k,  # [L, N, bs, KVH, D] device array
            "v": v,
            # [layers, N, rows, width] device array
            **({"state": state[0]} if state else {}),
        }

    def inject_kv_blocks(self, hashes: List[int], k, v, state=None) -> int:
        """Install transferred KV pages ([L, N, bs, KVH, D] — device
        arrays from the pipe or numpy from the HTTP relay) as cached
        (cold) prefix pages in ONE batched scatter dispatch, with the
        blocks' ``state`` ([layers, N, rows, width]) for a family that
        keeps one: pages without it are refused. Returns
        #blocks installed (cache-hit blocks count as installed). In
        multi-host mode the scatter rides the op channel (numpy payload
        fans out to every process; uniform host inputs feed the global
        scatter as replicated operands)."""
        alloc = self.kv_mgr.allocator
        if (state is None) != (self.block_state_shape is None):
            raise ValueError(
                "this model's cache blocks hold a state beside their pages: "
                "a payload must bring it" if state is None else
                "this model's cache blocks hold no state")
        with self._step_lock:
            if self.kv is None or not alloc.enable_prefix_caching:
                return 0
            fresh_idx: List[int] = []   # positions in the payload to write
            fresh_bids: List[int] = []
            already = 0
            with self._lock:
                for n, h in enumerate(hashes):
                    if h in alloc.prefix_map:
                        already += 1
                        continue
                    bid = alloc.allocate()
                    if bid is None:
                        break
                    fresh_idx.append(n)
                    fresh_bids.append(bid)
            # Spill anything evicted by the allocations before their pages
            # are overwritten below.
            self._drain_offload()
            if fresh_bids:
                try:
                    if self._mh is not None:
                        # Numpy payload so the op channel can ship it —
                        # CHUNKED: each dispatch holds mh.lock for its
                        # send, so one giant fan-out would stall every
                        # decode/prefill dispatch for the whole transfer;
                        # 4-block chunks bound the pause.
                        take = np.asarray(fresh_idx)
                        kk = _kv_leaf_index(_kv_leaf_np(k), take)
                        vv = _kv_leaf_index(_kv_leaf_np(v), take)
                        bids_np = np.asarray(fresh_bids, np.int32)
                        step = 4
                        for s0 in range(0, len(fresh_bids), step):
                            sl = slice(s0, s0 + step)
                            self._dispatch(
                                "write_blocks", {},
                                _flatten_kv_payload(
                                    bids_np[sl],
                                    _kv_leaf_index(kk, sl),
                                    _kv_leaf_index(vv, sl)))
                    else:
                        k_arr = self._as_pool_rows(_kv_leaf_jnp(k), 0)
                        v_arr = self._as_pool_rows(_kv_leaf_jnp(v), 1)
                        take = np.asarray(fresh_idx)
                        self.kv = self._write_blocks_fn(
                            self.kv, np.asarray(fresh_bids, np.int32),
                            _kv_leaf_index(k_arr, take),
                            _kv_leaf_index(v_arr, take),
                            *(() if state is None
                              else (jnp.asarray(state)[:, take],)),
                        )
                except Exception:
                    # Bad payload shape/dtype: give the blocks back
                    # instead of leaking them from the pool.
                    with self._lock:
                        for bid in fresh_bids:
                            alloc.release(bid)
                    raise
                with self._lock:
                    for n, bid in zip(fresh_idx, fresh_bids):
                        alloc.register_full_block(bid, hashes[n])
                        alloc.release(bid)  # cached, ref_count 0
        return already + len(fresh_bids)

    def inject_from_core(self, src: "EngineCore",
                         token_ids: List[int], adapter: str = "") -> int:
        """Same-device KV handoff: move the cached prefix pages of
        ``token_ids`` from another engine core's pool into this one's with
        ONE jitted HBM->HBM gather/scatter — no host transit at all. This
        is the fast path when prefill and decode engines share a chip or
        process (co-located multi-model pods; the dev-bench disagg
        topology); cross-host moves go through the transfer pipe or the
        TKV2 relay. Returns #blocks installed. Unsupported in multi-host
        mode (see extract_kv)."""
        if self._mh is not None or src._mh is not None:
            return 0
        if src.config.kv_cache_dtype != self.config.kv_cache_dtype:
            # Pools disagree on leaf structure (bf16 array vs int8
            # tuple): the direct HBM copy cannot convert — fall back to
            # the relay rungs, which re-encode host-side.
            return 0
        from production_stack_tpu.engine.kvcache import BlockAllocator

        bs = self.config.block_size
        src_alloc = src.kv_mgr.allocator
        # Consistent lock order for opposing concurrent pulls.
        first, second = ((src, self) if id(src) < id(self) else (self, src))
        with first._step_lock, second._step_lock:
            if self.kv is None or src.kv is None:
                return 0
            if not self.kv_mgr.allocator.enable_prefix_caching:
                return 0
            parent = src.kv_mgr.chain_root(adapter)
            hashes: List[int] = []
            src_bids: List[int] = []
            with src._lock:
                i = 0
                while i + bs <= len(token_ids):
                    h = BlockAllocator.chain_hash(
                        parent, tuple(token_ids[i : i + bs]))
                    bid = src_alloc.prefix_map.get(h)
                    if bid is None:
                        break
                    hashes.append(h)
                    src_bids.append(bid)
                    parent = h
                    i += bs
            if not hashes:
                return 0
            dst_alloc = self.kv_mgr.allocator
            take_idx: List[int] = []
            dst_bids: List[int] = []
            already = 0
            with self._lock:
                for n, h in enumerate(hashes):
                    if h in dst_alloc.prefix_map:
                        already += 1
                        continue
                    bid = dst_alloc.allocate()
                    if bid is None:
                        break
                    take_idx.append(n)
                    dst_bids.append(bid)
            self._drain_offload()
            if dst_bids:
                try:
                    sel = np.asarray(
                        [src_bids[n] for n in take_idx], np.int32)
                    # Every side of the pool: pages, and the blocks'
                    # state where the family keeps one.
                    self.kv = self._write_blocks_fn(
                        self.kv, np.asarray(dst_bids, np.int32),
                        *(_kv_leaf_index(side, sel) for side in src.kv))
                except Exception:
                    with self._lock:
                        for bid in dst_bids:
                            dst_alloc.release(bid)
                    raise
                with self._lock:
                    for n, bid in zip(take_idx, dst_bids):
                        dst_alloc.register_full_block(bid, hashes[n])
                        dst_alloc.release(bid)  # cached, ref_count 0
        return already + len(dst_bids)

    def inject_kv(self, hashes: List[int], k_blocks, v_blocks,
                  state_blocks=None) -> int:
        """Back-compat wrapper over :meth:`inject_kv_blocks` for payloads
        shaped [N, L, bs, KVH, D] (per-block lists / the TKV2 wire layout;
        ``state_blocks`` [N, layers, rows, width] as :meth:`extract_kv`
        gives it). The [N, L] -> [L, N] transpose happens on device
        inside the jit."""
        if not hashes:
            return 0
        k = _kv_leaf_np(k_blocks)
        v = _kv_leaf_np(v_blocks)
        return self.inject_kv_blocks(
            list(hashes), _kv_leaf_swap01(k), _kv_leaf_swap01(v),
            None if state_blocks is None
            else np.asarray(state_blocks).swapaxes(0, 1))

    # ------------------------------------------------------------------ #
    # public API (thread-safe)
    # ------------------------------------------------------------------ #
    def start(self) -> None:
        self._thread.start()

    def decode_warmup_args(self, K: int, maxb: int) -> tuple:
        """Dummy host operands of the K-step decode program at table
        width ``maxb`` (everything after params, kv and token counts):
        what warm-up compiles it with, and what a caller that wants the
        compiled program's text lowers it with."""
        B = self.config.max_num_seqs
        return (
            np.ones((B,), bool),         # reset_counts (warmup)
            self._no_burst_tokens,       # tokens_prev
            np.zeros((B,), np.int32),    # tok_idx
            np.zeros((B,), np.int32),    # host_tokens
            np.ones((B,), bool),         # use_host
            np.zeros((B,), np.int32),    # positions0
            np.full((B, K), -1, np.int64),
            np.zeros((B, maxb), np.int32),
            np.ones((B,), np.int32), np.zeros((B,), np.int32),
            np.zeros((B,), np.float32), np.zeros((B,), np.int32),
            np.ones((B,), np.float32), np.zeros((B,), np.int64),
            np.zeros((B,), np.float32),  # presence
            np.zeros((B,), np.float32),  # frequency
            np.zeros((B,), np.int32),    # min_tokens
            np.zeros((B,), np.int32),    # out_len0
            np.zeros((B, MAX_LOGIT_BIAS), np.int32),
            np.zeros((B, MAX_LOGIT_BIAS), np.float32),
            np.zeros((B, MAX_STOP_IDS), np.int32),
            np.zeros((B, MAX_STOP_IDS), np.float32),
            np.zeros((B, self._mask_row_bytes), np.uint8),
            np.zeros((B,), bool),
        )

    def warmup(self) -> None:
        """Precompile the serving programs (every prefill bucket, the
        cached-prefill variants, and each decode burst width) so no XLA
        compile lands inside a user request. Dummy inputs use negative
        slot ids, so the scatter writes drop and no real KV page or
        allocator state is touched."""
        cfg = self.config
        t0 = time.time()
        with self._step_lock:
            top = cfg.bucket_for(cfg.max_prefill_span)
            cached_buckets = set(cfg.prefill_buckets())
            n_prefill = 0
            firsts = {}  # a prefill's sampled tokens, by its row count

            def operands(rows: int, bucket: int, table: int) -> tuple:
                """Dummy host operands of a prefill program at [rows,
                bucket] and this table width, typed as serving's."""
                return (
                    np.zeros((rows, bucket), np.int32),
                    np.tile(np.arange(bucket, dtype=np.int32), (rows, 1)),
                    np.full((rows, bucket), -1, np.int64),
                    np.zeros((rows, table), np.int32),
                    np.full((rows,), min(bucket, 2), np.int32),
                    np.full((rows,), min(bucket, 2), np.int32),
                    np.zeros((rows,), np.int32),
                    np.zeros((rows,), np.float32),
                    np.zeros((rows,), np.int32),
                    np.ones((rows,), np.float32),
                    np.zeros((rows,), np.int64),
                    np.ones((rows,), np.int64), np.zeros((rows,), bool),
                    np.zeros((rows, MAX_LOGIT_BIAS), np.int32),
                    np.zeros((rows, MAX_LOGIT_BIAS), np.float32),
                    np.zeros((rows, MAX_STOP_IDS), np.int32),
                    np.zeros((rows, MAX_STOP_IDS), np.float32),
                    np.zeros((rows, self._mask_row_bytes), np.uint8),
                    np.zeros((rows,), bool))

            for bucket in cfg.prefill_buckets(plain=True):
                if bucket > top:
                    break
                # Plain prefill only ever sees context == span -> one tight
                # table width per bucket, so its ladder can afford the finer
                # rungs of prefill_buckets(plain=True), and the [R, rung]
                # programs of a group (serving reaches those only with R
                # same-rung prompts waiting: no warm prompt does).
                tight = self._table_width(bucket)
                for rows in sorted({1, cfg.prefill_group_rows(bucket)} - {0}):
                    out, self.kv = self._prefill_fn(
                        self.params, self.kv,
                        *operands(rows, bucket, tight))
                    firsts[rows] = out[0]
                    n_prefill += 1
                if bucket not in cached_buckets:
                    continue
                # Cached prefill: context (and so the table bucket) can be
                # anything >= the span; compile every reachable width.
                maxb = tight
                while True:
                    _, self.kv = self._prefill_cached_fn(
                        self.params, self.kv, *operands(1, bucket, maxb))
                    n_prefill += 1
                    if maxb >= cfg.max_blocks_per_seq:
                        break
                    maxb *= 2
            # The chunked step plan's rows ([prefill_batch, chunk] cached):
            # one variant per reachable block-table width, where that plan
            # can run.
            if (cfg.chunked_prefill_enabled and cfg.prefill_batch > 1
                    and cfg.prefill_chunk_size > 0):
                pb_bucket = cfg.bucket_for(
                    min(cfg.prefill_chunk_size, cfg.max_model_len))
                maxb_b = 4
                maxb_cap = self._prefill_batch_maxb()
                while True:
                    maxb_b = min(maxb_b, maxb_cap)
                    out, self.kv = self._prefill_cached_fn(
                        self.params, self.kv,
                        *operands(cfg.prefill_batch, pb_bucket, maxb_b))
                    firsts[cfg.prefill_batch] = out[0]
                    n_prefill += 1
                    if maxb_b >= maxb_cap:
                        break
                    maxb_b *= 2
            # The hand-over of a prefill's first tokens to the next burst
            # (_exec_op): one tiny program a row count of the prefill
            # programs, here with every row dropped.
            for sampled in firsts.values():
                self._feed_first_tokens_fn(
                    self._no_burst_tokens, sampled,
                    np.full(sampled.shape, cfg.max_num_seqs, np.int32))

            # Compile-phase boundary: the prefill warmups above staged
            # host-side dummy operands and XLA left per-compile host
            # scratch behind — collect now so peak host RSS during the
            # decode compiles doesn't stack on the prefill phase's
            # garbage (matters on 8B+ models whose compile scratch is
            # GB-scale).
            gc.collect()
            # Decode: the full burst width plus the pressure width
            # (decode_steps_pressure, used while prompts wait), one
            # variant per block-table bucket (4 doubling to
            # max_blocks_per_seq). tokens_prev is always full-width.
            B = cfg.max_num_seqs
            K_full = max(cfg.decode_steps, 1)
            widths = {K_full}
            if cfg.decode_steps_pressure > 0:
                widths.add(min(K_full, max(cfg.decode_steps_pressure, 1)))
            n_decode = 0
            for K in sorted(widths):
                fn = self._multi_decode_fn(K)
                maxb_w = 4
                while True:
                    maxb_w = min(maxb_w, cfg.max_blocks_per_seq)
                    _, self.kv, self._token_counts = fn(
                        self.params, self.kv, self._token_counts,
                        *self.decode_warmup_args(K, maxb_w))
                    n_decode += 1
                    if maxb_w >= cfg.max_blocks_per_seq:
                        break
                    maxb_w *= 2

            gc.collect()  # phase boundary (see above)
            # Speculative verify: ONE extra program per block-table
            # bucket (single width K = speculative_num_tokens), so spec
            # decoding adds at most one compiled variant per decode
            # variant — the compile-budget contract.
            n_spec = 0
            if cfg.speculative_num_tokens > 0:
                Ks = cfg.speculative_num_tokens
                fn = self._spec_verify_fn(Ks)
                maxb_w = 4
                while True:
                    maxb_w = min(maxb_w, cfg.max_blocks_per_seq)
                    _, self.kv = fn(
                        self.params, self.kv,
                        np.zeros((B, Ks), np.int32),     # tokens
                        np.zeros((B,), np.int32),        # positions0
                        np.full((B, Ks), -1, np.int64),  # slot_mat
                        np.zeros((B, maxb_w), np.int32),
                        np.ones((B,), np.int32),         # context0
                        np.zeros((B,), np.int32),        # adapter_ids
                        np.zeros((B,), np.float32), np.zeros((B,), np.int32),
                        np.ones((B,), np.float32), np.zeros((B,), np.int64),
                        np.zeros((B,), np.int32),        # min_tokens
                        np.zeros((B,), np.int32),        # out_len0
                        np.zeros((B, MAX_LOGIT_BIAS), np.int32),
                        np.zeros((B, MAX_LOGIT_BIAS), np.float32),
                        np.zeros((B, MAX_STOP_IDS), np.int32),
                        np.zeros((B, MAX_STOP_IDS), np.float32),
                        np.zeros((B, Ks, self._mask_row_bytes), np.uint8),
                        np.zeros((B, Ks), bool),
                    )
                    n_spec += 1
                    if maxb_w >= cfg.max_blocks_per_seq:
                        break
                    maxb_w *= 2
            # Draft-model programs: the drafter's own bounded set (one
            # catch-up forward per bucket + one greedy scan), compiled
            # against the DRAFTER's params — zero new target variants.
            n_draft = 0
            if self._draft is not None:
                gc.collect()  # phase boundary (see above)
                n_draft = self._draft.warmup(self._mask_row_bytes)
        self.warmup_variants = {
            "prefill": n_prefill, "decode": n_decode, "spec": n_spec,
            "draft": n_draft,
        }
        self._stats_pending.clear()  # the warm-up's forwards count nothing
        self.warmup_seconds = time.time() - t0
        logger.info("Warmup compiled %d prefill + %d decode + %d spec-verify "
                    "+ %d draft variants in %.1f s", n_prefill, n_decode,
                    n_spec, n_draft, self.warmup_seconds)

    def add_request(
        self,
        request_id: str,
        prompt_token_ids: List[int],
        sampling: SamplingParams,
        on_token: Callable[[Optional[int], Optional[str]], None],
        adapter_name: Optional[str] = None,
        trace=None,
        priority: int = 0,
    ) -> None:
        if self.fatal_error is not None:
            # The engine loop halted (multi-host lockstep break): nothing
            # will ever step this request — fail it NOW instead of
            # letting the client hang on a queue no one drains.
            on_token(None, "error")
            return
        adapter_id = self.lora_slots.get(adapter_name or "", 0)
        structured = None
        if sampling.structured is not None:
            try:
                structured = FSMState(
                    self._structured_fsm(sampling.structured))
            except Exception:  # noqa: BLE001 - server pre-validates; defensive
                logger.exception(
                    "Structured constraint failed to compile for %s",
                    request_id)
                on_token(None, "error")
                return
            self.structured_requests_total += 1
        req = EngineRequest(
            request_id=request_id,
            prompt_token_ids=list(prompt_token_ids),
            sampling=sampling,
            on_token=on_token,
            adapter_id=adapter_id,
            adapter_name=(adapter_name or "") if adapter_id else "",
            priority=priority,
            trace=trace,
            structured=structured,
        )
        with self._lock:
            self.scheduler.add(req)
            self._lock.notify()

    def _structured_fsm(self, spec):
        """Compiled token FSM for a StructuredSpec, LRU-cached by
        (schema-hash, tokenizer key)."""
        tok = self.tokenizer
        tok_key = "%s-%d-%s" % (type(tok).__name__,
                                self.model_config.vocab_size,
                                self.config.model)
        eos = getattr(tok, "eos_token_id", None)
        return self._structured_cache.get(
            spec.kind, spec.spec, tok, tok_key,
            self.model_config.vocab_size,
            int(eos) if eos is not None else None,
            lambda: compile_char_dfa(spec))

    def _fill_mask_row(self, mask_bits: np.ndarray, mask_on: np.ndarray,
                       i: int, req: EngineRequest) -> None:
        """Install row ``i``'s FSM mask from the request's CURRENT
        automaton state (no-op for unconstrained or dead-latched rows:
        the all-off row leaves the logits untouched in-program)."""
        st = req.structured
        if st is None or not st.masking:
            return
        mask_bits[i, :] = st.mask_row()
        mask_on[i] = True

    def abort_request(self, request_id: str) -> bool:
        with self._lock:
            return self.scheduler.abort(request_id)

    def stop(self) -> None:
        with self._lock:
            self._running = False
            self._lock.notify()
        if self._thread.ident is not None:  # started
            self._thread.join(timeout=10)
        if self._mh is not None and self._mh.is_leader:
            try:
                self._mh.channel.send(("stop", {}, []))
            except Exception:  # noqa: BLE001 - followers may be gone
                pass
            self._mh.channel.close()

    # -- sleep mode (reference relies on vLLM --enable-sleep-mode) ---------
    def sleep(self, level: int = 1) -> None:
        """Free HBM: discard KV, move weights to host RAM. In multi-host
        mode the leader broadcasts sleep as an op and EVERY process
        stages its own addressable parameter shards — no cross-host data
        movement at all (the reference gets engine sleep from vLLM at
        any size, ref src/vllm_router/service_discovery.py:443-460)."""
        with self._step_lock:  # wait out any in-flight forward step
            self._flush_pending_prefills()
            self._flush_pending_burst()
            with self._lock:
                if self._sleeping:
                    return
                self._sleeping = True
                self._sleep_level = level
                # Preempt everything so wake-up re-prefills from scratch.
                while self.scheduler.running():
                    self.scheduler.preempt_victim()
                # The pool is about to be discarded: spill every cached
                # block to the offload tier (when configured) so prefix
                # hits survive the nap via the restore path...
                alloc = self.kv_mgr.allocator
                if self.offload is not None:
                    for h, bid in list(alloc.prefix_map.items()):
                        self._offload_block(h, bid)
            self._drain_offload()
            with self._lock:
                # ...then drop ALL prefix-cache state. Leaving prefix_map
                # populated would cache-hit zeroed pages after wake_up's
                # fresh pool allocation (silent garbage attention).
                alloc.prefix_map.clear()
                for blk in alloc.blocks:
                    blk.prefix_hash = None
                    blk.token_count = 0
                    blk.ref_count = 0
                alloc.free_ids = list(range(alloc.num_blocks))
            self._dispatch("sleep", {}, [])
            with self._lock:
                self._lock.notify()
        logger.info("Engine asleep (level %d): HBM released", level)

    def _sleep_device(self) -> None:
        """Per-process HBM release: stage this process's parameter shards
        to host RAM (keyed by shard index for exact restore) and drop the
        device references. Works identically single- and multi-host.
        Mutates params/kv UNDER self._lock — LoRA hot-swap reads
        self.params more than once inside its own _lock section, so an
        unlocked null here races it into `{**None}` (stress-test race)."""

        def stage(a):
            return _StagedParam(
                shards={str(s.index): np.asarray(s.data)
                        for s in a.addressable_shards},
                shape=a.shape, sharding=a.sharding, dtype=a.dtype)

        with self._lock:
            if self.params is None:
                return
            self._host_params = jax.tree_util.tree_map(stage, self.params)
            self.params = None
            self.kv = None
            self._sleeping = True

    def wake_up(self) -> None:
        with self._step_lock:
            with self._lock:
                if not self._sleeping:
                    return
            self._dispatch("wake", {}, [])
            with self._lock:
                self._sleeping = False
                self._lock.notify()
        logger.info("Engine awake: weights restored, KV reallocated")

    def _wake_device(self) -> None:
        """Per-process restore: rebuild each parameter's global array
        from the locally staged shards, then reallocate the KV pool
        (a collective zeros every process joins). Same locking as
        :meth:`_sleep_device`."""

        def unstage(leaf):
            return jax.make_array_from_callback(
                leaf.shape, leaf.sharding,
                lambda idx, leaf=leaf: leaf.shards[str(idx)])

        with self._lock:
            if self._host_params is None:
                return
            self.params = jax.tree_util.tree_map(
                unstage, self._host_params,
                is_leaf=lambda x: isinstance(x, _StagedParam))
            self._host_params = None
        self.kv = self._alloc_kv()
        with self._lock:
            self._sleeping = False

    @property
    def is_sleeping(self) -> bool:
        return self._sleeping

    # -- LoRA hot-swap -----------------------------------------------------
    def load_lora_adapter(
        self, name: str, rank: Optional[int] = None,
        weights: Optional[dict] = None, alpha: float = 16.0,
    ) -> bool:
        """Install an adapter into a free slot without recompiling. The
        slot scatter is a device dispatch, so in multi-host mode it rides
        the op channel like any other (weights travel as numpy; the
        update itself is deterministic from the args)."""
        if weights is not None:
            weights = {k: np.asarray(v) for k, v in weights.items()}
        return self._dispatch(
            "lora_load",
            {"name": name, "rank": rank, "weights": weights, "alpha": alpha},
            [])

    def _lora_load_local(
        self, name: str, rank: Optional[int] = None,
        weights: Optional[dict] = None, alpha: float = 16.0,
    ) -> bool:
        rank = min(rank or self.config.max_lora_rank, self.config.max_lora_rank)
        with self._lock:
            # All state checks under the lock: sleep() can null self.params
            # between an outside check and the mutation (stress-test race).
            if self.params is None or "lora" not in self.params:
                return False
            if name in self.lora_slots:
                return True
            used = set(self.lora_slots.values())
            free = [
                s for s in range(1, self.config.max_loras) if s not in used
            ]
            if not free:
                return False
            slot = free[0]
            lora = dict(self.params["lora"])
            if weights is not None:
                for key in ("wq_a", "wq_b", "wv_a", "wv_b"):
                    if key in weights:
                        # put_global: the update operand must live on the
                        # same (possibly multi-host) mesh as the slot array.
                        w = multihost.put_global(
                            np.asarray(weights[key], lora[key].dtype),
                            self._repl)
                        lora[key] = lora[key].at[:, slot].set(w)
            else:
                # No weight source (zero egress): deterministic small init so
                # the adapter is a real, observable delta. crc32, not
                # hash(): str hashing is salted per process and multi-host
                # followers must derive the identical key.
                import zlib

                key = jax.random.key(zlib.crc32(name.encode()) % (2**31))
                for kname in ("wq_a", "wv_a"):
                    shape = lora[kname].shape  # [L, S, Hd, R]
                    upd = np.asarray(0.01 * jax.random.normal(
                        key, (shape[0], shape[2], shape[3]), jnp.float32
                    )).astype(lora[kname].dtype)
                    lora[kname] = lora[kname].at[:, slot].set(
                        multihost.put_global(upd, self._repl))
            lora["scaling"] = lora["scaling"].at[slot].set(alpha / rank)
            self.params = {**self.params, "lora": lora}
            self.lora_slots[name] = slot
        logger.info("Loaded LoRA adapter %s into slot %d", name, slot)
        return True

    def unload_lora_adapter(self, name: str) -> bool:
        return self._dispatch("lora_unload", {"name": name}, [])

    def _lora_unload_local(self, name: str) -> bool:
        with self._lock:
            if name not in self.lora_slots:
                return False
            if self.params is None:  # sleeping: weights are on the host
                return False
            slot = self.lora_slots.pop(name)
            lora = dict(self.params["lora"])
            lora["scaling"] = lora["scaling"].at[slot].set(0.0)
            self.params = {**self.params, "lora": lora}
        logger.info("Unloaded LoRA adapter %s (slot %d)", name, slot)
        return True

    # -- embeddings --------------------------------------------------------
    def _embed_fn(self, bucket: int):
        fn = self._embed_fns.get(bucket)
        if fn is not None:
            return fn
        apply = self._apply
        cfg = self.model_config
        bs = self.config.block_size
        page_dims, state = self.page_dims, self.block_state_shape
        side_dims = self.page_side_dims

        def embed_fwd(params, token_ids, positions, slot_mapping,
                      block_tables, seq_lens):
            # Throwaway single-page KV pool created INSIDE the program
            # (a host-side jnp.zeros would be committed to one process's
            # local device and could not feed a multi-host computation);
            # slot_mapping is all -1, so writes drop.
            kv = tuple(jnp.zeros((page_dims[0], 1, bs) + side,
                                 cfg.jnp_dtype) for side in side_dims)
            if state is not None:
                kv += (jnp.zeros((state[0], 1) + state[1:], cfg.jnp_dtype),)
            hidden, _ = apply(
                params, cfg, token_ids, positions, kv, slot_mapping,
                block_tables, seq_lens, seq_lens,
                mode="prefill", output_hidden=True,
            )
            T = token_ids.shape[1]
            mask = (jnp.arange(T)[None, :] < seq_lens[:, None]).astype(
                jnp.float32)
            pooled = (hidden * mask[..., None]).sum(axis=1) / jnp.maximum(
                seq_lens.astype(jnp.float32), 1.0)[:, None]
            norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
            return pooled / jnp.maximum(norm, 1e-12)

        fn = jax.jit(embed_fwd, out_shardings=self._repl)
        self._embed_fns[bucket] = fn
        return fn

    def embed(self, prompt_token_ids: List[int]) -> "list[float]":
        """Mean-pooled, L2-normalised FINAL hidden states of a full model
        pass (served by /v1/embeddings). Runs off the scheduler path with a
        throwaway single-page KV pool — the serving cache is untouched."""
        cfg = self.config
        mc = self.model_config
        ids = np.clip(
            np.asarray(prompt_token_ids, np.int32), 0, mc.vocab_size - 1
        )[: cfg.max_model_len - 1]
        n = max(len(ids), 1)
        bucket = cfg.bucket_for(min(n, cfg.prefill_chunk_size or n))
        n = min(n, bucket)

        with self._lock:  # consistent snapshot vs sleep()/wake_up()
            params = self.params
        if params is None:
            raise RuntimeError("engine is sleeping")

        token_ids = np.zeros((1, bucket), np.int32)
        token_ids[0, :n] = ids[:n]
        positions = np.arange(bucket, dtype=np.int32)[None, :]
        slot_mapping = np.full((1, bucket), -1, np.int64)  # writes dropped
        block_tables = np.zeros((1, 4), np.int32)
        seq_lens = np.asarray([n], np.int32)
        pooled = self._dispatch("embed", {"bucket": bucket}, [
            token_ids, positions, slot_mapping, block_tables, seq_lens])
        return np.asarray(jax.device_get(pooled), np.float32)[0].tolist()

    def kv_never_fits(self, n_tokens: int) -> bool:
        """True when a prompt of this length (+1-token decode headroom)
        needs more KV pages than the whole pool holds — the scheduler
        would deterministically reject it, so the server can fail fast
        with a 503 instead of queueing it."""
        bs = self.config.block_size
        needed = (n_tokens + 1 + bs - 1) // bs
        return needed > self.num_blocks

    def _window_dead_tokens(self, contexts) -> int:
        """Of the live sequences' pages, how many tokens' worth no kernel
        will read again: a window layer reads a sequence's last
        ``sliding_window`` tokens and the uniform pool keeps every token
        in every layer, so ``max(0, context - window)`` tokens a
        sequence are dead in the window layers' share of the page layers
        (what a per-kind allocator or a ring would free; ROADMAP M2/M3).
        0 for a model without a window."""
        window = self.model_config.sliding_window
        if not (window and self._window_layer_share):
            return 0
        past = np.maximum(np.asarray(contexts, np.int64) - window, 0).sum()
        return int(round(float(past) * self._window_layer_share))

    # -- stats -------------------------------------------------------------
    def stats(self) -> dict:
        alloc = self.kv_mgr.allocator
        with self._lock:
            contexts = [len(s.req.prompt_token_ids) + s.req.scheduled_steps
                        for s in self.scheduler.running()]
        budget = self.scheduler.token_budget if \
            self.scheduler.chunked_prefill else 0
        # Wall-clock split of the engine thread, from the loop's one
        # clock: steps by kind (a fused step counts under its own kind in
        # step_kind_stats, not here) and the loop's phases.
        kinds = self._steps.kind_stats()
        phases = self._steps.phase_stats()
        return {
            # Mid-prefill chunked sequences count as running: they hold KV
            # pages and will take a slot, and routers treat "running" as
            # engine load.
            "num_requests_running": (
                self.scheduler.num_running + len(self.scheduler.prefilling)),
            "num_requests_waiting": self.scheduler.num_waiting,
            "kv_usage": self.kv_mgr.usage(),
            "prefix_cache_hits": alloc.prefix_hits,
            "prefix_cache_queries": alloc.prefix_queries,
            "prompt_tokens_total": self.prompt_tokens_total,
            "cached_tokens_total": self.cached_tokens_total,
            "prefill_padded_tokens_total": self.prefill_padded_tokens_total,
            "kv_fetch_tokens_total": self.kv_fetch_tokens_total,
            "kv_window_dead_tokens": self._window_dead_tokens(contexts),
            "state_restores_total": self.state_restores_total,
            "state_blocks_written_total": self.state_blocks_written_total,
            "family_stats_total": dict(self.family_stats_total),
            "generation_tokens_total": self.generation_tokens_total,
            "emit_callbacks_total": self.emit_callbacks_total,
            "offload": self.offload.stats() if self.offload else None,
            # Page residency split: HBM pages currently allocated vs
            # pages living in the offload tier (host RAM / remote L3).
            "kv_page_occupancy": {
                "resident": self.num_blocks - alloc.num_free,
                "offload": (self.offload.stats()["blocks"]
                            if self.offload else 0),
            },
            "requests_finished_total": self.requests_finished_total,
            "prefix_evicts_total": self.prefix_evicts_total,
            "evict_listener_errors_total": self.evict_listener_errors_total,
            "num_preempted_total": self.scheduler.num_preempted_total,
            "num_blocks": self.num_blocks,
            "hbm_headroom_bytes": self.hbm_headroom_bytes,
            "pool_shrink_retries_total": self.pool_shrink_retries_total,
            "kv_cache_dtype": self.config.kv_cache_dtype,
            "kv_cache_bytes_per_token": (
                self._kv_bytes_per_block() // self.config.block_size),
            "is_sleeping": self._sleeping,
            "prefill_time_total": round(
                kinds["prefill"]["wall_s"]
                + kinds["prefill_chunk"]["wall_s"], 3),
            "decode_time_total": round(
                kinds["decode_burst"]["wall_s"]
                + kinds["spec_verify"]["wall_s"], 3),
            "flush_time_total": round(phases["readback"]["seconds"], 3),
            "prefill_count": (kinds["prefill"]["count"]
                              + kinds["prefill_chunk"]["count"]),
            "prefill_group_count": self.prefill_group_count,
            "prefill_group_rows": self.prefill_group_rows,
            "prefill_chunks_total": self.prefill_chunks_total,
            "deferred_prefill_tokens_total":
                self.deferred_prefill_tokens_total,
            "batched_token_utilization": (
                min(self.last_step_batched_tokens / budget, 1.0)
                if budget > 0 else 0.0),
            "rejected_requests": dict(self.scheduler.rejected_total),
            "preempted_by_priority":
                dict(self.scheduler.preempted_by_priority),
            "decode_burst_count": (kinds["decode_burst"]["count"]
                                   + kinds["spec_verify"]["count"]),
            "fused_steps_total": self.fused_steps_total,
            "prefill_attention_dispatch_total":
                dict(self.prefill_attention_dispatch_total),
            "expert_matmul_dispatch_total":
                dict(self.expert_matmul_dispatch_total),
            "latent_decode_dispatch_total":
                dict(self.latent_decode_dispatch_total),
            "latent_prefill_form_total":
                dict(self.latent_prefill_form_total),
            "first_token_feed_total": dict(self.first_token_feed_total),
            "dispatch_count_total": phases["enqueue"]["count"],
            "dispatch_enqueue_s": round(phases["enqueue"]["seconds"], 3),
            "decode_forward_steps_total": self.decode_forward_steps_total,
            "spec_proposed_tokens_total": self.spec_proposed_tokens_total,
            "spec_accepted_tokens_total": self.spec_accepted_tokens_total,
            "spec_proposed_by_source": dict(self.spec_proposed_by_source),
            "spec_accepted_by_source": dict(self.spec_accepted_by_source),
            "spec_draft_forward_steps_total":
                self.spec_draft_forward_steps_total,
            "spec_disabled_requests_total": self.spec_disabled_requests_total,
            "spec_verify_bursts_total": self.spec_verify_bursts_total,
            "structured_requests_total": self.structured_requests_total,
            "structured_compile_seconds_total": round(
                self._structured_cache.compile_seconds_total, 6),
            "structured_mask_states_total":
                self._structured_cache.mask_states_total,
            "structured_violations_total": self.structured_violations_total,
            "structured_cache_entries": len(self._structured_cache),
            "step_records_total": (
                self.step_recorder.recorded_total
                if self.step_recorder is not None else 0),
            "step_kind_stats": (
                self.step_recorder.kind_stats()
                if self.step_recorder is not None else {}),
            # None: recorder off, or a device with no published peak.
            "model_bandwidth_utilization": (
                self.step_recorder.bandwidth_utilization()
                if self.step_recorder is not None else None),
        }

    # ------------------------------------------------------------------ #
    # engine loop
    # ------------------------------------------------------------------ #
    def _loop(self) -> None:
        steps = self._steps
        while True:
            with steps.loop_step(self.step_recorder is not None):
                if not self._loop_once():
                    return

    def _loop_once(self) -> bool:
        """One iteration of the engine loop: wait for work, schedule, run
        the step. Every interval of it is timed as a phase of the loop's
        one clock (obs/steps.py): ``idle_wait`` and ``schedule`` here,
        ``build`` around the step function with ``enqueue``, ``readback``
        and ``emit`` inside it where the work happens. False when the
        loop should end."""
        steps = self._steps
        with steps.phase("schedule"), self._lock:
            while self._running and not self._pending_burst and (
                self._sleeping or not self.scheduler.has_work()
            ):
                with steps.phase("idle_wait"):
                    self._lock.wait(timeout=0.1)
            if not self._running:
                return False
            action, req = self.scheduler.next_action()
            if self.step_recorder is not None:
                live, cached, free = self.kv_mgr.block_counts()
                steps.note(waiting=self.scheduler.num_waiting,
                           running=self.scheduler.num_running,
                           kv_blocks_live=live, kv_blocks_cached=cached,
                           kv_blocks_free=free)
        self._step_info = None  # never carry info across a failed step
        try:
            with self._step_lock:
                if self._sleeping or self.params is None:
                    self._flush_pending_burst()
                    # sleep() won the race after next_action popped a
                    # request: requeue it for wake-up instead of failing.
                    # (Chunked plans pop nothing — their members stay in
                    # scheduler.prefilling and resume on wake.)
                    if action == "prefill" and req is not None:
                        with self._lock:
                            self.scheduler.requeue(req)
                    return True
                if action in ("prefill", "prefill_step", "fused", "decode"):
                    steps.start()
                    with steps.phase("build"):
                        if action == "prefill":
                            self._do_prefill(req)
                        elif action == "prefill_step":
                            self._do_prefill_step(req)
                        elif action == "fused":
                            # _do_fused records its legs itself where
                            # the fusion degrades.
                            self._do_fused(req)
                        else:
                            self._do_decode()
                    if (action == "prefill" and req.trace is not None
                            and req.trace.prefill_start):
                        req.trace.prefill_end = time.time()
                    self._record_step()
                else:
                    self._flush_pending_prefills()
                    self._flush_pending_burst()
                    time.sleep(0.001)
        except Exception as e:  # noqa: BLE001
            logger.exception("Engine step failed: %s", e)
            failed_reqs = []
            if action in ("prefill_step", "fused") and req:
                with self._lock:
                    for pc in req:  # req is the [PrefillChunk] plan
                        if pc.req in self.scheduler.prefilling:
                            self.scheduler.prefilling.remove(pc.req)
                            self.kv_mgr.free(pc.req.request_id)
                            self.scheduler.drop(pc.req)
                            failed_reqs.append(pc.req)
            elif action == "prefill" and req is not None:
                with self._lock:
                    self.scheduler.drop(req)
                failed_reqs.append(req)
            for r in failed_reqs:
                r.on_token(None, "error")
            if self.fatal_error is not None:
                # Lockstep is broken (op-channel fan-out failed
                # mid-send): keeping the loop alive would silently
                # diverge from the followers. Fail every request —
                # queued AND in-flight (their clients would otherwise
                # hang forever) — and stop stepping; /health is
                # already 503.
                logger.error(
                    "Engine loop halting on fatal error: %s",
                    self.fatal_error)
                with self._lock:
                    self._running = False
                    for seq in self.scheduler.running():
                        self.scheduler.finish(seq, "error")
                    for r in self.scheduler.drain_waiting():
                        r.on_token(None, "error")
                return False
        self.step_count += 1
        return True

    def _record_step(self, wall_s: Optional[float] = None) -> None:
        """Complete the step the step function stashed (if any): its wall
        time runs from the loop's ``start`` to now, unless ``wall_s``
        gives it (the legs of a degraded fused step). No-op when the step
        dispatched nothing (e.g. an alloc-starved prefill that requeued).
        With the recorder off only the per-kind totals are kept."""
        rec, info = self._steps, self._step_info
        self._step_info = None
        if info is None:
            return
        if self._stat_names:
            self._note_family_stats()
        if rec.param_bytes == 0 and self.params is not None:
            # Weight bytes for the roofline: resolved lazily because the
            # checkpoint may replace the init tree after construction.
            try:  # ONE forward's reads: a stack run again counts again
                rec.param_bytes = registry.forward_weight_bytes(
                    self.model_config, self.params)
                # (models/registry.py; obs/steps.py's roofline model)
            except (TypeError, ValueError, AttributeError):
                rec.param_bytes = 0
        rec.record(info.pop("kind"), wall_s,
                   ring=self.step_recorder is not None, **info)

    # -- prefill -----------------------------------------------------------
    def _allocate_for_prefill(self, req: EngineRequest, limit=None):
        """KV allocation + offload-restore for one prompt (``limit`` bounds
        fresh allocation to the first chunk under chunked prefill). Returns
        (block_ids, cached) or None after requeuing the request (pool
        exhausted / restore failure retry also failed)."""
        alloc = self.kv_mgr.allocate_prompt(
            req.request_id, req.all_token_ids, adapter=req.adapter_name,
            limit=limit,
        )
        if alloc is None:
            # Pool tight: settle the in-flight burst (its emission may
            # finish sequences and free pages), then retry once.
            self._flush_pending_burst()
            alloc = self.kv_mgr.allocate_prompt(
                req.request_id, req.all_token_ids, adapter=req.adapter_name,
                limit=limit,
            )
        self._drain_offload()
        if alloc is None:
            # Raced out of blocks; requeue.
            with self._lock:
                self.scheduler.requeue(req)
            return None
        block_ids, cached, restores = alloc
        if restores and not self._restore_blocks(restores):
            # Offload tier lied (e.g. remote evicted between HEAD and GET):
            # recompute from scratch with the external tier bypassed. The
            # restore blocks were registered in the prefix map before their
            # pages were written — unregister them so the retry (and any
            # concurrent prompt) cannot reuse garbage pages as cache.
            kv_alloc = self.kv_mgr.allocator
            with self._lock:
                for bid, h in restores:
                    if kv_alloc.prefix_map.get(h) == bid:
                        del kv_alloc.prefix_map[h]
                        kv_alloc.blocks[bid].prefix_hash = None
            self.kv_mgr.free(req.request_id)
            ext = self.kv_mgr.external_lookup
            self.kv_mgr.external_lookup = None
            try:
                alloc = self.kv_mgr.allocate_prompt(
                    req.request_id, req.all_token_ids,
                    adapter=req.adapter_name, limit=limit,
                )
            finally:
                self.kv_mgr.external_lookup = ext
            self._drain_offload()
            if alloc is None:
                with self._lock:
                    self.scheduler.requeue(req)
                return None
            block_ids, cached, _ = alloc
        return block_ids, cached

    def _feeds_first_token(self, req: EngineRequest) -> bool:
        """Whether the row's first decode burst can take its first token
        on the device, unseen by the host (``_exec_op`` scatters it into
        the burst's feedback array behind the prefill, and ``_do_decode``
        builds the burst while the prefill still runs). Told by what the
        request carries: not where the token's VALUE is needed before the
        burst is built (drafts under speculation, a grammar's mask, the
        penalty counts of a resumed row, which are rebuilt from its
        prior outputs and this token), not where the token ends the
        request whatever it is (no decode row is ever built for it), and
        not inside a captured fused pair, whose final-chunk row sits its
        burst out."""
        s = req.sampling
        return not (
            self._fused_capture is not None
            or self.config.speculative_num_tokens > 0
            or (req.structured is not None and req.structured.masking)
            or (req.output_token_ids
                and (s.presence_penalty or s.frequency_penalty))
            or s.max_tokens - len(req.output_token_ids) <= 1
            or len(req.all_token_ids) + 1 >= self.config.max_model_len)

    def _first_token_slots(self, reqs) -> "tuple[list, list]":
        """The decode slot each of ``reqs`` takes once its (final) prefill
        is dispatched, chosen BEFORE the dispatch, because the prefill op
        writes each first token into its slot's row of the feedback
        array: ``(slots, feed)``, ``feed[i]`` the slot again, or None
        where the row keeps the host path. Only this thread ever fills a
        slot (the scheduler guaranteed these), so a slot chosen here is
        still free when ``_start_prefilled`` takes it."""
        with self._lock:
            free = [i for i, s in enumerate(self.scheduler.slots)
                    if s is None]
        slots = free[:len(reqs)]
        return slots, [slot if self._feeds_first_token(r) else None
                       for r, slot in zip(reqs, slots)]

    def _feed_static(self, cached: bool, feed, rows: int) -> dict:
        """A prefill op's static part: ``feed`` (a slot or None a row of
        ``_first_token_slots``) as the op takes it, one slot for each of
        the program's ``rows``, past the last slot where the row feeds
        none; left out where no row does."""
        static = {"cached": cached}
        if any(slot is not None for slot in feed):
            drop = self.config.max_num_seqs
            static["feed"] = tuple(
                drop if slot is None else slot for slot in feed
            ) + (drop,) * (rows - len(feed))
        return static

    def _start_prefilled(self, req: EngineRequest, slot: int, fed: bool,
                         sampled, row: int = 0) -> None:
        """The request's last prefill is dispatched: it takes its decode
        slot, and its first token's readback is deferred
        (``_flush_pending_prefills``). ``fed``: the op also wrote the
        token into the feedback array, and the next burst's build says
        whether it took it there; a row that was not fed counts as
        ``host`` now."""
        with self._lock:
            seq = self.scheduler.start_running(req, slot)
        if fed:
            self._first_on_device.append((slot, seq))
        else:
            self.first_token_feed_total["host"] += 1
        self._pending_prefills.append(
            {"req": req, "seq": seq, "slot": slot, "sampled": sampled,
             "row": row, "fed": fed, "in_burst": False})

    def _do_prefill(self, req: EngineRequest) -> None:
        """Block accounting is host-only, so the prompt's chunk forwards are
        dispatched BEFORE the in-flight decode burst is read back: XLA
        orders them after the burst via the kv dependency, and the burst's
        host readback then overlaps the chunks' device execution. (A page
        freed by a finished sequence may still receive the burst's
        speculative write, but the burst was dispatched first, so the
        prefill's own writes land after it — device order.) The row's
        decode slot is chosen before the dispatch, which hands the first
        token to the next burst on the device (``_feeds_first_token``);
        the host reads it when the next step has dispatched, be that a
        prefill or the burst."""
        cfg = self.config
        tokens = req.all_token_ids
        n = len(tokens)
        got = self._allocate_for_prefill(req)
        if got is None:
            return
        block_ids, cached = got
        if req.trace is not None:
            # Queue wait ends at the first successful allocation (an
            # alloc-starved retry stays queued, not "prefilling").
            if not req.trace.prefill_start:
                req.trace.prefill_start = time.time()
            req.trace.cached_tokens = cached
            req.trace.preemptions = req.num_preemptions

        # An uncached one-span prompt takes the plain program: run it with
        # the waiting prompts of its rung in one [R, rung] dispatch, which
        # reads the weights once for all of them (_do_prefill_group).
        if cached == 0 and n <= cfg.max_prefill_span:
            rung = cfg.bucket_for(n, plain=True)
            rows = cfg.prefill_group_rows(rung)
            group = rows and self._gather_prefill_group(
                req, block_ids, rung, rows)
            if group:
                self._do_prefill_group(group, rung)
                return

        # Only the un-cached suffix runs through the model; its queries
        # attend to the prefix via the HBM pages (prefill_cached). Long
        # suffixes run in chunks so attention memory stays
        # O(chunk * context) instead of O(len^2) — the engine-level
        # long-context path (single chip; ring attention covers multi-chip).
        chunk = cfg.prefill_chunk_size or (n - cached)
        (slot,), feed = self._first_token_slots([req])
        sampled = None
        start = cached
        while start < n:
            end = min(start + chunk, n)
            sampled = self._prefill_span(
                req, tokens, block_ids, start, end,
                feed=feed if end == n else ())
            start = end
        n_chunks = max(1, -(-(n - cached) // max(chunk, 1)))
        self._step_info = {
            "kind": "prefill", "rows": 1, "tokens": n - cached,
            "forwards": n_chunks,
            # Chunk i's queries attend to the cached + previously
            # prefilled context via the HBM pages.
            "kv_read_tokens": (n_chunks * cached
                               + chunk * (n_chunks * (n_chunks - 1)) // 2),
            "kv_write_tokens": n - cached,
        }
        # Read back the in-flight burst while the chunks execute on device.
        self._flush_pending_burst()
        # Settle the PREVIOUS prefill now — after this one's dispatch —
        # so its ~100 ms readback overlaps this one's device execution
        # (depth-1 pipelining: a queue of arrivals drains at on-chip
        # rate, while each first token still lands one dispatch later at
        # most — deeper deferral measured better throughput but visibly
        # worse p50 TTFT).
        self._flush_pending_prefills()
        self.prompt_tokens_total += n
        self.cached_tokens_total += cached
        # The slot chosen above (next_action guaranteed a free one); the
        # sampled-token readback is deferred as above.
        self._start_prefilled(req, slot, feed[0] is not None, sampled)

    def _do_prefill_step(self, plan) -> None:
        """Execute one budgeted chunked-prefill step plan: advance each
        member by one bucket-snapped chunk. Multiple members' chunks share
        one batched [PB, chunk] dispatch when the batched-prefill program
        covers them (consecutive chunks of ONE prompt never share a
        dispatch — chunk N+1's queries attend to chunk N's pages).
        Final chunks take a decode slot, chosen before the dispatch,
        and defer their first-token readback exactly like the unchunked
        path (_start_prefilled)."""
        cfg = self.config
        ready = []  # (req, tokens, block_ids, start, end)
        step_tokens = 0
        for pc in plan:
            req = pc.req
            with self._lock:
                if req not in self.scheduler.prefilling:
                    continue  # aborted after the plan was built
            tokens = req.all_token_ids
            n = len(tokens)
            if pc.start == 0:
                # First chunk: allocate pages for it (the cached-prefix
                # walk is unbounded, so `cached` can exceed the chunk).
                got = self._allocate_for_prefill(req, limit=pc.end)
                if got is None:
                    continue  # requeued by _allocate_for_prefill
                block_ids, cached = got
                if req.trace is not None:
                    if not req.trace.prefill_start:
                        req.trace.prefill_start = time.time()
                    req.trace.cached_tokens = cached
                    req.trace.preemptions = req.num_preemptions
                self.cached_tokens_total += cached
                start = max(pc.start, cached)
                end = max(pc.end, cached)
                if start >= end or start >= n:
                    # Fully covered by cache: skip the dispatch; the next
                    # step continues from the cached frontier.
                    with self._lock:
                        if req in self.scheduler.prefilling:
                            req.num_computed_tokens = min(max(end, start), n)
                    continue
            else:
                block_ids = self.kv_mgr.extend_tokens(
                    req.request_id, tokens, pc.end)
                if block_ids is None:
                    # Pool tight: settle the in-flight burst (may free
                    # pages) and retry once, then give the pages back and
                    # requeue (re-prefills from scratch when readmitted).
                    self._flush_pending_burst()
                    block_ids = self.kv_mgr.extend_tokens(
                        req.request_id, tokens, pc.end)
                if block_ids is None:
                    self.kv_mgr.free(req.request_id)
                    self.prefill_chunk_requeues_total += 1
                    with self._lock:
                        self.scheduler.requeue(req)
                    continue
                start, end = pc.start, pc.end
            ready.append((req, tokens, block_ids, start, end))
            step_tokens += end - start

        if not ready:
            return
        # Dispatch: one batched [PB, chunk-bucket] program when compiled
        # and every row fits its block-table cap, else sequential spans.
        sampled_for: "dict[int, tuple]" = {}  # id(req) -> (sampled, row)
        finals = [req for req, tokens, _b, _s, end in ready
                  if end >= len(tokens)]
        slots, fed_slots = self._first_token_slots(finals)
        slot_of = {id(req): (slot, fed)
                   for req, slot, fed in zip(finals, slots, fed_slots)}
        feed = [slot_of.get(id(req), (None, None))[1] for req, *_ in ready]
        batched = (
            cfg.prefill_batch > 1 and cfg.prefill_chunk_size > 0
            and len(ready) > 1
            and all((end + cfg.block_size - 1) // cfg.block_size
                    <= self._prefill_batch_maxb()
                    for (_, _, _, _, end) in ready))
        if batched:
            sampled = self._prefill_rows(ready, pad_to=cfg.prefill_batch,
                                         feed=feed)
            for row_i, (req, *_rest) in enumerate(ready):
                sampled_for[id(req)] = (sampled, row_i)
        else:
            for (req, tokens, block_ids, start, end), slot in zip(ready,
                                                                  feed):
                sampled_for[id(req)] = (self._prefill_span(
                    req, tokens, block_ids, start, end, feed=[slot]), 0)
        self.prefill_chunks_total += len(ready)
        self.last_step_batched_tokens = step_tokens
        path = self._paged_attn_path()
        self._step_info = {
            "kind": "prefill_chunk", "rows": len(ready),
            "tokens": step_tokens,
            "forwards": 1 if batched else len(ready),
            # Each chunk's queries attend to its request's context so
            # far (cached prefix + earlier chunks). The flash kernel
            # streams ONLY the prefix pages (the chunk's own K/V is
            # attended from VMEM before it ever leaves the chip); the
            # XLA gather path re-reads the full written context —
            # prefix AND the just-scattered suffix.
            "kv_read_tokens": sum(
                (s if path == "pallas" else e)
                for (_r, _t, _b, s, e) in ready),
            "kv_write_tokens": step_tokens, "batched": batched,
        }

        # Same pipelining as the unchunked paths: read back the in-flight
        # burst and the previous prefill while these chunks execute.
        self._flush_pending_burst()
        self._flush_pending_prefills()

        now = time.time()
        for req, tokens, block_ids, start, end in ready:
            n = len(tokens)
            if req.trace is not None:
                req.trace.prefill_chunks += 1
            if end < n:
                self.deferred_prefill_tokens_total += n - end
                with self._lock:
                    if req in self.scheduler.prefilling:
                        req.num_computed_tokens = end
                continue
            # Final chunk: the sampled token of this dispatch is the
            # request's first generated token. It takes the decode slot
            # chosen for it before the dispatch (admission guaranteed
            # one stays free per mid-prefill seq).
            sampled, row = sampled_for[id(req)]
            with self._lock:
                if req not in self.scheduler.prefilling:
                    continue  # aborted while the chunk was in flight
                self.scheduler.prefilling.remove(req)
                req.num_computed_tokens = n
            if req.trace is not None:
                req.trace.prefill_end = now
            self.prompt_tokens_total += n
            slot, fed = slot_of[id(req)]
            self._start_prefilled(req, slot, fed is not None, sampled, row)

    def _count_expert_matmul_path(self, tokens: int) -> None:
        """One step program of ``tokens`` tokens a forward was dispatched:
        count it under the path its expert layers' grouped matmuls take
        (the trace-time choice of models/moe.py, evaluated again from the
        same shapes). A model without experts counts nothing."""
        mc = self.model_config
        if not mc.is_moe:
            return
        from production_stack_tpu.ops.pallas_grouped_matmul import (
            grouped_matmul_path,
        )

        self.expert_matmul_dispatch_total[grouped_matmul_path(
            tokens * mc.experts_per_token, mc.hidden_size,
            mc.moe_intermediate_size or mc.intermediate_size,
            mc.dtype, mc.num_experts, devices=self.mesh.size)] += 1

    def _paged_attn_path(self) -> str:
        """Which attention path cached-prefill and decode dispatches take
        at this engine's page shape: "pallas" (the paged kernels) or "xla"
        (gather reference). Trace-time static — labels
        tpu:prefill_attention_dispatch_total and the roofline's
        KV-read-byte model, and decides whether a decode burst counts
        ``kv_fetch_tokens``."""
        from production_stack_tpu.ops.attention import attention_path

        if self.own_page_sides is not None:
            # A latent cache: cached prefill up-projects a gathered
            # context (models/decoder.py::attend_latent), and decode has
            # a path of its own (_latent_decode_path).
            return "xla"
        _, rows, lanes = self.page_dims
        mc = self.model_config
        return attention_path(
            self.config.block_size, rows, lanes,
            self.config.kv_cache_dtype == "int8", self._kv_shards,
            packed=(rows, lanes) != (mc.num_kv_heads, mc.head_dim))

    def _latent_decode_path(self) -> str:
        """Which path the absorbed decode attention over a latent cache
        takes at this engine's shapes (the trace-time choice of
        ops/attention.py::latent_decode_attention, evaluated again from
        the same shapes)."""
        from production_stack_tpu.ops.attention import latent_decode_path

        (_, latent), (_, lanes) = self.page_side_dims
        mc = self.model_config
        return latent_decode_path(self.config.block_size, mc.num_heads,
                                  latent, lanes, mc.dtype)

    def _count_latent_prefill_form(self, bucket: int, table: int) -> None:
        """A cached-prefill program of a model with a latent cache is
        dispatched at ``[rows, bucket]`` tokens under a table of
        ``table`` blocks: count the form its attention takes (the
        trace-time choice of models/decoder.py::attend_latent, evaluated
        again from the same shapes)."""
        from production_stack_tpu.models.decoder import latent_prefill_form

        mc = self.model_config
        form = latent_prefill_form(
            bucket, table * self.config.block_size, mc.num_heads,
            mc.kv_lora_rank, mc.qk_nope_head_dim, mc.qk_rope_head_dim,
            mc.v_head_dim)
        self.latent_prefill_form_total[form] += 1
        self._steps.note_sum(**{f"latent_prefill_{form}": 1})

    def _do_fused(self, plan) -> None:
        """Execute one scheduler "fused" action: the budgeted prefill
        chunk span AND the decode burst as ONE dispatch. Both legs run
        their normal host-side build/bookkeeping code; _dispatch diverts
        their device ops into a capture list, and the pair is issued as
        a single "fused" op (the already-compiled programs run back to
        back on device — zero new warmup variants, one op-channel send,
        one enqueue). Any op fusion cannot carry (spec verify, counts
        rebuild, KV restores...) aborts the capture and the step
        degrades to the alternating dispatches — the token streams are
        byte-identical either way; only dispatch counts differ.

        A sequence whose FINAL prefill chunk rides the fused op has no
        readable first token while the decode leg is being built, so it
        sits that burst out and joins the next one (per-row positions,
        seeds, and penalty state make its stream identical to the
        alternating schedule's)."""
        self._fused_capture = cap = []
        fused = False
        info_p = info_d = None
        dt_p = dt_d = 0.0
        try:
            t0 = time.perf_counter()
            self._do_prefill_step(plan)
            dt_p = time.perf_counter() - t0
            info_p, self._step_info = self._step_info, None
            t0 = time.perf_counter()
            self._do_decode()
            dt_d = time.perf_counter() - t0
            info_d, self._step_info = self._step_info, None
        finally:
            aborted = self._fused_capture is None
            self._fused_capture = None
            names = [c[0] for c in cap]
            fused = (not aborted and "prefill" in names
                     and names[-1] == "decode")
            if fused:
                try:
                    results = self._dispatch("fused", {
                        "names": names,
                        "statics": [c[1] for c in cap],
                        "counts": [len(c[2]) for c in cap],
                    }, [a for c in cap for a in c[2]])
                except Exception as e:  # noqa: BLE001
                    for _n, _s, _a, ph in cap:
                        if not ph.ready:
                            ph.error, ph.ready = e, True
                    raise
                for (_n, _s, _a, ph), out in zip(cap, results):
                    ph.value, ph.ready = out, True
                self.fused_steps_total += 1
            else:
                # Degraded (capture aborted, or a leg dispatched
                # nothing): issue whatever is still pending one by one.
                self._drain_captured(cap)
        if fused and info_p is not None and info_d is not None:
            self._step_info = {
                "kind": "fused",
                "rows": info_p["rows"] + info_d["rows"],
                "tokens": info_p["tokens"] + info_d["tokens"],
                "forwards": info_p["forwards"] + info_d["forwards"],
                "kv_read_tokens": (info_p["kv_read_tokens"]
                                   + info_d["kv_read_tokens"]),
                "kv_write_tokens": (info_p["kv_write_tokens"]
                                    + info_d["kv_write_tokens"]),
                "batched": info_p.get("batched", False),
            }  # _loop records it with the full step wall time
        else:
            # Degraded: record the legs as the individual step kinds
            # they actually were, with their own wall times.
            if info_p is not None:
                self._step_info = info_p
                self._record_step(dt_p)
            if info_d is not None:
                self._step_info = info_d
                self._record_step(dt_d)

    def _flush_pending_prefills(self) -> None:
        """Read back and emit deferred prefill first tokens, in dispatch
        order: after the next step's dispatch, so that the readback
        overlaps it on the device. The next step may be the burst that
        takes these tokens on the device (``_do_decode`` marks such an
        entry ``in_burst`` and has done the row's bookkeeping as if the
        token were emitted); any other entry must be settled here before
        a burst is built, which needs the token's value for it."""
        if not self._pending_prefills:
            return
        pending, self._pending_prefills = self._pending_prefills, []
        keep: "list[dict]" = []
        steps = self._steps
        read_of = read = None
        rows, finished0 = 0, self.requests_finished_total
        with steps.phase("emit"):
            for entry in pending:
                sampled = entry["sampled"]
                if (isinstance(sampled, _FusedPlaceholder)
                        and not sampled.ready):
                    # Captured for a fused dispatch that has not issued yet:
                    # the readback waits for the fused op. Unready entries
                    # are always the queue's tail (they were captured this
                    # step), so dispatch-order emission still holds.
                    keep.append(entry)
                    continue
                req, seq, slot = entry["req"], entry["seq"], entry["slot"]
                row_i = entry["row"]  # batched prefills: row per req
                try:
                    if sampled is not read_of:  # a group's: read once
                        with steps.phase("readback"):
                            read = [np.asarray(a) for a in jax.device_get(
                                _unwrap_fused(sampled))]
                        read_of = sampled
                    s_arr, lp_arr, top_lp_arr, top_id_arr = read
                except Exception:  # noqa: BLE001 - async device failure
                    # The deferred readback failed AFTER the dispatch
                    # succeeded: the request would otherwise hang with its
                    # slot leaked (the loop's error handler only covers the
                    # current action's req). Finish it with an error.
                    logger.exception(
                        "Deferred prefill readback failed for %s",
                        req.request_id)
                    with self._lock:
                        if self.scheduler.slots[slot] is seq:
                            self.scheduler.finish(seq, "error")
                    continue
                with self._lock:
                    if self.scheduler.slots[slot] is not seq:
                        continue  # aborted/finished before its first token
                token = int(s_arr[row_i])
                lp = None
                if req.sampling.logprobs is not None:
                    k = min(req.sampling.logprobs, top_lp_arr.shape[1])
                    lp = {"logprob": float(lp_arr[row_i]),
                          "top": [(int(top_id_arr[row_i, j]),
                                   float(top_lp_arr[row_i, j]))
                                  for j in range(k)]}
                prior = req.output_token_ids
                in_burst = entry["in_burst"]
                if in_burst:
                    # The burst in flight took this token on the device:
                    # it reset the slot's counts and counted the token,
                    # and it is scheduled from the position behind it.
                    pass
                elif prior and (req.sampling.presence_penalty
                                or req.sampling.frequency_penalty):
                    # Resume after preemption with penalties active: rebuild
                    # the slot's count row from the carried-forward outputs
                    # instead of resetting it (the row may hold another
                    # request's counts). Rare path — one extra dispatch only
                    # when it matters.
                    row = np.zeros((self.model_config.vocab_size,), np.int32)
                    # prior outputs + the continuation token just sampled
                    # (the in-burst tokens0 count only runs for reset slots).
                    ids = np.clip(np.asarray(prior + [token], np.int64), 0,
                                  self.model_config.vocab_size - 1)
                    np.add.at(row, ids, 1)
                    self._dispatch("set_counts_row", {}, [np.int32(slot), row])
                    with self._lock:
                        self._counts_reset.discard(slot)
                else:
                    with self._lock:
                        # Fresh output in this slot: its penalty counts reset
                        # at the next burst (which also counts this token).
                        self._counts_reset.add(slot)
                if req.trace is not None:
                    req.trace.delivered(time.time(), req.output_token_ids)
                self._emit_token(seq, token, lp)
                rows += 1
                # Decode position bookkeeping starts from the emitted tokens
                # (a re-prefill after preemption carries prior outputs).
                if not in_burst:
                    req.scheduled_steps = len(req.output_token_ids)
        # ``emit_tokens`` follows ``generation_tokens_total``, which
        # counts a burst's tokens and not a prefill's first.
        steps.note_sum(
            emit_tokens=0, emit_rows=rows,
            emit_finished=self.requests_finished_total - finished0)
        if keep:
            self._pending_prefills = keep + self._pending_prefills

    def _cached_prefix_len(self, tokens: List[int],
                           adapter: str = "") -> int:
        """Read-only cached-prefix length estimate: walk the chain hashes
        through the prefix map — and the offload tier's external_lookup,
        which ``allocate_prompt`` also counts as cached — WITHOUT
        allocating. Mirrors allocate_prompt's bound (never reuse past the
        last token). Callers hold self._lock."""
        from production_stack_tpu.engine.kvcache import BlockAllocator

        bs = self.config.block_size
        alloc = self.kv_mgr.allocator
        ext = self.kv_mgr.external_lookup
        parent = self.kv_mgr.chain_root(adapter)
        i = 0
        while i + bs <= len(tokens) - 1:
            h = BlockAllocator.chain_hash(parent, tuple(tokens[i:i + bs]))
            if h not in alloc.prefix_map and not (
                    ext is not None and alloc.enable_prefix_caching
                    and ext(h)):
                break
            parent = h
            i += bs
        return i

    def _table_width(self, tokens: int, cap: Optional[int] = None) -> int:
        """The block-table bucket of a context of ``tokens``: the power of
        two, from 4, that holds its pages, within ``cap`` (the model's
        longest table by default). A plain prefill's context is its span,
        so its bucket has the one width of its ``tokens``."""
        blocks_needed = -(-tokens // self.config.block_size)
        width = 4
        while width < blocks_needed:
            width *= 2
        return min(width, cap or self.config.max_blocks_per_seq)

    def _prefill_batch_maxb(self) -> int:
        """Widest block table the chunked step plan's [prefill_batch,
        chunk] cached programs compile (64 blocks = 4k-token contexts at
        the default page size): bounds the PB-row cached-attention f32
        temp at warmup and serving time."""
        return min(64, self.config.max_blocks_per_seq)

    def _gather_prefill_group(self, req: EngineRequest, block_ids,
                              rung: int, rows: int) -> "list[dict]":
        """The head request (uncached, one span, allocated) and ``rows`` -
        1 waiting requests that can share its plain prefill NOW: uncached
        one-span prompts of the same plain-ladder ``rung``, in the order
        the scheduler would serve them, where free slots and the pool hold
        them all. ``rows`` is the one compiled row count of the rung, so
        it is the whole group or none ([]): no row is ever padding, and
        nobody waits for a mate. Members leave the waiting queue with
        their KV allocated."""
        cfg = self.config
        lo = max((b for b in cfg.prefill_buckets(plain=True) if b < rung),
                 default=0)
        mates = []
        bs = cfg.block_size
        with self._lock:
            if sum(1 for s in self.scheduler.slots if s is None) < rows:
                return []
            need = 0  # tokens of the mates' pages (the head has its own)
            # Members share no first page: the second would find the
            # first's registered and be a cached prompt after all.
            firsts = {(req.adapter_name, tuple(req.all_token_ids[:bs]))}
            for cand in sorted(self.scheduler.live_waiting(),
                               key=lambda r: r.priority):
                n_c = len(cand.prompt_token_ids) + len(cand.output_token_ids)
                if not lo < n_c <= rung:
                    continue
                tokens_c = cand.all_token_ids
                first = (cand.adapter_name, tuple(tokens_c[:bs]))
                if first in firsts or self._cached_prefix_len(
                        tokens_c, cand.adapter_name):
                    continue
                firsts.add(first)
                mates.append(cand)
                need += -(-(n_c + 1) // bs) * bs
                if len(mates) == rows - 1:
                    break
            if len(mates) < rows - 1 or not self.kv_mgr.can_allocate(need):
                return []
            # Mates leave the queue only once all of them hold their pages:
            # where one cannot (the pool, or a prefix that came to be
            # cached meanwhile), all stay where they wait, pages unwritten.
            held = []
            for cand in mates:
                got = self.kv_mgr.allocate_prompt(
                    cand.request_id, cand.all_token_ids,
                    adapter=cand.adapter_name)
                if got is None:
                    break
                held.append((cand, got))
                if got[1]:
                    break
            whole = len(held) == rows - 1 and not held[-1][1][1]
            for cand, got in held:
                if whole:
                    self.scheduler.take_waiting(cand)
                else:
                    self.kv_mgr.free_unwritten(cand.request_id, got[2])
        self._drain_offload()
        if not whole:
            return []
        return [{"req": req, "block_ids": block_ids}] + [
            {"req": cand, "block_ids": got[0]} for cand, got in held]

    def _do_prefill_group(self, group: "list[dict]", rung: int) -> None:
        """One plain prefill over the group's prompts, a row each: the
        ``prefill`` program at [len(group), rung]. Rows do not mix (no
        reduction runs across them), so each member gets the tokens and
        pages of the single path; its first token is its row of the
        sample, read back deferred like a single prefill's."""
        self.prefill_group_count += 1
        self.prefill_group_rows += len(group)
        now = time.time()
        for m in group[1:]:  # the head's trace: _do_prefill and the loop
            tr = m["req"].trace
            if tr is not None:
                if not tr.prefill_start:
                    tr.prefill_start = now
                tr.cached_tokens = 0
                tr.preemptions = m["req"].num_preemptions
        slots, feed = self._first_token_slots([m["req"] for m in group])
        try:
            sampled = self._prefill_rows(
                [(m["req"], m["req"].all_token_ids, m["block_ids"], 0,
                  len(m["req"].all_token_ids)) for m in group],
                plain_rung=rung, feed=feed)
        except Exception:
            # The loop fails the head; its mates fail with it.
            for m in group[1:]:
                with self._lock:
                    self.kv_mgr.free_unwritten(m["req"].request_id)
                    self.scheduler.drop(m["req"])
                m["req"].on_token(None, "error")
            raise
        new_tokens = sum(len(m["req"].all_token_ids) for m in group)
        self._step_info = {
            "kind": "prefill", "rows": len(group), "tokens": new_tokens,
            "forwards": 1, "kv_read_tokens": 0,
            "kv_write_tokens": new_tokens, "batched": True,
        }
        # Same pipelining as the single path: settle the in-flight burst
        # and the previous prefill while the group executes on device.
        self._flush_pending_burst()
        self._flush_pending_prefills()
        now = time.time()
        self.prompt_tokens_total += new_tokens
        for row, m in enumerate(group):
            req_m = m["req"]
            if row and req_m.trace is not None:
                req_m.trace.prefill_end = now
            self._start_prefilled(req_m, slots[row], feed[row] is not None,
                                  sampled, row)

    def _note_attn_pairs(self, start: int, end: int) -> None:
        """A prefill span ``[start, end)`` of a prompt is dispatched: its
        causal pairs (the query at position p sees p + 1 keys) join the
        step's record as ``attn_pairs``, the model's own count of what
        prefill attention has to compute, whatever the program pads."""
        self._steps.note_sum(
            attn_pairs=(end * (end + 1) - start * (start + 1)) // 2)

    def _prefill_rows(self, rows, pad_to: int = 0, plain_rung: int = 0,
                      feed=()):
        """One batched prefill dispatch: rows = [(req, tokens, block_ids,
        start, end), ...]. With ``plain_rung`` the rows are whole uncached
        prompts of that rung and run the plain program at [len(rows),
        rung]. Otherwise they are chunks of the chunked step plan, padded
        to ``pad_to`` rows (padding rows have seq_lens 0 and dropped page
        writes), and run the cached-prefill program at the CHUNK bucket —
        one compiled variant per block-table width regardless of the
        step's composition. ``feed``: the decode slot a row whose first
        token the op hands to the next burst (_first_token_slots).
        Returns the sampled tuple (a row each)."""
        cfg = self.config
        for row in rows:
            self._note_attn_pairs(*row[3:5])
        if plain_rung:
            R, bucket = len(rows), plain_rung
            maxb = self._table_width(bucket)
        else:
            R = pad_to
            bucket = cfg.bucket_for(
                min(cfg.prefill_chunk_size, cfg.max_model_len))
            maxb = self._table_width(max(m[4] for m in rows),
                                     self._prefill_batch_maxb())

        token_arr = np.zeros((R, bucket), np.int32)
        positions = np.zeros((R, bucket), np.int32)
        slot_mapping = np.full((R, bucket), -1, np.int64)
        block_table = np.zeros((R, maxb), np.int32)
        context_lens = np.ones((R,), np.int32)
        seq_lens = np.zeros((R,), np.int32)
        adapter_ids = np.zeros((R,), np.int32)
        temp = np.zeros((R,), np.float32)
        topk = np.zeros((R,), np.int32)
        topp = np.ones((R,), np.float32)
        seeds = np.zeros((R,), np.int64)
        steps = np.ones((R,), np.int64)
        suppress_eos = np.zeros((R,), bool)
        bias_ids = np.zeros((R, MAX_LOGIT_BIAS), np.int32)
        bias_vals = np.zeros((R, MAX_LOGIT_BIAS), np.float32)
        stop_ids = np.zeros((R, MAX_STOP_IDS), np.int32)
        stop_valid = np.zeros((R, MAX_STOP_IDS), np.float32)
        mask_bits = np.zeros((R, self._mask_row_bytes), np.uint8)
        mask_on = np.zeros((R,), bool)

        for i, (req, tokens, block_ids, start, end) in enumerate(rows):
            take = end - start
            token_arr[i, :take] = tokens[start:end]
            positions[i, :bucket] = start + np.arange(bucket)
            pos_idx = start + np.arange(take)
            blocks = np.asarray(block_ids, np.int64)
            slot_mapping[i, :take] = (
                blocks[pos_idx // cfg.block_size] * cfg.block_size
                + pos_idx % cfg.block_size
            )
            use = min(len(block_ids), maxb)
            block_table[i, :use] = block_ids[:use]
            context_lens[i] = end
            seq_lens[i] = take
            adapter_ids[i] = req.adapter_id
            t, k_, p_, seed = self._sampling_for(req)
            temp[i], topk[i], topp[i], seeds[i] = t, k_, p_, seed
            steps[i] = len(tokens)
            suppress_eos[i] = (
                len(req.output_token_ids) < req.sampling.min_tokens)
            self._fill_bias_row(bias_ids[i], bias_vals[i],
                                self._resume_bias(req))
            self._fill_stop_row(stop_ids[i], stop_valid[i],
                                req.sampling.stop_token_ids)
            # Structured: the chunk's sampled token only matters on the
            # FINAL span, where the FSM is at the request's current state
            # (re-prefill after preemption included — output tokens were
            # already advanced through the automaton at emission).
            self._fill_mask_row(mask_bits, mask_on, i, req)

        if not plain_rung:
            self.prefill_attention_dispatch_total[
                self._paged_attn_path()] += 1
        return self._dispatch("prefill", self._feed_static(
                not plain_rung, feed, R), [
            token_arr, positions, slot_mapping,
            block_table, context_lens, seq_lens, adapter_ids,
            temp, topk, topp, seeds, steps,
            suppress_eos, bias_ids, bias_vals, stop_ids, stop_valid,
            mask_bits, mask_on,
        ])

    def _prefill_span(self, req: EngineRequest, tokens, block_ids,
                      start: int, end: int, feed=()):
        """Dispatch one prefill chunk (tokens[start:end]) and return its
        on-device sampled next token (only the LAST chunk's sample is read
        back, and handed to the next burst: ``feed``, its decode slot as
        _first_token_slots gives it). Spans after the first attend to
        earlier tokens through the pages (prefill_cached); the span's own
        K/V is written first, so attention over the block table sees the
        full prefix."""
        cfg = self.config
        take = end - start
        self._note_attn_pairs(start, end)
        bucket = cfg.bucket_for(take, plain=start == 0)
        # Bucket the block-table width (power of two, min 4) so
        # cached-prefill attention cost scales with the real context, not
        # max_model_len — and so warmup() can precompile every variant.
        maxb = self._table_width(end)

        token_arr = np.zeros((1, bucket), np.int32)
        token_arr[0, :take] = tokens[start:end]
        positions = np.zeros((1, bucket), np.int32)
        positions[0, :bucket] = start + np.arange(bucket)
        slot_mapping = np.full((1, bucket), -1, np.int64)
        pos_idx = start + np.arange(take)
        blocks = np.asarray(block_ids, np.int64)
        slot_mapping[0, :take] = (
            blocks[pos_idx // cfg.block_size] * cfg.block_size
            + pos_idx % cfg.block_size
        )
        block_table = np.zeros((1, maxb), np.int32)
        use = min(len(block_ids), maxb)
        block_table[0, :use] = block_ids[:use]
        context_lens = np.asarray([end], np.int32)
        seq_lens = np.asarray([take], np.int32)
        adapter_ids = np.asarray([req.adapter_id], np.int32)
        t, k_, p_, seed = self._sampling_for(req)
        suppress_eos = np.asarray(
            [len(req.output_token_ids) < req.sampling.min_tokens], bool)
        bias_ids = np.zeros((1, MAX_LOGIT_BIAS), np.int32)
        bias_vals = np.zeros((1, MAX_LOGIT_BIAS), np.float32)
        self._fill_bias_row(bias_ids[0], bias_vals[0],
                            self._resume_bias(req))
        stop_ids = np.zeros((1, MAX_STOP_IDS), np.int32)
        stop_valid = np.zeros((1, MAX_STOP_IDS), np.float32)
        self._fill_stop_row(stop_ids[0], stop_valid[0],
                            req.sampling.stop_token_ids)
        mask_bits = np.zeros((1, self._mask_row_bytes), np.uint8)
        mask_on = np.zeros((1,), bool)
        self._fill_mask_row(mask_bits, mask_on, 0, req)

        if start > 0:
            self.prefill_attention_dispatch_total[
                self._paged_attn_path()] += 1
        return self._dispatch("prefill", self._feed_static(
                start > 0, feed, 1), [
            token_arr, positions, slot_mapping,
            block_table, context_lens, seq_lens, adapter_ids,
            np.asarray([t], np.float32), np.asarray([k_], np.int32),
            np.asarray([p_], np.float32), np.asarray([seed], np.int64),
            np.asarray([len(tokens)], np.int64),
            suppress_eos, bias_ids, bias_vals, stop_ids, stop_valid,
            mask_bits, mask_on,
        ])

    # -- decode ------------------------------------------------------------
    def _do_decode(self) -> None:
        """Dispatch one fused decode burst, pipelined: burst N+1 is sent to
        the device (feedback token selected on device from burst N's output)
        BEFORE burst N's tokens are read back, so the host<->device round
        trip overlaps device execution. Sequences whose burst-N tokens turn
        out to finish the request are covered speculatively in burst N+1;
        their extra tokens are discarded at emission and their stray page
        writes are overwritten before ever becoming readable (pages freed by
        the finish are re-written by any later owner before its attention
        can read them — device dispatch order guarantees it).

        The hand-over from a prefill is pipelined the same way: a row's
        first token reaches its first burst on the device (the prefill op
        wrote it into the feedback array, ``_first_on_device``), so the
        burst is built and enqueued while the prefill program still runs,
        with the row's positions, seeds and penalty counts as if the
        token had been emitted, and the token is read back and emitted
        after this dispatch, before any token of the burst. A first token
        that ends its request has cost one burst's speculative cover, as
        above. A burst that needs a token's value first (a structured
        row's mask, drafts under speculation) and a row that does
        (``_feeds_first_token``) settle the prefills before the build,
        as every burst did before."""
        cfg = self.config
        if any(not e["fed"] for e in self._pending_prefills):
            # A first token whose value this build needs on the host.
            self._flush_pending_prefills()
        if cfg.speculative_num_tokens > 0:
            # Prompt-lookup speculation: host drafts need the TRUE last
            # token, so spec mode collapses the dispatch/readback
            # pipeline (flush first, then dispatch; use_prev stays
            # False). That trades the one-burst overlap for verifying
            # up to K tokens per model forward when drafts accept.
            # Fusion cannot carry this: a captured prefill's sample must
            # actually execute (and emit) before it can seed a draft.
            self._abort_fused_capture()
            self._flush_pending_prefills()
            self._flush_pending_burst()
            plan = self._propose_spec_drafts()
            if plan:
                self._do_decode_spec(plan)
                return
        # Structured rows build their mask from the CURRENT automaton
        # state, which the host only learns by reading back the in-flight
        # burst — so a structured participant collapses the dispatch/
        # readback pipeline exactly like spec mode (flush first, feedback
        # via host_tokens).
        with self._lock:
            has_structured = any(
                s.req.structured is not None and s.req.structured.masking
                for s in self.scheduler.running())
        if has_structured:
            # Masks read the CURRENT automaton state, which only the
            # emitted tokens advance — a captured prefill's sample must
            # really execute (and flush) before a mask row is built.
            self._abort_fused_capture()
            self._flush_pending_prefills()
            self._flush_pending_burst()
        # The rows the prefills since the last burst fed (a burst that
        # settled them above for a token's value takes none of them on the
        # device), and of them those whose token the host has not read.
        fed, self._first_on_device = self._first_on_device, []
        plain = not (has_structured or cfg.speculative_num_tokens > 0)
        unread = {id(e["seq"]): e for e in self._pending_prefills
                  if e["fed"]}
        B = cfg.max_num_seqs
        K = max(cfg.decode_steps, 1)
        # Prompts waiting AND admissible (free slot — a slot-blocked
        # waiter gains nothing from shorter bursts): shrink the burst so
        # the prefill starts within ~pressure_K step-times instead of a
        # full burst (the big-model TTFT tail — a 3B/8B burst is
        # ~0.5-1 s of wall time).
        with self._lock:
            waiter = self.scheduler.peek_waiting()
            admissible_waiter = (
                waiter is not None
                and self.scheduler._free_slot() is not None
                and self.kv_mgr.can_allocate(
                    len(waiter.all_token_ids) + 1))
        if cfg.decode_steps_pressure > 0 and admissible_waiter:
            K = min(K, max(cfg.decode_steps_pressure, 1))

        # Per-seq usable burst width (bounded by max_tokens/max_model_len);
        # a fixed K with per-seq masking keeps ONE compiled program per
        # block-table width instead of one per burst-width combination.
        # Bounds use all_token_ids which may lag the in-flight burst, so
        # this over-schedules at most one extra burst near the end caps.
        def seq_allow(r: EngineRequest, ahead: int) -> int:
            if r.structured is not None and r.structured.masking:
                # The FSM mask is constant across the scan (the host
                # advances the automaton only at burst boundaries):
                # schedule one usable step — later steps would sample
                # under a stale mask — and discard the rest at emission.
                return 1
            # ``ahead``: the first token, where the host has not read it
            # yet and the lists below lack it.
            return max(1, min(
                K,
                r.sampling.max_tokens - len(r.output_token_ids) - ahead,
                cfg.max_model_len - len(r.all_token_ids) - ahead + 1,
            ))

        prev = self._pending_burst
        prev_slots = (
            {id(s): prev["allows"].get(s.req.request_id, 1)
             for s in prev["active"]} if prev else {}
        )

        # Sequences whose first token is still captured for the fused
        # dispatch being built: no host-visible sample yet, so they sit
        # this burst out and join the next one (per-row positions/seeds
        # keep their stream identical to the alternating schedule's).
        pending_first = {
            e["req"].request_id for e in self._pending_prefills
            if isinstance(e["sampled"], _FusedPlaceholder)
            and not e["sampled"].ready}

        with self._lock:
            active0 = [s for s in self.scheduler.running()
                       if s.req.request_id not in pending_first]
            allows: Dict[str, int] = {}
            # Account the about-to-be-written tokens; preempt on OOM.
            for seq in list(self.scheduler.running()):
                if self.scheduler.slots[seq.slot] is not seq:
                    continue  # already preempted this pass
                if seq.req.request_id in pending_first:
                    continue  # first token still in the fused capture
                need = seq_allow(seq.req, id(seq) in unread)
                allows[seq.req.request_id] = need
                while need > 0:
                    ok = self.kv_mgr.append_token(
                        seq.req.request_id, seq.req.all_token_ids[-1]
                    )
                    if ok:
                        need -= 1
                        continue
                    victim = self.scheduler.preempt_victim()
                    if victim is None or victim.req is seq.req:
                        break
                    # (victim's pages are back; retry this append)
            active0_ids = {id(s) for s in active0}
            active = [
                s for s in self.scheduler.running() if id(s) in active0_ids
            ]
            # Fed rows this burst takes on the device: those still in
            # their slot. One that left before any burst (its first token
            # ended it, an abort, a preemption) took the host's path.
            on_device = {id(seq) for slot, seq in fed
                         if plain and self.scheduler.slots[slot] is seq}
        self.first_token_feed_total["device"] += len(on_device)
        self.first_token_feed_total["host"] += len(fed) - len(on_device)
        self._drain_offload()  # spill pages evicted during block accounting
        if not active:
            self._flush_pending_burst()
            self._flush_pending_prefills()
            return
        self._steps.note(first_on_device_rows=len(on_device))

        # Bucket the block-table width (power of two over the widest live
        # sequence) so the gather in paged attention scales with real
        # context, not max_model_len.
        max_blocks = max(
            (len(self.kv_mgr.block_table(s.req.request_id)) for s in active),
        )
        maxb = 4
        while maxb < max_blocks:
            maxb *= 2
        maxb = min(maxb, cfg.max_blocks_per_seq)

        host_tokens = np.zeros((B,), np.int32)
        use_host = np.ones((B,), bool)
        tok_idx = np.zeros((B,), np.int32)
        positions0 = np.zeros((B,), np.int32)
        slot_mat = np.full((B, K), -1, np.int64)
        block_table = np.zeros((B, maxb), np.int32)
        context0 = np.ones((B,), np.int32)
        adapter_ids = np.zeros((B,), np.int32)
        temperature = np.zeros((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        seed_base = np.zeros((B,), np.int64)
        presence = np.zeros((B,), np.float32)
        frequency = np.zeros((B,), np.float32)
        min_tok = np.zeros((B,), np.int32)
        out_len0 = np.zeros((B,), np.int32)
        bias_ids = np.zeros((B, MAX_LOGIT_BIAS), np.int32)
        bias_vals = np.zeros((B, MAX_LOGIT_BIAS), np.float32)
        stop_ids = np.zeros((B, MAX_STOP_IDS), np.int32)
        stop_valid = np.zeros((B, MAX_STOP_IDS), np.float32)
        mask_bits = np.zeros((B, self._mask_row_bytes), np.uint8)
        mask_on = np.zeros((B,), bool)
        reset_counts = np.zeros((B,), bool)
        with self._lock:
            for slot in self._counts_reset:
                reset_counts[slot] = True
            self._counts_reset.clear()

        for seq in active:
            i = seq.slot
            r = seq.req
            # Position/context bookkeeping counts *scheduled* tokens: with a
            # burst in flight the host hasn't seen its tokens yet, but their
            # pages and positions are committed.
            sched_ahead = id(seq) in prev_slots
            if sched_ahead:
                # Feedback token comes from the in-flight burst's output, on
                # device.
                use_host[i] = False
                tok_idx[i] = prev_slots[id(seq)] - 1
            elif id(seq) in on_device:
                # Its first token, which its prefill wrote over the whole
                # of row i of the feedback array: any tok_idx finds it.
                use_host[i] = False
                entry = unread.get(id(seq))
                if entry is not None:
                    # Not read back yet: the row is built as it would be
                    # with the token emitted, and the flush behind this
                    # dispatch leaves its bookkeeping alone.
                    entry["in_burst"] = True
                    r.scheduled_steps = len(r.output_token_ids) + 1
                    reset_counts[i] = True
            else:
                host_tokens[i] = r.all_token_ids[-1]
            base = len(r.prompt_token_ids) + r.scheduled_steps
            allow = allows.get(r.request_id, 1)
            positions0[i] = base - 1
            context0[i] = base
            bids = self.kv_mgr.block_table(r.request_id)
            use = min(len(bids), maxb)
            block_table[i, :use] = bids[:use]
            bid_arr = np.asarray(bids, np.int64)
            pos = base - 1 + np.arange(allow)
            slot_mat[i, :allow] = (
                bid_arr[pos // cfg.block_size] * cfg.block_size
                + pos % cfg.block_size
            )
            adapter_ids[i] = r.adapter_id
            t, k_, p_, seed = self._sampling_for(r)
            temperature[i] = t
            top_k[i] = k_
            top_p[i] = p_
            seed_base[i] = seed + r.scheduled_steps
            presence[i] = r.sampling.presence_penalty
            frequency[i] = r.sampling.frequency_penalty
            min_tok[i] = r.sampling.min_tokens
            out_len0[i] = r.scheduled_steps
            self._fill_bias_row(bias_ids[i], bias_vals[i],
                                r.sampling.logit_bias)
            self._fill_stop_row(stop_ids[i], stop_valid[i],
                                r.sampling.stop_token_ids)
            self._fill_mask_row(mask_bits, mask_on, i, r)
            r.scheduled_steps += allow

        if (self._latent_decode_path() if self.own_page_sides is not None
                else self._paged_attn_path()) == "pallas":
            # What the decode kernel copies for this burst, beside what
            # it has to: per scan step, the live pages of the rows that
            # write a token in it, and the tokens they hold (the host's
            # twin of decoder.attend's contexts).
            from production_stack_tpu.ops.pallas_paged_attention import (
                fetch_tokens,
            )

            live = np.where(
                slot_mat >= 0, context0[:, None] + np.arange(K), 0)
            fetched = fetch_tokens(live, cfg.block_size, maxb)
            self.kv_fetch_tokens_total += fetched
            self._steps.note(
                kv_fetch_tokens=fetched, kv_live_tokens=int(live.sum()))
            window = self.model_config.sliding_window
            if window:
                # The same two counts for a call of a sliding layer,
                # whose first live token is ``context - window``: the
                # counts above are a full layer's.
                self._steps.note(
                    kv_fetch_tokens_window=fetch_tokens(
                        live, cfg.block_size, maxb, window),
                    kv_live_tokens_window=int(
                        np.minimum(live, window).sum()))
        if self._window_layer_share:
            self._steps.note(kv_window_dead_tokens=self._window_dead_tokens(
                [context0[s.slot] for s in active]))
        if self.block_state_shape:
            # A decode step writes the entry of the block it writes its
            # token into.
            written = int((slot_mat >= 0).sum())
            self.state_blocks_written_total += written
            self._steps.note_sum(state_blocks_written=written)
        outs = self._dispatch(
            "decode", {"K": K,
                       "use_prev": prev is not None or bool(on_device)}, [
                reset_counts, tok_idx, host_tokens, use_host, positions0,
                slot_mat, block_table, context0, adapter_ids, temperature,
                top_k, top_p, seed_base, presence, frequency,
                min_tok, out_len0, bias_ids, bias_vals, stop_ids, stop_valid,
                mask_bits, mask_on,
            ])
        self.decode_forward_steps_total += K
        sched = sum(allows.get(s.req.request_id, 1) for s in active)
        self._step_info = {
            "kind": "decode_burst", "rows": len(active),
            "tokens": sched, "forwards": K,
            # Every scan step re-reads each live row's full context
            # through paged attention (growing by one per step; the
            # context0 snapshot is the roofline's lower bound).
            "kv_read_tokens": K * int(
                sum(context0[s.slot] for s in active)),
            "kv_write_tokens": sched,
        }
        # Read back the PREVIOUS burst (overlaps this burst's execution),
        # then the first tokens this burst took on the device: a stream's
        # first token is delivered before any token of its burst.
        self._flush_pending_burst()
        self._flush_pending_prefills()
        self._pending_burst = {
            "out": outs, "active": active, "allows": allows,
        }

    def _propose_spec_drafts(self):
        """Drafting for the next burst. Returns a list of ``(seq, draft)``
        covering EVERY running row, or None. Drafts come from the draft
        model when one is configured, from host prompt lookup otherwise;
        either way the verify burst that consumes the plan is identical.

        All-or-nothing: a verify burst replaces the whole batched decode
        step, so it only pays when every live row brings at least one
        draft token and is eligible. Any row that is draft-less,
        adaptively disabled, or spec-ineligible (presence/frequency
        penalties need the in-scan device token counts the verify
        program omits) sends the whole batch down the plain path — which
        is exactly the no-worse-than-baseline fallback for adversarial
        text."""
        cfg = self.config
        K = cfg.speculative_num_tokens
        use_draft = self._draft is not None
        with self._lock:
            active = [s for s in self.scheduler.running()
                      if self.scheduler.slots[s.slot] is s]
        if not active:
            return None
        rows = []
        for seq in active:
            r = seq.req
            if r.sampling.presence_penalty or r.sampling.frequency_penalty:
                return None
            if r.spec is None:
                r.spec = SpecState(
                    cfg.speculative_ngram_size,
                    source="draft_model" if use_draft else "ngram",
                    probation=(cfg.speculative_draft_probation
                               if use_draft else 0),
                )
            if r.spec.disabled:
                # Each plain burst the request sits out counts against a
                # drafter's probation; an n-gram latch (probation 0)
                # stays permanent.
                r.spec.tick_probation()
                if r.spec.disabled:
                    return None
            allow = max(1, min(
                K,
                r.sampling.max_tokens - len(r.output_token_ids),
                cfg.max_model_len - len(r.all_token_ids) + 1,
            ))
            if allow < 2:
                return None
            rows.append((seq, allow))
        if use_draft:
            return self._propose_draft_model(rows)
        plan = []
        for seq, allow in rows:
            draft = seq.req.spec.propose(seq.req.all_token_ids, allow - 1)
            if not draft:
                return None
            plan.append((seq, list(draft)))
        return plan

    def _propose_draft_model(self, rows):
        """Batched draft-model proposal. Phase A catches the drafter's KV
        up with every token it has not seen (the whole prompt right after
        prefill, one verified suffix in steady state), chunked through
        the warmed buckets, and takes the greedy next token at each row's
        frontier as the first draft. Phase B extends to the full draft
        width: one fused greedy scan when no row is FSM-masked, else
        token-by-token forwards with each row's token-FSM mask applied to
        the DRAFTER's logits — the same mask walk (local cursor, dead
        state unmasks) the verify program applies, so constrained rows
        draft only DFA-legal tokens. Returns a plan for _do_decode_spec,
        or None to fall back to a plain burst."""
        cfg = self.config
        d = self._draft
        B = cfg.max_num_seqs
        bs = cfg.block_size
        maxb = cfg.max_blocks_per_seq
        info = []
        with self._lock:
            for seq, allow in rows:
                r = seq.req
                rid = r.request_id
                n = len(r.all_token_ids)
                # Worst-case feeds this burst: catch-up to n, then
                # allow-2 draft-extension steps.
                if not d.ensure_capacity(rid, n + allow - 2):
                    return None  # drafter pool exhausted: plain burst
                start = min(d.computed.get(rid, 0), n - 1)
                st = (r.structured
                      if self.config.speculative_draft_constrain else None)
                info.append({
                    "seq": seq, "rid": rid, "allow": allow, "n": n,
                    "start": start,
                    "feed": list(r.all_token_ids[start:]),
                    "table": np.asarray(d.block_table(rid), np.int64),
                    "st": st if (st is not None and st.masking) else None,
                })
        buckets = d.buckets()
        maxW = buckets[-1]

        def page_slots(table, positions):
            return table[positions // bs] * bs + positions % bs

        # -- phase A: chunked KV catch-up + first draft token ----------
        drafts: list = [None] * len(info)
        fed = [0] * len(info)
        pending = set(range(len(info)))
        while pending:
            take = {i: min(len(info[i]["feed"]) - fed[i], maxW)
                    for i in pending}
            W = cfg.bucket_for(max(take.values()))
            tokens_a = np.zeros((B, W), np.int32)
            positions = np.zeros((B, W), np.int32)
            slot_map = np.full((B, W), -1, np.int64)
            tables = np.zeros((B, maxb), np.int32)
            ctx = np.ones((B,), np.int32)
            sl = np.ones((B,), np.int32)
            mask_bits = np.zeros((B, self._mask_row_bytes), np.uint8)
            mask_on = np.zeros((B,), bool)
            done_now = []
            for i in sorted(pending):
                e = info[i]
                b = e["seq"].slot
                t = take[i]
                lo = e["start"] + fed[i]
                span = np.arange(lo, lo + t, dtype=np.int64)
                tokens_a[b, :t] = e["feed"][fed[i]:fed[i] + t]
                positions[b, :t] = span
                slot_map[b, :t] = page_slots(e["table"], span)
                use = min(len(e["table"]), maxb)
                tables[b, :use] = e["table"][:use]
                ctx[b] = lo + t
                sl[b] = t
                fed[i] += t
                if lo + t == e["n"]:
                    # This round produces the row's first draft; mask it
                    # with the request's CURRENT automaton state — the
                    # same mask the verify program applies at position 0.
                    done_now.append(i)
                    if e["st"] is not None and e["st"].state >= 0:
                        mask_bits[b] = e["st"].mask_row()
                        mask_on[b] = True
            out = self._dispatch("draft_forward", {"bucket": W}, [
                tokens_a, positions, slot_map, tables, ctx, sl,
                mask_bits, mask_on])
            self.spec_draft_forward_steps_total += 1
            toks = np.asarray(jax.device_get(_unwrap_fused(out)))
            for i in done_now:
                drafts[i] = [int(toks[info[i]["seq"].slot])]
                pending.discard(i)

        # -- phase B: extend to the full draft width -------------------
        steps_max = max(e["allow"] for e in info) - 2
        any_masked = any(e["st"] is not None for e in info)
        if steps_max >= 1 and not any_masked:
            S = cfg.speculative_num_tokens - 2
            token0 = np.zeros((B,), np.int32)
            positions0 = np.zeros((B,), np.int32)
            slot_mat = np.full((B, S), -1, np.int64)
            tables = np.zeros((B, maxb), np.int32)
            ctx0 = np.ones((B,), np.int32)
            for i, e in enumerate(info):
                b = e["seq"].slot
                token0[b] = drafts[i][0]
                positions0[b] = e["n"]
                ctx0[b] = e["n"] + 1
                t = e["allow"] - 2
                if t > 0:
                    span = np.arange(e["n"], e["n"] + t, dtype=np.int64)
                    slot_mat[b, :t] = page_slots(e["table"], span)
                use = min(len(e["table"]), maxb)
                tables[b, :use] = e["table"][:use]
            out = self._dispatch("draft_scan", {}, [
                token0, positions0, slot_mat, tables, ctx0])
            self.spec_draft_forward_steps_total += S
            toks = np.asarray(jax.device_get(_unwrap_fused(out)))
            for i, e in enumerate(info):
                b = e["seq"].slot
                drafts[i].extend(
                    int(x) for x in toks[b, :e["allow"] - 2])
        elif steps_max >= 1:
            # FSM-constrained drafting: step token by token so each
            # masked row's mask reflects the tokens drafted so far. A
            # LOCAL cursor walks the automaton exactly like the
            # verify-side mask walk (the request's real state advances
            # only at emission); once the cursor leaves the language the
            # remaining positions draft unmasked, mirroring the verify
            # walk's break.
            W0 = buckets[0]
            cur = []
            for i, e in enumerate(info):
                c = e["st"].state if e["st"] is not None else -1
                if c >= 0:
                    c = e["st"].fsm.advance(c, drafts[i][0])
                cur.append(c)
            for s in range(1, steps_max + 1):
                live = [i for i, e in enumerate(info)
                        if e["allow"] - 1 > s]
                if not live:
                    break
                tokens_a = np.zeros((B, W0), np.int32)
                positions = np.zeros((B, W0), np.int32)
                slot_map = np.full((B, W0), -1, np.int64)
                tables = np.zeros((B, maxb), np.int32)
                ctx = np.ones((B,), np.int32)
                sl = np.ones((B,), np.int32)
                mask_bits = np.zeros((B, self._mask_row_bytes), np.uint8)
                mask_on = np.zeros((B,), bool)
                for i in live:
                    e = info[i]
                    b = e["seq"].slot
                    p = e["n"] + s - 1
                    tokens_a[b, 0] = drafts[i][s - 1]
                    positions[b, 0] = p
                    slot_map[b, 0] = (
                        int(e["table"][p // bs]) * bs + p % bs)
                    ctx[b] = p + 1
                    sl[b] = 1
                    use = min(len(e["table"]), maxb)
                    tables[b, :use] = e["table"][:use]
                    if e["st"] is not None and cur[i] >= 0:
                        mask_bits[b] = e["st"].fsm.mask_row(cur[i])
                        mask_on[b] = True
                out = self._dispatch("draft_forward", {"bucket": W0}, [
                    tokens_a, positions, slot_map, tables, ctx, sl,
                    mask_bits, mask_on])
                self.spec_draft_forward_steps_total += 1
                toks = np.asarray(jax.device_get(_unwrap_fused(out)))
                for i in live:
                    e = info[i]
                    tok = int(toks[e["seq"].slot])
                    drafts[i].append(tok)
                    if e["st"] is not None and cur[i] >= 0:
                        cur[i] = e["st"].fsm.advance(cur[i], tok)

        # -- bookkeeping + plan ----------------------------------------
        plan = []
        with self._lock:
            for i, e in enumerate(info):
                dr = drafts[i][:e["allow"] - 1]
                # Drafter KV now covers the request's n tokens plus the
                # drafts it fed back (all but the last drafted token).
                d.computed[e["rid"]] = e["n"] + len(dr) - 1
                plan.append((e["seq"], dr))
        return plan

    def _do_decode_spec(self, plan) -> None:
        """Dispatch one speculative verify burst: ONE model forward scores
        the last emitted token plus each row's host drafts at their true
        positions; the flush accepts the longest draft prefix matching
        what plain decode would have sampled and rolls back the KV tail
        appended for rejected positions. Not pipelined — acceptance is
        data-dependent, so the next burst's drafts need this one's
        tokens on the host first."""
        cfg = self.config
        B = cfg.max_num_seqs
        K = cfg.speculative_num_tokens
        drafts = {s.req.request_id: d for s, d in plan}
        with self._lock:
            active0_ids = {id(s) for s, _ in plan}
            allows: Dict[str, int] = {}
            # Account the about-to-be-written tokens; preempt on OOM
            # (mirrors _do_decode: the loop ends fully appended or
            # self-preempted, so surviving rows have exactly `allow`
            # pages committed — the flush's rollback relies on that).
            for seq, draft in plan:
                if self.scheduler.slots[seq.slot] is not seq:
                    continue  # already preempted this pass
                need = len(draft) + 1
                allows[seq.req.request_id] = need
                while need > 0:
                    ok = self.kv_mgr.append_token(
                        seq.req.request_id, seq.req.all_token_ids[-1]
                    )
                    if ok:
                        need -= 1
                        continue
                    victim = self.scheduler.preempt_victim()
                    if victim is None or victim.req is seq.req:
                        break
            active = [
                s for s in self.scheduler.running() if id(s) in active0_ids
            ]
        self._drain_offload()
        if not active:
            return

        max_blocks = max(
            (len(self.kv_mgr.block_table(s.req.request_id)) for s in active),
        )
        maxb = 4
        while maxb < max_blocks:
            maxb *= 2
        maxb = min(maxb, cfg.max_blocks_per_seq)

        tokens = np.zeros((B, K), np.int32)
        positions0 = np.zeros((B,), np.int32)
        slot_mat = np.full((B, K), -1, np.int64)
        block_table = np.zeros((B, maxb), np.int32)
        context0 = np.ones((B,), np.int32)
        adapter_ids = np.zeros((B,), np.int32)
        temperature = np.zeros((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        seed_base = np.zeros((B,), np.int64)
        min_tok = np.zeros((B,), np.int32)
        out_len0 = np.zeros((B,), np.int32)
        bias_ids = np.zeros((B, MAX_LOGIT_BIAS), np.int32)
        bias_vals = np.zeros((B, MAX_LOGIT_BIAS), np.float32)
        stop_ids = np.zeros((B, MAX_STOP_IDS), np.int32)
        stop_valid = np.zeros((B, MAX_STOP_IDS), np.float32)
        mask_bits = np.zeros((B, K, self._mask_row_bytes), np.uint8)
        mask_on = np.zeros((B, K), bool)

        for seq in active:
            i = seq.slot
            r = seq.req
            draft = drafts[r.request_id]
            allow = allows.get(r.request_id, 1)
            base = len(r.prompt_token_ids) + r.scheduled_steps
            row = [r.all_token_ids[-1]] + draft
            tokens[i, :len(row)] = row
            positions0[i] = base - 1
            context0[i] = base
            bids = self.kv_mgr.block_table(r.request_id)
            use = min(len(bids), maxb)
            block_table[i, :use] = bids[:use]
            bid_arr = np.asarray(bids, np.int64)
            pos = base - 1 + np.arange(allow)
            slot_mat[i, :allow] = (
                bid_arr[pos // cfg.block_size] * cfg.block_size
                + pos % cfg.block_size
            )
            adapter_ids[i] = r.adapter_id
            t, k_, p_, seed = self._sampling_for(r)
            temperature[i] = t
            top_k[i] = k_
            top_p[i] = p_
            seed_base[i] = seed + r.scheduled_steps
            min_tok[i] = r.sampling.min_tokens
            out_len0[i] = r.scheduled_steps
            self._fill_bias_row(bias_ids[i], bias_vals[i],
                                r.sampling.logit_bias)
            self._fill_stop_row(stop_ids[i], stop_valid[i],
                                r.sampling.stop_token_ids)
            st = r.structured
            if st is not None and st.masking:
                # Per-position masks walked through the draft: position
                # s gets the mask plain decode would apply after
                # emitting drafts 0..s-1. If the draft exits the
                # language at position t, position t's mask makes
                # sampled[t] != draft[t], so acceptance stops there and
                # the unmasked positions past it are never emitted —
                # drafts outside the grammar are rejected by the SAME
                # term the plain path applies.
                cur = st.state
                for s in range(allow):
                    if cur < 0:
                        break
                    mask_bits[i, s] = st.fsm.mask_row(cur)
                    mask_on[i, s] = True
                    if s < len(draft):
                        cur = st.fsm.advance(cur, draft[s])
            # scheduled_steps advances at FLUSH by the emitted count —
            # acceptance is data-dependent, unlike the plain burst.

        outs = self._dispatch(
            "spec_verify", {"K": K}, [
                tokens, positions0, slot_mat, block_table, context0,
                adapter_ids, temperature, top_k, top_p, seed_base,
                min_tok, out_len0, bias_ids, bias_vals, stop_ids,
                stop_valid, mask_bits, mask_on,
            ])
        self.spec_verify_bursts_total += 1
        self.decode_forward_steps_total += 1
        sched = sum(allows.get(s.req.request_id, 1) for s in active)
        self._step_info = {
            "kind": "spec_verify", "rows": len(active),
            "tokens": sched, "forwards": 1,
            "kv_read_tokens": int(
                sum(context0[s.slot] for s in active)),
            "kv_write_tokens": sched,
        }
        self._pending_burst = {
            "out": outs, "active": active, "allows": allows,
            "spec": True, "drafts": drafts,
        }

    # ``post(fn, *args)`` runs ``fn`` on the server's event loop, in turn
    # behind whatever the loop was handed before: the
    # ``call_soon_threadsafe`` that the tokens of a server's stream take.
    # The server sets it on its core; None without one. With it a burst
    # posts two markers, one before its first token and one after its
    # last, which time the hand-over into the step's record.
    post_to_server_loop: Optional[Callable[..., None]] = None
    # Deliveries that bursts' flushes made to requests' callbacks: beside
    # ``generation_tokens_total``, the tokens they carried.
    emit_callbacks_total = 0

    def _flush_pending_burst(self) -> None:
        """Read back and emit the in-flight decode burst, if any."""
        pending = self._pending_burst
        if pending is None:
            return
        out = pending["out"]
        if isinstance(out, _FusedPlaceholder) and not out.ready:
            # Captured for a fused dispatch that has not issued yet —
            # nothing to read back. (Defensive: _do_fused settles every
            # placeholder before returning.)
            return
        self._pending_burst = None
        steps = self._steps
        with steps.phase("readback"):
            arrays = [np.asarray(a)
                      for a in jax.device_get(_unwrap_fused(out))
                      ]  # [B, K], [B, K], [B, K, LOGPROB_K] x2
        # The hand-over to the server's loop, by two markers a burst: the
        # loop's queue is first in, first out, so the first runs when the
        # loop has woken and the second when the burst's last token is in
        # its request's queue. A stream of the server's carries a clock.
        post, step = self.post_to_server_loop, None
        if (post is not None and self.step_recorder is not None
                and any(s.req.trace is not None for s in pending["active"])):
            step = steps.open_step()
        if step is not None:
            post(steps.mark, step, "deliver_wake_s", time.perf_counter())
        tokens0 = self.generation_tokens_total
        finished0 = self.requests_finished_total
        sample = [0.0, 0, 0]  # callback seconds, rows, callbacks
        with steps.phase("emit"):
            if pending.get("spec"):
                self._flush_spec_burst(pending, arrays, sample)
            else:
                self._emit_burst(pending, arrays, sample)
        if step is not None:
            post(steps.mark, step, "deliver_drain_s", time.perf_counter())
        self.emit_callbacks_total += sample[2]
        tokens = self.generation_tokens_total - tokens0
        # Every delivery is timed, so the callbacks' seconds are all of
        # them and their sample is the tokens they carried: all.
        steps.note_sum(
            emit_tokens=tokens, emit_callback_samples=tokens,
            emit_finished=self.requests_finished_total - finished0,
            emit_callback_s=round(sample[0], 7),
            emit_rows=sample[1], emit_callbacks=sample[2])

    def _emit_seq(self, seq: RunningSeq, upto: int, arrays,
                  sample: list) -> int:
        """Emit up to ``upto`` of a burst's tokens to one sequence, as
        far as it still runs; returns how many. The request's callback
        holds them, and the reason where the burst ends the sequence,
        and they are handed over in one delivery after the last
        (``scheduler.TokenDelivery``). What the recorder and the
        request's trace learn of the stream they learn here, once per
        sequence and burst and never per token (``obs/steps.py``, the
        budget): one stamp of the request's clock, before its tokens so
        that whoever sees the stream end finds it, and the delivery
        timed and counted into ``sample``."""
        sampled, lps, top_lps, top_idxs = arrays
        req, slot = seq.req, seq.slot
        want_lp = req.sampling.logprobs
        if req.trace is not None and self.scheduler.slots[slot] is seq:
            req.trace.delivered(time.time(), req.output_token_ids)
        emitted = 0
        req.on_token.hold()
        try:
            for s in range(upto):
                if self.scheduler.slots[slot] is not seq:
                    break  # finished / aborted / preempted mid-burst
                lp = None
                if want_lp is not None:
                    k = min(want_lp, top_lps.shape[2])
                    lp = {"logprob": float(lps[slot, s]),
                          "top": [(int(top_idxs[slot, s, j]),
                                   float(top_lps[slot, s, j]))
                                  for j in range(k)]}
                self._emit_token(seq, int(sampled[slot, s]), lp)
                emitted += 1
        finally:
            with self._lock:  # an abort's sentinel keeps its place
                t0 = time.perf_counter()
                delivered = req.on_token.release()
                sample[0] += time.perf_counter() - t0
        self.generation_tokens_total += emitted
        sample[1] += emitted > 0
        sample[2] += delivered
        return emitted

    def _emit_burst(self, pending, arrays, sample) -> None:
        """Emit a plain decode burst's tokens, each sequence as far as
        it was allowed and still runs."""
        emitted_seqs = []
        for seq in pending["active"]:
            allow = pending["allows"].get(seq.req.request_id, 1)
            emitted = self._emit_seq(seq, allow, arrays, sample)
            if emitted and self.scheduler.slots[seq.slot] is seq:
                emitted_seqs.append(seq)
        if emitted_seqs:
            # Token values are now known: extend the prefix-hash chain over
            # any decode-completed blocks so follow-up prompts that extend
            # this output hit the cache.
            with self._lock:
                for seq in emitted_seqs:
                    self.kv_mgr.register_decode_blocks(
                        seq.req.request_id, seq.req.all_token_ids
                    )

    def _flush_spec_burst(self, pending, arrays, sample) -> None:
        """Emit a verify burst: accept the longest draft prefix whose
        tokens match what plain decode would have sampled, then emit the
        SAMPLES themselves — the accepted drafts ARE those samples, and
        the first mismatch position doubles as the corrected/bonus token
        (so every verify burst makes at least one step of progress).
        Rolls back the worst-case KV tail appended for rejected
        positions and feeds the per-request adaptive latch."""
        cfg = self.config
        emitted_seqs = []
        rollbacks = []
        draft_rollbacks = []
        for seq in pending["active"]:
            r = seq.req
            allow = pending["allows"].get(r.request_id, 1)
            draft = pending["drafts"].get(r.request_id, [])
            if self.scheduler.slots[seq.slot] is not seq:
                # Finished/aborted/preempted between dispatch and flush:
                # its KV was freed wholesale, nothing to roll back.
                continue
            j = accepted_prefix_len(draft, arrays[0][seq.slot])
            # finishing mid-burst (EOS / stop / max_tokens) ends it early
            emitted = self._emit_seq(seq, j + 1, arrays, sample)
            r.scheduled_steps += emitted
            self.spec_proposed_tokens_total += len(draft)
            self.spec_accepted_tokens_total += j
            source = r.spec.source if r.spec is not None else "ngram"
            self.spec_proposed_by_source[source] = (
                self.spec_proposed_by_source.get(source, 0) + len(draft))
            self.spec_accepted_by_source[source] = (
                self.spec_accepted_by_source.get(source, 0) + j)
            if r.spec is not None and r.spec.judge(
                    len(draft), j, cfg.speculative_accept_window,
                    cfg.speculative_accept_threshold):
                self.spec_disabled_requests_total += 1
            rollbacks.append((r.request_id, allow - emitted))
            if self._draft is not None:
                # The drafter fed len(draft)-1 draft tokens past the
                # request's pre-burst length n; keep the accepted ones
                # (all fed drafts when the whole draft landed) and roll
                # the rejected positions' pages back.
                n_before = len(r.all_token_ids) - emitted
                draft_rollbacks.append(
                    (r.request_id,
                     n_before + min(j, max(len(draft) - 1, 0))))
            if emitted and self.scheduler.slots[seq.slot] is seq:
                emitted_seqs.append(seq)
        with self._lock:
            for rid, n in rollbacks:
                # Stale device pages past the accepted tail are fine:
                # each decode/verify step writes its own position before
                # any attention can read it.
                self.kv_mgr.rollback_tokens(rid, n)
            for rid, keep in draft_rollbacks:
                self._draft.truncate(rid, keep)
            for seq in emitted_seqs:
                self.kv_mgr.register_decode_blocks(
                    seq.req.request_id, seq.req.all_token_ids
                )

    def _fill_stop_row(self, row_ids, row_valid,
                       stop_token_ids: "list | None") -> None:
        """Fill one slot's stop_token_ids mask arrays (masked alongside
        EOS while min_tokens is unmet)."""
        if not stop_token_ids:
            return
        vocab = self.model_config.vocab_size
        ids = [t for t in stop_token_ids if 0 <= t < vocab][:MAX_STOP_IDS]
        for j, tid in enumerate(ids):
            row_ids[j] = tid
            row_valid[j] = 1.0

    def _resume_bias(self, req: EngineRequest) -> "dict | None":
        """Effective logit_bias for the prefill program: the request's own
        bias, plus — on preemption-resume with penalties active — the
        penalty terms for the most-frequent prior output tokens (top
        MAX_LOGIT_BIAS approximation; the burst program applies exact
        counts from the next step on)."""
        bias = dict(req.sampling.logit_bias or {})
        pres = req.sampling.presence_penalty
        freq = req.sampling.frequency_penalty
        if req.output_token_ids and (pres or freq):
            from collections import Counter

            top = Counter(req.output_token_ids).most_common(MAX_LOGIT_BIAS)
            for tid, cnt in top:
                bias[tid] = bias.get(tid, 0.0) - freq * cnt - pres
        return bias or None

    def _fill_bias_row(self, row_ids, row_vals,
                       logit_bias: "dict | None") -> None:
        """Fill one slot's sparse logit_bias arrays (deterministic order,
        excess entries dropped; padding rows add 0.0 to token 0)."""
        if not logit_bias:
            return
        vocab = self.model_config.vocab_size
        # Filter BEFORE capping so out-of-vocab keys can't crowd out
        # valid biases.
        items = sorted(
            (tid, val) for tid, val in logit_bias.items()
            if 0 <= tid < vocab
        )[:MAX_LOGIT_BIAS]
        for j, (tid, val) in enumerate(items):
            row_ids[j] = tid
            row_vals[j] = val

    def _sampling_for(self, r: EngineRequest):
        """Per-request sampling knobs (shared by prefill and burst decode):
        (temperature, clamped top_k, top_p, seed)."""
        seed = (r.sampling.seed if r.sampling.seed is not None
                else hash(r.request_id) % (2**31))
        return (r.sampling.temperature,
                min(r.sampling.top_k, self.config.max_top_k),
                r.sampling.top_p, seed)

    def _emit_token(self, seq: RunningSeq, token: int,
                    lp: Optional[dict] = None) -> None:
        """Emit one generated token to its request's callback (inside a
        burst the callback holds it for the sequence's one delivery:
        ``_emit_seq``). When the request asked for
        logprobs, the callback payload is ``(token, lp)`` with
        ``lp = {"logprob": float, "top": [(token_id, logprob), ...]}``;
        otherwise the bare int (the common path stays allocation-free).
        Nothing here reads a clock: this runs a thousand times a burst,
        and the request's trace is stamped by the caller, once."""
        req = seq.req
        req.output_token_ids.append(token)
        if req.structured is not None and not req.structured.advance(token):
            # The emitted token left the grammar — the mask makes this
            # unreachable, so any hit is a masking bug worth a loud
            # counter. The request latches mask-off (dead) and finishes
            # unconstrained rather than sampling from an all -1e30 row.
            self.structured_violations_total += 1
            logger.warning(
                "Structured request %s emitted token %d outside its "
                "grammar", req.request_id, token)
        finish = None
        eos = getattr(self.tokenizer, "eos_token_id", None)
        n_out = len(req.output_token_ids)
        min_ok = n_out >= req.sampling.min_tokens
        if (not req.sampling.ignore_eos) and eos is not None \
                and token == eos and min_ok:
            finish = "stop"
        elif req.sampling.stop_token_ids and min_ok \
                and token in req.sampling.stop_token_ids:
            finish = "stop"
        elif n_out >= req.sampling.max_tokens:
            finish = "length"
        elif len(req.all_token_ids) >= self.config.max_model_len:
            finish = "length"
        payload = token if lp is None else (token, lp)
        req.on_token(payload, None)
        if finish is not None:
            st = req.structured
            if st is not None and not st.dead and not st.accepting:
                # Finished (length cap / stop sequence) with the
                # automaton mid-structure: the stream is not a complete
                # member of the grammar.
                self.structured_violations_total += 1
            # Counted before the client is told: ``finish`` ends the
            # stream, and whoever saw it end may read the counter next
            # (chip_smoke's count of finished requests read one short
            # under load when this line came after).
            self.requests_finished_total += 1
            with self._lock:
                self.scheduler.finish(seq, finish)
