"""OpenAI-compatible HTTP server wrapping :class:`EngineCore`.

This is the TPU-native replacement for the ``vllm serve`` process the
reference launches in every engine pod
(``helm/templates/deployment-vllm-multi.yaml:108-199``,
``operator/internal/controller/vllmruntime_controller.go:228-286``). The
surface is exactly what the stack's router and operator need:

- OpenAI API: ``/v1/chat/completions``, ``/v1/completions``,
  ``/v1/embeddings``, ``/v1/score``, ``/v1/rerank``, ``/v1/models``,
  ``/tokenize``, ``/detokenize``
- lifecycle: ``/health``, ``/sleep``, ``/wake_up``, ``/is_sleeping``
  (sleep mode semantics of vLLM ``--enable-sleep-mode``,
  ``service_discovery.py:443-460``)
- LoRA: ``/v1/load_lora_adapter``, ``/v1/unload_lora_adapter``,
  ``/v1/lora_adapters`` (vLLM API used by the reference's LoraAdapter
  controller, ``loraadapter_controller.go:582-610``)
- ``/metrics`` in the exact ``vllm:*`` Prometheus exposition the router's
  scraper parses (``engine_stats.py:63-76``) — with TPU HBM KV usage
  exported under ``vllm:gpu_cache_usage_perc`` for dashboard compatibility
  and additionally as ``tpu:hbm_kv_usage_perc``.
- KV transfer (disaggregated prefill): ``/kv/extract``, ``/kv/inject``
  handled by :mod:`production_stack_tpu.kv.transfer` when enabled.

Token flow: EngineCore emits tokens on its engine thread; each request owns
an asyncio queue bridged with ``call_soon_threadsafe``; SSE chunks stream as
tokens land (true token-level streaming, TTFT = first sampled token).
"""

from __future__ import annotations

import argparse
import asyncio
import os
import itertools
import json
import tempfile
import threading
import time
import uuid
from collections import OrderedDict
from typing import List, Optional

from aiohttp import web

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core import EngineCore
from production_stack_tpu.engine.sampling import MAX_LOGIT_BIAS, SamplingParams
from production_stack_tpu.structured.api import compile_char_dfa
from production_stack_tpu.engine.scheduler import parse_priority
from production_stack_tpu.engine.tokenizer import IncrementalDetokenizer
from production_stack_tpu.engine.tools import (
    parse_tool_calls,
    render_tools_preamble,
    tool_names,
)
from production_stack_tpu.obs.trace import StageClock, TraceRecorder
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

# Hostile-input bound for request bodies: large enough for any real
# OpenAI-API payload (long prompts, logit_bias maps, KV pull manifests),
# small enough that a malicious body cannot balloon worker memory.
MAX_BODY_BYTES = 32 << 20


def _bad_request(message: str) -> web.HTTPBadRequest:
    return web.HTTPBadRequest(
        text=json.dumps({"error": {"message": message,
                                   "type": "BadRequestError"}}),
        content_type="application/json")


async def _json_body(request: web.Request) -> dict:
    """Read and parse a JSON request body defensively.

    Hostile input — truncated/garbage JSON, non-UTF8 bytes, nesting
    bombs deep enough to overflow the parser's recursion, or a
    non-object top level — maps to a clean 4xx.  A bare
    ``await request.json()`` turns those into aiohttp 500s
    (RecursionError/UnicodeDecodeError escape the handler) and, for
    pathological inputs, a wedged worker.  An empty body parses as {}
    so body-less control POSTs (/sleep, /drain) keep working.
    """
    raw = await request.read()
    if len(raw) > MAX_BODY_BYTES:
        # Backstop for transports that bypass client_max_size (chunked
        # bodies with no Content-Length on some aiohttp versions).
        raise web.HTTPRequestEntityTooLarge(
            max_size=MAX_BODY_BYTES, actual_size=len(raw),
            text=json.dumps({"error": {"message": "request body too large",
                                       "type": "BadRequestError"}}),
            content_type="application/json")
    try:
        body = json.loads(raw) if raw else {}
    except (ValueError, RecursionError):
        raise _bad_request("request body is not parsable JSON") from None
    if not isinstance(body, dict):
        raise _bad_request("request body must be a JSON object")
    return body


class _TokenStream:
    """Bridges the engine thread's deliveries into an asyncio queue: the
    request's callback (``__call__``), which takes what a burst gives the
    sequence as one list (``on_burst``: one hand-over to the loop, however
    many tokens) and yields it a ``(token, finish)`` pair at a time."""

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self.loop = loop
        self.queue: asyncio.Queue = asyncio.Queue()

    def on_burst(self, items: "List[tuple]") -> None:
        self.loop.call_soon_threadsafe(self.queue.put_nowait, items)

    def __call__(self, token_id: Optional[int], finish: Optional[str]) -> None:
        self.on_burst([(token_id, finish)])

    async def __aiter__(self):
        while True:
            for token_id, finish in await self.queue.get():
                yield token_id, finish
                if finish is not None:
                    return


class EngineServer:
    def __init__(self, config: EngineConfig,
                 served_model_names: Optional[List[str]] = None,
                 warmup: bool = False,
                 kv_controller_url: Optional[str] = None,
                 instance_id: Optional[str] = None,
                 advertise_url: Optional[str] = None,
                 api_key: Optional[str] = None,
                 kv_heartbeat_interval: float = 10.0,
                 kv_resync_interval: float = 60.0,
                 kv_pull_max_concurrency: int = 8,
                 trace_buffer: int = 512,
                 slow_trace_threshold_s: float = 0.0,
                 trace_export: Optional[str] = None,
                 trace_sample_rate: float = 1.0,
                 slow_trace_log_interval_s: float = 0.0,
                 profile_dir: Optional[str] = None,
                 loop_monitor: bool = False,
                 loop_stall_threshold_ms: float = 100.0,
                 devices: Optional[list] = None):
        # ``devices``: the JAX devices this engine's mesh is built from.
        # None takes the first tp*dp*pp of jax.devices() — so two
        # engines in one process BOTH land on device 0 unless each is
        # handed its own.
        # Serving-surface auth (reference tutorial 11 "secure vLLM
        # serve": VLLM_API_KEY): /v1/* requests must carry
        # `Authorization: Bearer <key>`; the intra-stack control plane
        # (probes, /metrics, /kv/*, sleep admin) stays open — see
        # utils/auth.py. None disables.
        from production_stack_tpu.utils.auth import resolve_api_keys

        self.api_keys = resolve_api_keys(api_key)
        self.api_key = self.api_keys[0] if self.api_keys else None
        self.config = config
        self.core = EngineCore(config, devices=devices)
        if warmup:
            self.core.warmup()
        self.core.start()
        self.served_models = served_model_names or [config.model]
        self.start_time = time.time()
        # KV-aware routing: this engine reports its prefix admissions to
        # the router's KV controller (the reference's LMCache worker ->
        # controller channel, deployment-vllm-multi.yaml:324-339).
        self.kv_controller_url = (
            kv_controller_url.rstrip("/") if kv_controller_url else None
        )
        self.instance_id = instance_id or f"engine-{uuid.uuid4().hex[:8]}"
        self.advertise_url = advertise_url
        self._kv_registered = False
        # Crash consistency (leases + anti-entropy): each PROCESS gets a
        # fresh generation id, so a same-URL restart registers as a new
        # incarnation and the controller atomically sweeps the dead one's
        # claims. The heartbeat task renews the lease; the resync task
        # heals drift from timeout-swallowed admit/evict reports.
        self.generation = uuid.uuid4().hex
        self.kv_heartbeat_interval = float(kv_heartbeat_interval)
        self.kv_resync_interval = float(kv_resync_interval)
        self._kv_tasks: "list[asyncio.Task]" = []
        # /kv/pull admission: at most this many concurrent transfers are
        # served before excess pulls get 503 + Retry-After (the router
        # degrades to recompute). The counter doubles as the
        # tpu:kv_pull_inflight gauge; single-threaded event loop, so the
        # check-then-increment below is race-free.
        self.kv_pull_max_concurrency = max(1, int(kv_pull_max_concurrency))
        self._pull_inflight = 0
        self.kv_pull_rejected_total = 0
        # Admission registry for eviction reporting: maps this engine's
        # page chain-hashes back to the controller's text-chunk hashes so
        # a dropped chain is reported with /kv/evict instead of lingering
        # as a stale routable claim until the TTL (the exactness gap
        # PARITY.md used to carry). Bounded; guarded by _adm_lock
        # (admissions land on the event loop, evictions fire on the
        # engine thread).
        self._adm_lock = threading.Lock()
        self._admissions: "OrderedDict[int, tuple]" = OrderedDict()
        self._block_admissions: "dict[int, set]" = {}
        self._adm_counter = itertools.count(1)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        # Disaggregated-prefill transfer counters (exported via /metrics).
        self.kv_transfer_tx_bytes = 0
        self.kv_transfer_rx_bytes = 0
        self.kv_transfer_rx_seconds = 0.0
        self.kv_transfer_pulls = 0
        # Device-pipe (jax.experimental.transfer) counters + lazy server.
        self.kv_transfer_device_pulls = 0
        self.kv_transfer_device_bytes = 0
        self.kv_transfer_device_seconds = 0.0
        # Fleet pulls answered from the shared L3 tier: the peer missed
        # but the prefix is resident in the remote cache server, so
        # prefill restores it instead of recomputing.
        self.l3_pull_hits = 0
        self.l3_pull_blocks = 0
        # Per-adapter request metering (tpu:lora_requests_total{adapter}).
        # Only adapter-addressed requests land here, so the base-model
        # /metrics exposition is unchanged until an adapter serves.
        self.lora_request_counts: "dict[str, int]" = {}
        self._device_pipe = None
        self._device_pipe_failed = False
        # Per-request stage tracing (queue/prefill/decode spans recorded
        # after each request; served at /debug/traces, rolled up into the
        # tpu:*_time_seconds exposition).
        self.trace_recorder = TraceRecorder(
            "tpu-stack-engine",
            capacity=trace_buffer,
            slow_threshold_s=slow_trace_threshold_s,
            export=trace_export,
            sample_rate=trace_sample_rate,
            slow_log_interval_s=slow_trace_log_interval_s,
        )
        # Event-loop introspection (--loop-monitor): scheduling-lag
        # ring + blocking-call watchdog, started with the server's loop
        # in make_app's on_startup. None when off — the flag-off
        # /metrics exposition and hot path are byte-identical.
        self.loop_monitor = None
        if loop_monitor:
            from production_stack_tpu.obs.looplag import LoopMonitor

            self.loop_monitor = LoopMonitor(
                "tpu-stack-engine",
                stall_threshold_s=float(loop_stall_threshold_ms) / 1000.0,
            )
        # Programmatic profiler capture (POST /debug/profile): one
        # jax.profiler trace at a time, written under profile_dir and
        # served back at /debug/profile/artifacts/. Privileged (bearer
        # key) like the other destructive control-plane endpoints.
        self.profile_dir = profile_dir or os.path.join(
            tempfile.gettempdir(), f"tpu-stack-profiles-{os.getpid()}")
        self._profile_lock = threading.Lock()
        self._profile_runs = 0
        # Last HBM headroom sample: the gauge is exported even when the
        # current stats() sample is missing, so dashboards and alerts
        # never see the series disappear.
        self._last_hbm_headroom = 0
        # Graceful drain (POST /drain, wired as the helm preStop hook):
        # once draining, new inference requests get 503 + Retry-After
        # (the router's failover sends them elsewhere), /health flips to
        # 503 so readiness probes and the router's health sweep pull
        # this replica, and in-flight requests run to completion —
        # tracked by the middleware counter below.
        self.draining = False
        self._inflight = 0

    async def start_kv_reporting(self, own_url: str) -> None:
        """Register with the router's KV controller (retried lazily on
        each admission until it succeeds) and hook eviction reporting."""
        self._loop = asyncio.get_running_loop()
        # The way the tokens take (``_TokenStream.on_burst``): the core
        # posts two markers a burst along it to time the hand-over.
        self.core.post_to_server_loop = self._loop.call_soon_threadsafe
        # Hooked unconditionally (no-ops on an empty registry): the
        # controller URL can be wired after startup.
        self.core.prefix_evict_listener = self._on_prefix_evict
        if self.kv_controller_url is None:
            return
        if self.advertise_url is None:
            self.advertise_url = own_url
        await self._kv_register()
        if self.kv_heartbeat_interval > 0:
            self._kv_tasks.append(
                self._loop.create_task(self._kv_heartbeat_loop()))
        if self.kv_resync_interval > 0:
            self._kv_tasks.append(
                self._loop.create_task(self._kv_resync_loop()))

    async def stop_kv_reporting(self) -> None:
        """Cancel the heartbeat/resync background tasks. Called on app
        cleanup AND on drain: a draining engine that kept beating (or
        whose heartbeat re-registered after the drain's /kv/deregister)
        would pull routable claims back onto a disappearing replica."""
        tasks, self._kv_tasks = self._kv_tasks, []
        for t in tasks:
            t.cancel()
        for t in tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass

    async def _kv_register(self) -> bool:
        import aiohttp

        try:
            async with aiohttp.ClientSession(headers=self._auth_headers()) as s:
                async with s.post(
                    f"{self.kv_controller_url}/kv/register",
                    json={"instance_id": self.instance_id,
                          "url": self.advertise_url,
                          "generation": self.generation,
                          "heartbeat_interval": self.kv_heartbeat_interval},
                    timeout=aiohttp.ClientTimeout(total=5),
                ) as resp:
                    self._kv_registered = resp.status == 200
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            logger.debug("KV controller register failed: %s", e)
            self._kv_registered = False
        return self._kv_registered

    async def _kv_heartbeat_loop(self) -> None:
        """Lease renewal: a controller that stops hearing these beats
        expires this instance after ``--kv-lease-misses`` intervals and
        sweeps its claims, so a kill -9'd replica stops being a pull
        target within one lease window."""
        import aiohttp

        while True:
            await asyncio.sleep(self.kv_heartbeat_interval)
            body: dict = {}
            try:
                async with aiohttp.ClientSession(
                        headers=self._auth_headers()) as s:
                    async with s.post(
                        f"{self.kv_controller_url}/kv/heartbeat",
                        json={"instance_id": self.instance_id,
                              "generation": self.generation,
                              "heartbeat_interval": self.kv_heartbeat_interval,
                              "url": self.advertise_url},
                        timeout=aiohttp.ClientTimeout(total=5),
                    ) as resp:
                        if resp.status == 200:
                            body = await resp.json()
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                logger.debug("KV heartbeat failed: %s", e)
                continue
            if not body.get("known"):
                # Controller restarted or superseded this record:
                # re-register, then push authoritative state.
                if await self._kv_register():
                    await self._kv_resync(force=True)
            elif body.get("revived"):
                # Our lease HAD expired (process paused, not dead): the
                # claims were swept — restore them from the registry.
                logger.info("KV lease revived; resyncing swept claims")
                await self._kv_resync(force=True)

    async def _kv_resync_loop(self) -> None:
        while True:
            await asyncio.sleep(self.kv_resync_interval)
            try:
                await self._kv_resync()
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 - resync is best-effort
                logger.debug("KV resync failed: %s", e)

    def _admitted_paths(self) -> "list[list[int]]":
        """Root-anchored chunk-hash paths this engine still serves — the
        engine-side truth the anti-entropy digest is computed from."""
        paths: "list[list[int]]" = []
        seen: "set[tuple]" = set()
        with self._adm_lock:
            for chunks, _blocks in self._admissions.values():
                t = tuple(int(h) for h in chunks)
                if t and t not in seen:
                    seen.add(t)
                    paths.append(list(t))
        return paths

    async def _kv_resync(self, force: bool = False) -> None:
        """Anti-entropy round: compare claim digests with the controller
        and, on mismatch (or ``force``), replace our claims wholesale.
        Heals admit/evict reports lost to swallowed timeouts."""
        import aiohttp

        from production_stack_tpu.kv.controller import claim_digest, path_keys

        paths = self._admitted_paths()
        keys: "set[int]" = set()
        for p in paths:
            keys.update(path_keys(p))
        count, xor = claim_digest(keys)
        try:
            async with aiohttp.ClientSession(headers=self._auth_headers()) as s:
                if not force:
                    check: dict = {}
                    async with s.post(
                        f"{self.kv_controller_url}/kv/resync",
                        json={"instance_id": self.instance_id,
                              "count": count, "xor": xor},
                        timeout=aiohttp.ClientTimeout(total=5),
                    ) as resp:
                        if resp.status == 200:
                            check = await resp.json()
                    if check.get("match"):
                        return
                    if not check.get("known") and not await self._kv_register():
                        return
                async with s.post(
                    f"{self.kv_controller_url}/kv/resync_state",
                    json={"instance_id": self.instance_id, "paths": paths},
                    timeout=aiohttp.ClientTimeout(total=10),
                ) as resp:
                    if resp.status == 200:
                        body = await resp.json()
                        if body.get("swept"):
                            logger.info(
                                "KV resync: swept %s drifted claims, %s "
                                "claim nodes reasserted",
                                body.get("swept"), body.get("claims", 0))
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            logger.debug("KV resync round failed: %s", e)

    def _track_admission(self, text: str, ids: List[int],
                         adapter: str = "",
                         offsets: Optional[List[int]] = None) -> None:
        """Record the mapping between this prompt's page chain-hashes and
        its controller text-chunk hashes, so evictions can be reported.
        The char->token alignment uses the tokenizer's EXACT per-token
        char offsets (byte positions for the byte tokenizer, the fast
        tokenizer's offset mapping for BPE), so the controller evicts
        precisely the chunks the dropped chain covered — proportional
        mapping over-evicted kvaware-routable prefixes under BPE."""
        from production_stack_tpu.engine.kvcache import BlockAllocator
        from production_stack_tpu.kv.controller import (
            CHUNK_SIZE,
            chunk_hashes,
        )

        # Adapter requests salt the controller-side chunk hashes (the
        # page chains are already adapter-scoped via chain_root), so the
        # eviction paths reported from here match the salted admissions.
        chunks = chunk_hashes(text, salt=adapter or None)
        n = len(ids)
        if not chunks or n == 0:
            return
        bs = self.core.config.block_size
        parent = self.core.kv_mgr.chain_root(adapter)
        if offsets is None or len(offsets) != n:
            offsets = self.core.tokenizer.token_char_offsets(text, ids)
        blocks = []
        i = 0
        while i + bs <= n:
            parent = BlockAllocator.chain_hash(parent, tuple(ids[i : i + bs]))
            chunk_start = min(offsets[i] // CHUNK_SIZE, len(chunks) - 1)
            blocks.append((parent, chunk_start))
            i += bs
        if not blocks:
            return
        aid = next(self._adm_counter)
        with self._adm_lock:
            self._admissions[aid] = (chunks, blocks)
            for bh, _ in blocks:
                self._block_admissions.setdefault(bh, set()).add(aid)
            while len(self._admissions) > 1024:
                old_aid, (_, old_blocks) = self._admissions.popitem(False)
                for bh, _ in old_blocks:
                    members = self._block_admissions.get(bh)
                    if members is not None:
                        members.discard(old_aid)
                        if not members:
                            del self._block_admissions[bh]

    def _on_prefix_evict(self, prefix_hash: int, bid: int) -> None:
        """Engine-thread allocator hook: a cached chain block was recycled
        — tell the controller the chunks from that block onward are no
        longer served here (kills the TTL staleness window).

        The controller's evict takes a ROOT-ANCHORED chunk path and sweeps
        the subtree below its last hash, so each affected admission
        contributes ``chunks[:cut+1]`` (the path down to the first dead
        chunk), not a bag of suffix hashes."""
        paths: "list[list[int]]" = []
        seen_paths: "set[tuple]" = set()
        with self._adm_lock:
            aids = self._block_admissions.get(prefix_hash)
            if not aids:
                return
            for aid in list(aids):
                entry = self._admissions.pop(aid, None)
                if entry is None:
                    continue
                chunks, blocks = entry
                cut = next((cs for bh, cs in blocks
                            if bh == prefix_hash), None)
                if cut is not None:
                    path = tuple(int(h) for h in chunks[: cut + 1])
                    if path and path not in seen_paths:
                        seen_paths.add(path)
                        paths.append(list(path))
                for bh, _ in blocks:
                    members = self._block_admissions.get(bh)
                    if members is not None:
                        members.discard(aid)
                        if not members:
                            del self._block_admissions[bh]
        if not paths or self._loop is None or self.kv_controller_url is None:
            return

        # This listener only fires when NO offload tier is configured
        # (core._dispatch_evict short-circuits into the spill path and
        # deliberately keeps the controller claims otherwise — the
        # prefix is still servable here via contains()/restore), so the
        # evicted chunks are simply gone from this replica: never report
        # them as spilled. The /kv/evict protocol's ``spilled=true`` is
        # reserved for callers that have CONFIRMED the blocks reached
        # the L3 — an optimistic report would send fleet pulls on
        # round-trips that can only end in a miss.

        async def _send():
            import aiohttp

            try:
                async with aiohttp.ClientSession(headers=self._auth_headers()) as s:
                    await s.post(
                        f"{self.kv_controller_url}/kv/evict",
                        json={"instance_id": self.instance_id,
                              "paths": paths},
                        timeout=aiohttp.ClientTimeout(total=5),
                    )
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                logger.debug("KV evict report failed: %s", e)

        try:
            self._loop.call_soon_threadsafe(
                lambda: self._loop.create_task(_send()))
        except RuntimeError:
            pass  # loop closed (shutdown)

    def _encode_prompt(self, text: str):
        """(ids, per-token char offsets | None): one tokenizer pass that
        also yields the offsets the admission tracker needs (only
        requested when a KV controller is wired)."""
        tok = self.core.tokenizer
        if self.kv_controller_url is not None and hasattr(
                tok, "encode_with_offsets"):
            return tok.encode_with_offsets(text)
        return tok.encode(text), None

    def _report_kv_admission(self, prompt_text: str,
                             prompt_ids: Optional[List[int]] = None,
                             adapter: str = "",
                             offsets: Optional[List[int]] = None) -> None:
        """Fire-and-forget admission report (prompt text chunk hashes)."""
        if self.kv_controller_url is None or not prompt_text:
            return
        if prompt_ids:
            # Chain hashing over thousands of tokens: keep it off the
            # event loop (registry is lock-guarded; an eviction racing
            # ahead of its admission is benign — TTL backstops).
            asyncio.get_running_loop().run_in_executor(
                None, self._track_admission, prompt_text, list(prompt_ids),
                adapter, offsets)

        async def _send():
            import aiohttp

            if not self._kv_registered and not await self._kv_register():
                return
            try:
                async with aiohttp.ClientSession(headers=self._auth_headers()) as s:
                    body = {"instance_id": self.instance_id,
                            "text": prompt_text}
                    if adapter:
                        body["salt"] = adapter
                    await s.post(
                        f"{self.kv_controller_url}/kv/admit",
                        json=body,
                        timeout=aiohttp.ClientTimeout(total=5),
                    )
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                logger.debug("KV admit report failed: %s", e)

        asyncio.get_running_loop().create_task(_send())

    # ------------------------------------------------------------------ #
    # app assembly
    # ------------------------------------------------------------------ #
    def _auth_headers(self) -> dict:
        """Default headers for this engine's OUTBOUND calls (router KV
        controller, peer engines in disagg): under a shared deployment
        API key every tier authenticates with the same credential."""
        if self.api_key:
            return {"Authorization": f"Bearer {self.api_key}"}
        return {}

    @web.middleware
    async def _auth_middleware(self, request: web.Request, handler):
        from production_stack_tpu.utils import auth

        # Engines gate the inference surface AND /kv/* — /kv/extract
        # returns raw cache pages (exfiltration surface), and every
        # legitimate in-stack caller (router controller reports, peer
        # engines in disagg) attaches the shared deployment key via
        # _auth_headers(). The router's own /kv controller endpoints stay
        # open so an edge-only-key topology (router key, keyless
        # engines) keeps its kvaware reporting channel.
        gated = (auth.is_gated(request.path)
                 or auth.is_privileged(request.path)
                 or request.path.startswith("/kv/"))
        if self.api_keys and gated and not auth.check_bearer(
                request.headers.get("Authorization"), self.api_keys):
            return auth.unauthorized_response()
        if not auth.is_gated(request.path):
            return await handler(request)
        # Inference surface: refuse new admissions while draining
        # (in-flight requests — already counted — run to completion; the
        # router's pre-first-byte failover reroutes rejected ones), and
        # count in-flight requests so /drain knows when the replica is
        # quiescent. /kv/*, /health, /metrics stay open throughout.
        if self.draining:
            return web.json_response(
                {"error": {"message": "engine is draining",
                           "type": "ServiceUnavailable"}},
                status=503, headers={"Retry-After": "1"})
        self._inflight += 1
        try:
            return await handler(request)
        finally:
            self._inflight -= 1

    def make_app(self) -> web.Application:
        app = web.Application(middlewares=[self._auth_middleware],
                              client_max_size=MAX_BODY_BYTES)
        r = app.router
        r.add_get("/v1/models", self.handle_models)
        r.add_post("/v1/chat/completions", self.handle_chat)
        r.add_post("/v1/completions", self.handle_completion)
        r.add_post("/v1/embeddings", self.handle_embeddings)
        r.add_post("/v1/score", self.handle_score)
        r.add_post("/score", self.handle_score)
        r.add_post("/v1/rerank", self.handle_rerank)
        r.add_post("/rerank", self.handle_rerank)
        r.add_post("/tokenize", self.handle_tokenize)
        r.add_post("/detokenize", self.handle_detokenize)
        r.add_get("/metrics", self.handle_metrics)
        r.add_get("/health", self.handle_health)
        r.add_get("/version", self.handle_version)
        r.add_post("/drain", self.handle_drain)
        r.add_post("/sleep", self.handle_sleep)
        r.add_post("/wake_up", self.handle_wake)
        r.add_get("/is_sleeping", self.handle_is_sleeping)
        r.add_post("/v1/load_lora_adapter", self.handle_load_lora)
        r.add_post("/v1/unload_lora_adapter", self.handle_unload_lora)
        r.add_get("/v1/lora_adapters", self.handle_list_lora)
        # KV transfer (disaggregated prefill / cross-engine KV sharing).
        # Its wire formats carry keys and values of one shape: a family
        # whose blocks hold a state beside them (Family.block_state), or
        # whose two sides are of their own shapes (Family.page_sides),
        # answers 501 on every route rather than hand out half of it.
        for path, handler in (
                ("/kv/extract", self.handle_kv_extract),
                ("/kv/inject", self.handle_kv_inject),
                ("/kv/pull", self.handle_kv_pull),
                ("/kv/prepare_pull", self.handle_kv_prepare_pull),
                ("/kv/release", self.handle_kv_release)):
            r.add_post(path, self._kv_transfer_refused
                       if self.core.block_state_shape
                       or self.core.own_page_sides else handler)
        r.add_post("/v1/audio/transcriptions", self.handle_transcriptions)
        # Flight recorder (engine-side stage spans per request).
        from production_stack_tpu.obs.debug import (
            add_debug_routes,
            add_step_debug_routes,
        )

        add_debug_routes(r, self.trace_recorder)
        # Step flight recorder (per-step kind/wall/roofline records),
        # with the live resident/offload page-occupancy split folded in.
        if self.core.step_recorder is not None:
            def _occupancy_stats() -> dict:
                alloc = self.core.kv_mgr.allocator
                return {"kv_page_occupancy": {
                    "resident": self.core.num_blocks - alloc.num_free,
                    "offload": (self.core.offload.stats()["blocks"]
                                if self.core.offload else 0),
                }}

            add_step_debug_routes(r, self.core.step_recorder,
                                  extra_stats=_occupancy_stats)
        # Programmatic profiler capture + served artifacts (privileged).
        r.add_post("/debug/profile", self.handle_debug_profile)
        r.add_get("/debug/profile/artifacts", self.handle_profile_artifacts)
        r.add_get("/debug/profile/artifacts/{name:.+}",
                  self.handle_profile_artifact_file)
        # Event-loop health (--loop-monitor): the monitor must start on
        # the server's own loop, so it hooks app startup/cleanup.
        if self.loop_monitor is not None:
            from production_stack_tpu.obs.debug import add_loop_debug_routes

            add_loop_debug_routes(r, self.loop_monitor)

            async def _start_loop_monitor(app: web.Application):
                self.loop_monitor.start()

            async def _stop_loop_monitor(app: web.Application):
                self.loop_monitor.stop()

            app.on_startup.append(_start_loop_monitor)
            app.on_cleanup.append(_stop_loop_monitor)
        app["engine_server"] = self
        return app

    # ------------------------------------------------------------------ #
    # helpers
    # ------------------------------------------------------------------ #
    def _resolve_adapter(self, model: str) -> Optional[str]:
        """A request for a loaded adapter name selects that LoRA slot."""
        return model if model in self.core.lora_slots else None

    def _check_model(self, model: str) -> bool:
        return (
            model in self.served_models
            or model == self.config.model
            or model in self.core.lora_slots
        )

    async def _generate(self, prompt_ids: List[int], sampling: SamplingParams,
                        request_id: str, adapter: Optional[str],
                        trace: Optional[StageClock] = None,
                        priority: int = 0):
        stream = _TokenStream(asyncio.get_running_loop())
        self.core.add_request(
            request_id, prompt_ids, sampling, stream,
            adapter_name=adapter, trace=trace, priority=priority,
        )
        return stream

    @staticmethod
    def _split_token(payload):
        """Engine emission -> (token_id, lp|None): logprob-requesting
        streams carry (token, {"logprob", "top"}) tuples."""
        if isinstance(payload, tuple):
            return payload
        return payload, None

    def _lp_entry(self, token_id: int, lp: dict) -> dict:
        """One OpenAI chat-logprobs content entry."""
        text = self.core.tokenizer.decode([token_id])
        entry = {"token": text, "logprob": lp["logprob"],
                 "bytes": list(text.encode())}
        tops = []
        for tid, tlp in lp["top"]:
            ttext = self.core.tokenizer.decode([tid])
            tops.append({"token": ttext, "logprob": tlp,
                         "bytes": list(ttext.encode())})
        entry["top_logprobs"] = tops
        return entry

    @staticmethod
    def _apply_stop(text_so_far: str, delta: str, stop: Optional[List[str]]):
        """Returns (emit_delta, stopped). Stop strings end the stream and are
        not emitted."""
        if not stop:
            return delta, False
        combined = text_so_far + delta
        for s in stop:
            idx = combined.find(s)
            if idx >= 0:
                return combined[len(text_so_far):idx], True
        return delta, False

    # ------------------------------------------------------------------ #
    # OpenAI handlers
    # ------------------------------------------------------------------ #
    async def handle_models(self, request: web.Request) -> web.Response:
        now = int(self.start_time)
        data = [
            {"id": m, "object": "model", "created": now,
             "owned_by": "production-stack-tpu"}
            for m in self.served_models
        ] + [
            {"id": name, "object": "model", "created": now,
             "owned_by": "production-stack-tpu", "parent": self.config.model}
            for name in self.core.lora_slots
        ]
        return web.json_response({"object": "list", "data": data})

    async def handle_chat(self, request: web.Request) -> web.StreamResponse:
        body = await _json_body(request)
        model = body.get("model", self.config.model)
        if not self._check_model(model):
            return web.json_response(
                {"error": {"message": f"model {model!r} not found",
                           "type": "NotFoundError"}}, status=404)
        if self.core.is_sleeping:
            return web.json_response(
                {"error": {"message": "engine is sleeping",
                           "type": "ServiceUnavailable"}}, status=503)
        messages = body.get("messages", [])
        tools = body.get("tools") or []
        if tools and body.get("tool_choice") != "none":
            # Fold the function schemas + <tool_call> output contract into
            # the system context (hermes convention; vLLM does this via
            # per-model parser plugins, ref tutorial 13). tool_choice
            # "none" skips both the preamble and output parsing.
            preamble = render_tools_preamble(
                tools, body.get("tool_choice", "auto"))
            messages = (
                [{"role": "system", "content": preamble}] + list(messages))
        prompt = self.core.tokenizer.apply_chat_template(messages)
        prompt_ids, offs = self._encode_prompt(prompt)
        adapter = self._resolve_adapter(model)
        self._report_kv_admission(prompt, prompt_ids, adapter or "",
                                  offsets=offs)
        sampling, bad = self._parse_sampling(body, default_max_tokens=128)
        if bad is not None:
            return bad
        rid = request.headers.get("X-Request-Id") or f"chatcmpl-{uuid.uuid4().hex[:16]}"
        return await self._respond(
            request, body, prompt_ids, sampling, rid, model, adapter,
            kind="chat",
        )

    async def handle_completion(self, request: web.Request) -> web.StreamResponse:
        body = await _json_body(request)
        model = body.get("model", self.config.model)
        if not self._check_model(model):
            return web.json_response(
                {"error": {"message": f"model {model!r} not found",
                           "type": "NotFoundError"}}, status=404)
        if self.core.is_sleeping:
            return web.json_response(
                {"error": {"message": "engine is sleeping",
                           "type": "ServiceUnavailable"}}, status=503)
        prompt = body.get("prompt", "")
        # OpenAI accepts: str | [str, ...] | [int, ...] | [[int, ...], ...].
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], list):
            prompt = prompt[0]
        adapter = self._resolve_adapter(model)
        if isinstance(prompt, list) and prompt and all(
            isinstance(t, int) for t in prompt
        ):
            prompt_ids = [int(t) for t in prompt]  # pre-tokenized
        else:
            if isinstance(prompt, list):
                prompt = prompt[0] if prompt else ""
            prompt_ids, offs = self._encode_prompt(str(prompt))
            self._report_kv_admission(
                str(prompt), prompt_ids, adapter or "", offsets=offs)
        sampling, bad = self._parse_sampling(body, default_max_tokens=16)
        if bad is not None:
            return bad
        rid = request.headers.get("X-Request-Id") or f"cmpl-{uuid.uuid4().hex[:16]}"
        return await self._respond(
            request, body, prompt_ids, sampling, rid, model, adapter,
            kind="completion",
        )

    def _parse_sampling(self, body: dict, *, default_max_tokens: int):
        """(sampling, None) or (None, 400 response). Malformed sampling
        fields (non-integer max_tokens, non-numeric logit_bias values)
        and uncompilable structured constraints are client errors — the
        constraint DFA is compiled here, before the request is admitted,
        so a bad schema can never reach the engine thread (the compile
        is memoized, so the engine's own lookup is then a cache hit)."""
        try:
            sampling = SamplingParams.from_request(
                body, default_max_tokens=default_max_tokens)
            if sampling.structured is not None:
                compile_char_dfa(sampling.structured)
        except ValueError as exc:  # StructuredError is a ValueError
            return None, web.json_response(
                {"error": {"message": str(exc),
                           "type": "BadRequestError"}}, status=400)
        return sampling, self._reject_sampling(sampling)

    @staticmethod
    def _reject_sampling(sampling) -> Optional[web.Response]:
        """400 for sampling params beyond the compiled programs' capacity
        instead of silently truncating (the fused programs bake in sparse
        logit_bias slots — MAX_LOGIT_BIAS — so excess entries cannot be
        applied; OpenAI accepts up to 300 but partial application would be
        silent wrong output)."""
        if sampling.logit_bias and len(sampling.logit_bias) > MAX_LOGIT_BIAS:
            return web.json_response(
                {"error": {
                    "message": (
                        f"logit_bias supports at most {MAX_LOGIT_BIAS} "
                        f"entries on this engine "
                        f"(got {len(sampling.logit_bias)})"),
                    "type": "BadRequestError",
                }}, status=400)
        return None

    async def _respond(self, request, body, prompt_ids, sampling, rid, model,
                       adapter, *, kind: str) -> web.StreamResponse:
        """Trace-recording shell around the actual response path: one
        StageClock rides into EngineCore (which stamps queue/prefill/
        decode boundaries on the engine thread); the completed timeline is
        recorded whether the request finishes, errors, or disconnects."""
        t_recv = time.time()
        clock = StageClock(arrival=t_recv)
        clock.prompt_tokens = len(prompt_ids)
        if adapter:
            self.lora_request_counts[adapter] = (
                self.lora_request_counts.get(adapter, 0) + 1)
        try:
            return await self._respond_inner(
                request, body, prompt_ids, sampling, rid, model, adapter,
                kind=kind, clock=clock,
            )
        finally:
            self._record_request_trace(request, rid, model, t_recv, clock)

    def _record_request_trace(self, request, rid: str, model: str,
                              t_recv: float, clock: StageClock) -> None:
        rec = self.trace_recorder
        if rec is None:
            return
        now = time.time()
        trace = rec.begin(rid, request.headers.get("traceparent"))
        root = trace.start_span(
            "engine.request", start=t_recv, model=model,
            prompt_tokens=clock.prompt_tokens, tokens=clock.tokens,
        )
        queue_end = clock.prefill_start or now
        # Queue wait by cause: the steps of other requests that held the
        # loop between arrival and this request's prefill, read off the
        # step recorder's ring here, once per request and off the engine
        # thread. The three parts sum to the span.
        steps = self.core.step_recorder

        def busy_between(t0: float, t1: float) -> dict:
            if steps is None:
                return {"decode": 0.0, "prefill": 0.0, "steps": 0}
            return steps.busy_between(t0, t1)

        behind = busy_between(clock.arrival, queue_end)
        trace.add_span(
            "engine.queue", clock.arrival, queue_end, parent=root,
            behind_decode_s=round(behind["decode"], 6),
            behind_prefill_s=round(behind["prefill"], 6),
            behind_other_s=round(max(
                0.0, queue_end - clock.arrival - behind["decode"]
                - behind["prefill"]), 6),
            steps_waited=behind["steps"])
        if clock.prefill_start:
            trace.add_span(
                "engine.prefill", clock.prefill_start,
                clock.prefill_end or clock.prefill_start, parent=root,
                prompt_tokens=clock.prompt_tokens,
                cached_tokens=clock.cached_tokens,
                uncached_tokens=max(
                    0, clock.prompt_tokens - clock.cached_tokens),
                preemptions=clock.preemptions,
                prefill_chunks=clock.prefill_chunks,
            )
        if clock.first_token:
            if clock.prefill_end:
                # How long the sampled first token sat on the device and
                # in the loop before the next flush emitted it.
                trace.add_span(
                    "engine.first_token", clock.prefill_end,
                    max(clock.first_token, clock.prefill_end), parent=root)
            decode_start = clock.prefill_end or clock.first_token
            decode = trace.add_span(
                "engine.decode", decode_start,
                max(clock.last_token, decode_start), parent=root,
                steps=clock.tokens, tokens=clock.tokens,
                time_to_first_token_s=round(
                    clock.first_token - clock.arrival, 6),
            )
            if clock.gap_end:
                # The stream's longest gap, by cause: the steps that held
                # the loop between the two deliveries around it, read off
                # the ring like the queue's. The step that ended the gap
                # ends after it and is left out, so the causes add up to
                # less than the span.
                behind = busy_between(clock.gap_start, clock.gap_end)
                trace.add_span(
                    "engine.stream_gap", clock.gap_start, clock.gap_end,
                    parent=decode,
                    behind_decode_s=round(behind["decode"], 6),
                    behind_prefill_s=round(behind["prefill"], 6),
                    steps=behind["steps"], at_token=clock.gap_at_token)
        root.finish(end=now, tokens=clock.tokens)
        rec.record(trace)

    async def _respond_inner(self, request, body, prompt_ids, sampling, rid,
                             model, adapter, *, kind: str,
                             clock: Optional[StageClock] = None,
                             ) -> web.StreamResponse:
        stream_mode = bool(body.get("stream", False))
        # KV-capacity pre-check: a prompt that can never fit the engine's
        # KV pool fails fast — 503 with Retry-After — instead of queueing
        # until the scheduler rejects it (which historically mislabeled
        # the rejection as finish_reason "length").
        if self.core.kv_never_fits(len(prompt_ids)):
            self.core.scheduler.rejected_total["kv_capacity"] += 1
            return web.json_response(
                {"error": {
                    "message": (
                        f"prompt ({len(prompt_ids)} tokens) exceeds this "
                        f"engine's KV cache capacity"),
                    "type": "ServiceUnavailable",
                }}, status=503, headers={"Retry-After": "1"})
        priority = parse_priority(request.headers.get("X-Priority"))
        stream = await self._generate(prompt_ids, sampling, rid, adapter,
                                      trace=clock, priority=priority)
        detok = IncrementalDetokenizer(self.core.tokenizer)
        created = int(time.time())
        obj = "chat.completion" if kind == "chat" else "text_completion"

        def chunk_payload(delta_text: str, finish: Optional[str], first: bool):
            if kind == "chat":
                delta = {}
                if first:
                    delta["role"] = "assistant"
                if delta_text:
                    delta["content"] = delta_text
                choice = {"index": 0, "delta": delta, "finish_reason": finish}
                return {"id": rid, "object": "chat.completion.chunk",
                        "created": created, "model": model, "choices": [choice]}
            choice = {"index": 0, "text": delta_text, "finish_reason": finish}
            return {"id": rid, "object": obj, "created": created,
                    "model": model, "choices": [choice]}

        # Tool-call requests buffer the stream: calls can only be parsed
        # from the complete text (vLLM's streaming tool parsers do
        # per-model incremental parsing; the whole-text parse is the
        # model-agnostic subset).
        buffer_tools = (bool(body.get("tools")) and kind == "chat"
                        and body.get("tool_choice") != "none")
        declared_tools = tool_names(body.get("tools") or [])
        if sampling.n > 1:
            return await self._respond_n(
                request, body, prompt_ids, sampling, rid, model, adapter,
                kind=kind, stream=stream, stream_mode=stream_mode,
                created=created, obj=obj, buffer_tools=buffer_tools,
                declared_tools=declared_tools)
        if stream_mode:
            resp = web.StreamResponse()
            resp.content_type = "text/event-stream"
            resp.headers["Cache-Control"] = "no-cache"
            resp.headers["X-Request-Id"] = rid
            await resp.prepare(request)
            text_so_far = ""
            first = True
            finish_reason = "stop"
            # Logprob entries for tokens whose text is held back by the
            # incremental detokenizer (partial UTF-8) ride the next
            # written chunk instead of being dropped.
            pending_lp: List[dict] = []
            try:
                if sampling.echo and kind == "completion":
                    # OpenAI echo: the prompt text leads the stream.
                    payload = chunk_payload(
                        self.core.tokenizer.decode(prompt_ids), None, True)
                    await resp.write(
                        f"data: {json.dumps(payload)}\n\n".encode())
                async for raw_tok, finish in stream:
                    if raw_tok is None:
                        if finish in ("stop", "length", "abort",
                                      "kv_capacity"):
                            finish_reason = finish
                        if finish == "error":
                            finish_reason = "stop"
                        break
                    token_id, lp = self._split_token(raw_tok)
                    if lp is not None:
                        pending_lp.append(self._lp_entry(token_id, lp))
                    delta = detok.push(token_id)
                    if finish is not None:
                        delta += detok.flush()
                        finish_reason = finish
                    emit, stopped = self._apply_stop(
                        text_so_far, delta, sampling.stop)
                    if emit or first:
                        if not buffer_tools:
                            payload = chunk_payload(emit, None, first)
                            if pending_lp:
                                payload["choices"][0]["logprobs"] = (
                                    {"content": pending_lp}
                                    if kind == "chat" else
                                    self._completions_logprobs(pending_lp))
                                pending_lp = []
                            await resp.write(
                                f"data: {json.dumps(payload)}\n\n".encode())
                        first = False
                        text_so_far += emit
                    if stopped:
                        finish_reason = "stop"
                        self.core.abort_request(rid)
                        break
                    if finish is not None:
                        break
                if buffer_tools:
                    content, tool_calls = parse_tool_calls(
                        text_so_far, declared_tools)
                    delta = {"role": "assistant"}
                    if tool_calls:
                        delta["tool_calls"] = [
                            {**tc, "index": i}
                            for i, tc in enumerate(tool_calls)
                        ]
                        finish_reason = "tool_calls"
                        if content:
                            delta["content"] = content
                    else:
                        delta["content"] = text_so_far
                    choice = {"index": 0, "delta": delta,
                              "finish_reason": None}
                    if pending_lp:  # buffered mode: all entries ride here
                        choice["logprobs"] = {"content": pending_lp}
                        pending_lp = []
                    payload = {
                        "id": rid, "object": "chat.completion.chunk",
                        "created": created, "model": model,
                        "choices": [choice],
                    }
                    await resp.write(
                        f"data: {json.dumps(payload)}\n\n".encode())
                    first = False
                final = chunk_payload("", finish_reason, first)
                if pending_lp:
                    # Entries whose token text never surfaced (EOS, a
                    # stop-trimmed tail) ride the final chunk so stream
                    # and non-stream report the same token set.
                    final["choices"][0]["logprobs"] = (
                        {"content": pending_lp} if kind == "chat"
                        else self._completions_logprobs(pending_lp))
                await resp.write(f"data: {json.dumps(final)}\n\n".encode())
                await resp.write(b"data: [DONE]\n\n")
                await resp.write_eof()
            except (ConnectionResetError, asyncio.CancelledError):
                self.core.abort_request(rid)
                raise
            return resp

        # Non-streaming: collect all tokens.
        pieces: List[str] = []
        lp_entries: List[dict] = []
        n_generated = 0
        finish_reason = "stop"
        text_so_far = ""
        async for raw_tok, finish in stream:
            if raw_tok is None:
                if finish == "length" and n_generated == 0:
                    # Scheduler rejection: the prompt itself exceeds
                    # max_model_len. Surface as a client error, not an
                    # empty completion.
                    return web.json_response(
                        {"error": {
                            "message": (
                                f"prompt ({len(prompt_ids)} tokens) "
                                f"exceeds max_model_len "
                                f"{self.config.max_model_len}"),
                            "type": "BadRequestError",
                        }}, status=400)
                if finish == "kv_capacity" and n_generated == 0:
                    # Async scheduler rejection (pool transiently pinned
                    # below the prompt's footprint): retryable, not a
                    # client error.
                    return web.json_response(
                        {"error": {
                            "message": (
                                f"prompt ({len(prompt_ids)} tokens) "
                                f"exceeds currently available KV cache "
                                f"capacity"),
                            "type": "ServiceUnavailable",
                        }}, status=503, headers={"Retry-After": "1"})
                if finish in ("stop", "length", "abort", "kv_capacity"):
                    finish_reason = finish
                break
            token_id, lp = self._split_token(raw_tok)
            n_generated += 1
            if lp is not None:
                lp_entries.append(self._lp_entry(token_id, lp))
            delta = detok.push(token_id)
            if finish is not None:
                delta += detok.flush()
                finish_reason = finish
            emit, stopped = self._apply_stop(text_so_far, delta, sampling.stop)
            pieces.append(emit)
            text_so_far += emit
            if stopped:
                finish_reason = "stop"
                self.core.abort_request(rid)
                break
            if finish is not None:
                break
        text = "".join(pieces)
        usage = {
            "prompt_tokens": len(prompt_ids),
            "completion_tokens": n_generated,
            "total_tokens": len(prompt_ids) + n_generated,
        }
        if kind == "chat":
            message = {"role": "assistant", "content": text}
            if buffer_tools:
                content, tool_calls = parse_tool_calls(text, declared_tools)
                if tool_calls:
                    message = {"role": "assistant",
                               "content": content or None,
                               "tool_calls": tool_calls}
                    finish_reason = "tool_calls"
            choice = {
                "index": 0,
                "message": message,
                "finish_reason": finish_reason,
            }
            if lp_entries:
                choice["logprobs"] = {"content": lp_entries}
            payload = {
                "id": rid, "object": obj, "created": created, "model": model,
                "choices": [choice],
                "usage": usage,
            }
        else:
            out_text = text
            if sampling.echo:
                out_text = self.core.tokenizer.decode(prompt_ids) + text
            choice = {"index": 0, "text": out_text,
                      "finish_reason": finish_reason}
            if lp_entries:
                choice["logprobs"] = self._completions_logprobs(lp_entries)
            payload = {
                "id": rid, "object": obj, "created": created, "model": model,
                "choices": [choice],
                "usage": usage,
            }
        return web.json_response(payload, headers={"X-Request-Id": rid})

    @staticmethod
    def _completions_logprobs(entries: List[dict]) -> dict:
        """Chat-style entries -> the legacy completions logprobs object."""
        offsets = []
        pos = 0
        for e in entries:
            offsets.append(pos)
            pos += len(e["token"])
        return {
            "tokens": [e["token"] for e in entries],
            "token_logprobs": [e["logprob"] for e in entries],
            "top_logprobs": [
                {t["token"]: t["logprob"] for t in e["top_logprobs"]}
                for e in entries
            ],
            "text_offset": offsets,
        }

    async def _respond_n(self, request, body, prompt_ids, sampling, rid,
                         model, adapter, *, kind, stream, stream_mode,
                         created, obj, buffer_tools, declared_tools):
        """n>1 sampling: n independent engine requests (seeds derived per
        choice) merged into one response — interleaved ``index``-tagged SSE
        chunks when streaming, a choices array otherwise (vLLM's n
        semantics on the OpenAI surface).

        NOTE: this intentionally mirrors _respond's per-choice contract
        (stop strings, buffered tools, finish reasons, oversize-prompt
        400). A behavior change in _respond's n=1 path must land here too
        — the shapes differ enough (merged queue vs single stream) that a
        shared implementation would obscure both."""
        import dataclasses

        n = sampling.n
        if len(prompt_ids) >= self.config.max_model_len:
            # Mirror the n=1 path's scheduler-rejection contract up front
            # (each sub-request would be rejected with zero tokens). The
            # choice-0 request is already enqueued (_respond creates it
            # before branching here) — abort it rather than leaving it to
            # the async scheduler rejection.
            self.core.abort_request(rid)
            return web.json_response(
                {"error": {
                    "message": (f"prompt ({len(prompt_ids)} tokens) "
                                f"exceeds max_model_len "
                                f"{self.config.max_model_len}"),
                    "type": "BadRequestError",
                }}, status=400)
        base_seed = (sampling.seed if sampling.seed is not None
                     else hash(rid) % (2**31))

        def choice_rid(i: int) -> str:
            return rid if i == 0 else f"{rid}-c{i}"

        def abort_all() -> None:
            for i in range(n):
                self.core.abort_request(choice_rid(i))

        streams = [stream]
        for i in range(1, n):
            s_i = dataclasses.replace(sampling, seed=base_seed + i, n=1)
            streams.append(await self._generate(
                prompt_ids, s_i, choice_rid(i), adapter,
                priority=parse_priority(request.headers.get("X-Priority"))))
        detoks = [IncrementalDetokenizer(self.core.tokenizer)
                  for _ in range(n)]
        texts = [""] * n
        finishes = ["stop"] * n
        counts = [0] * n
        lp_all: "list[list[dict]]" = [[] for _ in range(n)]

        # Per-choice logprob entries not yet shipped in a chunk (held-back
        # text, EOS, stop-trimmed tails) — the finish chunk drains them.
        pendings: "list[list[dict]]" = [[] for _ in range(n)]

        async def consume(i):
            """Yields (emit_text, [lp_entries]) per written delta."""
            async for raw_tok, finish in streams[i]:
                if raw_tok is None:
                    if finish in ("stop", "length", "abort"):
                        finishes[i] = finish
                    break
                token_id, lp = self._split_token(raw_tok)
                if lp is not None:
                    entry = self._lp_entry(token_id, lp)
                    lp_all[i].append(entry)
                    pendings[i].append(entry)
                counts[i] += 1
                delta = detoks[i].push(token_id)
                if finish is not None:
                    delta += detoks[i].flush()
                    finishes[i] = finish
                emit, stopped = self._apply_stop(
                    texts[i], delta, sampling.stop)
                texts[i] += emit
                if emit:
                    # before the stop-break: never drop the tail
                    yield emit, pendings[i]
                    pendings[i] = []
                if stopped:
                    finishes[i] = "stop"
                    self.core.abort_request(choice_rid(i))
                    break
                if finish is not None:
                    break

        if stream_mode:
            resp = web.StreamResponse()
            resp.content_type = "text/event-stream"
            resp.headers["Cache-Control"] = "no-cache"
            resp.headers["X-Request-Id"] = rid
            await resp.prepare(request)
            queue: asyncio.Queue = asyncio.Queue()

            async def pump(i):
                try:
                    async for emit, entries in consume(i):
                        await queue.put((i, emit, entries))
                finally:
                    # Sentinel even on error: the merge loop must not
                    # wait forever on a dead choice.
                    await queue.put((i, None, None))

            tasks = [asyncio.get_running_loop().create_task(pump(i))
                     for i in range(n)]
            first = [True] * n
            live = n

            def chunk(choice):
                return {"id": rid, "object": (
                    "chat.completion.chunk" if kind == "chat" else obj),
                    "created": created, "model": model,
                    "choices": [choice]}

            try:
                if sampling.echo and kind == "completion":
                    # OpenAI echo: the prompt text leads each choice.
                    prompt_text = self.core.tokenizer.decode(prompt_ids)
                    for i in range(n):
                        payload = chunk({"index": i, "text": prompt_text,
                                         "finish_reason": None})
                        await resp.write(
                            f"data: {json.dumps(payload)}\n\n".encode())
                while live:
                    i, emit, entries = await queue.get()
                    if emit is None:
                        live -= 1
                        continue
                    if buffer_tools:
                        continue  # parsed + emitted per choice below
                    delta = ({"role": "assistant", "content": emit}
                             if first[i] and kind == "chat"
                             else {"content": emit})
                    first[i] = False
                    choice = ({"index": i, "delta": delta,
                               "finish_reason": None} if kind == "chat"
                              else {"index": i, "text": emit,
                                    "finish_reason": None})
                    if entries:
                        choice["logprobs"] = (
                            {"content": entries} if kind == "chat"
                            else self._completions_logprobs(entries))
                    await resp.write(
                        f"data: {json.dumps(chunk(choice))}\n\n".encode())
                for i in range(n):
                    finish_reason = finishes[i]
                    if buffer_tools:
                        # Same buffered-tools contract as the n=1 stream:
                        # one parsed delta per choice (all the choice's
                        # logprob entries ride it — nothing streamed
                        # earlier).
                        content, tool_calls = parse_tool_calls(
                            texts[i], declared_tools)
                        delta = {"role": "assistant"}
                        if tool_calls:
                            delta["tool_calls"] = [
                                {**tc, "index": k}
                                for k, tc in enumerate(tool_calls)]
                            finish_reason = "tool_calls"
                            if content:
                                delta["content"] = content
                        else:
                            delta["content"] = texts[i]
                        tool_choice_payload = {"index": i, "delta": delta,
                                               "finish_reason": None}
                        if lp_all[i]:
                            tool_choice_payload["logprobs"] = {
                                "content": lp_all[i]}
                            pendings[i] = []
                        payload = chunk(tool_choice_payload)
                        await resp.write(
                            f"data: {json.dumps(payload)}\n\n".encode())
                    choice = ({"index": i, "delta": {},
                               "finish_reason": finish_reason}
                              if kind == "chat"
                              else {"index": i, "text": "",
                                    "finish_reason": finish_reason})
                    if pendings[i]:
                        # Entries whose text never surfaced (EOS, stop
                        # tails) drain through the finish chunk.
                        choice["logprobs"] = (
                            {"content": pendings[i]} if kind == "chat"
                            else self._completions_logprobs(pendings[i]))
                        pendings[i] = []
                    await resp.write(
                        f"data: {json.dumps(chunk(choice))}\n\n".encode())
                await resp.write(b"data: [DONE]\n\n")
                await resp.write_eof()
            except (ConnectionResetError, asyncio.CancelledError):
                abort_all()
                raise
            finally:
                for t in tasks:
                    t.cancel()
            return resp

        async def drain(i):
            async for _ in consume(i):
                pass

        try:
            await asyncio.gather(*[drain(i) for i in range(n)])
        except (ConnectionResetError, asyncio.CancelledError):
            # Client vanished mid-gather (aiohttp cancels the handler):
            # abort all n generations like the n=1 and streaming paths.
            abort_all()
            raise
        choices = []
        for i in range(n):
            if kind == "chat":
                message = {"role": "assistant", "content": texts[i]}
                finish_reason = finishes[i]
                if buffer_tools:
                    content, tool_calls = parse_tool_calls(
                        texts[i], declared_tools)
                    if tool_calls:
                        message = {"role": "assistant",
                                   "content": content or None,
                                   "tool_calls": tool_calls}
                        finish_reason = "tool_calls"
                choice = {"index": i, "message": message,
                          "finish_reason": finish_reason}
                if lp_all[i]:
                    choice["logprobs"] = {"content": lp_all[i]}
                choices.append(choice)
            else:
                out_text = texts[i]
                if sampling.echo:
                    out_text = (self.core.tokenizer.decode(prompt_ids)
                                + out_text)
                choice = {"index": i, "text": out_text,
                          "finish_reason": finishes[i]}
                if lp_all[i]:
                    choice["logprobs"] = self._completions_logprobs(
                        lp_all[i])
                choices.append(choice)
        total_new = sum(counts)
        payload = {
            "id": rid, "object": obj, "created": created, "model": model,
            "choices": choices,
            "usage": {
                "prompt_tokens": len(prompt_ids),
                "completion_tokens": total_new,
                "total_tokens": len(prompt_ids) + total_new,
            },
        }
        return web.json_response(payload, headers={"X-Request-Id": rid})

    async def handle_embeddings(self, request: web.Request) -> web.Response:
        """Mean-pooled final hidden state as the embedding vector."""
        if self.core.is_sleeping:
            return web.json_response(
                {"error": {"message": "engine is sleeping",
                           "type": "ServiceUnavailable"}}, status=503)
        body = await _json_body(request)
        inputs = body.get("input", [])
        # str | [str, ...] | [int, ...] (one token array) | [[int, ...], ...]
        if isinstance(inputs, str):
            inputs = [inputs]
        elif isinstance(inputs, list) and inputs and all(
            isinstance(t, int) for t in inputs
        ):
            inputs = [inputs]
        data = []
        total_tokens = 0
        for i, text in enumerate(inputs):
            if isinstance(text, list):
                ids = [int(t) for t in text]  # pre-tokenized
            else:
                ids = self.core.tokenizer.encode(str(text))
            total_tokens += len(ids)
            vec = await asyncio.get_running_loop().run_in_executor(
                None, self.core.embed, ids
            )
            data.append({"object": "embedding", "index": i, "embedding": vec})
        return web.json_response({
            "object": "list", "model": body.get("model", self.config.model),
            "data": data,
            "usage": {"prompt_tokens": total_tokens,
                      "total_tokens": total_tokens},
        })

    async def _embed_texts(self, texts: List[str]):
        """Embeddings for texts (model forward deduplicated across repeats),
        plus the total token count over all occurrences (vLLM counts usage
        per pair, so duplicates still count)."""
        loop = asyncio.get_running_loop()
        cache: dict = {}
        total_tokens = 0
        out = []
        for text in texts:
            if text not in cache:
                ids = self.core.tokenizer.encode(text)
                emb = await loop.run_in_executor(None, self.core.embed, ids)
                cache[text] = (emb, len(ids))
            emb, n_tokens = cache[text]
            total_tokens += n_tokens
            out.append(emb)
        return out, total_tokens

    @staticmethod
    def _as_text_list(value) -> Optional[List[str]]:
        """str | [str, ...] -> list of texts; anything else is invalid."""
        if isinstance(value, str):
            return [value]
        if isinstance(value, list) and all(isinstance(t, str) for t in value):
            return list(value)
        return None

    @staticmethod
    def _dot(a: List[float], b: List[float]) -> float:
        # embed() L2-normalises, so the dot product IS cosine similarity.
        return float(sum(x * y for x, y in zip(a, b)))

    async def handle_score(self, request: web.Request) -> web.Response:
        """Similarity scores for text pairs (vLLM ``/v1/score`` surface the
        router proxies; ref ``src/vllm_router/routers/main_router.py:117-170``).

        Embedding-based scorer: cosine similarity of the pooled hidden-state
        embeddings (the path vLLM uses for embedding models). ``text_1`` may
        be a single text (broadcast over ``text_2``) or a list pairing
        element-wise with ``text_2``.
        """
        if self.core.is_sleeping:
            return web.json_response(
                {"error": {"message": "engine is sleeping",
                           "type": "ServiceUnavailable"}}, status=503)
        body = await _json_body(request)
        list_1 = self._as_text_list(body.get("text_1"))
        list_2 = self._as_text_list(body.get("text_2"))
        if list_1 is None or list_2 is None:
            return web.json_response(
                {"error": {"message": "text_1 and text_2 are required and "
                           "must each be a string or a list of strings",
                           "type": "BadRequestError"}}, status=400)
        if len(list_1) == 1:
            list_1 = list_1 * len(list_2)
        if len(list_1) != len(list_2):
            return web.json_response(
                {"error": {"message": (
                    f"text_1 ({len(list_1)}) and text_2 ({len(list_2)}) "
                    "must pair up (or text_1 must be a single text)"),
                    "type": "BadRequestError"}}, status=400)
        # One call so repeats across the two lists share a model forward.
        embs, total = await self._embed_texts(list_1 + list_2)
        emb_1, emb_2 = embs[: len(list_1)], embs[len(list_1):]
        data = [
            {"index": i, "object": "score", "score": self._dot(a, b)}
            for i, (a, b) in enumerate(zip(emb_1, emb_2))
        ]
        return web.json_response({
            "id": f"score-{uuid.uuid4().hex[:16]}",
            "object": "list",
            "created": int(time.time()),
            "model": body.get("model", self.config.model),
            "data": data,
            "usage": {"prompt_tokens": total, "total_tokens": total},
        })

    async def handle_rerank(self, request: web.Request) -> web.Response:
        """Jina/Cohere-compatible rerank (vLLM ``/v1/rerank`` surface):
        score ``query`` against each document, return the top_n sorted by
        descending relevance."""
        if self.core.is_sleeping:
            return web.json_response(
                {"error": {"message": "engine is sleeping",
                           "type": "ServiceUnavailable"}}, status=503)
        body = await _json_body(request)
        query = body.get("query")
        documents = body.get("documents")
        if not query or not isinstance(documents, list) or not documents:
            return web.json_response(
                {"error": {"message":
                           "query and a non-empty documents list are required",
                           "type": "BadRequestError"}}, status=400)
        documents = [
            d.get("text", "") if isinstance(d, dict) else str(d)
            for d in documents
        ]
        try:
            top_n = int(body.get("top_n", len(documents)))
        except (TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "top_n must be an integer",
                           "type": "BadRequestError"}}, status=400)
        embs, total_tokens = await self._embed_texts(
            [str(query)] + documents)
        q_emb, d_embs = embs[0], embs[1:]
        ranked = sorted(
            (
                {"index": i, "document": {"text": doc},
                 "relevance_score": self._dot(q_emb, emb)}
                for i, (doc, emb) in enumerate(zip(documents, d_embs))
            ),
            key=lambda r: r["relevance_score"], reverse=True,
        )[: max(top_n, 0)]
        return web.json_response({
            "id": f"rerank-{uuid.uuid4().hex[:16]}",
            "model": body.get("model", self.config.model),
            "usage": {"total_tokens": total_tokens},
            "results": ranked,
        })

    async def handle_tokenize(self, request: web.Request) -> web.Response:
        body = await _json_body(request)
        text = body.get("prompt")
        if text is None and "messages" in body:
            text = self.core.tokenizer.apply_chat_template(body["messages"])
        ids = self.core.tokenizer.encode(text or "")
        return web.json_response({
            "tokens": ids, "count": len(ids),
            "max_model_len": self.config.max_model_len,
        })

    async def handle_detokenize(self, request: web.Request) -> web.Response:
        body = await _json_body(request)
        return web.json_response(
            {"prompt": self.core.tokenizer.decode(body.get("tokens", []))})

    async def handle_transcriptions(self, request: web.Request) -> web.Response:
        """Audio transcription is served by dedicated ASR pods
        (:mod:`production_stack_tpu.engine.asr_server`, helm
        ``modelType: transcription``) that the router proxies multipart
        audio to — mirroring the reference's separate Whisper vLLM pods.
        This text-generation engine answers 501 with a pointer rather than
        404 so misrouted clients get a diagnosis."""
        await request.post()  # drain the multipart body
        return web.json_response(
            {"error": {
                "message": "this pod serves text generation; deploy a "
                           "whisper-class ASR pod (python -m production_"
                           "stack_tpu.engine.asr_server, or a helm "
                           "modelSpec with modelType: transcription) and "
                           "route audio there",
                "type": "NotImplementedError",
            }},
            status=501,
        )

    # ------------------------------------------------------------------ #
    # lifecycle / metrics
    # ------------------------------------------------------------------ #
    async def handle_health(self, request: web.Request) -> web.Response:
        if self.core.fatal_error is not None:
            # Unrecoverable fault (e.g. multi-host op-channel break):
            # report unhealthy so probes restart the pod instead of
            # routing traffic into a wedged job.
            return web.json_response(
                {"status": "unhealthy", "error": self.core.fatal_error},
                status=503)
        if self.draining:
            # Readiness flips on drain: k8s pulls the pod from Service
            # endpoints and the router's health sweep stops routing
            # here while in-flight requests finish.
            return web.json_response(
                {"status": "draining", "in_flight": self._inflight},
                status=503, headers={"Retry-After": "1"})
        body = {"status": "ok"}
        mh = self.core._mh
        if mh is not None:
            # All processes joined by construction (jax.distributed and
            # the op channel both barrier at startup) — report the span.
            body.update({"role": "leader",
                         "num_processes": mh.num_processes,
                         "mesh": dict(self.core.mesh.shape)})
        return web.json_response(body)

    async def handle_version(self, request: web.Request) -> web.Response:
        from production_stack_tpu import __version__

        return web.json_response({"version": __version__})

    # -- programmatic profiler capture (POST /debug/profile) ------------- #

    def _run_profile_capture(self, out_dir: str, duration_s: float,
                             python_tracer: bool = False) -> dict:
        """Blocking jax.profiler capture, run in an executor thread. The
        engine thread keeps stepping — that's the point: the trace shows
        real serving steps, not an idle device. The Python tracer is off
        unless asked for: under serving load it writes some hundred
        thousand events a second, and a trace of that size comes back
        without the device's plane (PERF.md, PR 24, finding 2); the
        engine loop's own ``engine.*`` annotations say what the host did.
        No-op friendly: platforms without profiler support report the
        failure instead of 500ing."""
        import jax

        os.makedirs(out_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        try:
            jax.profiler.start_trace(out_dir, profiler_options=options)
        except Exception as e:  # noqa: BLE001 — backend-specific errors
            return {"ok": False, "error": f"profiler unavailable: {e}"}
        try:
            time.sleep(duration_s)
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                return {"ok": False, "error": f"profiler stop failed: {e}"}
        files = []
        for root, _dirs, names in os.walk(out_dir):
            for name in names:
                rel = os.path.relpath(os.path.join(root, name),
                                      self.profile_dir)
                files.append(rel)
        return {"ok": True, "files": sorted(files)}

    async def handle_debug_profile(self, request: web.Request) -> web.Response:
        """Time-bounded ``jax.profiler`` trace into the served artifact
        dir. Body: ``{"duration_s": 2.0, "python_tracer": false}``
        (duration clamped to (0, 60]; the Python tracer is off by default,
        see ``_run_profile_capture``). One capture at a time; a second
        request while one is running gets 409. Privileged: requires the
        deployment key when one is set."""
        body = await _json_body(request)
        try:
            duration_s = float(body.get("duration_s", 2.0))
        except (TypeError, ValueError):
            raise _bad_request("duration_s must be a number") from None
        if not duration_s > 0:
            raise _bad_request("duration_s must be > 0")
        duration_s = min(duration_s, 60.0)
        python_tracer = body.get("python_tracer", False)
        if not isinstance(python_tracer, bool):
            raise _bad_request("python_tracer must be true or false")
        if not self._profile_lock.acquire(blocking=False):
            return web.json_response(
                {"error": {"message": "a profile capture is already running",
                           "type": "Conflict"}}, status=409)
        try:
            self._profile_runs += 1
            run_name = (f"run-{self._profile_runs:04d}-"
                        f"{time.strftime('%Y%m%d-%H%M%S')}")
            out_dir = os.path.join(self.profile_dir, run_name)
            result = await asyncio.get_running_loop().run_in_executor(
                None, self._run_profile_capture, out_dir, duration_s,
                python_tracer)
        finally:
            self._profile_lock.release()
        status = 200 if result.get("ok") else 503
        return web.json_response({
            "duration_s": duration_s,
            "python_tracer": python_tracer,
            "run": run_name,
            "artifact_dir": out_dir,
            "artifacts_url": "/debug/profile/artifacts",
            **result,
        }, status=status)

    async def handle_profile_artifacts(
            self, request: web.Request) -> web.Response:
        """List captured profile artifacts (relative paths under the
        profile dir)."""
        files = []
        if os.path.isdir(self.profile_dir):
            for root, _dirs, names in os.walk(self.profile_dir):
                for name in names:
                    files.append(os.path.relpath(
                        os.path.join(root, name), self.profile_dir))
        return web.json_response(
            {"profile_dir": self.profile_dir, "files": sorted(files)})

    async def handle_profile_artifact_file(
            self, request: web.Request) -> web.StreamResponse:
        """Serve one artifact file. Path-traversal safe: the resolved
        path must stay under the profile dir."""
        name = request.match_info["name"]
        base = os.path.realpath(self.profile_dir)
        full = os.path.realpath(os.path.join(base, name))
        if not (full == base or full.startswith(base + os.sep)):
            return web.json_response(
                {"error": {"message": "invalid artifact path",
                           "type": "BadRequestError"}}, status=400)
        if not os.path.isfile(full):
            return web.json_response(
                {"error": {"message": "artifact not found",
                           "type": "NotFoundError"}}, status=404)
        return web.FileResponse(full)

    async def handle_drain(self, request: web.Request) -> web.Response:
        """Graceful drain (the helm preStop hook, and any rollout
        orchestrator): stop admitting inference requests, flip /health
        to 503 so readiness and the router pull this replica, then wait
        until in-flight requests finish (bounded by ?timeout_s=, default
        30). Idempotent — repeat calls just re-await quiescence."""
        try:
            timeout_s = float(request.query.get("timeout_s", "30"))
        except ValueError:
            return web.json_response(
                {"error": {"message": "timeout_s must be a number",
                           "type": "BadRequestError"}}, status=400)
        first_drain = not self.draining
        if first_drain:
            logger.info("Drain requested: admission stopped, %d in flight",
                        self._inflight)
        self.draining = True
        if first_drain:
            # Stop the lease heartbeat/resync tasks FIRST: a beat landing
            # after the /kv/deregister below would get known=False and
            # re-register, pulling routable claims back onto a replica
            # that is going away.
            await self.stop_kv_reporting()
        if first_drain and self.kv_controller_url is not None:
            # Announce departure to the KV controller immediately: the
            # router must stop treating this replica as a prefix holder
            # (kvaware picks, fleet pull sources) while it quiesces.
            import aiohttp

            try:
                async with aiohttp.ClientSession(
                        headers=self._auth_headers()) as s:
                    await s.post(
                        f"{self.kv_controller_url}/kv/deregister",
                        json={"instance_id": self.instance_id},
                        timeout=aiohttp.ClientTimeout(total=5),
                    )
                self._kv_registered = False
            # aiohttp's total timeout raises asyncio.TimeoutError, which
            # is NOT a ClientError: a hung controller must degrade to the
            # admit TTL, never abort the drain before the quiescence wait.
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                logger.debug("KV deregister report failed: %s", e)
        deadline = time.monotonic() + max(0.0, timeout_s)
        while self._inflight > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        drained = self._inflight == 0
        return web.json_response(
            {"status": "drained" if drained else "draining",
             "in_flight": self._inflight},
            status=200 if drained else 202)

    async def handle_sleep(self, request: web.Request) -> web.Response:
        level = int(request.query.get("level", "1"))
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, self.core.sleep, level)
        except RuntimeError as e:  # multi-host: params sharded across hosts
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "BadRequestError"}}, status=400)
        return web.json_response({"status": "sleeping", "level": level})

    async def handle_wake(self, request: web.Request) -> web.Response:
        await asyncio.get_running_loop().run_in_executor(None, self.core.wake_up)
        return web.json_response({"status": "awake"})

    async def handle_is_sleeping(self, request: web.Request) -> web.Response:
        return web.json_response({"is_sleeping": self.core.is_sleeping})

    async def handle_load_lora(self, request: web.Request) -> web.Response:
        body = await _json_body(request)
        name = body.get("lora_name")
        if not name:
            return web.json_response(
                {"error": "lora_name required"}, status=400)
        ok = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.core.load_lora_adapter(
                name, rank=body.get("lora_rank"),
            ))
        if not ok:
            return web.json_response(
                {"error": f"could not load adapter {name!r} "
                          "(no free slots or LoRA disabled)"}, status=400)
        return web.json_response({"status": "ok", "lora_name": name})

    async def handle_unload_lora(self, request: web.Request) -> web.Response:
        body = await _json_body(request)
        name = body.get("lora_name")
        ok = self.core.unload_lora_adapter(name or "")
        if not ok:
            return web.json_response(
                {"error": f"adapter {name!r} not loaded"}, status=400)
        return web.json_response({"status": "ok", "lora_name": name})

    async def handle_list_lora(self, request: web.Request) -> web.Response:
        # Residency surface for the router's AdapterRegistry scrape:
        # adapters plus slot capacity (slot 0 is the base model, so
        # max_loras-1 slots are loadable) and the base model name.
        max_loras = int(getattr(self.config, "max_loras", 1))
        adapters = [
            {"lora_name": name, "slot": slot}
            for name, slot in self.core.lora_slots.items()
        ]
        return web.json_response({
            "adapters": adapters,
            "max_loras": max_loras,
            "capacity": max(max_loras - 1, 0),
            "base_model": self.config.model,
        })

    # ------------------------------------------------------------------ #
    # KV transfer (the reference's NIXL/LMCache pipe equivalent)
    # ------------------------------------------------------------------ #
    def _tokens_from_body(self, body: dict) -> List[int]:
        """Token ids for a KV-transfer request: explicit ids, a raw prompt,
        or chat messages (both engines share the tokenizer, so ids match)."""
        if body.get("token_ids"):
            return [int(t) for t in body["token_ids"]]
        if body.get("messages") is not None:
            prompt = self.core.tokenizer.apply_chat_template(body["messages"])
            return self.core.tokenizer.encode(prompt)
        prompt = body.get("prompt", "")
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], int):
            return [int(t) for t in prompt]
        return self.core.tokenizer.encode(str(prompt))

    async def _kv_transfer_refused(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"error": "this model's cache blocks hold what the KV transfer "
                      "formats do not carry (a state beside the pages, or "
                      "two page sides of unequal width)"},
            status=501)

    async def handle_kv_extract(self, request: web.Request) -> web.StreamResponse:
        """Serialize the cached KV pages for a prompt's prefix. The raw
        array buffers stream straight to the socket (no payload-sized
        concatenation copy — this path moves multi-GB KV at 8B/70B scale)."""
        from production_stack_tpu.kv.offload import pack_transfer_buffers

        body = await _json_body(request)
        token_ids = self._tokens_from_body(body)
        adapter = self._resolve_adapter(body.get("model", "")) or ""
        payload = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.core.extract_kv(token_ids, adapter)
        )
        if payload is None:
            return web.json_response(
                {"error": "no cached prefix for these tokens"}, status=404)
        buffers = pack_transfer_buffers(
            payload["hashes"], payload["num_tokens"],
            payload["k"], payload["v"],
        )
        total = sum(len(b) for b in buffers)
        resp = web.StreamResponse(headers={
            "Content-Type": "application/octet-stream",
            "Content-Length": str(total),
            "X-KV-Tokens": str(payload["num_tokens"]),
        })
        await resp.prepare(request)
        for buf in buffers:
            await resp.write(buf)
        await resp.write_eof()
        self.kv_transfer_tx_bytes += total
        return resp

    async def handle_kv_inject(self, request: web.Request) -> web.Response:
        """Install transferred KV blocks (inverse of /kv/extract)."""
        from production_stack_tpu.kv.offload import unpack_transfer

        data = await request.read()
        try:
            payload = unpack_transfer(data)
        except Exception:  # noqa: BLE001
            return web.json_response({"error": "bad payload"}, status=400)
        injected = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.core.inject_kv(
                payload["hashes"], payload["k"], payload["v"])
        )
        return web.json_response(
            {"status": "ok", "injected_blocks": injected,
             "num_tokens": payload["num_tokens"]})

    # Engines served from THIS process, keyed by bound port (registered by
    # run_engine_server): same-device KV moves skip the host entirely.
    _local_peers: "dict[str, EngineServer]" = {}

    def _resolve_local_peer(self, source_url: str) -> "EngineServer | None":
        from urllib.parse import urlparse

        parsed = urlparse(source_url)
        if parsed.hostname not in ("127.0.0.1", "localhost", "::1"):
            return None
        peer = EngineServer._local_peers.get(str(parsed.port))
        if peer is None or peer is self:
            return None
        # Page layout must match for an HBM->HBM move, and the peer must
        # still be live (a stopped core's cache is frozen/stale).
        if (peer.core.model_config != self.core.model_config
                or peer.core.config.block_size
                != self.core.config.block_size
                or not peer.core._running or peer.core.kv is None):
            return None
        return peer

    def _get_device_pipe(self):
        """Lazy KV device pipe (jax.experimental.transfer). None when the
        backend's transfer runtime is unavailable — callers fall back to
        the TKV2 HTTP relay."""
        if self._device_pipe is not None or self._device_pipe_failed:
            return self._device_pipe
        from production_stack_tpu.kv.device_pipe import (
            KVDevicePipe,
            device_pipe_available,
        )

        try:
            if device_pipe_available():
                self._device_pipe = KVDevicePipe()
            else:
                self._device_pipe_failed = True
        except Exception as e:  # noqa: BLE001
            logger.warning("KV device pipe init failed: %s", e)
            self._device_pipe_failed = True
        return self._device_pipe

    async def handle_kv_prepare_pull(
            self, request: web.Request) -> web.Response:
        """Sender side of the device-to-device disagg handoff: gather the
        prompt's cached prefix pages ON DEVICE and park them for the
        decode engine to pull over the transfer runtime (the NIXL-pipe
        equivalent; ref helm/templates/deployment-vllm-multi.yaml:267-305).
        501 when the backend has no transfer runtime (caller falls back to
        /kv/extract)."""
        pipe = await asyncio.get_running_loop().run_in_executor(
            None, self._get_device_pipe)
        if pipe is None:
            return web.json_response(
                {"error": "device pipe unavailable on this backend"},
                status=501)
        body = await _json_body(request)
        token_ids = self._tokens_from_body(body)
        adapter = self._resolve_adapter(body.get("model", "")) or ""
        payload = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.core.extract_kv_device(token_ids, adapter)
        )
        if payload is None:
            return web.json_response(
                {"error": "no cached prefix for these tokens"}, status=404)
        uuid_ = pipe.offer([payload["k"], payload["v"]])
        if uuid_ is None:
            # Offer table full (outstanding await_pull registrations pin
            # HBM and cannot be cancelled) — puller falls back to
            # /kv/extract.
            return web.json_response(
                {"error": "device pipe offer capacity exhausted"},
                status=503)
        k = payload["k"]
        nbytes = int(k.size * k.dtype.itemsize * 2)
        self.kv_transfer_tx_bytes += nbytes
        # Bind address may be wildcard; the puller substitutes the host it
        # already reaches this engine at.
        addr = pipe.address()
        port = addr.rsplit(":", 1)[-1]
        return web.json_response({
            "uuid": uuid_,
            "transfer_port": int(port),
            "hashes": [int(h) for h in payload["hashes"]],
            "num_tokens": payload["num_tokens"],
            "shape": list(k.shape),
            "dtype": str(k.dtype),
            "bytes": nbytes,
        })

    async def handle_kv_release(self, request: web.Request) -> web.Response:
        """Free a parked prepare_pull offer once the peer's pull is done
        (fallback: the pipe's TTL pruning)."""
        body = await _json_body(request)
        if self._device_pipe is not None and "uuid" in body:
            self._device_pipe.release(int(body["uuid"]))
        return web.json_response({"status": "ok"})

    async def _pull_device(self, source: str, token_ids, req_body) -> "dict | None":
        """Try the device-to-device pull. Returns the /kv/pull response
        dict, or None to fall back to the HTTP relay."""
        import aiohttp

        # First use runs the subprocess availability probe — keep it off
        # the event loop or every other request on this engine stalls.
        pipe = await asyncio.get_running_loop().run_in_executor(
            None, self._get_device_pipe)
        if pipe is None:
            return None
        t0 = time.monotonic()
        try:
            async with aiohttp.ClientSession(headers=self._auth_headers()) as session:
                async with session.post(
                    source.rstrip("/") + "/kv/prepare_pull",
                    json={"token_ids": token_ids,
                          "model": req_body.get("model", "")},
                    timeout=aiohttp.ClientTimeout(total=30),
                ) as resp:
                    if resp.status != 200:
                        return None
                    offer = await resp.json()
        except aiohttp.ClientError:
            return None

        import jax
        import jax.numpy as jnp
        from urllib.parse import urlparse

        host = urlparse(source).hostname
        address = f"{host}:{offer['transfer_port']}"
        shape = tuple(offer["shape"])
        dtype = jnp.dtype(offer["dtype"])
        # Pull onto THIS engine's first device (not jax.devices()[0]: a
        # second engine in the process owns another one).
        sharding = jax.sharding.SingleDeviceSharding(
            self.core.mesh.devices.flat[0])
        specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                 for _ in range(2)]
        pipe = self._device_pipe
        loop = asyncio.get_running_loop()

        try:
            k_dev, v_dev = await loop.run_in_executor(
                None, lambda: pipe.pull(address, offer["uuid"], specs))
        except Exception as e:  # noqa: BLE001 - peer/transport error
            # Deliberately NO /kv/release here: the sender's await_pull
            # registration cannot be cancelled, so its buffers stay pinned
            # whether or not the slot is freed. Keeping the slot counted
            # means repeated pull failures exhaust MAX_PENDING_OFFERS and
            # the pair degrades to the HTTP relay instead of pinning
            # unbounded HBM on the sender.
            logger.warning("device pull failed, falling back: %s", e)
            return None
        # The pull consumed the sender's buffers, so release its offer
        # slot NOW — before inject, whose failure must not burn the slot.
        # Retried, status-checked: a swallowed failure would permanently
        # hold one of the sender's slots (TTL expiry deliberately does
        # not free them).
        for attempt in range(3):
            try:
                async with aiohttp.ClientSession(headers=self._auth_headers()) as session:
                    async with session.post(
                            source.rstrip("/") + "/kv/release",
                            json={"uuid": offer["uuid"]},
                            timeout=aiohttp.ClientTimeout(total=5)) as resp:
                        if resp.status < 300:
                            break
            except (aiohttp.ClientError, asyncio.TimeoutError):
                pass
            if attempt == 2:
                logger.warning(
                    "kv/release to %s failed; sender offer slot %s "
                    "stays held until its process restarts",
                    source, offer["uuid"])
            else:
                await asyncio.sleep(0.2 * (attempt + 1))
        try:
            injected = await loop.run_in_executor(
                None, lambda: self.core.inject_kv_blocks(
                    [int(h) for h in offer["hashes"]], k_dev, v_dev))
        except Exception as e:  # noqa: BLE001 - local pool pressure etc.
            logger.warning("device pull injected 0 blocks, falling back: %s",
                           e)
            return None
        total = time.monotonic() - t0
        nbytes = int(offer.get("bytes", 0))
        self.kv_transfer_device_pulls += 1
        self.kv_transfer_device_bytes += nbytes
        self.kv_transfer_device_seconds += total
        self.kv_transfer_pulls += 1
        return {
            "status": "ok", "injected_blocks": injected,
            "num_tokens": offer["num_tokens"],
            "transfer": {
                "path": "device",
                "bytes": nbytes,
                "total_seconds": round(total, 6),
                "gigabytes_per_second": round(
                    nbytes / max(total, 1e-9) / 1e9, 6),
            }}

    async def handle_kv_pull(self, request: web.Request) -> web.Response:
        """Trace shell for :meth:`_kv_pull_impl`: records one
        ``engine.kv_transfer`` span per pull (path, bytes, seconds) under
        the router's trace when a ``traceparent`` arrives.

        Admission-gated: past ``--kv-pull-max-concurrency`` concurrent
        transfers the engine answers 503 + Retry-After instead of letting
        a popular prefix stampede one holder — the router degrades the
        rejected pull to plain recompute."""
        if self._pull_inflight >= self.kv_pull_max_concurrency:
            self.kv_pull_rejected_total += 1
            return web.json_response(
                {"status": "rejected",
                 "error": "pull admission full "
                          f"({self.kv_pull_max_concurrency} in flight)"},
                status=503, headers={"Retry-After": "1"})
        self._pull_inflight += 1
        t0 = time.time()
        try:
            resp = await self._kv_pull_impl(request)
        finally:
            self._pull_inflight -= 1
        if self.trace_recorder is not None:
            rid = (request.headers.get("X-Request-Id")
                   or f"kvpull-{uuid.uuid4().hex[:12]}")
            trace = self.trace_recorder.begin(
                rid, request.headers.get("traceparent"))
            attrs = {"status": resp.status}
            try:
                payload = json.loads(resp.body)
                attrs["result"] = payload.get("status", "error")
                attrs["injected_blocks"] = payload.get("injected_blocks", 0)
                transfer = payload.get("transfer") or {}
                for k in ("path", "bytes", "total_seconds"):
                    if k in transfer:
                        attrs[k] = transfer[k]
            except (ValueError, TypeError):
                pass
            trace.add_span("engine.kv_transfer", t0, time.time(), **attrs)
            self.trace_recorder.record(trace)
        return resp

    def _l3_probe(self, token_ids: List[int], adapter: str) -> int:
        """How many leading blocks of ``token_ids`` are resident in the
        offload tier (host RAM or the remote L3 cache server). 0 when no
        tier is configured. Runs on an executor: remote probes are HEAD
        requests against the cache server."""
        core = self.core
        if core.offload is None:
            return 0
        from production_stack_tpu.engine.kvcache import BlockAllocator

        bs = core.config.block_size
        parent = core.kv_mgr.chain_root(adapter)
        blocks = 0
        i = 0
        while i + bs <= len(token_ids):
            h = BlockAllocator.chain_hash(parent, tuple(token_ids[i:i + bs]))
            if not core.offload.contains(h):
                break
            parent = h
            blocks += 1
            i += bs
        return blocks

    def _l3_fallback(self, token_ids: List[int],
                     req_body: dict) -> Optional[web.Response]:
        """Peer pull missed: if the prefix is L3-resident, answer
        ``status: l3`` — prefill will restore the blocks through the
        offload tier (kv_mgr.external_lookup), no transfer needed here.
        Returns None when the L3 misses too (caller reports miss)."""
        if self.core.offload is None:
            return None
        adapter = self._resolve_adapter(req_body.get("model", "")) or ""
        blocks = self._l3_probe(token_ids, adapter)
        if blocks <= 0:
            return None
        self.l3_pull_hits += 1
        self.l3_pull_blocks += blocks
        return web.json_response({
            "status": "l3", "injected_blocks": 0, "l3_blocks": blocks,
            "num_tokens": blocks * self.core.config.block_size,
        })

    async def _kv_pull_impl(self, request: web.Request) -> web.Response:
        """Pull the KV for a prompt from another engine and install it —
        the decode-side step of disaggregated prefill. Data moves engine to
        engine; the router only sends this control message. Path
        negotiation: "device" (transfer runtime, device-to-device) is
        tried first unless kv_path forces "host"; the TKV2 HTTP relay is
        the always-available fallback."""
        import aiohttp

        from production_stack_tpu.kv.offload import unpack_transfer

        body = await _json_body(request)
        source = body.get("source_url")
        if not source:
            return web.json_response(
                {"error": "source_url required"}, status=400)
        req_body = body.get("request", body)
        token_ids = self._tokens_from_body(req_body)
        kv_path = body.get("kv_path", "auto")
        if kv_path == "auto":
            # Fastest rung: the source engine shares this chip/process
            # (co-located multi-model pods, dev-bench disagg) -> one
            # HBM->HBM page move, no host transit. ("device" forces the
            # transfer pipe; "host" forces the TKV2 relay.)
            peer = self._resolve_local_peer(source)
            if peer is not None:
                t0 = time.monotonic()
                adapter = self._resolve_adapter(
                    req_body.get("model", "")) or ""
                try:
                    injected = await (
                        asyncio.get_running_loop().run_in_executor(
                            None, lambda: self.core.inject_from_core(
                                peer.core, token_ids, adapter)))
                except Exception as e:  # noqa: BLE001 - fall to next rung
                    logger.warning(
                        "local-device pull failed, falling back: %s", e)
                    injected = 0
                if injected > 0:
                    total = time.monotonic() - t0
                    bs = self.core.config.block_size
                    nbytes = injected * self.core._kv_bytes_per_block()
                    self.kv_transfer_device_pulls += 1
                    self.kv_transfer_device_bytes += nbytes
                    self.kv_transfer_device_seconds += total
                    self.kv_transfer_pulls += 1
                    return web.json_response({
                        "status": "ok", "injected_blocks": injected,
                        "num_tokens": injected * bs,
                        "transfer": {
                            "path": "local-device",
                            "bytes": nbytes,
                            "total_seconds": round(total, 6),
                            "gigabytes_per_second": round(
                                nbytes / max(total, 1e-9) / 1e9, 6),
                        }})
        if kv_path in ("auto", "device"):
            result = await self._pull_device(source, token_ids, req_body)
            if result is not None:
                return web.json_response(result)
            if kv_path == "device":
                return web.json_response(
                    {"error": "device path unavailable"}, status=501)
        t0 = time.monotonic()
        try:
            async with aiohttp.ClientSession(headers=self._auth_headers()) as session:
                async with session.post(
                    source.rstrip("/") + "/kv/extract",
                    json={"token_ids": token_ids,
                          "model": req_body.get("model", "")},
                    timeout=aiohttp.ClientTimeout(total=60),
                ) as resp:
                    if resp.status != 200:
                        # Peer miss → try the shared L3 tier before
                        # conceding a recompute.
                        l3 = await asyncio.get_running_loop(
                        ).run_in_executor(
                            None,
                            lambda: self._l3_fallback(token_ids, req_body))
                        if l3 is not None:
                            return l3
                        return web.json_response(
                            {"status": "miss", "injected_blocks": 0})
                    data = await resp.read()
        except aiohttp.ClientError as e:
            l3 = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self._l3_fallback(token_ids, req_body))
            if l3 is not None:
                return l3
            return web.json_response(
                {"error": f"source unreachable: {e}"}, status=502)
        fetch_seconds = time.monotonic() - t0
        try:
            payload = unpack_transfer(data)
        except Exception:  # noqa: BLE001 - truncated/version-skewed payload
            l3 = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self._l3_fallback(token_ids, req_body))
            if l3 is not None:
                return l3
            return web.json_response({"status": "miss", "injected_blocks": 0})
        injected = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.core.inject_kv(
                payload["hashes"], payload["k"], payload["v"])
        )
        total_seconds = time.monotonic() - t0
        self.kv_transfer_rx_bytes += len(data)
        self.kv_transfer_rx_seconds += total_seconds
        self.kv_transfer_pulls += 1
        return web.json_response(
            {"status": "ok", "injected_blocks": injected,
             "num_tokens": payload["num_tokens"],
             "transfer": {
                 "path": "host",
                 "bytes": len(data),
                 # fetch covers the donor's extract (device_get + pack) plus
                 # the HTTP transfer; total adds the local inject. This is
                 # end-to-end handoff throughput, not link bandwidth.
                 "fetch_seconds": round(fetch_seconds, 6),
                 "total_seconds": round(total_seconds, 6),
                 "gigabytes_per_second": round(
                     len(data) / max(fetch_seconds, 1e-9) / 1e9, 6),
             }})

    async def handle_metrics(self, request: web.Request) -> web.Response:
        s = self.core.stats()
        model = self.config.model
        labels = f'model_name="{model}"'
        # HBM headroom: emit last-known (0 before the first sample) rather
        # than dropping the series — a gauge that disappears breaks
        # dashboards and alert rules.
        headroom = s.get("hbm_headroom_bytes")
        if headroom is None:
            headroom = self._last_hbm_headroom
        else:
            self._last_hbm_headroom = headroom
        # Request-lifecycle rollups from the flight recorder (avg stage
        # time = rate(sum)/rate(count) in Grafana).
        stage = self.trace_recorder.stage_stats()
        q_sum, q_count = stage.get("engine.queue", (0.0, 0))
        pf_sum, pf_count = stage.get("engine.prefill", (0.0, 0))
        dec_sum, dec_count = stage.get("engine.decode", (0.0, 0))
        spec_proposed = s.get("spec_proposed_tokens_total", 0)
        spec_rate = (s.get("spec_accepted_tokens_total", 0) / spec_proposed
                     if spec_proposed else 0.0)
        kv_dtype_labels = (
            f'{labels},kv_cache_dtype="{s.get("kv_cache_dtype", "bf16")}"')
        lines = [
            "# TYPE vllm:num_requests_running gauge",
            f"vllm:num_requests_running{{{labels}}} {s['num_requests_running']}",
            "# TYPE vllm:num_requests_waiting gauge",
            f"vllm:num_requests_waiting{{{labels}}} {s['num_requests_waiting']}",
            # TPU HBM KV usage exported under the GPU metric name so the
            # unchanged router scraper (engine_stats.py:63-76) and Grafana
            # dashboards keep working; tpu:* is the native name.
            "# TYPE vllm:gpu_cache_usage_perc gauge",
            f"vllm:gpu_cache_usage_perc{{{labels}}} {s['kv_usage']:.6f}",
            "# TYPE tpu:hbm_kv_usage_perc gauge",
            f"tpu:hbm_kv_usage_perc{{{labels}}} {s['kv_usage']:.6f}",
            "# TYPE tpu:kv_window_dead_tokens gauge",
            f"tpu:kv_window_dead_tokens{{{labels}}} "
            f"{s.get('kv_window_dead_tokens', 0)}",
            "# TYPE vllm:gpu_prefix_cache_hits counter",
            f"vllm:gpu_prefix_cache_hits_total{{{labels}}} {s['prefix_cache_hits']}",
            "# TYPE vllm:gpu_prefix_cache_queries counter",
            f"vllm:gpu_prefix_cache_queries_total{{{labels}}} {s['prefix_cache_queries']}",
            "# TYPE vllm:prompt_tokens counter",
            f"vllm:prompt_tokens_total{{{labels}}} {s['prompt_tokens_total']}",
            "# TYPE vllm:generation_tokens counter",
            f"vllm:generation_tokens_total{{{labels}}} {s['generation_tokens_total']}",
            # Deliveries of bursts' tokens to requests' callbacks: the
            # generated tokens over these is what one hand-over carries.
            "# TYPE tpu:emit_callbacks counter",
            f"tpu:emit_callbacks_total{{{labels}}} {s['emit_callbacks_total']}",
            "# TYPE vllm:request_success counter",
            f"vllm:request_success_total{{{labels}}} {s['requests_finished_total']}",
            "# TYPE vllm:num_preemptions counter",
            f"vllm:num_preemptions_total{{{labels}}} {s['num_preempted_total']}",
            # Per-priority preemption counts (QoS victim selection picks
            # batch-class requests before interactive ones).
            "# TYPE tpu:preempted_requests counter",
            f"tpu:preempted_requests_total{{{labels},priority=\"interactive\"}} "
            f"{s['preempted_by_priority']['interactive']}",
            f"tpu:preempted_requests_total{{{labels},priority=\"batch\"}} "
            f"{s['preempted_by_priority']['batch']}",
            "# TYPE tpu:num_kv_blocks gauge",
            f"tpu:num_kv_blocks{{{labels}}} {s['num_blocks']}",
            # Page residency split (tier=resident is HBM-allocated pages;
            # tier=offload counts pages in the host/remote tier — 0 when
            # no offload tier is configured).
            "# TYPE tpu:kv_page_occupancy gauge",
            f"tpu:kv_page_occupancy{{{labels},tier=\"resident\"}} "
            f"{s['kv_page_occupancy']['resident']}",
            f"tpu:kv_page_occupancy{{{labels},tier=\"offload\"}} "
            f"{s['kv_page_occupancy']['offload']}",
            "# TYPE tpu:hbm_headroom_bytes gauge",
            f"tpu:hbm_headroom_bytes{{{labels}}} {headroom}",
            # KV cache storage cost per token slot (int8 KV cache roughly
            # halves this vs bf16); the dtype rides as a label so capacity
            # dashboards can split fleets mid-migration.
            "# TYPE tpu:kv_cache_bytes_per_token gauge",
            f"tpu:kv_cache_bytes_per_token{{{kv_dtype_labels}}} "
            f"{s.get('kv_cache_bytes_per_token', 0)}",
            "# TYPE tpu:engine_sleeping gauge",
            f"tpu:engine_sleeping{{{labels}}} {int(s['is_sleeping'])}",
            # Fault tolerance: OOM pool-shrink ladder rungs taken at KV
            # allocation, and the graceful-drain flag (1 while POST
            # /drain has admission stopped).
            "# TYPE tpu:pool_shrink_retries counter",
            f"tpu:pool_shrink_retries_total{{{labels}}} "
            f"{s.get('pool_shrink_retries_total', 0)}",
            "# TYPE tpu:engine_draining gauge",
            f"tpu:engine_draining{{{labels}}} {int(self.draining)}",
            "# TYPE tpu:cached_prompt_tokens counter",
            f"tpu:cached_prompt_tokens_total{{{labels}}} {s['cached_tokens_total']}",
            "# TYPE tpu:prefill_padded_tokens counter",
            f"tpu:prefill_padded_tokens_total{{{labels}}} "
            f"{s['prefill_padded_tokens_total']}",
            "# TYPE tpu:kv_fetch_tokens counter",
            f"tpu:kv_fetch_tokens_total{{{labels}}} "
            f"{s['kv_fetch_tokens_total']}",
            # Prefill rows that began from a cache block's state (a
            # family with Family.block_state; 0 for any other).
            "# TYPE tpu:conv_state_restores counter",
            f"tpu:conv_state_restores_total{{{labels}}} "
            f"{s.get('state_restores_total', 0)}",
            # The expert layer's counts (models/moe.py::STATS), 0 for a
            # model without one.
            "# TYPE tpu:moe_assignments counter",
            f"tpu:moe_assignments_total{{{labels}}} "
            f"{s['family_stats_total'].get('moe_assignments', 0)}",
            "# TYPE tpu:moe_experts_hit counter",
            f"tpu:moe_experts_hit_total{{{labels}}} "
            f"{s['family_stats_total'].get('moe_experts_hit', 0)}",
            # Expert layers of the forwards in which no held expert
            # received a row.
            "# TYPE tpu:moe_idle_layers counter",
            f"tpu:moe_idle_layers_total{{{labels}}} "
            f"{s['family_stats_total'].get('moe_idle_layers', 0)}",
            # Passes over the layer stack (models/ouro.py::STATS), 0 for
            # a model whose layers run once.
            "# TYPE tpu:loop_passes counter",
            f"tpu:loop_passes_total{{{labels}}} "
            f"{s['family_stats_total'].get('loop_passes', 0)}",
            # Disaggregated-prefill KV handoff (the NIXL-pipe equivalent).
            "# TYPE tpu:kv_transfer_tx_bytes counter",
            f"tpu:kv_transfer_tx_bytes_total{{{labels}}} {self.kv_transfer_tx_bytes}",
            "# TYPE tpu:kv_transfer_rx_bytes counter",
            f"tpu:kv_transfer_rx_bytes_total{{{labels}}} {self.kv_transfer_rx_bytes}",
            "# TYPE tpu:kv_transfer_rx_seconds counter",
            f"tpu:kv_transfer_rx_seconds_total{{{labels}}} {self.kv_transfer_rx_seconds:.6f}",
            "# TYPE tpu:kv_transfer_pulls counter",
            f"tpu:kv_transfer_pulls_total{{{labels}}} {self.kv_transfer_pulls}",
            # Pull stampede control: concurrent /kv/pull transfers being
            # served, and pulls bounced 503 at the admission gate.
            "# TYPE tpu:kv_pull_inflight gauge",
            f"tpu:kv_pull_inflight{{{labels}}} {self._pull_inflight}",
            "# TYPE tpu:kv_pull_rejected counter",
            f"tpu:kv_pull_rejected_total{{{labels}}} "
            f"{self.kv_pull_rejected_total}",
            # Eviction-report stream health: dispatched prefix-evict
            # events and listener callbacks that raised (dropped reports
            # the anti-entropy resync has to heal).
            "# TYPE tpu:prefix_evicts counter",
            f"tpu:prefix_evicts_total{{{labels}}} "
            f"{s.get('prefix_evicts_total', 0)}",
            "# TYPE tpu:evict_listener_errors counter",
            f"tpu:evict_listener_errors_total{{{labels}}} "
            f"{s.get('evict_listener_errors_total', 0)}",
            "# TYPE tpu:kv_transfer_device_pulls counter",
            f"tpu:kv_transfer_device_pulls_total{{{labels}}} "
            f"{self.kv_transfer_device_pulls}",
            "# TYPE tpu:kv_transfer_device_bytes counter",
            f"tpu:kv_transfer_device_bytes_total{{{labels}}} "
            f"{self.kv_transfer_device_bytes}",
            "# TYPE tpu:kv_transfer_device_seconds counter",
            f"tpu:kv_transfer_device_seconds_total{{{labels}}} "
            f"{self.kv_transfer_device_seconds:.6f}",
            # Request lifecycle: queue / prefill / decode stage times
            # (sum+count pairs, matching the hand-rolled exposition style).
            "# TYPE tpu:queue_time_seconds summary",
            f"tpu:queue_time_seconds_sum{{{labels}}} {q_sum:.6f}",
            f"tpu:queue_time_seconds_count{{{labels}}} {q_count}",
            "# TYPE tpu:prefill_time_seconds summary",
            f"tpu:prefill_time_seconds_sum{{{labels}}} {pf_sum:.6f}",
            f"tpu:prefill_time_seconds_count{{{labels}}} {pf_count}",
            "# TYPE tpu:decode_time_seconds summary",
            f"tpu:decode_time_seconds_sum{{{labels}}} {dec_sum:.6f}",
            f"tpu:decode_time_seconds_count{{{labels}}} {dec_count}",
            "# TYPE tpu:slow_requests counter",
            f"tpu:slow_requests_total{{{labels}}} "
            f"{self.trace_recorder.slow_requests}",
            # Chunked prefill (--enable-chunked-prefill /
            # --max-num-batched-tokens).
            "# TYPE tpu:prefill_chunks counter",
            f"tpu:prefill_chunks_total{{{labels}}} "
            f"{s.get('prefill_chunks_total', 0)}",
            "# TYPE tpu:deferred_prefill_tokens counter",
            f"tpu:deferred_prefill_tokens_total{{{labels}}} "
            f"{s.get('deferred_prefill_tokens_total', 0)}",
            "# TYPE tpu:batched_token_utilization gauge",
            f"tpu:batched_token_utilization{{{labels}}} "
            f"{s.get('batched_token_utilization', 0.0):.6f}",
            # Speculative decoding (--speculative-num-tokens): drafts
            # (prompt-lookup n-grams, or a draft model when
            # --speculative-draft-model is set) verified in single-pass
            # batched bursts. proposed/accepted split by proposer via
            # the source label; both label values always emitted so
            # rate() never sees a vanishing series.
            "# TYPE tpu:spec_proposed_tokens counter",
            f'tpu:spec_proposed_tokens_total{{{labels},source="ngram"}} '
            f"{s.get('spec_proposed_by_source', {}).get('ngram', 0)}",
            f'tpu:spec_proposed_tokens_total{{{labels},'
            f'source="draft_model"}} '
            f"{s.get('spec_proposed_by_source', {}).get('draft_model', 0)}",
            "# TYPE tpu:spec_accepted_tokens counter",
            f'tpu:spec_accepted_tokens_total{{{labels},source="ngram"}} '
            f"{s.get('spec_accepted_by_source', {}).get('ngram', 0)}",
            f'tpu:spec_accepted_tokens_total{{{labels},'
            f'source="draft_model"}} '
            f"{s.get('spec_accepted_by_source', {}).get('draft_model', 0)}",
            "# TYPE tpu:spec_acceptance_rate gauge",
            f"tpu:spec_acceptance_rate{{{labels}}} {spec_rate:.6f}",
            "# TYPE tpu:spec_disabled_requests counter",
            f"tpu:spec_disabled_requests_total{{{labels}}} "
            f"{s.get('spec_disabled_requests_total', 0)}",
            "# TYPE tpu:spec_verify_bursts counter",
            f"tpu:spec_verify_bursts_total{{{labels}}} "
            f"{s.get('spec_verify_bursts_total', 0)}",
            # Draft-model forwards behind the proposals (small-model
            # steps; NOT in decode_forward_steps_total, which counts
            # target-model forwards only).
            "# TYPE tpu:spec_draft_forward_steps counter",
            f"tpu:spec_draft_forward_steps_total{{{labels}}} "
            f"{s.get('spec_draft_forward_steps_total', 0)}",
            "# TYPE tpu:decode_forward_steps counter",
            f"tpu:decode_forward_steps_total{{{labels}}} "
            f"{s.get('decode_forward_steps_total', 0)}",
            # Fused step program (--fused-step): prefill-chunk + decode-
            # burst pairs issued as ONE dispatch.
            "# TYPE tpu:fused_steps counter",
            f"tpu:fused_steps_total{{{labels}}} "
            f"{s.get('fused_steps_total', 0)}",
            # Cached-prefill attention path taken per dispatch: "pallas"
            # (flash prefix kernel — prefix pages streamed, suffix from
            # VMEM) vs "xla" (full-context gather reference). Both label
            # values always emitted so rate() never sees a vanishing
            # series.
            "# TYPE tpu:prefill_attention_dispatch counter",
            f'tpu:prefill_attention_dispatch_total{{{labels},'
            f'path="pallas"}} '
            f"{s.get('prefill_attention_dispatch_total', {}).get('pallas', 0)}",
            f'tpu:prefill_attention_dispatch_total{{{labels},path="xla"}} '
            f"{s.get('prefill_attention_dispatch_total', {}).get('xla', 0)}",
            # Step programs of a model with an expert layer, by the path
            # its grouped matmuls take: "pallas" (the grouped-matmul
            # kernel), "pallas_one_tile" (the kernel over rows that are
            # one row tile, in the tokens' order) or "xla" (ragged_dot:
            # off the TPU, across devices, or a shape that does not
            # tile). Every label value always.
            "# TYPE tpu:expert_matmul_dispatch counter",
            *(f'tpu:expert_matmul_dispatch_total{{{labels},path="{path}"}} '
              f"{s.get('expert_matmul_dispatch_total', {}).get(path, 0)}"
              for path in ("pallas", "pallas_one_tile", "xla")),
            # Decode programs of a model with a latent cache, by the path
            # its absorbed attention takes (0 for any other model).
            "# TYPE tpu:latent_decode_dispatch counter",
            f'tpu:latent_decode_dispatch_total{{{labels},path="pallas"}} '
            f"{s.get('latent_decode_dispatch_total', {}).get('pallas', 0)}",
            f'tpu:latent_decode_dispatch_total{{{labels},path="xla"}} '
            f"{s.get('latent_decode_dispatch_total', {}).get('xla', 0)}",
            # Cached-prefill programs of such a model, by the form their
            # attention over the gathered latents takes (a rule of the
            # program's shapes; 0 for any other model).
            "# TYPE tpu:latent_prefill_form counter",
            *(f'tpu:latent_prefill_form_total{{{labels},form="{form}"}} '
              f"{s.get('latent_prefill_form_total', {}).get(form, 0)}"
              for form in ("absorbed", "up_projected")),
            # Prefilled rows by where their first decode burst took their
            # first token from: the device (handed over behind the
            # prefill program, the burst enqueued while it still ran) or
            # the host (read back first). Both label values always.
            "# TYPE tpu:first_token_feed counter",
            *(f'tpu:first_token_feed_total{{{labels},path="{path}"}} '
              f"{s.get('first_token_feed_total', {}).get(path, 0)}"
              for path in ("device", "host")),
            # Structured output (guided_json / guided_regex /
            # response_format): grammar constraints compiled to token FSMs
            # applied inside the fused programs.
            "# TYPE tpu:structured_requests counter",
            f"tpu:structured_requests_total{{{labels}}} "
            f"{s.get('structured_requests_total', 0)}",
            "# TYPE tpu:structured_compile_seconds counter",
            f"tpu:structured_compile_seconds_total{{{labels}}} "
            f"{s.get('structured_compile_seconds_total', 0.0):.6f}",
            "# TYPE tpu:structured_mask_states counter",
            f"tpu:structured_mask_states_total{{{labels}}} "
            f"{s.get('structured_mask_states_total', 0)}",
            "# TYPE tpu:structured_violations counter",
            f"tpu:structured_violations_total{{{labels}}} "
            f"{s.get('structured_violations_total', 0)}",
        ]
        # Per-adapter request metering: series appear only once an
        # adapter-addressed request has been served, so the base-model
        # exposition stays byte-identical with no adapters configured.
        if self.lora_request_counts:
            lines.append("# TYPE tpu:lora_requests counter")
            lines += [
                f'tpu:lora_requests_total{{{labels},adapter="{name}"}} '
                f"{count}"
                for name, count in sorted(self.lora_request_counts.items())
            ]
        # Step flight recorder: per-kind step duration sum/count pairs,
        # scheduled tokens, the roofline HBM byte estimate, and the
        # bandwidth-utilization gauge (achieved bytes/s over the recent
        # step window vs the device HBM floor). Every kind is always
        # emitted so rate() queries never see a vanishing series.
        step_rec = self.core.step_recorder
        if step_rec is not None:
            lines += [
                "# TYPE tpu:step_duration_seconds summary",
            ]
            kind_stats = step_rec.kind_stats()
            for kind in sorted(kind_stats):
                kl = f'{labels},kind="{kind}"'
                ks = kind_stats[kind]
                lines += [
                    f"tpu:step_duration_seconds_sum{{{kl}}} "
                    f"{ks['wall_s']:.6f}",
                    f"tpu:step_duration_seconds_count{{{kl}}} "
                    f"{ks['count']}",
                ]
            lines.append("# TYPE tpu:step_scheduled_tokens counter")
            for kind in sorted(kind_stats):
                kl = f'{labels},kind="{kind}"'
                lines.append(
                    f"tpu:step_scheduled_tokens_total{{{kl}}} "
                    f"{kind_stats[kind]['tokens']}")
            lines.append("# TYPE tpu:step_hbm_bytes counter")
            for kind in sorted(kind_stats):
                kl = f'{labels},kind="{kind}"'
                lines.append(
                    f"tpu:step_hbm_bytes_total{{{kl}}} "
                    f"{kind_stats[kind]['hbm_bytes']}")
            utilization = step_rec.bandwidth_utilization()
            if utilization is not None:  # absent without a device peak
                lines += [
                    "# TYPE tpu:model_bandwidth_utilization gauge",
                    f"tpu:model_bandwidth_utilization{{{labels}}} "
                    f"{utilization:.6f}",
                ]
        # Trace head-sampling activity (--trace-sample-rate /
        # --slow-trace-log-interval-s).
        lines += [
            "# TYPE tpu:trace_sampled_out counter",
            f"tpu:trace_sampled_out_total{{{labels}}} "
            f"{self.trace_recorder.sampled_out_total}",
            "# TYPE tpu:slow_trace_logs_suppressed counter",
            f"tpu:slow_trace_logs_suppressed_total{{{labels}}} "
            f"{self.trace_recorder.slow_logs_suppressed_total}",
        ]
        # Event-loop health (--loop-monitor): scheduling-lag lifetime
        # accumulators, ring-window rollups, and severity-bucketed stall
        # counts. Omitted entirely when off (flag-off exposition is
        # byte-identical).
        mon = self.loop_monitor
        if mon is not None:
            pct = mon.percentiles()
            lines += [
                "# TYPE tpu:event_loop_lag_seconds summary",
                f"tpu:event_loop_lag_seconds_sum{{{labels}}} "
                f"{mon.lag_s_sum:.6f}",
                f"tpu:event_loop_lag_seconds_count{{{labels}}} "
                f"{mon.samples_total}",
                "# TYPE tpu:event_loop_lag_p50_seconds gauge",
                f"tpu:event_loop_lag_p50_seconds{{{labels}}} "
                f"{pct['p50']:.6f}",
                "# TYPE tpu:event_loop_lag_p99_seconds gauge",
                f"tpu:event_loop_lag_p99_seconds{{{labels}}} "
                f"{pct['p99']:.6f}",
                "# TYPE tpu:event_loop_lag_max_seconds gauge",
                f"tpu:event_loop_lag_max_seconds{{{labels}}} "
                f"{pct['max']:.6f}",
                "# TYPE tpu:loop_stalls counter",
            ]
            for bucket, count in sorted(mon.stalls().items()):
                bucket_labels = (f'{labels},bucket="{bucket}"' if labels
                                 else f'bucket="{bucket}"')
                lines.append(
                    f"tpu:loop_stalls_total{{{bucket_labels}}} {count}")
        # Admission rejections by reason; both reasons always emitted so
        # rate() queries never see a vanishing series.
        rejected = s.get("rejected_requests") or {}
        lines.append("# TYPE tpu:rejected_requests counter")
        for reason in sorted(set(rejected) | {"length", "kv_capacity"}):
            reason_labels = f'{labels},reason="{reason}"' if labels \
                else f'reason="{reason}"'
            lines.append(
                f"tpu:rejected_requests_total{{{reason_labels}}} "
                f"{rejected.get(reason, 0)}")
        if s.get("offload"):
            off = s["offload"]
            lines += [
                "# TYPE tpu:kv_offload_blocks gauge",
                f"tpu:kv_offload_blocks{{{labels}}} {off['blocks']}",
                "# TYPE tpu:kv_offload_bytes gauge",
                f"tpu:kv_offload_bytes{{{labels}}} {off['bytes']}",
                "# TYPE tpu:kv_offload_hits counter",
                f"tpu:kv_offload_hits_total{{{labels}}} {off['hits']}",
                "# TYPE tpu:kv_offload_misses counter",
                f"tpu:kv_offload_misses_total{{{labels}}} {off['misses']}",
            ]
            if off.get("remote"):
                # L3 (shared cache server) tier traffic + cross-replica
                # pulls answered out of L3 instead of a peer transfer.
                lines += [
                    "# TYPE tpu:l3_spill_blocks counter",
                    f"tpu:l3_spill_blocks_total{{{labels}}} "
                    f"{off.get('remote_put_blocks', 0)}",
                    "# TYPE tpu:l3_spill_bytes counter",
                    f"tpu:l3_spill_bytes_total{{{labels}}} "
                    f"{off.get('remote_put_bytes', 0)}",
                    "# TYPE tpu:l3_hit_blocks counter",
                    f"tpu:l3_hit_blocks_total{{{labels}}} "
                    f"{off.get('remote_get_blocks', 0)}",
                    "# TYPE tpu:l3_hit_bytes counter",
                    f"tpu:l3_hit_bytes_total{{{labels}}} "
                    f"{off.get('remote_get_bytes', 0)}",
                    "# TYPE tpu:l3_pull_hits counter",
                    f"tpu:l3_pull_hits_total{{{labels}}} {self.l3_pull_hits}",
                ]
        return web.Response(text="\n".join(lines) + "\n",
                            content_type="text/plain")


async def run_engine_server(server: EngineServer, host: str, port: int) -> web.AppRunner:
    app = server.make_app()
    bound_port: "list[int]" = []

    async def _unregister(app):
        # Drop the local-peer registration so a recycled port can never
        # resolve to this (stopped) server's frozen KV cache.
        await server.stop_kv_reporting()
        if bound_port and EngineServer._local_peers.get(
                str(bound_port[0])) is server:
            del EngineServer._local_peers[str(bound_port[0])]

    app.on_cleanup.append(_unregister)  # before setup(): hooks freeze then
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    real_port = site._server.sockets[0].getsockname()[1]
    bound_port.append(real_port)
    EngineServer._local_peers[str(real_port)] = server
    await server.start_kv_reporting(f"http://{host}:{real_port}")
    logger.info("Engine server on %s:%d (model=%s)", host, real_port,
                server.config.model)
    return runner


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU-native OpenAI engine server")
    p.add_argument("model", nargs="?", default=None)
    p.add_argument("--model", dest="model_flag", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--served-model-name", action="append", default=None)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--quantization", default=None, choices=["int8"],
                   help="weight-only quantization: int8 weights + "
                        "per-channel scales (llama family)")
    p.add_argument("--kv-cache-dtype", default="bf16",
                   choices=["bf16", "int8"],
                   help="KV cache storage dtype: int8 stores quantized "
                        "K/V pages with per-token per-kv-head f32 scales, "
                        "halving KV HBM traffic and roughly doubling KV "
                        "capacity at equal HBM budget")
    p.add_argument("--api-key", default=None,
                   help="require 'Authorization: Bearer <key>' on the "
                        "serving surface (default: VLLM_API_KEY / "
                        "TPU_STACK_API_KEY env; /health and /metrics "
                        "stay open)")
    p.add_argument("--max-model-len", type=int, default=2048)
    p.add_argument("--max-num-seqs", type=int, default=8)
    p.add_argument("--block-size", type=int, default=64)
    p.add_argument("--num-blocks", type=int, default=None)
    p.add_argument("--hbm-utilization", type=float, default=0.7)
    p.add_argument("--hbm-headroom-reserve", type=float, default=0.0,
                   help="GiB of per-device HBM kept free when auto-"
                        "sizing the KV pool (residual allocations "
                        "memory_stats misses); on ResourceExhausted the "
                        "pool additionally shrinks itself in retry "
                        "rungs instead of dying (single-host)")
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--pipeline-parallel-size", type=int, default=1,
                   help="stage-shard the layer stack over a pp mesh axis")
    p.add_argument("--pp-microbatches", type=int, default=0,
                   help="GPipe microbatches per forward (0 -> pp)")
    p.add_argument("--enable-prefix-caching", action="store_true", default=True)
    p.add_argument("--no-enable-prefix-caching", dest="enable_prefix_caching",
                   action="store_false")
    p.add_argument("--max-loras", type=int, default=8)
    p.add_argument("--max-lora-rank", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--kv-offload-gb", type=float, default=0.0,
                   help="host-RAM KV offload budget (0 disables)")
    p.add_argument("--kv-remote-url", default=None,
                   help="remote cache server URL (second offload tier)")
    p.add_argument("--prefill-chunk-size", type=int, default=1024,
                   help="long prompts prefill in chunks of this many "
                        "tokens (0 disables chunking)")
    p.add_argument("--enable-chunked-prefill", action="store_true",
                   default=False,
                   help="Sarathi-style chunked prefill: schedule prompt "
                        "prefills as bucket-snapped chunks interleaved "
                        "with decode steps, bounded per step by "
                        "--max-num-batched-tokens, so arrival bursts "
                        "cannot stall running decodes")
    p.add_argument("--max-num-batched-tokens", type=int, default=0,
                   help="per-step prefill token budget for chunked "
                        "prefill (0 with --enable-chunked-prefill: use "
                        "--prefill-chunk-size; setting this > 0 also "
                        "enables chunked prefill)")
    p.add_argument("--max-consecutive-prefills", type=int, default=2,
                   help="chunked prefill: force a decode step after this "
                        "many consecutive prefill steps while sequences "
                        "are running (the decode-starvation cap)")
    p.add_argument("--fused-step", action="store_true", default=False,
                   help="fused step program: when the chunked-prefill "
                        "scheduler has both a prefill plan and running "
                        "decodes, dispatch the prefill chunk span AND "
                        "the decode burst as ONE device program "
                        "(requires --enable-chunked-prefill; compiles "
                        "zero new variants)")
    p.add_argument("--speculative-num-tokens", type=int, default=0,
                   help="speculative decoding: verify up to this many "
                        "tokens per forward pass; 0 disables. Drafts come "
                        "from the draft model when "
                        "--speculative-draft-model is set, otherwise from "
                        "prompt lookup (an n-gram index over each "
                        "request's own prompt+output)")
    p.add_argument("--speculative-ngram-size", type=int, default=3,
                   help="n-gram length matched by the prompt-lookup "
                        "draft index (ignored when a draft model is "
                        "configured)")
    p.add_argument("--speculative-draft-model", default=None,
                   help="zoo model that drafts for the target (same "
                        "vocab; e.g. tpu-llama-1b drafting for "
                        "Llama-3-8B). Shares the mesh, runs its own "
                        "greedy draft programs against its own bf16 KV "
                        "pages; replaces the prompt-lookup proposer")
    p.add_argument("--speculative-draft-probation", type=int, default=64,
                   help="plain bursts after which a request whose "
                        "draft-model speculation was adaptively latched "
                        "off retries drafting (0 = latch is permanent, "
                        "as prompt-lookup latches always are)")
    p.add_argument("--structured-cache-size", type=int, default=32,
                   help="LRU capacity of the compiled structured-output "
                        "token-FSM cache (one entry per distinct "
                        "schema/regex per tokenizer)")
    p.add_argument("--prefill-batch", type=int,
                   default=EngineConfig.prefill_batch,
                   help="largest group of waiting uncached prompts of one "
                        "prefill bucket that share one [R, bucket] prefill "
                        "dispatch, R 4 or 2 by bucket (1 disables; see "
                        "EngineConfig.prefill_batch)")
    p.add_argument("--no-warmup", dest="warmup", action="store_false",
                   default=True,
                   help="skip precompiling serving programs at startup")
    p.add_argument("--kv-controller-url", default=None,
                   help="router URL to report KV admissions to "
                        "(enables kv-aware routing against this engine)")
    p.add_argument("--instance-id", default=None)
    p.add_argument("--kv-heartbeat-interval", type=float, default=10.0,
                   help="seconds between lease heartbeats to the KV "
                        "controller; the controller expires this "
                        "instance's claims after --kv-lease-misses "
                        "missed beats (0 disables heartbeating)")
    p.add_argument("--kv-resync-interval", type=float, default=60.0,
                   help="seconds between anti-entropy resync rounds "
                        "(digest compare + full-state replace on "
                        "mismatch) against the KV controller; heals "
                        "admit/evict reports lost to timeouts "
                        "(0 disables)")
    p.add_argument("--kv-pull-max-concurrency", type=int, default=8,
                   help="max concurrent /kv/pull transfers served before "
                        "excess pulls get 503 + Retry-After (the router "
                        "degrades them to recompute)")
    p.add_argument("--chat-template", default=None,
                   help="custom jinja chat-template file (HF checkpoints)")
    p.add_argument("--advertise-url", default=None,
                   help="URL the router should route to for this instance")
    p.add_argument("--trace-export", default=None,
                   help="export completed traces as OTLP-JSON: "
                        "'file:/path/traces.jsonl' (one line per trace) or "
                        "an 'http(s)://collector:4318/v1/traces' endpoint")
    p.add_argument("--slow-trace-threshold-s", type=float, default=0.0,
                   help="log one structured JSON line (full span timeline) "
                        "for any request slower than this many seconds; "
                        "0 disables")
    p.add_argument("--trace-buffer", type=int, default=512,
                   help="completed traces kept in the in-process flight "
                        "recorder, served at /debug/traces")
    p.add_argument("--trace-sample-rate", type=float, default=1.0,
                   help="fraction of requests whose traces are retained "
                        "and exported (deterministic by trace id, so the "
                        "router and engine keep the same requests); stage "
                        "rollup metrics still count every request")
    p.add_argument("--slow-trace-log-interval-s", type=float, default=0.0,
                   help="emit at most one slow-trace log line per this "
                        "many seconds (suppressed lines are still counted "
                        "as slow requests); 0 logs every slow trace")
    p.add_argument("--no-step-recorder", dest="step_recorder",
                   action="store_false", default=True,
                   help="disable the per-step flight recorder "
                        "(/debug/steps + tpu:step_* metrics)")
    p.add_argument("--step-record-capacity", type=int, default=1024,
                   help="step records kept in the flight-recorder ring")
    p.add_argument("--profile-dir", default=None,
                   help="directory for POST /debug/profile jax.profiler "
                        "artifacts (default: a per-process tempdir)")
    p.add_argument("--loop-monitor", action="store_true",
                   help="measure event-loop scheduling lag and detect "
                        "blocking calls on the server loop (watchdog "
                        "stack sampler); serves GET /debug/loop and the "
                        "tpu:event_loop_* metrics. Off = hot path "
                        "byte-identical")
    p.add_argument("--loop-stall-threshold-ms", type=float, default=100.0,
                   help="loop lag counted as a stall and sampled by the "
                        "blocking-call watchdog once the loop has not "
                        "ticked for this long")
    return p


def engine_config_from_args(args) -> EngineConfig:
    """The EngineConfig the server's parsed flags describe."""
    return EngineConfig(
        model=args.model_flag or args.model or "tiny-llama",
        dtype=args.dtype,
        quantization=args.quantization,
        kv_cache_dtype=args.kv_cache_dtype,
        prefill_chunk_size=args.prefill_chunk_size,
        prefill_batch=args.prefill_batch,
        enable_chunked_prefill=args.enable_chunked_prefill,
        max_num_batched_tokens=args.max_num_batched_tokens,
        max_consecutive_prefills=args.max_consecutive_prefills,
        fused_step=args.fused_step,
        max_model_len=args.max_model_len,
        max_num_seqs=args.max_num_seqs,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        hbm_utilization=args.hbm_utilization,
        hbm_headroom_reserve=int(args.hbm_headroom_reserve * (1 << 30)),
        tensor_parallel_size=args.tensor_parallel_size,
        pipeline_parallel_size=args.pipeline_parallel_size,
        pp_microbatches=args.pp_microbatches,
        enable_prefix_caching=args.enable_prefix_caching,
        max_loras=args.max_loras,
        max_lora_rank=args.max_lora_rank,
        seed=args.seed,
        speculative_num_tokens=args.speculative_num_tokens,
        speculative_ngram_size=args.speculative_ngram_size,
        speculative_draft_model=args.speculative_draft_model,
        speculative_draft_probation=args.speculative_draft_probation,
        structured_cache_size=args.structured_cache_size,
        kv_offload_bytes=int(args.kv_offload_gb * (1 << 30)),
        kv_remote_url=args.kv_remote_url,
        chat_template=args.chat_template,
        step_recorder=args.step_recorder,
        step_record_capacity=args.step_record_capacity,
    )


def engine_server_from_args(args, devices=None) -> EngineServer:
    """The EngineServer the server's parsed flags describe (built,
    warmed up and started). ``devices``: see :class:`EngineServer`."""
    return EngineServer(
        engine_config_from_args(args), args.served_model_name,
        warmup=args.warmup,
        kv_controller_url=args.kv_controller_url,
        instance_id=args.instance_id,
        advertise_url=args.advertise_url,
        api_key=args.api_key,
        kv_heartbeat_interval=args.kv_heartbeat_interval,
        kv_resync_interval=args.kv_resync_interval,
        kv_pull_max_concurrency=args.kv_pull_max_concurrency,
        trace_buffer=args.trace_buffer,
        slow_trace_threshold_s=args.slow_trace_threshold_s,
        trace_export=args.trace_export,
        trace_sample_rate=args.trace_sample_rate,
        slow_trace_log_interval_s=args.slow_trace_log_interval_s,
        profile_dir=args.profile_dir,
        loop_monitor=args.loop_monitor,
        loop_stall_threshold_ms=args.loop_stall_threshold_ms,
        devices=devices)


def main(argv: Optional[List[str]] = None) -> None:
    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    # Multi-host: join the jax.distributed job BEFORE any device use. The
    # engine's mesh then spans the global device set; follower processes
    # (process_id > 0) run the mirror loop instead of serving HTTP (the
    # reference's equivalent is a KubeRay worker pod, ray-cluster.yaml).
    from production_stack_tpu.parallel import multihost

    mh_env = multihost.initialize_from_env()
    args = build_arg_parser().parse_args(argv)
    if mh_env is not None and mh_env["process_id"] != 0:
        _run_follower(engine_config_from_args(args), args)
        return

    server = engine_server_from_args(args)

    async def _run():
        await run_engine_server(server, args.host, args.port)
        while True:
            await asyncio.sleep(3600)

    asyncio.run(_run())


def _run_follower(config: EngineConfig, args) -> None:
    """Follower process of a multi-host engine: build the identical core
    (its __init__ and warmup enqueue the same collective programs as the
    leader's), serve a bare /health for pod probes, then replay the
    leader's op stream until it stops."""
    core = EngineCore(config)
    if args.warmup:
        core.warmup()

    async def _health(request):
        return web.json_response({
            "status": "ok", "role": "follower",
            "process_id": core._mh.process_id,
            "num_processes": core._mh.num_processes,
        })

    async def _serve_health():
        app = web.Application()
        app.router.add_get("/health", _health)
        app.router.add_get("/healthz", _health)
        runner = web.AppRunner(app)
        await runner.setup()
        await web.TCPSite(runner, args.host, args.port).start()
        return runner

    loop = asyncio.new_event_loop()
    loop.run_until_complete(_serve_health())
    t = threading.Thread(target=loop.run_forever, daemon=True,
                         name="follower-health")
    t.start()
    try:
        core.run_follower()
    finally:
        loop.call_soon_threadsafe(loop.stop)


if __name__ == "__main__":
    main()
