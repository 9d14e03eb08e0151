"""Device-to-device KV pipe over ``jax.experimental.transfer`` (DCN).

The reference moves disaggregated-prefill KV device-to-device through a
NIXL/UCX side channel wired into its engine pods
(``helm/templates/deployment-vllm-multi.yaml:267-305``,
``examples/disaggregated_prefill/pd.yaml``). This is the TPU-native
equivalent: each engine process runs a ``TransferServer`` bound to its
PJRT client, the prefill side parks the gathered KV pages as *device*
arrays awaiting pull, and the decode side pulls them straight into its own
device memory over the transfer runtime — no host staging, no HTTP body.

Availability: the transfer runtime needs
``PJRT_Client_CreateBuffersForAsyncHostToDevice`` from the backend, and a
failed pull can fatally abort the *process* (a CHECK in the bulk-transport
layer), so it is never tried in the serving process. Where the platform
can be opened by more than one process (the CPU) a pair of throwaway
children round-trips a pull once and the answer is cached. A TPU belongs
to the one process that holds it: a child that needs it can only fail or
hang, so there nothing is probed and the pipe is on only where the
deployment says so (``TPU_STACK_KV_DEVICE_PIPE=1``). When unavailable,
callers fall back to the zero-copy TKV2 HTTP relay
(:mod:`production_stack_tpu.kv.offload`).
"""

from __future__ import annotations

import itertools
import os
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

# The probe runs the REAL topology — offerer and puller in separate
# processes (engines are separate processes; a same-process loopback
# pull succeeds on runtimes whose cross-process transport is broken, so
# probing loopback would steer engines onto a crashing path). Both
# children are pinned to the CPU: the probe only ever runs there.
_PROBE_OFFER = r"""
import sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.experimental import transfer
srv = transfer.start_transfer_server(jax.devices()[0].client)
x = jnp.arange(2048, dtype=jnp.bfloat16).reshape(2, 32, 32)
srv.await_pull(1, [x])
with open(sys.argv[1], "w") as f:
    f.write(srv.address())
time.sleep(60)
"""

_PROBE_PULL = r"""
import sys
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.experimental import transfer
with open(sys.argv[1]) as f:
    addr = f.read().strip()
srv = transfer.start_transfer_server(jax.devices()[0].client)
conn = srv.connect(addr)
x = jnp.arange(2048, dtype=jnp.bfloat16).reshape(2, 32, 32)
spec = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
out = conn.pull(1, [spec])
assert bool(jnp.all(out[0] == x))
print("DEVICE_PIPE_OK")
"""

_probe_result: Optional[bool] = None
_probe_lock = threading.Lock()


def device_pipe_available(timeout: float = 120.0) -> bool:
    """True when the device pipe may be used by this process.

    ``TPU_STACK_KV_DEVICE_PIPE=0|1`` decides where set. Otherwise, on
    the CPU, a pair of child processes round-trips one pull (a failing
    pull can fatally abort the process, not just raise) and the answer
    is cached for the engine's lifetime; on any other platform this
    process holds the device, no child could open it, and the answer is
    no."""
    global _probe_result
    override = os.environ.get("TPU_STACK_KV_DEVICE_PIPE")
    if override is not None:
        return override not in ("0", "false", "off")
    with _probe_lock:
        if _probe_result is None:
            import jax

            platform = jax.devices()[0].platform
            if platform != "cpu":
                _probe_result = False
                logger.info(
                    "KV device pipe off, not probed: this process holds "
                    "the %s and a probing child could not open it; "
                    "handoffs take the HTTP relay "
                    "(TPU_STACK_KV_DEVICE_PIPE=1 turns the pipe on)",
                    platform)
                return _probe_result
            offerer = None
            try:
                import tempfile

                with tempfile.TemporaryDirectory() as d:
                    addr_file = os.path.join(d, "addr")
                    offerer = subprocess.Popen(
                        [sys.executable, "-c", _PROBE_OFFER, addr_file],
                        stdout=subprocess.DEVNULL,
                        stderr=subprocess.DEVNULL,
                    )
                    deadline = time.monotonic() + timeout / 2
                    while (not os.path.exists(addr_file)
                           or not open(addr_file).read().strip()):
                        if (offerer.poll() is not None
                                or time.monotonic() > deadline):
                            raise RuntimeError("probe offerer died")
                        time.sleep(0.1)
                    proc = subprocess.run(
                        [sys.executable, "-c", _PROBE_PULL, addr_file],
                        capture_output=True, timeout=timeout,
                    )
                    _probe_result = b"DEVICE_PIPE_OK" in proc.stdout
            except Exception:  # noqa: BLE001 - treat as unavailable
                _probe_result = False
            finally:
                if offerer is not None and offerer.poll() is None:
                    offerer.kill()
            logger.info("KV device pipe %s",
                        "available" if _probe_result else
                        "unavailable (falling back to HTTP relay)")
        return _probe_result


class KVDevicePipe:
    """One per engine process: offers extracted KV pages for pull and
    pulls offered pages from peers, all as device arrays."""

    # Offers not pulled within this window are dropped from OUR table (the
    # decode side re-requests through the HTTP fallback on miss). NOTE:
    # expiry does NOT reclaim HBM — the experimental transfer API has no
    # await_pull cancel, so the server-side registration keeps the device
    # buffers alive until the peer pulls or the process exits. The
    # MAX_PENDING_OFFERS cap below bounds that pinned memory: offer()
    # refuses when full and the caller falls back to the HTTP relay.
    OFFER_TTL_SEC = 120.0

    # Upper bound on concurrently registered (offered, not yet released)
    # page bundles. At the default disagg shapes one bundle is tens of MB,
    # so 8 bounds pinned HBM to a few hundred MB worst case.
    MAX_PENDING_OFFERS = 8

    def __init__(self, listen: str = "0.0.0.0:0"):
        import jax
        from jax.experimental import transfer

        self._transfer = transfer
        self._server = transfer.start_transfer_server(
            jax.devices()[0].client, listen)
        self._uuid = itertools.count(int(time.time() * 1000) % (1 << 30))
        # uuid -> (arrays, deadline): keeps device buffers alive until
        # pulled or expired.
        self._pending: Dict[int, Tuple[Any, float]] = {}
        # uuids with a live await_pull registration. Unlike _pending this
        # never decays with the TTL (expiry cannot unregister buffers);
        # entries leave only via release() of that exact uuid, so
        # duplicate/bogus release calls cannot undercount pinned HBM.
        self._registered: set = set()
        self._conns: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def address(self) -> str:
        return self._server.address()

    def offer(self, arrays: List[Any]) -> Optional[int]:
        """Park device arrays for a peer to pull; returns the pull uuid,
        or None when MAX_PENDING_OFFERS registrations are already
        outstanding (un-released) — the caller must fall back to the HTTP
        relay rather than pin more HBM behind an uncancellable
        await_pull."""
        now = time.monotonic()
        with self._lock:
            self._pending = {
                u: (a, dl) for u, (a, dl) in self._pending.items()
                if dl > now
            }
            if len(self._registered) >= self.MAX_PENDING_OFFERS:
                logger.warning(
                    "KV device pipe: %d offers outstanding, refusing new "
                    "offer (HTTP relay fallback)", len(self._registered))
                return None
            uuid = next(self._uuid)
            self._registered.add(uuid)
            self._pending[uuid] = (arrays, now + self.OFFER_TTL_SEC)
        try:
            self._server.await_pull(uuid, arrays)
        except Exception:  # noqa: BLE001 - registration failed: no pin
            with self._lock:
                self._registered.discard(uuid)
                self._pending.pop(uuid, None)
            raise
        return uuid

    def release(self, uuid: int) -> None:
        """Mark an offer consumed (peer pulled it, or the handoff was
        abandoned and the puller told us). Frees a MAX_PENDING_OFFERS
        slot; the device buffers themselves are reclaimed by the transfer
        server once pulled."""
        with self._lock:
            self._pending.pop(uuid, None)
            self._registered.discard(uuid)

    def pull(self, address: str, uuid: int, specs: List[Any]) -> List[Any]:
        """Pull device arrays matching ``specs`` (ShapeDtypeStructs with
        shardings) from the peer transfer server at ``address``."""
        with self._lock:
            conn = self._conns.get(address)
            if conn is None:
                conn = self._server.connect(address)
                self._conns[address] = conn
        return conn.pull(uuid, specs)
