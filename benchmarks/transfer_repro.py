#!/usr/bin/env python3
"""Minimal reproduction of the KV device pipe's blocker: does
jax.experimental.transfer round-trip on a backend (PARITY.md "Known
gaps")?

Runs the canonical transfer-server handshake in a child process (a failed
pull CHECK-aborts the process, so the probe must be crash-isolated) on a
chosen backend and prints the exact failure. The parent never touches
JAX, so the child may take the chip.

  python benchmarks/transfer_repro.py cpu    # CPU PJRT plugin
  python benchmarks/transfer_repro.py tpu    # the attached chip
"""

from __future__ import annotations

import os
import subprocess
import sys

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))

_CHILD = r"""
import sys
backend = sys.argv[1]
import jax
if backend == "cpu":
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
print("jax", jax.__version__, "backend", jax.devices()[0].platform,
      flush=True)
from jax.experimental import transfer
# Step 1: create a transfer server (this alone fails on both runtimes).
srv = transfer.start_transfer_server(jax.devices()[0].client)
print("server address:", srv.address(), flush=True)
# Step 2: offer an array and pull it back through the loopback.
x = jnp.arange(8.0)
uuid = 7
srv.await_pull(uuid, [x])
conn = srv.connect(srv.address())
from jax.sharding import SingleDeviceSharding
aval = jax.ShapeDtypeStruct(
    x.shape, x.dtype, sharding=SingleDeviceSharding(jax.devices()[0]))
out = conn.pull(uuid, [aval])
print("pulled:", [o.tolist() for o in out], flush=True)
"""


def main() -> None:
    backend = sys.argv[1] if len(sys.argv) > 1 else "cpu"
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, backend],
        env=env, capture_output=True, text=True, timeout=300)
    print(proc.stdout)
    if proc.returncode != 0:
        print(f"--- exit code {proc.returncode} ---")
        print(proc.stderr[-3000:])


if __name__ == "__main__":
    main()
