"""Shared scaffolding for the component profilers (decode_profile,
prefill_profile).

Timing: every timed sequence ENDS IN A REAL READBACK (np.asarray) and the
constant host<->device round trip is differenced out via two pipelined
runs of different depth (:func:`pipelined_seconds`).
"""

from __future__ import annotations

import time
from typing import Callable, List

from production_stack_tpu.obs.steps import device_hbm_bytes_per_s



def hbm_bytes_per_s():
    """Peak HBM bytes/s of the attached device, for roofline floors (the
    table and the TPU_STACK_HBM_GBS override the engine's step recorder
    reads). None off the TPU: there is no floor to compare with, and the
    profilers then leave their floors out."""
    import jax

    return device_hbm_bytes_per_s(jax.devices()[0])


def build_engine(model: str, *, max_model_len: int = 8192,
                 max_num_seqs: int = 16, decode_steps: int = 16,
                 num_blocks: int = 900, **overrides):
    """An :class:`EngineCore` at profiling shape (no HTTP server, no
    warmup — each profiled program compiles on first call)."""
    from production_stack_tpu.engine.config import EngineConfig
    from production_stack_tpu.engine.core import EngineCore

    return EngineCore(EngineConfig(
        model=model, max_model_len=max_model_len,
        max_num_seqs=max_num_seqs, decode_steps=decode_steps,
        max_loras=0, num_blocks=num_blocks, **overrides))


def pipelined_seconds(run: Callable, readback: Callable,
                      reps: int = 8) -> float:
    """Pipelined steady-state seconds per call of ``run``.

    ``run`` dispatches one program execution and returns something
    ``readback`` can force to the host (a REAL np.asarray readback, not
    block_until_ready — see module docstring). The first call compiles
    and settles; then walls of depth n1 and n2 are differenced so the
    constant RTT and dispatch overheads cancel.
    """
    readback(run())  # compile + settle
    walls = {}
    n1, n2 = 2, reps + 2
    for n in (n1, n2, n1, n2):
        t0 = time.perf_counter()
        last = None
        for _ in range(n):
            last = run()
        readback(last)
        walls.setdefault(n, []).append(time.perf_counter() - t0)
    return (min(walls[n2]) - min(walls[n1])) / (n2 - n1)


def install_params_holder() -> List:
    """Patch EngineCore.__init__ to stash every core's param tree in the
    returned list, so roofline floor calcs can size the weights after
    ``main()`` has freed the core. Call BEFORE building any engine."""
    import production_stack_tpu.engine.core as _c

    holder: List = []
    _orig_init = _c.EngineCore.__init__

    def _patched(self, *a, **kw):
        _orig_init(self, *a, **kw)
        holder.append(self.params)

    _c.EngineCore.__init__ = _patched
    return holder


def params_bytes(params) -> int:
    """Total bytes of a parameter tree (for weight-read floors)."""
    import jax

    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))
