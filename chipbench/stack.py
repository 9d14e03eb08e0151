"""The system under test, started as a deployment starts it: the engine
server built from its own flags, the router in front with static
discovery, both on loopback ports in this process — the only one that
touches JAX, so the only one that holds the chip. (The pattern is
``chip_smoke.py``'s ``Stack``; copied, not imported, so that the
yardstick does not move when that file does.)
"""

from __future__ import annotations

import json
import os
import time

from chipbench.registry import model_keys


def write_model_dir(config: dict, work: str, name: str) -> str:
    """A model directory holding ``config.json`` with the model's keys
    of the configuration as published, nested blocks included: the
    program reads a local directory like a HuggingFace checkpoint and,
    finding no weights there, draws them from its ``--seed``."""
    path = os.path.join(work, "models", name)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(model_keys(config), f, indent=1, sort_keys=True)
    return path


def seeded_weights(args, seed: int):
    """The parameter tree the engine serves, drawn from ``seed`` on the
    device by the program's own init (and quantisation) in one jitted
    call, then parked on the host. The key is an argument, so the program
    is the same for every seed and comes from the compile cache."""
    import jax

    from production_stack_tpu.engine.server import engine_config_from_args
    from production_stack_tpu.models import build_model, get_model_config

    cfg = engine_config_from_args(args)
    mc = get_model_config(cfg.model).replace(dtype=cfg.dtype)
    init_fn, _ = build_model(mc)
    # every init of the program takes these; one without adapters drops them
    lora = ({"lora_slots": cfg.max_loras, "lora_rank": cfg.max_lora_rank}
            if cfg.max_loras > 0 else {})

    def init(key):
        params = init_fn(mc, key, **lora)
        if cfg.quantization == "int8":
            from production_stack_tpu.models.quantize import quantize_tree

            params = quantize_tree(
                params, mc.arch, quantize_embeddings=cfg.quantize_embeddings)
        return params

    on_device = jax.jit(init)(jax.random.key(seed))
    on_host = jax.device_get(on_device)
    for leaf in jax.tree_util.tree_leaves(on_device):
        leaf.delete()
    return on_host


class Stack:
    """``seed`` makes the weights. The server's own ``--seed`` stays at
    its default: the program bakes it into every step program as a
    constant (``engine/core.py``, ``seed_static``), so a server seed that
    changed from run to run would compile every program anew in every
    run. The engine is built and warmed up as deployed, then its
    parameters are replaced by the tree drawn from ``seed``: same shapes,
    types and placement, so every warmed program serves it."""

    def __init__(self, model_dir: str, served_name: str, flags, seed: int,
                 devices=None):
        import jax

        from production_stack_tpu.engine.server import (
            build_arg_parser,
            engine_server_from_args,
        )

        self.served_name = served_name
        self.args = build_arg_parser().parse_args(
            [model_dir, "--served-model-name", served_name, *flags])
        t0 = time.time()
        weights = seeded_weights(self.args, seed)
        self.weights_seconds = time.time() - t0
        self.server = engine_server_from_args(self.args, devices=devices)
        self.core = self.server.core
        t1 = time.time()
        for leaf in jax.tree_util.tree_leaves(self.core.params):
            leaf.delete()
        self.core.params = jax.device_put(weights,
                                          self.core._param_shardings)
        jax.block_until_ready(self.core.params)
        self.weights_seconds += time.time() - t1
        self.start_seconds = time.time() - t0
        self._runners = []

    async def start(self) -> None:
        from aiohttp import web

        from production_stack_tpu.engine.server import run_engine_server
        from production_stack_tpu.router.app import build_app
        from production_stack_tpu.router.parser import build_parser

        runner = await run_engine_server(self.server, "127.0.0.1", 0)
        self._runners.append(runner)
        port = list(runner.sites)[0]._server.sockets[0].getsockname()[1]
        self.engine_url = f"http://127.0.0.1:{port}"
        rargs = build_parser().parse_args([])
        rargs.service_discovery = "static"
        rargs.static_backends = self.engine_url
        rargs.static_models = self.served_name
        rargs.routing_logic = "roundrobin"
        router = web.AppRunner(build_app(rargs))
        await router.setup()
        site = web.TCPSite(router, "127.0.0.1", 0)
        await site.start()
        self._runners.append(router)
        self.url = "http://127.0.0.1:%d" % (
            site._server.sockets[0].getsockname()[1])

    async def stop(self) -> None:
        for runner in reversed(self._runners):
            await runner.cleanup()
        self._runners = []
        self.core.stop()

    def free_device_memory(self) -> None:
        """Give parameters and pages back to the device (for a second
        engine in this process, which is sized from what is free)."""
        import gc

        import jax

        for leaf in jax.tree_util.tree_leaves((self.core.params,
                                               self.core.kv)):
            leaf.delete()
        self.core.params = self.core.kv = None
        gc.collect()
