"""Find a cell's knee: one engine, one set-up, several offered rates.

    python -m chipbench.sweep --workload <name> --seed <n> --seconds 30 --rates 3,4,5,6,7,8

Each rate gets the cell's own traffic file with ``params.rate_per_s``
replaced, a fresh preload, ramp and window, and one line of JSON: the
end-to-end numbers, the share of requests that met the traffic file's
limits, and the engine's queue at the window's end (a backlog that grows
with the rate's window is past the knee). The knee is the highest rate at
which at least 90% of the requests due met both limits and the queue was
empty at the end; the cell runs at the highest swept rate not above four
fifths of it. Re-run when an optimisation has moved the knee (nearly
every request meets its limits): a later ``benchmark`` PR then sets the
new rate. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import json

from chipbench import timeline
from chipbench.registry import REPO
from chipbench.run import Cell


def main(argv=None, **kwargs) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--rates", required=True)
    p.add_argument("--root", default=REPO)
    a = p.parse_args(argv)
    cell = Cell(a.workload, a.seed, root=a.root, **kwargs)

    async def session_main():
        await cell.stack.start()
        try:
            for rate in [float(r) for r in a.rates.split(",")]:
                traffic = copy.deepcopy(cell.traffic)
                traffic["params"]["rate_per_s"] = rate
                out = await cell.measure(a.seconds, False, traffic=traffic,
                                         check=False)
                stats = cell.stack.core.stats()
                line = {"rate_per_s": rate, "attempted": out["attempted"],
                        "failed": out["failed"],
                        "waiting_at_end": stats["num_requests_waiting"],
                        "running_at_end": stats["num_requests_running"],
                        **{k: v["value"] for k, v in out["metrics"].items()},
                        **out.get("extra", {})}
                print("sweep: " + json.dumps(line), flush=True)
                await asyncio.sleep(2.0)
        finally:
            await cell.stack.stop()

    asyncio.run(session_main())


if __name__ == "__main__":
    main()
