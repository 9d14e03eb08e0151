"""Run a cell's proof sets the way the driver's check does: two sets of
runs with the same seeds, each run its own process, then each metric's
spreads in each set (:func:`spreads`). Never imports JAX: the runs hold
the chip, one after the other.

    python -m chipbench.tools.sets --workload <cell> --seeds 1,2,3,4,5,6 --seconds 51 [--traced-seed 7] --out chiprun_out/<dir>
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def one(workload, seed, seconds, trace, log_path):
    t0 = time.time()
    with open(log_path, "w") as log:
        done = subprocess.run(
            [sys.executable, "-m", "chipbench.run", "--workload", workload,
             "--seed", str(seed), "--seconds", str(seconds), "--trace",
             str(trace)], stdout=subprocess.PIPE, stderr=log, text=True)
    lines = [ln for ln in done.stdout.splitlines() if not ln.startswith("[")]
    result = None
    if done.returncode == 0 and lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    notes = [ln for ln in lines[:-1]
             if ln.startswith(("compared:", "setup:", "check numbers:"))]
    return {"seed": seed, "trace": trace, "rc": done.returncode,
            "wall_s": round(time.time() - t0, 1), "result": result,
            "notes": notes}


def spreads(values):
    """A set's spread as a share of its median, three ways. ``quartiles``:
    distance between the first and third quartile of
    ``statistics.quantiles(n=4)``, which the builder's contract names and
    the check reads for "too loose". ``less_farthest``: the same with the
    run farthest from the median left out, which the check reads for "too
    tight" (mean of the two sets). ``range_less_farthest``: largest less
    smallest of those remaining runs, the widest reading of what the
    ledger's refusals report. A bound has to stand under all three."""
    def quartile_distance(v):
        q = statistics.quantiles(v, n=4)
        return q[2] - q[0]
    median = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - median))[:-1]
    out = {"quartiles": quartile_distance(values) / median}
    if len(rest) >= 2:
        out["less_farthest"] = quartile_distance(rest) / median
        out["range_less_farthest"] = (max(rest) - min(rest)) / median
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=51)
    p.add_argument("--traced-seed", type=int, default=None)
    p.add_argument("--out", required=True)
    a = p.parse_args()
    os.makedirs(a.out, exist_ok=True)
    seeds = [int(s) for s in a.seeds.split(",")]
    runs = []
    for k in range(2):
        for i, seed in enumerate(seeds):
            r = one(a.workload, seed, a.seconds, 0,
                    os.path.join(a.out, f"set{k}_{i}.err"))
            r["set"] = k
            runs.append(r)
            print("run: " + json.dumps(r), flush=True)
            with open(os.path.join(a.out, "runs.jsonl"), "a") as f:
                f.write(json.dumps(r) + "\n")
    if a.traced_seed is not None:
        r = one(a.workload, a.traced_seed, a.seconds, 1,
                os.path.join(a.out, "traced.err"))
        r["set"] = "traced"
        print("run: " + json.dumps(r), flush=True)
        with open(os.path.join(a.out, "runs.jsonl"), "a") as f:
            f.write(json.dumps(r) + "\n")
    good = [r for r in runs if r["result"]]
    names = sorted({n for r in good for n in r["result"]["metrics"]})
    for name in names:
        row = {"metric": name}
        for k in range(2):
            vals = [r["result"]["metrics"][name]["value"] for r in good
                    if r["set"] == k and name in r["result"]["metrics"]]
            if name == "setup_s":
                vals = vals[1:] if k == 0 else vals  # the first run compiles
            if len(vals) >= 2:
                row[f"set{k}"] = {"median": statistics.median(vals),
                                  "spread": spreads(vals), "n": len(vals),
                                  "values": vals}
        print("spread: " + json.dumps(row), flush=True)
    print("correct:", [r["result"]["correct"] if r["result"] else None
                       for r in runs], flush=True)


if __name__ == "__main__":
    main()
