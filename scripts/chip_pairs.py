"""A builder's pairs of parent and change in one chip call (first used by
PR 31): the parent commit unpacked in ``_parent/``, the change from
``git archive $(git write-tree)`` in ``_proof/`` (both git-ignored), each run
its own process of ``python -m chipbench.run`` from its own tree. Never
imports JAX: the runs hold the chip one after the other.

    chiprun --timeout 3600 -- python scripts/chip_pairs.py chiprun_out/<dir> <cell> PCCPPC <first-seed>

P / C: an untraced run of the parent / the change; T: a traced run of the
change. The P and C of a pair share a seed (``<first-seed> + 10 + pair``),
every other run counts up from ``<first-seed>``. A P or C run whose
``setup_s`` shows that it compiled (no cache from an earlier call) is kept
apart (``left_out``) and made again, once a tree. Six warm runs of one cell
take about 25 minutes, two more that compile about 16. Writes
``<cell>.<order>.json`` (every run's whole result object) and each run's
standard error beside it (3.4 MB a run; with it the parent's tree read its
warm set-up 9% slower than the change's at PR 31, with ``/dev/null`` the
same: PERF.md section 6).
"""
import json
import os
import subprocess
import sys
import time

out, cell, order, seed0 = sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4])
root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # the repo
os.makedirs(out, exist_ok=True)
trees = {"P": "_parent", "C": "_proof", "T": "_proof"}
seed, pair_seed, in_pair = seed0, {}, 0
results = []
queue = list(order)
n = -1
repeated = set()
while queue:
    kind = queue.pop(0)
    n += 1
    if kind in "PC":
        pair = in_pair // 2
        in_pair += 1
        s = pair_seed.setdefault(pair, seed0 + 10 + pair)
    else:
        s = seed
        seed += 1
    tree = os.path.join(root, trees[kind])
    t0 = time.time()
    log = os.path.join(out, f"{cell}.{n}{kind}.err")
    with open(log, "w") as err:
        done = subprocess.run(
            [sys.executable, "-m", "chipbench.run", "--workload", cell,
             "--seed", str(s), "--seconds", "51", "--trace",
             "1" if kind == "T" else "0"],
            cwd=tree, stdout=subprocess.PIPE, stderr=err, text=True)
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
    result = json.loads(lines[-1]) if done.returncode == 0 and lines else None
    rec = {"n": n, "kind": kind, "tree": trees[kind], "cell": cell,
           "seed": s, "rc": done.returncode,
           "wall_s": round(time.time() - t0, 1), "result": result}
    results.append(rec)
    brief = None
    if result:
        brief = {k: v["value"] for k, v in result["metrics"].items()
                 if k in ("out_tokens_per_s", "tpot_p50_s", "itl_p99_s",
                          "setup_s")}
        brief.update(correct=result["correct"], failed=result["failed"],
                     device=result["device"].get("kind"))
        # A run that compiled (no cache from an earlier call) is kept apart
        # and made again, once a tree.
        if (kind in "PC" and brief.get("setup_s", 0) > 300
                and trees[kind] not in repeated):
            repeated.add(trees[kind])
            rec["left_out"] = "compiled"
            in_pair -= 1
            queue.insert(0, kind)
    with open(os.path.join(out, f"{cell}.{order}.json"), "w") as f:
        json.dump(results, f, indent=1)
    print(kind, s, done.returncode, rec["wall_s"], rec.get("left_out", ""),
          json.dumps(brief), flush=True)
    if result is None:
        with open(log) as f:
            print(f.read()[-3000:], flush=True)
