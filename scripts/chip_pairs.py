"""A builder's pairs of parent and change in one chip call (first used by
PR 31): the parent commit unpacked in ``_parent/``, the change from
``git archive $(git write-tree)`` in ``_proof/`` (both git-ignored), each run
its own process of ``python -m chipbench.run``. Never imports JAX: the
runs hold the chip one after the other. Both sides run from one directory,
``_run/`` (git-ignored; made here as a copy of ``_proof/``), into which the
side's ``production_stack_tpu/`` is copied before each run: the compile
cache keys a program by its source's paths and lines, so two trees share
no entry, and a configuration whose programs fill the machine's cache
alone compiled anew at every switch of sides. A change that moves no
traced line (PR 39) shares every program with the parent there, and the
sides can alternate at no cost.

    chiprun --timeout 3600 -- python scripts/chip_pairs.py chiprun_out/<dir> <cell> PCCPPC <first-seed>

P / C: an untraced run of the parent / the change; T / Q: a traced run of
the change / the parent (for Q lay the change's ``BENCHMARK.json`` and
``chipbench/`` over ``_parent/`` first, as the driver does, so that a new
reader meets the parent's trace). The P and C of a pair share a seed (``<first-seed> + 10 + pair``),
every other run counts up from ``<first-seed>``. A P or C run whose
``setup_s`` shows that it compiled (no cache from an earlier call) is kept
apart (``left_out``) and made again, once a tree. Six warm runs of one cell
take about 25 minutes, two more that compile about 16. Writes
``<cell>.<order>.json`` (every run's whole result object). A run's
standard error (3.4 MB) goes to the machine's temporary directory and only
the end of a failed run's is printed: a dozen of them beside the results
are most of the 64 MiB that a call may bring back (PR 37 lost a call's
results that way).
"""
import filecmp
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

out, cell, order, seed0 = sys.argv[1], sys.argv[2], sys.argv[3], int(sys.argv[4])
root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # the repo
os.makedirs(out, exist_ok=True)
trees = {"P": "_parent", "C": "_proof", "T": "_proof", "Q": "_parent"}
run_tree = os.path.join(root, "_run")
if not os.path.isdir(run_tree):
    shutil.copytree(os.path.join(root, "_proof"), run_tree)


def place(tree: str) -> None:
    """Make ``_run/``'s program that of ``tree``, file by file."""
    src = os.path.join(root, tree, "production_stack_tpu")
    for folder, _, files in os.walk(src):
        dst = os.path.join(run_tree, "production_stack_tpu",
                           os.path.relpath(folder, src))
        os.makedirs(dst, exist_ok=True)
        for name in files:
            a, b = os.path.join(folder, name), os.path.join(dst, name)
            if not (os.path.exists(b) and filecmp.cmp(a, b, shallow=False)):
                shutil.copy(a, b)


seed, made = seed0, {"P": 0, "C": 0}
results = []
queue = list(order)
n = -1
repeated = set()
while queue:
    kind = queue.pop(0)
    n += 1
    if kind in "PC":
        s = seed0 + 10 + made[kind]
        made[kind] += 1
    else:
        s = seed
        seed += 1
    place(trees[kind])
    t0 = time.time()
    log = os.path.join(tempfile.gettempdir(), f"{cell}.{n}{kind}.err")
    with open(log, "w") as err:
        done = subprocess.run(
            [sys.executable, "-m", "chipbench.run", "--workload", cell,
             "--seed", str(s), "--seconds", "51", "--trace",
             "1" if kind in "TQ" else "0"],
            cwd=run_tree, stdout=subprocess.PIPE, stderr=err, text=True)
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
    result = json.loads(lines[-1]) if done.returncode == 0 and lines else None
    rec = {"n": n, "kind": kind, "tree": trees[kind], "cell": cell,
           "seed": s, "rc": done.returncode,
           "wall_s": round(time.time() - t0, 1), "result": result}
    results.append(rec)
    brief = None
    if result:
        brief = {k: v["value"] for k, v in result["metrics"].items()
                 if kind in "TQ" or k in ("out_tokens_per_s", "tpot_p50_s",
                                          "itl_p99_s", "setup_s")}
        brief.update(correct=result["correct"], failed=result["failed"],
                     device=result["device"].get("kind"))
        # A run that compiled (no cache from an earlier call) is kept apart
        # and made again, once a tree.
        if (kind in "PC" and brief.get("setup_s", 0) > 300
                and trees[kind] not in repeated):
            repeated.add(trees[kind])
            rec["left_out"] = "compiled"
            made[kind] -= 1
            queue.insert(0, kind)
    with open(os.path.join(out, f"{cell}.{order}.json"), "w") as f:
        json.dump(results, f, indent=1)
    print(kind, s, done.returncode, rec["wall_s"], rec.get("left_out", ""),
          json.dumps(brief), flush=True)
    if result is None:
        with open(log) as f:
            print(f.read()[-3000:], flush=True)
