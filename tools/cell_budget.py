#!/usr/bin/env python3
"""What a benchmark cell costs BEFORE its window, read the way the driver
pays for it: the whole process's wall seconds, ``setup_s``, the compile
requests (and how many the persistent cache served) and the bytes the cell's
compile cache holds — from an EMPTY cache (cold), then again (warm).

    python3 tools/cell_budget.py --workload lfm2-8b-a1b.train-seq8k \
        [--root <another checkout>] [--seed n] [--seconds 45] [--runs 2]

Why a tool: PR 44's change was faster in its window and was refused all the
same — its compile grew ``setup_s`` past the cell's 10% bound, its run was
still going at the 360 s the driver gave it, and its executables filled the
192 MiB the chip machine lets a cache directory keep (past the cap the
least-recently-used entries go, and a cell whose programs outgrow it never
has a warm run).  Run this on the parent and on the change, in ONE chip
call, and compare the four numbers a run (``docs/OPERATIONS.md``, "A
cell's set-up budget").

The runs are child processes (this one never touches JAX, so the chip is
the child's); their output goes to ``chiprun_out/budget/`` and the last
line here is one JSON object.
"""

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_CAP_BYTES = 192 * 2 ** 20     # the chip machine's, per cache directory


def dir_bytes(path):
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, fs in os.walk(path) for f in fs)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--root", default=REPO,
                    help="the checkout whose benchmarks/run.py is run")
    ap.add_argument("--label", default=None)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--runs", type=int, default=2,
                    help="the first is cold, the others warm")
    args = ap.parse_args()

    root = os.path.abspath(args.root)
    label = args.label or os.path.basename(root)
    # inside the checkout that is run: the path is part of a cache key
    cache = os.path.join(root, ".jax_cache", "budget-" + args.workload)
    shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache)
    logs = os.path.join(REPO, "chiprun_out", "budget")
    os.makedirs(logs, exist_ok=True)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
    rows = []
    for i in range(args.runs):
        log = os.path.join(logs, f"{label}_{args.workload}_{i}.log")
        start = time.perf_counter()
        with open(log, "w") as out:
            rc = subprocess.call(
                [sys.executable, "benchmarks/run.py", "--workload",
                 args.workload, "--seed", str(args.seed + i), "--seconds",
                 str(args.seconds), "--trace", "0"],
                cwd=root, env=env, stdout=out, stderr=subprocess.STDOUT)
        wall = time.perf_counter() - start
        text = open(log).read()
        last = text.strip().rsplit("\n", 1)[-1]
        try:
            result = json.loads(last)
        except ValueError:
            result = {}
        metrics = {k: v["value"] for k, v in result.get("metrics", {}).items()}
        asked = re.search(r"compile requests (\d+) \((\d+) from the cache\)",
                          text)
        row = {"label": label, "run": "cold" if i == 0 else "warm",
               "rc": rc, "process_wall_s": round(wall, 1),
               "correct": result.get("correct"), **metrics,
               "compile_requests": asked and int(asked.group(1)),
               "from_cache": asked and int(asked.group(2)),
               "cache_bytes": dir_bytes(cache),
               "cache_files": len(os.listdir(cache)),
               "memory_peak_bytes": result.get("device", {}).get(
                   "memory_peak_bytes")}
        rows.append(row)
        print(f"[budget] {json.dumps(row)}", flush=True)
    over = [r for r in rows if r["cache_bytes"] > CACHE_CAP_BYTES]
    print(json.dumps({"workload": args.workload, "label": label,
                      "cache_cap_bytes": CACHE_CAP_BYTES,
                      "over_cap": bool(over), "runs": rows}))


if __name__ == "__main__":
    main()
