#!/usr/bin/env python
"""spread.py — medians and spreads of the result lines that
``measure_cells.sh`` wrote, as the driver reads them: per cell, per set and
per end-to-end metric the median and the spread (distance between the
quartiles over the median); the wider of the two sets' spreads; the bound
the rule gives (five times the widest spread over the cells, never under
1%); and how far the second set's median is from the first's.

    python benchmarks/tools/spread.py chiprun_out/measure/*.jsonl
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import stats  # noqa: E402


def main(paths):
    widest = {}
    for path in paths:
        cell = os.path.basename(path)[:-len(".jsonl")]
        rows = [json.loads(line) for line in open(path) if line.strip()]
        sets = {}
        for r in rows:
            if r["set"] and r.get("correct"):
                for name, m in r["metrics"].items():
                    sets.setdefault(name, {}).setdefault(r["set"], []).append(
                        m["value"])
        bad = [r["seed"] for r in rows if r["set"] and not r.get("correct")]
        print(f"{cell}: {sum(1 for r in rows if r['set'])} runs"
              + (f", NOT correct: seeds {bad}" if bad else ""))
        for name, by_set in sorted(sets.items()):
            med = {s: stats.median(v) for s, v in by_set.items()}
            spr = {s: stats.spread(v) for s, v in by_set.items()}
            wide = max(spr.values())
            shift = (abs(med[2] - med[1]) / med[1]
                     if 1 in med and 2 in med else float("nan"))
            print(f"  {name}: " + "; ".join(
                f"set {s} n={len(by_set[s])} median {med[s]:.4f} spread "
                f"{100 * spr[s]:.2f}%" for s in sorted(by_set))
                + f"; wider {100 * wide:.2f}%; set 2 vs set 1 "
                  f"{100 * shift:.2f}%")
            if name != "setup_s":
                widest[name] = max(widest.get(name, 0.0), wide)
    for name, wide in sorted(widest.items()):
        print(f"bound for {name}: 5 x {100 * wide:.2f}% = "
              f"{100 * max(5 * wide, 0.01):.2f}% (driver accepts "
              f"{100 * max(2 * wide, 0.01):.2f}% .. "
              f"{100 * max(8 * wide, 0.01):.2f}%)")


if __name__ == "__main__":
    main(sys.argv[1:])
