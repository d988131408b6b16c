"""How much ``served_tokens_per_s`` of a backlog cell depends on WHERE its
window lies: one run of the cell with a long window (``--seconds``, say 110),
every first-token event of it kept, then the cell's own estimator
(``serve_runner.served_rate``) over each window of ``--window`` seconds (the
manifest's ``run_seconds``) that the long one holds, a quarter of a second
apart — as if the mix's ``lead_in_s`` had been that much longer.

A closed loop on a periodic mix is timed over whole blocks on the premise that
it settles into a cycle one block long.  Where it does, every window reads the
same rate; where it does not (the fill's transient, or first tokens that do not
repeat block by block), the table shows by how much a lead-in, or a later
change of the step's time, moves the reading with nothing else changed, and
``edge_s`` how near an event sits to either edge (an event a run-to-run drift
can carry across an edge).

    chiprun -- python3 benchmarks/tools/served_rate_phase.py \\
        --workload smallthinker-21b-a3b.serve-longdocs --seed 7 --seconds 110

The events and the table go to ``chiprun_out/phase/<cell>.json`` and the table
to stdout after the run's own result line (which is over the LONG window: not
a result of the cell).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def windows(m: dict, width: float, step: float = 0.25):
    """``served_rate`` over each ``[a, a + width]`` inside the run's window:
    rows ``(seconds after the window's opening, rate, events, seconds from
    either edge to its nearest event)``."""
    from benchmarks.harness.serve_runner import served_rate

    lo, hi = m["win"]
    events = sorted(m["first_token_events"])
    rows, a = [], lo
    while a + width <= hi + 1e-9:
        inside = [e for e in events if a <= e[0] <= a + width]
        rate = served_rate({**m, "first_token_events": inside})
        edge = min((abs(e[0] - x) for e in events for x in (a, a + width)),
                   default=None)
        rows.append((round(a - lo, 3), rate, len(inside), edge))
        a += step
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=110.0)
    ap.add_argument("--window", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks import run as bench
    from benchmarks.harness import manifest, serve_runner
    from benchmarks.harness.common import log

    width = args.window or float(
        manifest.Cell(args.workload).manifest["run_seconds"])
    kept = {}
    rate = serve_runner.served_rate

    def keeping(m):
        kept.update(m)
        return rate(m)

    serve_runner.served_rate = keeping
    sys.argv = [sys.argv[0], "--workload", args.workload, "--seed",
                str(args.seed), "--seconds", str(args.seconds), "--trace", "0"
                ] + ["--rehearse"] * args.rehearse
    try:
        bench.main()
    finally:
        serve_runner.served_rate = rate
    if not kept.get("block"):
        raise SystemExit(f"{args.workload}: no periodic mix, no served rate")
    rows = windows(kept, width)
    rates = [r for _, r, _, _ in rows if r]
    log(f"[phase] {len(kept['first_token_events'])} first tokens in "
        f"{args.seconds} s; served rate over {len(rows)} windows of {width} s:"
        f" {min(rates):.1f} .. {max(rates):.1f} "
        f"({100 * (max(rates) - min(rates)) / min(rates):.2f}% apart)")
    for a, r, n, edge in rows:
        log(f"[phase] +{a:6.2f} s  {r:10.1f} tokens/s  {n:3d} events  "
            f"nearest to an edge {edge:.3f} s")
    out = os.path.join(ROOT, "chiprun_out", "phase")
    os.makedirs(out, exist_ok=True)
    lo = kept["win"][0]
    with open(os.path.join(out, args.workload + ".json"), "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "seconds": args.seconds, "window": width,
                   "block": kept["block"],
                   "block_tokens": kept["block_tokens"],
                   "events": [[t - lo, seq] for t, _, seq in
                              sorted(kept["first_token_events"])],
                   "windows": rows}, f)


if __name__ == "__main__":
    main()
