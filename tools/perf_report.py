"""perf_report — where did the device time go, and was it well spent?

Reads one or more ``perf_attribution.jsonl`` streams (written by a run
with the perf profiler on: ``Observability(perf=True)`` for ``fit()``,
``ServingEngine(perf=...)`` for a serving run) and answers the
three bottleneck questions from the artifact alone:

- **top time-eaters** — families ranked by accounted device time;
- **how far off roofline** — achieved vs the device's lower-bound time
  (compute- or bandwidth-limited, whichever dominates at the family's
  arithmetic intensity);
- **what bounds them** — compute- vs memory-bound per family, so the fix
  is obvious: memory-bound wants quantized KV / bigger pages / batch,
  compute-bound wants better kernels or more chips.

Multiple files (e.g. the per-replica streams of a fleet run) merge
additively — calls, device time, flops and bytes SUM and the roofline
numbers are recomputed against the merged totals.

Usage:
    python tools/perf_report.py RUN_DIR          # *perf_attribution.jsonl
    python tools/perf_report.py a.jsonl b.jsonl  # explicit streams
    python tools/perf_report.py RUN_DIR --json   # machine-readable summary
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # runnable as `python tools/perf_report.py`
    sys.path.insert(0, REPO)


def _discover(paths) -> list:
    """Expand dirs to their ``*perf_attribution.jsonl`` files."""
    out = []
    for p in paths:
        if os.path.isdir(p):
            out += sorted(glob.glob(os.path.join(p, "*perf_attribution.jsonl")))
            out += sorted(glob.glob(
                os.path.join(p, "*", "*perf_attribution.jsonl")))
        else:
            out.append(p)
    return out


def _fmt_intensity(v) -> str:
    return "n/a" if v is None else f"{v:,.1f}"


def render(summary: dict, top: int) -> str:
    """Human rendering: the rollup verdict first, then the per-family
    table sorted by device time (the top time-eaters)."""
    lines = [f"device: {summary['device']}"]
    roll = summary.get("rollup")
    if roll:
        ceiling = (f", tokens/s ceiling {roll['toks_per_s_ceiling']:,.0f}"
                   if roll.get("toks_per_s_ceiling") else "")
        lines.append(
            f"rollup: {roll['device_ms']:,.1f} ms accounted, "
            f"MFU {roll['mfu']:.1%}, MBU {roll['mbu']:.1%}, "
            f"{roll['pct_roofline']:.1%} of roofline "
            f"({roll['bound']}-bound{ceiling})")
    lines += ["",
              "| family | calls | device ms | intensity | bound "
              "| % roofline | MFU | MBU |",
              "|---|---|---|---|---|---|---|---|"]
    fams = sorted(summary["families"].items(),
                  key=lambda kv: -kv[1]["device_ms"])
    for fam, f in fams[:top]:
        lines.append(
            f"| {fam} | {f['calls']:,.0f} | {f['device_ms']:,.1f} "
            f"| {_fmt_intensity(f['arithmetic_intensity'])} | {f['bound']} "
            f"| {f['pct_roofline']:.1%} | {f['mfu']:.1%} | {f['mbu']:.1%} |")
    if len(fams) > top:
        lines.append(f"| ... {len(fams) - top} more | | | | | | | |")
    lines.append("")
    for fam, f in fams[:top]:
        gap = 1.0 - f["pct_roofline"]
        hint = ("stream fewer bytes: quantized KV, larger pages, batching"
                if f["bound"] == "memory"
                else "more math throughput: kernel tuning, larger tiles")
        lines.append(f"- {fam}: {gap:.0%} of its device time is headroom "
                     f"({f['bound']}-bound — {hint})")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("paths", nargs="+",
                   help="perf_attribution.jsonl files and/or run dirs "
                        "(dirs expand to their *perf_attribution.jsonl, "
                        "one level of replica subdirs included)")
    p.add_argument("--top", type=int, default=10,
                   help="families shown in the table (default 10)")
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable summary instead of "
                        "the rendered table")
    p.add_argument("--out", default=None,
                   help="also write the JSON summary here")
    args = p.parse_args(argv)

    from neuronx_distributed_tpu.obs.aggregate import merge_perf_files
    from neuronx_distributed_tpu.obs.perf import summarize_perf

    paths = _discover(args.paths)
    missing = [q for q in paths if not os.path.exists(q)]
    if missing:
        print(f"perf_report: missing: {', '.join(missing)}", file=sys.stderr)
        return 2
    summary = summarize_perf(merge_perf_files(paths))
    if summary is None:
        print("perf_report: no attribution records in "
              f"{', '.join(paths) or 'the given paths'}", file=sys.stderr)
        return 2

    doc = {"sources": paths, **summary}
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(doc, indent=2) + "\n")
    if args.json:
        print(json.dumps(doc, indent=2))
    else:
        print(render(summary, args.top), end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
