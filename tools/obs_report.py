"""obs_report — merge a run's telemetry artifacts into one summary.

Reads whatever exists of: an obs run dir (``scalars.jsonl`` registry dumps,
``flight_record.json``, ``hlo_audit.jsonl``, timeline traces), extra scalar
streams (e.g. the trainer's ``--scalar-dir``), and extra timeline files —
and emits a single JSON summary (stdout or ``--out``) plus an optional
markdown rendering.  The "why was step N slow / why did the run die / how
many bytes did this program move / how much of each step was the host
blocked on the device" questions answered from artifacts alone — the async
hot path's ``train/host_blocked_ms`` / ``serving/host_blocked_ms`` and
``data/prefetch_*`` metrics surface in the histograms section, and
``health.host_blocked`` derives the per-subsystem blocked fraction.

Usage:
    python tools/obs_report.py --run-dir /runs/r1/obs
    python tools/obs_report.py --run-dir obs/ --scalar-dir /tb/run1 \
        --timeline trace.json --out report.json --markdown report.md
    python tools/obs_report.py --trace trace_events.jsonl \
        --serving-stats serving_stats.jsonl --markdown report.md
    python tools/obs_report.py --compare RUN_A RUN_B

The ``--trace`` section reconstructs per-request waterfalls from the
serving stack's ``trace_events.jsonl`` spans (queue / prefill / decode /
preempted milliseconds, failover hops, top-5 slowest requests), linked to
their terminal ``serving_stats`` records via ``trace_id``.

A FLEET run dir is auto-discovered: immediate subdirectories holding a
replica's ``scalars.jsonl`` / ``serving_stats.jsonl`` merge into one
report (per-replica counters and histogram buckets SUM, serving stats
concatenate, a top-level ``router_stats.jsonl`` rolls into the fleet
section), and every ``*alerts.jsonl`` (top level or per replica) builds
the "alerts" health section — firing count, worst severity, per-rule
firing edges and time-firing.

``--compare RUN_A RUN_B`` diffs two runs' resource ledgers and alerts
(``compile_ledger.jsonl`` + ``memory_breakdown.json`` + ``*alerts.jsonl``
in each dir): markdown table to stdout (or ``--markdown``), JSON via
``--out``, and a NONZERO exit code when run B regressed — more compiles
than ``(1 + --compile-regress-threshold) * A``, new compile storms, any
subsystem's peak bytes past ``(1 + --mem-regress-threshold) * A``'s, any
alert rule firing in B that never fired in A, or B's
autopilot action rate past ``(1 + --autopilot-regress-threshold) * A``'s
(a controller acting more often under the same workload is flapping or
fighting a real regression), weight-swap FAILURES appearing in B when
every swap in A committed, or any replica's weights_version going
non-monotonic in B (both threshold-free deploy gates) — so CI can gate
on it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # runnable as `python tools/obs_report.py`
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--run-dir", default=None,
                   help="obs output dir (scalars.jsonl / flight_record.json / "
                        "hlo_audit.jsonl / *trace*.json inside it)")
    p.add_argument("--scalar-dir", action="append", default=[],
                   help="extra dir holding a scalars.jsonl (repeatable)")
    p.add_argument("--scalars", action="append", default=[],
                   help="extra scalars.jsonl file (repeatable)")
    p.add_argument("--flight", default=None, help="flight_record.json path")
    p.add_argument("--hlo-audit", default=None, help="hlo_audit.jsonl path")
    p.add_argument("--timeline", action="append", default=[],
                   help="Chrome-trace timeline file (repeatable)")
    p.add_argument("--supervisor-events", default=None,
                   help="supervisor_events.jsonl path (restarts / crash "
                        "causes / time-to-recover; auto-detected in "
                        "--run-dir)")
    p.add_argument("--trace", action="append", default=[],
                   help="trace_events.jsonl file (repeatable; auto-detected "
                        "in --run-dir) — builds the per-request waterfall "
                        "section (queue/prefill/decode/preempted ms, top-5 "
                        "slowest with their span breakdown)")
    p.add_argument("--serving-stats", default=None,
                   help="serving_stats.jsonl path (v4 or v5; auto-detected "
                        "in --run-dir) — links trace waterfalls to their "
                        "terminal records via trace_id")
    p.add_argument("--compile-ledger", default=None,
                   help="compile_ledger.jsonl path (auto-detected in "
                        "--run-dir) — builds the compile health section")
    p.add_argument("--memory-breakdown", default=None,
                   help="memory_breakdown.json path (auto-detected in "
                        "--run-dir) — builds the memory health section")
    p.add_argument("--alerts", action="append", default=[],
                   help="alerts.jsonl file (repeatable; *alerts.jsonl "
                        "auto-detected in --run-dir and its replica "
                        "subdirs) — builds the alerts section (firing "
                        "count, worst severity, per-rule time-firing)")
    p.add_argument("--router-stats", default=None,
                   help="router_stats.jsonl path (auto-detected in "
                        "--run-dir) — rolls fleet terminal records into "
                        "the fleet section")
    p.add_argument("--autopilot", action="append", default=[],
                   help="autopilot_actions.jsonl file (repeatable; "
                        "*autopilot_actions.jsonl auto-detected in "
                        "--run-dir) — builds the autopilot section "
                        "(action table, per-trigger rollup, action rate)")
    p.add_argument("--weight-swaps", action="append", default=[],
                   help="weight_swaps.jsonl file (repeatable; "
                        "*weight_swaps.jsonl auto-detected in --run-dir "
                        "and its replica subdirs) — builds the weights "
                        "section (live-swap/failure counts by source, "
                        "per-replica version table, monotonicity check)")
    p.add_argument("--compare", nargs=2, metavar=("RUN_A", "RUN_B"),
                   default=None,
                   help="compile/memory regression diff between two run "
                        "dirs; nonzero rc when B regressed past the "
                        "thresholds")
    p.add_argument("--compile-regress-threshold", type=float, default=0.0,
                   help="--compare: allowed fractional growth in compile "
                        "count before rc 1 (default 0: any extra compile "
                        "regresses)")
    p.add_argument("--mem-regress-threshold", type=float, default=0.05,
                   help="--compare: allowed fractional growth in any "
                        "subsystem's peak bytes before rc 1 (default 5%%)")
    p.add_argument("--autopilot-regress-threshold", type=float, default=0.5,
                   help="--compare: allowed fractional growth in run B's "
                        "autopilot action rate over A's before rc 1 "
                        "(default 50%%; actions appearing in B when A "
                        "never acted regress threshold-free; only applies "
                        "when both runs carry autopilot action ledgers)")
    p.add_argument("--tail", type=int, default=10,
                   help="flight-record tail length in the summary")
    p.add_argument("--out", default=None, help="write JSON here (default stdout)")
    p.add_argument("--markdown", default=None, help="also write a markdown rendering")
    args = p.parse_args(argv)

    if args.compare:
        from neuronx_distributed_tpu.obs.report import compare_resources

        diff = compare_resources(
            args.compare[0], args.compare[1],
            compile_threshold=args.compile_regress_threshold,
            mem_threshold=args.mem_regress_threshold,
            autopilot_threshold=args.autopilot_regress_threshold)
        if args.out:
            doc = {k: diff[k] for k in ("a", "b", "compile", "memory",
                                        "alerts", "autopilot", "weights",
                                        "regressions", "regressed")}
            with open(args.out, "w") as f:
                f.write(json.dumps(doc, indent=2) + "\n")
        if args.markdown:
            with open(args.markdown, "w") as f:
                f.write(diff["markdown"])
        print(diff["markdown"])
        if diff["regressed"]:
            for r in diff["regressions"]:
                print(f"obs_report: REGRESSION: {r}", file=sys.stderr)
            return 1
        return 0

    if not (args.run_dir or args.scalar_dir or args.scalars or args.flight
            or args.hlo_audit or args.timeline or args.supervisor_events
            or args.trace or args.compile_ledger or args.memory_breakdown
            or args.alerts or args.router_stats
            or args.autopilot or args.weight_swaps):
        p.error("nothing to report on: pass --run-dir or explicit artifact paths")

    from neuronx_distributed_tpu.obs.report import build_report, render_markdown
    from neuronx_distributed_tpu.obs.schemas import validate_record

    scalar_paths = list(args.scalars)
    for d in args.scalar_dir:
        q = os.path.join(d, "scalars.jsonl")
        if os.path.exists(q):
            scalar_paths.append(q)
        else:
            print(f"obs_report: no scalars.jsonl in {d}", file=sys.stderr)

    report = build_report(
        run_dir=args.run_dir,
        scalar_paths=scalar_paths,
        flight_path=args.flight,
        hlo_audit_path=args.hlo_audit,
        timeline_paths=args.timeline,
        supervisor_events_path=args.supervisor_events,
        trace_paths=args.trace,
        serving_stats_path=args.serving_stats,
        compile_ledger_path=args.compile_ledger,
        memory_breakdown_path=args.memory_breakdown,
        alerts_paths=args.alerts,
        router_stats_path=args.router_stats,
        autopilot_paths=args.autopilot,
        weights_paths=args.weight_swaps,
        tail=args.tail,
    )
    validate_record("obs_report", report)  # the emitter honors its own schema

    text = json.dumps(report, indent=2, sort_keys=False)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    else:
        print(text)
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(render_markdown(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
