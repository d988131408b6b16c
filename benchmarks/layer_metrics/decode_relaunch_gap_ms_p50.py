"""decode_relaunch_gap_ms_p50 — median over the engine steps of the traced
window of the first chip's idle time between the end of the program the step's
``nxd/serve/fetch`` waited for (the last one launched by the step before from
its ``nxd/serve/dispatch``) and the start of the first program the step's own
``dispatch`` launched: the per-token round trip through the host (ROADMAP S4),
at a named boundary and on the device's clock alone.  Programs are matched to
the spans that launched them by run id, not by time.

BENCHMARK.json holds this metric's entries (``decode_relaunch_gap_ms_p50`` or ``decode_relaunch_gap_ms_p50.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "ms"
SOURCE = "device_trace"

from benchmarks.harness import stats, trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    gaps = sc.relaunch_gaps() if sc is not None else []
    return stats.median([g * 1e3 for g, _ in gaps]) if gaps else None
