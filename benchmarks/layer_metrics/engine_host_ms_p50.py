"""engine_host_ms_p50 — median over the engine steps of the traced window of the
step's own host time: the length of its ``nxd/serve/step`` span less its
blocking ``nxd/serve/fetch`` spans (the collect's, and the first-token fetch of
a prompt whose last chunk ran in the step).  When it nears
``engine_step_ms_p50`` the host sets the pace, not the device.

BENCHMARK.json holds this metric's entries (``engine_host_ms_p50`` or ``engine_host_ms_p50.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "ms"
SOURCE = "program_span"

from benchmarks.harness import stats, trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    steps = sc.steps() if sc is not None else []
    return stats.median([s.host_s * 1e3 for s in steps]) if steps else None
