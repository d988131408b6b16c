"""flash_fwd_time_share — self time of the forward flash kernel (Mosaic call ``flash_fwd``) over the
device's busy time.  With the remat policy of the train cells the forward runs
once a layer a step (``flash_fwd_roofline`` prints the count).

BENCHMARK.json holds this metric's entries (``flash_fwd_time_share`` or ``flash_fwd_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    return None if sc is None else sc.share("flash_fwd")
