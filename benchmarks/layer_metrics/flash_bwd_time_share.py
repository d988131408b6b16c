"""flash_bwd_time_share — self time of the two backward flash kernels (Mosaic calls ``flash_dq`` and
``flash_dkv``) over the device's busy time.

BENCHMARK.json holds this metric's entries (``flash_bwd_time_share`` or ``flash_bwd_time_share.<tag>``,
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
    return None if sc is None else sc.share("flash_bwd")
