"""sampler_time_share — self time of the sampler and the token pack (scopes ``sample`` and
``pack_tokens`` of ``serving/engine.py``) over the device's busy time.

BENCHMARK.json holds this metric's entries (``sampler_time_share`` or ``sampler_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    return None if sc is None else sc.share("sample")
