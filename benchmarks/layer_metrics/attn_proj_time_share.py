"""attn_proj_time_share — self time of the operations inside an attention module but outside its
kernel and its cache write (q/k/v and output projections, RoPE, layout
changes around the kernel) over the device's busy time.

BENCHMARK.json holds this metric's entries (``attn_proj_time_share`` or ``attn_proj_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    return None if sc is None else sc.share("attn_proj")
