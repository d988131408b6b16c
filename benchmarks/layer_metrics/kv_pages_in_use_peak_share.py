"""kv_pages_in_use_peak_share — largest kvcache/pages_in_use over
kvcache/pages_total seen after a step of the window.

BENCHMARK.json holds this metric's entries (``kv_pages_in_use_peak_share`` or ``kv_pages_in_use_peak_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kv cache"
UNIT = "%"
SOURCE = "program_counter"

def read(r):
    xs = r.samples.get("pages_in_use_share", [])
    return max(xs) if xs else None
