"""window_pages_held_share — ``kvcache/window_pages_held_total`` over
``kvcache/window_pages_unfreed_total``: summed a step over the live slots, the
pages their window layers hold beside what the same slots would hold were the
window only a mask (a request's whole worst case from its admission);
booked where the pages are given back (``serving/paged.py``).  Lower is
better; None for a program without the counters.

BENCHMARK.json holds this metric's entries (``window_pages_held_share`` or ``window_pages_held_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kv cache"
UNIT = "%"
SOURCE = "program_counter"

def read(r):
    held = r.counters.get("kvcache/window_pages_held_total")
    unfreed = r.counters.get("kvcache/window_pages_unfreed_total")
    if not unfreed or held is None:
        return None
    return 100.0 * held / unfreed
