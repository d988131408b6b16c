"""hbm_bytes_in_use_gib — memory_stats()['bytes_in_use'] of the fullest chip when
the window closes: what is resident (weights, optimizer state, page pool),
NOT a step's temporaries.

BENCHMARK.json holds this metric's entries (``hbm_bytes_in_use_gib`` or ``hbm_bytes_in_use_gib.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "device"
UNIT = "GiB"
SOURCE = "program_counter"

def read(r):
    b = r.counters.get("bytes_in_use")
    return b / 2 ** 30 if b else None
