"""state_bytes_share — the gauge ``kvcache/state_bytes`` (the recurrent
layers' state rows, every slot's, as the engine built them) over the device
memory in use when the window closed (``bytes_in_use``): how much of what the
chip holds is per-sequence state that no page pool accounts for.  ``None``
for a program without the gauge or a run without the memory reading.

BENCHMARK.json holds this metric's entries (``state_bytes_share`` or ``state_bytes_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kv cache"
UNIT = "%"
SOURCE = "program_counter"

def read(r):
    state = r.counters.get("kvcache/state_bytes")
    in_use = r.counters.get("bytes_in_use")
    if not state or not in_use:
        return None
    return 100.0 * state / in_use
