"""moe_experts_hit_share — of the experts of the routed blocks that ran with at least one
token, the share that was given a row: ``moe/experts_hit_total`` over
``moe/layer_calls_total`` x ``num_experts``, the program's counters over the
whole run (warm-up, lead-in and window; fed by the per-layer loads that ride
the step's token fetch).  The weights of an expert that is hit are read
whatever it is given, so this is the share of the expert weights a call
reads: ~88% for 16 decode rows of 8 in 64, 100% for a chunk.  ``None``
where the program counts no expert block.

BENCHMARK.json holds this metric's entries (``moe_experts_hit_share`` or ``moe_experts_hit_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"


def read(r):
    calls = r.counters.get("moe/layer_calls_total")
    hit = r.counters.get("moe/experts_hit_total")
    if not calls or hit is None:
        return None
    return 100.0 * hit / (calls * r.cell.config["num_experts"])
