"""moe_expert_load_max_over_mean — the program's gauge ``moe/expert_load_max_over_mean``: per layer, the
assignments the busiest expert took over those of the mean expert, summed
since the engine began; the mean over layers.  1.0 is a perfectly even
router; the seeded random router of a benchmark run is near it, a trained
one is not.  The skew is reported, never imposed.  ``None`` where the
program has no such gauge.

BENCHMARK.json holds this metric's entries (``moe_expert_load_max_over_mean`` or ``moe_expert_load_max_over_mean.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "ratio"
SOURCE = "program_counter"


def read(r):
    return r.counters.get("moe/expert_load_max_over_mean")
