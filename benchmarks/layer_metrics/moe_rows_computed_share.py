"""moe_rows_computed_share — of the (token, expert) assignment rows the
routers made, the share that the routed blocks PASSED OVER after the sort
(gathered, multiplied, activated, combined): ``moe/rows_computed_total`` over
``moe/assignments_total``, the program's counters over the whole run (fed by
what rides the step's loss fetch).  A block that runs once over its whole
sorted array reads 100; a block that holds a share of its experts and
computes over the rows it holds reads the spans that ran — at 8 of 32
experts held and a first span of 18,432 of 65,536 rows, ~28.  Beside
``moe_assignments_held_share`` (the rows that HAD to be computed) it says how
much slack is left.  ``None`` where the program does not count the rows (a
program older than the counter).

BENCHMARK.json holds this metric's entries (``moe_rows_computed_share`` or
``moe_rows_computed_share.<tag>``, one per end-to-end metric it moves) with
their ``moves`` and ``workloads``; the three constants below must agree with
them (``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"


def read(r):
    total = r.counters.get("moe/assignments_total")
    computed = r.counters.get("moe/rows_computed_total")
    if not total or computed is None:
        return None
    return 100.0 * computed / total
