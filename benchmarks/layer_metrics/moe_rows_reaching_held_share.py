"""moe_rows_reaching_held_share — of the rows a group-limited router routed (a valid token
in a routed layer), the share with AT LEAST ONE assignment to an expert
whose weights this chip holds: ``moe/rows_reaching_held_total`` over
``moe/rows_routed_total``, the program's counters over the whole run (warm-up,
lead-in and window; fed by the per-layer loads that ride the step's token
fetch).  A chip that holds one routing group of ``n_group`` under a router
that keeps ``topk_group`` groups a row reads about ``topk_group /
n_group`` where the router treats its groups alike (3/8 = 37.5% at
DeepSeek-V2's sizes; a kept group need not win an expert, but its best one
is among the row's three highest group scores and nearly always does):
what an exchange would send this rank, and the share of rows that
do routed work here at all — the rest pass the shared expert alone.
``None`` where the program does not count them (no group limit, no held
share, or a program older than the counters).

BENCHMARK.json holds this metric's entries (``moe_rows_reaching_held_share`` or
``moe_rows_reaching_held_share.<tag>``, one per end-to-end metric it moves)
with their ``moves`` and ``workloads``; the three constants below must agree
with them (``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"


def read(r):
    routed = r.counters.get("moe/rows_routed_total")
    reached = r.counters.get("moe/rows_reaching_held_total")
    if not routed or reached is None:
        return None
    return 100.0 * reached / routed
