"""moe_assignments_held_share — of the (token, expert) assignments the routers made, the
share that went to an expert whose weights this chip HOLDS:
``moe/assignments_held_total`` over ``moe/assignments_total``, the program's
counters over the whole run (warm-up, lead-in and window; fed by the
per-layer loads that ride the step's token fetch).  A chip that holds 64 of
128 experts under a near-uniform router reads ~50%: the share sees the load
its rank of the deployment would.  ``None`` where the program does not count
held assignments (a program older than the counter).

BENCHMARK.json holds this metric's entries (``moe_assignments_held_share`` or
``moe_assignments_held_share.<tag>``, one per end-to-end metric it moves) with
their ``moves`` and ``workloads``; the three constants below must agree with
them (``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "program_counter"


def read(r):
    total = r.counters.get("moe/assignments_total")
    held = r.counters.get("moe/assignments_held_total")
    if not total or held is None:
        return None
    return 100.0 * held / total
