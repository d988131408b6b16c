"""moe_dispatch_time_share — self time of what the routed expert blocks do that is NOT
their matmuls — the router and top-k (scope ``moe_router``), the sort by
expert and the gather of rows (``moe_dispatch``), the un-sort and the
weighted sum (``moe_combine``) — over the device's busy time: what a naive
dispatch costs beside the grouped matmuls it feeds.  Classified by each
operation's own name stack; ``None`` where no such operation ran.

BENCHMARK.json holds this metric's entries (``moe_dispatch_time_share`` or ``moe_dispatch_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("moe_router", "moe_dispatch", "moe_combine")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops
              if set(trace_scopes.components(op.tf_op)) & set(SCOPES))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
