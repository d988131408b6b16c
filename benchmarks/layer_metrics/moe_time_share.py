"""moe_time_share — self time of every operation of the routed expert blocks (router,
sort and gather, grouped matmuls, activation, un-sort and weighted sum) over
the device's busy time.  An operation belongs to the expert block when its
name stack (``tf_op``) passes through one of the program's scopes
``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine`` or through
the module ``moe_mlp`` itself.  ``None`` where no such operation ran (a dense
model, or a program older than the scopes).

BENCHMARK.json holds this metric's entries (``moe_time_share`` or ``moe_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          "moe_mlp")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops
              if set(trace_scopes.components(op.tf_op)) & set(SCOPES))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
