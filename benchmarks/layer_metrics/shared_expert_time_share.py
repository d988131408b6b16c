"""shared_expert_time_share — self time of the routed blocks' SHARED expert over the
device's busy time: every operation whose name stack passes through the
program's scope ``moe_shared`` (its up and down matmuls and the activation
between them; every row passes it, so it is a dense MLP beside the routed
experts).  ``None`` where no such operation ran (a mixture without a shared
expert, a program older than the scope).

BENCHMARK.json holds this metric's entries (``shared_expert_time_share`` or
``shared_expert_time_share.<tag>``, one per end-to-end metric it moves) with
their ``moves`` and ``workloads``; the three constants below must agree with
them (``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops
              if "moe_shared" in trace_scopes.components(op.tf_op))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
