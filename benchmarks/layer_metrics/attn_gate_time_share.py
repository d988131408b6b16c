"""attn_gate_time_share — self time of the attention layers' OUTPUT GATE over the
device's busy time: every operation whose name stack passes through the
program's scope ``attn_gate`` (the gate's projection as wide as q, its
sigmoid and the product with the attention's output before ``o_proj``).
``None`` where no such operation ran (a model without the gate, a program
older than the scope).

BENCHMARK.json holds this metric's entries (``attn_gate_time_share`` or ``attn_gate_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
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
              if "attn_gate" in trace_scopes.components(op.tf_op))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
