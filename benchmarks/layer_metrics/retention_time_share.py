"""retention_time_share — self time of the power-retention CORE over the
device's busy time: every operation whose name stack passes through one of
the program's scopes ``retention_chunk`` or ``retention_step`` (the two
Pallas kernels are named so, and the XLA operations around them — a block's
own square, the normaliser, a step's ``phi`` rows — run under the same
scopes) or ``state_read`` / ``state_write`` (a state row sliced out and
written back where no kernel runs); NOT the layer's projections, norms and
RoPE (``retention_proj``, ``retention_norm``), which are matmuls and
elementwise work like any layer's.  ``None`` where no such operation ran (a
model without these layers, a program older than the scopes).

BENCHMARK.json holds this metric's entries (``retention_time_share`` or ``retention_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("retention_chunk", "retention_step", "state_read", "state_write")


def core_ops(dev, scopes=SCOPES):
    return [op for op in dev.ops
            if set(trace_scopes.components(op.tf_op)) & set(scopes)]


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in core_ops(d))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
