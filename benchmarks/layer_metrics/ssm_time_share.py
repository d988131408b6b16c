"""ssm_time_share — self time of the Mamba-2 layers' CORE over the device's busy time:
every operation whose name stack passes through one of the program's scopes
``ssm_conv``, ``ssm_scan_chunk``, ``ssm_step``, ``state_read`` or
``state_write`` — the convolution with its carried taps, the chunked scan,
its one-token step and the state rows' traffic; NOT the layer's projections,
gate and norm, which are matmuls and elementwise work like any layer's.
``None`` where no such operation ran (a model without these layers, a
program older than the scopes).

BENCHMARK.json holds this metric's entries (``ssm_time_share`` or ``ssm_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("ssm_conv", "ssm_scan_chunk", "ssm_step", "state_read",
          "state_write")


def core_ops(dev):
    return [op for op in dev.ops
            if set(trace_scopes.components(op.tf_op)) & set(SCOPES)]


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in core_ops(d))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
