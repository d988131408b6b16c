"""gdn_time_share — self time of the gated-delta layers' CORE over the device's
busy time: every operation whose name stack passes through one of the
program's scopes ``gdn_conv``, ``gdn_chunk``, ``gdn_step``, ``state_read`` or
``state_write`` — the convolution with its carried taps, the chunked form of
the delta rule (its block operands, the inverse, the walk over the blocks),
the one-token step and the state rows' traffic; NOT the layer's projections
(``gdn_proj``), gates (``gdn_gates``) and gated norm (``gdn_norm``), which are
matmuls and elementwise work like any layer's.  ``None`` where no such
operation ran (a model without these layers, a program older than the
scopes).

BENCHMARK.json holds this metric's entries (``gdn_time_share`` or ``gdn_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("gdn_conv", "gdn_chunk", "gdn_step", "state_read", "state_write")
OWN = ("gdn_conv", "gdn_chunk", "gdn_step")


def core_ops(dev, scopes=SCOPES):
    """The device's operations under ``scopes`` — none where no operation
    ran under a scope that is the delta layers' OWN (``state_read`` and
    ``state_write`` are every recurrent kind's names)."""
    ops = [(op, set(trace_scopes.components(op.tf_op))) for op in dev.ops]
    if not any(names & set(OWN) for _, names in ops):
        return []
    return [op for op, names in ops if names & set(scopes)]


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in core_ops(d))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
