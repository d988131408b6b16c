"""conv_time_share — self time of every operation of the gated short-convolution mixers
(LFM2's ``conv`` layers: the input projection, the two gates, the causal
depthwise taps, the output projection; forward, recomputation and backward)
over the device's busy time.  An operation belongs to the mixer when its
name stack (``tf_op``) passes through one of the program's scopes
``conv_in``, ``conv_gate``, ``conv_taps`` or ``conv_out``
(``models/hybrid.py::ConvMixer``).  ``None`` where no such operation ran (a
model without the mixer, or a program older than the scopes).

BENCHMARK.json holds this metric's entries (``conv_time_share`` or ``conv_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("conv_in", "conv_gate", "conv_taps", "conv_out")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops
              if set(trace_scopes.components(op.tf_op)) & set(SCOPES))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
