"""hc_time_share — self time of the hyper-connected residual over the device's busy time:
every operation whose name stack passes through one of the program's scopes
``hc_maps`` (the streams' norm, the matmul against ``phi``, the sigmoids),
``hc_sinkhorn`` (the residual map's sweeps) or ``hc_mix`` (what a sublayer
reads of the streams, what it writes back, the read-out before the head).
``None`` where no such operation ran (a model with one residual stream).

BENCHMARK.json holds this metric's entries (``hc_time_share`` or ``hc_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("hc_maps", "hc_sinkhorn", "hc_mix")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops
              if set(trace_scopes.components(op.tf_op)) & set(SCOPES))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
