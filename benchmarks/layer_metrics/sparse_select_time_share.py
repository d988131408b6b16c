"""sparse_select_time_share — self time of the block selection of the block-sparse softmax
layers over the device's busy time: every operation whose name stack passes
through one of the program's scopes ``sparse_compress`` (the compressed keys
a call completes), ``sparse_score`` (the queries against the compressed keys,
the softmax, the block maxima) or ``sparse_topk`` (the chosen set).  What the
selection costs, beside what it saves in ``sparse_attn_time_share``.
``None`` where no such operation ran.

BENCHMARK.json holds this metric's entries (``sparse_select_time_share`` or ``sparse_select_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("sparse_compress", "sparse_score", "sparse_topk")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops
              if set(trace_scopes.components(op.tf_op)) & set(SCOPES))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
