"""sparse_topk_time_share — self time of CHOOSING among the scored blocks of the block-sparse
softmax layers over the device's busy time: every operation whose name stack
passes through the program's scope ``sparse_topk`` (the chosen set from the
block scores, and since PR 40 the decode's table of chosen pages).  The part
of ``sparse_select_time_share`` that is not the compressed keys or the
scores: until PR 40 a full sort of 328 (score, page) pairs a query row,
thirteen of that entry's fourteen points.
``None`` where no such operation ran.

BENCHMARK.json holds this metric's entries (``sparse_topk_time_share`` or ``sparse_topk_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

SCOPES = ("sparse_topk",)


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops
              if set(trace_scopes.components(op.tf_op)) & set(SCOPES))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
