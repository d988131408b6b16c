"""sparse_attn_time_share — self time of the attention over SELECTED blocks over the
device's busy time: the kernels ``sparse_attention_decode`` (a walk over a
table of chosen pages a (slot, kv head)) and ``sparse_attention_chunk`` (the
chunk walk under a per-(row, page) mask), found by their names in the
operation's name stack, with the operations that build their tables and
masks under the same names.  ``None`` where no such kernel ran.

BENCHMARK.json holds this metric's entries (``sparse_attn_time_share`` or ``sparse_attn_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes

KERNELS = ("sparse_attention_decode", "sparse_attention_chunk")


def kernel_of(op):
    """Which of the two kernels an operation is (by its name stack), or None."""
    parts = trace_scopes.components(op.tf_op)
    return next((k for k in KERNELS if k in parts), None)


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    own = sum(op.own for d in sc.devices for op in d.ops if kernel_of(op))
    return 100.0 * own / len(sc.devices) / sc.busy_s if own else None
