"""loss_head_time_share — self time of the operations under the ``loss_head`` scope (the chunk scan of
``make_causal_lm_loss_sum``: head matmul, cross entropy, their recomputation
and backward) over the device's busy time.

BENCHMARK.json holds this metric's entries (``loss_head_time_share`` or ``loss_head_time_share.<tag>``,
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
    return None if sc is None else sc.share("loss_head")
