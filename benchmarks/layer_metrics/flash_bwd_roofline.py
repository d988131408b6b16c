"""flash_bwd_roofline — least time the chip could take for ONE backward of the
flash kernels (``flash_dq`` then ``flash_dkv``: 5 of the 7 matmuls of
``flops.flash_train_flops`` over the attended keys of one layer; q, k, v, o, do
read and dq, dk, dv written once) over the measured time of the pair.  The two
kernels together run 7 matmuls (each recomputes QK^T and dP): the least time
counts what the algorithm needs.

BENCHMARK.json holds this metric's entries (``flash_bwd_roofline`` or ``flash_bwd_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.layer_metrics.flash_fwd_roofline import BACKWARD, roofline


def read(r):
    return roofline(r, BACKWARD)
