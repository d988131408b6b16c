"""attn_window_time_share — self time of the paged-attention calls and the attention projections
(q/k/v and output projections, RoPE, layout changes around the kernel) of
the WINDOW layers — told by ``model/layer_N`` of the operation's name stack
against the configuration's ``sliding_window`` list
(``harness/window_flops.py``) — over the device's busy time.

BENCHMARK.json holds this metric's entries (``attn_window_time_share`` or ``attn_window_time_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "model"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import window_flops


def read(r):
    return window_flops.attention_share(r, "window")
