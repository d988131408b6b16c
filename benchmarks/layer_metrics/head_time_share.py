"""head_time_share — self time of the operations whose name stack passes through the model's
head (``lm_head``: the ``[rows, hidden] x [hidden, vocabulary]`` product of
every program that returns logits) over the device's busy time
(``harness/trace_scopes.py`` holds the table: group ``head``).  A decode
applies the head to its slots' rows and a speculative verify to every row;
a prefill chunk to the ONE row its prompt's first token is sampled from, and
to none where it is not the prompt's last (PR 37: before it, to all 512).

BENCHMARK.json holds this metric's entries (``head_time_share`` or ``head_time_share.<tag>``,
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
    return None if sc is None else sc.share("head")
