"""engine_step_ms_max — the longest step of the serve loop since its warm-up was
declared done, ms: the gauge ``serving/step_ms_max`` of the step account, on
the account's own monotonic clock round the whole of ``ServingEngine.step``.
It shows the holes that stay under the stall rule's 250 ms, which
``engine_stall_share`` does not count.  ``None`` where the program keeps no
such account.

BENCHMARK.json holds this metric's entries (``engine_step_ms_max`` or ``engine_step_ms_max.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "ms"
SOURCE = "program_counter"


def read(r):
    return r.counters.get("serving/step_ms_max")
