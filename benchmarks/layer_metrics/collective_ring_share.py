"""collective_ring_share — the seconds of ``collective-permute`` operations (sync,
``-start`` and ``-done`` forms) over the seconds of all collective operations,
mean over chips: how much of the collective time goes through rings of
permutes (the compiler's own windowed einsums today: no code of the package
issues a permute on the tensor axes).  ``None`` on one chip, without a trace,
or where no collective ran.

BENCHMARK.json holds this metric's entries (``collective_ring_share`` or ``collective_ring_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "collectives"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_reduce

PERMUTES = ("collective-permute", "collective-permute-start",
            "collective-permute-done")


def is_permute(text: str) -> bool:
    return trace_reduce.opcode(text) in PERMUTES


def read(r):
    if r.trace is None or r.chips < 2:
        return None
    collective_s = r.trace.time_of(trace_reduce.is_collective)
    if not collective_s:
        return None
    return 100.0 * r.trace.time_of(is_permute) / collective_s
