"""startup_compile_path_s — seconds of the start-up that JAX spent on its
compile path: tracing (self time: a jitted helper traced inside another's
trace counts once), lowering to MLIR, the compiler itself, and reading the
persistent cache in its place — the four startup/compile_ms_total/* of
the start-up account, summed; they are disjoint (JAX's compile duration holds
the cache's read; the account takes it out).  A second axis: these seconds
lie INSIDE the phases (weights, warmup, step0, process).  Warm,
what is left is mostly trace + lower, which no cache saves; cold, the
compiler's part is the cache's whole worth.  None where the program keeps
no such account.

BENCHMARK.json holds this metric's entry with its ``moves`` and ``workloads``; the
three constants below must agree with it (``benchmarks/tests/test_manifest.py``).
"""

from benchmarks.harness import startup_account

LAYER = "compiled programs"
UNIT = "s"
SOURCE = "program_counter"


def read(r):
    snap = startup_account.snapshot()
    if snap is None:
        return None
    return sum(startup_account.by(snap, startup_account.STAGE_MS).values())
