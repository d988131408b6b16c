"""startup_cache_misses — programs the persistent compilation cache did not
hold before ready and had to be compiled (and were written to it):
startup/cache_misses_total of the start-up account, JAX's own
/jax/compilation_cache/cache_misses events.  0 on a warm run of unchanged
code, but for programs the caller's reference check compiles anew; on a run
whose cache directory was not empty, a miss is a program whose key moved (the
side of a pair that runs second, an orphaned entry): the [startup] line
names the programs with the most compile seconds.  None where the program
keeps no such account.

BENCHMARK.json holds this metric's entry with its ``moves`` and ``workloads``; the
three constants below must agree with it (``benchmarks/tests/test_manifest.py``).
"""

from benchmarks.harness import startup_account

LAYER = "compiled programs"
UNIT = "count"
SOURCE = "program_counter"


def read(r):
    snap = startup_account.snapshot()
    if snap is None:
        return None
    return snap["startup/cache_misses_total"]
