"""compiles_in_window — compile requests JAX made between the window's opening
and the end of the run (the benchmark's jax.monitoring listener; for serving
also the program's compile ledger, the larger of the two).  Must be 0, else
the run is not correct.

BENCHMARK.json holds this metric's entries (``compiles_in_window`` or ``compiles_in_window.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "compiled programs"
UNIT = "count"
SOURCE = "program_counter"

def read(r):
    return r.counters.get("compiles_in_window")
