"""startup_weights_s — seconds of the start-up inside the calls that make the
model's state: startup/ms_total/weights (init_sharded_params,
initialize_parallel_model, checkpoint loads and conversions) plus
startup/ms_total/optimizer (initialize_parallel_optimizer) of the
start-up account.  HOST time: tracing the initialiser, its compile or cache
read, the dispatch.  The device's fill runs on after the call returns and is
waited for by whoever reads the weights first (the reference check, in
process).  None where the program keeps no such account.

BENCHMARK.json holds this metric's entry with its ``moves`` and ``workloads``; the
three constants below must agree with it (``benchmarks/tests/test_manifest.py``).
"""

from benchmarks.harness import startup_account

LAYER = "model"
UNIT = "s"
SOURCE = "program_counter"


def read(r):
    snap = startup_account.snapshot()
    if snap is None:
        return None
    phases = startup_account.by(snap, startup_account.PHASE_MS)
    return phases["weights"] + phases["optimizer"]
