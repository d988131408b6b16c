"""startup_caller_share — of the seconds from the process's start to ready
(startup_ready_s), the percentage that no call of the program owns:
startup/ms_total/process of the start-up account over startup/ready_s.
It holds the interpreter's start, the entry point's own imports and arguments,
and whatever the CALLER does between the program's set-up calls — in a
benchmark run the TPU client's start in check_devices, the reference check
and, in a train cell, the wait for the weights' fill that the reference's
read of them ends.  None where the program keeps no such account.

BENCHMARK.json holds this metric's entry with its ``moves`` and ``workloads``; the
three constants below must agree with it (``benchmarks/tests/test_manifest.py``).
"""

from benchmarks.harness import startup_account

LAYER = "entry"
UNIT = "%"
SOURCE = "program_counter"


def read(r):
    snap = startup_account.snapshot()
    if snap is None:
        return None
    return 100.0 * snap[startup_account.PHASE_MS + "process"] / 1e3 \
        / snap[startup_account.READY]
