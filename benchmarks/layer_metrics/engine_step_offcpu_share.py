"""engine_step_offcpu_share — of the time the serve loop spent stepping since its
warm-up was declared done, the percentage in which the loop's thread neither
ran nor waited for the device: ``serving/step_ms_total`` less
``serving/step_cpu_ms_total`` (``time.thread_time`` over each step) less
``serving/step_blocked_ms_total`` (wall time inside the blocking ``fetch``),
floored at 0, over ``serving/step_ms_total``.  A host that deschedules the
thread, faults its pages in or steals its core shows here; time on the CPU
inside a fetch (a runtime that spins before it sleeps) is taken off twice, so
the share leans low.  ``None`` where the program keeps no such account.

BENCHMARK.json holds this metric's entries (``engine_step_offcpu_share`` or ``engine_step_offcpu_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "%"
SOURCE = "program_counter"


def read(r):
    c = r.counters
    total = c.get("serving/step_ms_total")
    if total is None:
        return None
    off = total - c.get("serving/step_cpu_ms_total", 0.0) \
        - c.get("serving/step_blocked_ms_total", 0.0)
    return 100.0 * max(off, 0.0) / total if total else 0.0
