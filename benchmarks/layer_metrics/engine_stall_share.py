"""engine_stall_share — of the time the serve loop spent stepping since its
warm-up was declared done (lead-in, window and drain), the percentage that
was STALL: ``serving/stall_ms_total`` over ``serving/step_ms_total``, the
step account's counters (``obs.flight.StepAccount``).  A step is a stall when
its wall time passes 3x the trailing median of 32 steps AND the median +
250 ms; what counts is the excess over the median.  0.0 in a clean run; a
hole of 0.7-13 s in a 45 s window reads 1-20.  A hole BETWEEN two steps (the
generator, a stream callback's caller, the profiler's start) is the
caller's, not the loop's: it is booked to ``serving/stall_ms_total/between``
and is not in this number.  Before the number the reader prints a ``[steps]``
line: the stepping time by phase (SELF times, ``serving/host_ms_total/*``),
its on-CPU and blocked parts, the longest step, and the stalls by the phase
that held them.  ``None`` where the program keeps no such account (older
than it).

BENCHMARK.json holds this metric's entries (``engine_stall_share`` or ``engine_stall_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "%"
SOURCE = "program_counter"

PREFIX = "serving/"


def by_phase(counters, family):
    head = PREFIX + family + "/"
    return {k[len(head):]: v for k, v in counters.items()
            if k.startswith(head)}


def read(r):
    c = r.counters
    total = c.get(PREFIX + "step_ms_total")
    if total is None:
        return None
    host, stalls = by_phase(c, "host_ms_total"), by_phase(c, "stall_ms_total")
    print(f"[steps] {total:.1f} ms of stepping since the warm-up: on the CPU "
          f"{c.get(PREFIX + 'step_cpu_ms_total', 0.0):.1f}, blocked in a "
          f"fetch {c.get(PREFIX + 'step_blocked_ms_total', 0.0):.1f}, "
          f"longest step {c.get(PREFIX + 'step_ms_max', 0.0):.3f}; by phase "
          "(self time) " + ", ".join(
              f"{p} {v:.1f}" for p, v in sorted(host.items(),
                                                key=lambda kv: -kv[1]))
          + f"; stalls {c.get(PREFIX + 'stalls_total', 0.0):.0f}, ms over "
          f"the median {c.get(PREFIX + 'stall_ms_total', 0.0):.1f}"
          + ("".join(f", {p} {v:.1f}" for p, v in sorted(stalls.items())
                     if v) or ", none in any phase"), flush=True)
    return 100.0 * c.get(PREFIX + "stall_ms_total", 0.0) / total if total \
        else 0.0
