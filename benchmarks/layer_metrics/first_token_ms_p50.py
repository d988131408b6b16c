"""first_token_ms_p50 — median over requests DUE in the window of (first
``stream_cb`` token, benchmark's clock) - (time the request was due by the
arrival schedule): the time to first token, recorded and NOT judged.

It is what a chat user feels first, and the issue of PR 22 wanted it as an
end-to-end metric.  Measured on the v5e at 0.8 of the knee (1.6 requests/s,
~72 requests due in a 45 s window) six runs of the same code read 321-351
ms, a spread of 4.4% and 2.7% in two sets of three.  The rule of five times
the spread asks for a bound of 22% where a bound may be 10% at most, and the
driver admits a cell only where the spread is under half the bound: 4.4% of
5%.  (At 2.0/s, which is the knee itself, the spread was 20-28%.)  A first
token waits for the running step — ``generator_lateness_p50_ms``, half an
engine step — then takes one or two steps: the median of 72 such times
moves by a tenth of a step with the seed's arrivals.  It rides on the same
engine step as ``tpot_p50_ms``: a slower step moves both.  A cell that can
judge it needs some hundreds of requests in a window (PERF.md, Open
questions).

BENCHMARK.json holds this metric's entry with its ``moves`` and
``workloads``; the three constants below must agree with it
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "entry"
UNIT = "ms"
SOURCE = "host_clock"

from benchmarks.harness import stats


def read(r):
    return stats.median(r.samples.get("ttft_ms", []))
