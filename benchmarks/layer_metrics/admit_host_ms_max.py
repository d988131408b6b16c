"""admit_host_ms_max — the longest ``nxd/serve/admit`` span of the traced window,
ms: of the spans that granted a request (``granted`` >= 1) where the trace
carries that attribute and the window holds such a span, else of all (a
window in which nothing was admitted — a cell of few, long requests — reads
the sweep's and the gate's own fraction of a millisecond).  The medians over
steps (``engine_host_ms_p50``, ``decode_relaunch_gap_ms_p50``) do not see
admission: one step in a dozen admits.  Before its number the reader prints
an ``[admit]`` line: how many spans admitted, their lengths, and the
program's eviction counters over the whole run where it has them
(``kvcache/evict_scanned_total`` over ``kvcache/evictions_total``: nodes of
the prefix index looked at a page evicted).  ``None`` where the window holds
no such span.

BENCHMARK.json holds this metric's entries (``admit_host_ms_max`` or ``admit_host_ms_max.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kv cache"
UNIT = "ms"
SOURCE = "program_span"

from benchmarks.harness import stats, trace_scopes

ADMIT = trace_scopes.SERVE + "admit"


def admitting(spans):
    """The ``admit`` spans to judge: those that granted a request, where the
    spans say how many they granted and any did; else every one."""
    if all("granted" in s.attrs for s in spans):
        return [s for s in spans if s.attrs["granted"] >= 1] or spans
    return spans


def read(r):
    sc = trace_scopes.of(r)
    spans = sc.named(ADMIT) if sc is not None else []
    if not spans:
        return None
    ms = sorted(s.dur * 1e3 for s in admitting(spans))
    evicted = r.counters.get("kvcache/evictions_total")
    scanned = r.counters.get("kvcache/evict_scanned_total")
    print(f"[admit] {len(spans)} admit spans in the window, {len(ms)} of "
          "them judged (those granting a request, where the trace says and "
          f"any did), the longest 32: ms {' '.join(f'{v:.2f}' for v in ms[-32:])}; sum "
          f"{sum(ms):.2f}, p50 {stats.median(ms)}; over the whole run pages "
          f"evicted {evicted}, index nodes scanned for them {scanned}"
          + (f" ({scanned / evicted:.1f} a page)" if scanned and evicted
             else ""), flush=True)
    return ms[-1]
