"""first_token_idle_share — the first chip's idle seconds INSIDE the serve loop's
``nxd/serve/first_token`` spans (a prompt's first-token tail: the sampler's
launch, the blocking read that drains the device's queue once a request,
the hand-over to decode) as a percentage of the traced window.
``Scopes.idle_by_span`` books a stretch to the INNERMOST span, so the idle
under the ``fetch`` inside a first-token tail is the ``fetch``'s there; this
reader clips the device's idle stretches to the ``first_token`` spans itself
(the device's times moved onto the host's clock by the same lower bound of
the offset).  0.0 where the window holds ``tail`` spans — a program that has
the span — and no first token; ``None`` where it holds neither (a program
older than the spans).

BENCHMARK.json holds this metric's entries (``first_token_idle_share`` or ``first_token_idle_share.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "serve loop"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_reduce, trace_scopes

FIRST = trace_scopes.SERVE + "first_token"
TAIL = trace_scopes.SERVE + "tail"


def idle_inside(sc, spans):
    """Seconds the first chip idled inside the (disjoint) spans."""
    lo, hi = sc.window
    shift = sc.clock_offset_bounds()[0] or 0.0
    rest = trace_reduce.complement(
        trace_scopes._intervals(sc.devices[0].ops), lo, hi) + shift
    return sum(trace_reduce.total(trace_reduce.clip(rest, s.start, s.end))
               for s in spans)


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices:
        return None
    spans = sc.named(FIRST)
    if not spans:
        return 0.0 if sc.named(TAIL) else None
    lo, hi = sc.window
    got = idle_inside(sc, spans)
    print(f"[first_token] {len(spans)} first-token tails in the window, "
          f"{sum(s.dur for s in spans) * 1e3:.2f} ms long together, the "
          f"device idle inside them {got * 1e3:.2f} ms", flush=True)
    return 100.0 * got / (hi - lo)
