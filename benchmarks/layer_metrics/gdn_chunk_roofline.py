"""gdn_chunk_roofline — over the gated-delta cores of the PREFILL-CHUNK programs
that ran whole inside the traced window, the least time the chip could take
(``harness/gdn_flops.py::chunk_least_seconds``: the larger of the chunked
form's matmuls at blocks of 64 rows over the bf16 peak and of the core's
bytes — the one sequence's float32 state and taps read and written once, the
chunk's q, k, v and o rows — over the HBM bandwidth) summed, over the
measured self time of the cores' operations (scopes ``gdn_conv``,
``gdn_chunk``, ``state_read``, ``state_write``) summed.  The rows of a program
are the valid rows of the ``nxd/serve/prefill_chunk`` span that launched it
(``width``, at most ``ctx_tokens``); each program runs one core a delta
layer (``gdn_flops.delta_layers``).  ``None`` where nothing matched.

BENCHMARK.json holds this metric's entries (``gdn_chunk_roofline`` or ``gdn_chunk_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import gdn_flops, trace_scopes
from benchmarks.layer_metrics.gdn_time_share import core_ops

SCOPES = ("gdn_conv", "gdn_chunk", "state_read", "state_write")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev, cfg = sc.devices[0], r.cell.config
    layers = gdn_flops.delta_layers(cfg)
    lo, hi = sc.window
    by_program = {}
    for op in core_ops(dev, SCOPES):
        by_program.setdefault(op.program, []).append(op)
    least = measured = 0.0
    bounds = {}
    for index, ops in by_program.items():
        prog = dev.programs[index] if index >= 0 else None
        span = prog.span if prog is not None else None
        if (span is None or prog.start < lo or prog.end > hi
                or not span.name.endswith("prefill_chunk")
                or "width" not in span.attrs):
            continue
        rows = min(float(span.attrs["width"]),
                   float(span.attrs.get("ctx_tokens", span.attrs["width"])))
        t, bound = gdn_flops.chunk_least_seconds(rows, cfg, r.peak)
        bounds[bound] = bounds.get(bound, 0) + 1
        least += t * layers
        measured += sum(op.own for op in ops)
    if not measured or not layers:
        return None
    print(f"[gdn_chunk_roofline] chunk programs by bound {bounds}: least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
