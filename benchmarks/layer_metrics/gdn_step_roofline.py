"""gdn_step_roofline — over the gated-delta cores of the DECODE programs that ran
whole inside the traced window, the least time the chip could take
(``harness/gdn_flops.py::step_least_seconds``: ONE read and one write of the
float32 state and the convolution taps of every row stepped, plus the row's
q, k, v and o, over the HBM bandwidth) summed, over the measured self time of
the step's operations (scopes ``gdn_conv``, ``gdn_step``, ``state_read``,
``state_write``) summed.  The rows of a program come from the host span that
launched it: ``state_rows`` (else ``active``) of ``nxd/serve/dispatch``; each
program runs one core a delta layer (``gdn_flops.delta_layers``).  ``None``
where nothing matched.

BENCHMARK.json holds this metric's entries (``gdn_step_roofline`` or ``gdn_step_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import gdn_flops, trace_scopes
from benchmarks.layer_metrics.gdn_time_share import core_ops

SCOPES = ("gdn_conv", "gdn_step", "state_read", "state_write")


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
    n = 0
    for index, ops in by_program.items():
        prog = dev.programs[index] if index >= 0 else None
        span = prog.span if prog is not None else None
        if (span is None or prog.start < lo or prog.end > hi
                or not span.name.endswith("dispatch")):
            continue
        rows = span.attrs.get("state_rows", span.attrs.get("active"))
        if rows is None or float(rows) <= 0:
            continue
        least += layers * gdn_flops.step_least_seconds(float(rows), cfg,
                                                       r.peak)
        measured += sum(op.own for op in ops)
        n += 1
    if not measured or not layers:
        return None
    print(f"[gdn_step_roofline] {n} decode program(s): least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
