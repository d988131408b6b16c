"""ssm_step_roofline — over the Mamba-2 cores of the DECODE programs that ran
whole inside the traced window, the least time the chip could take
(``harness/ssm_flops.py::scan_bytes``: ONE read and one write of the float32
scan state and the convolution taps of every row stepped, plus the row's x,
B, C and y, over the HBM bandwidth) summed, over the measured self time of
the step's operations (scopes ``ssm_step``, ``state_read``, ``state_write``)
summed.  The rows of a program come from the host span that launched it:
``state_rows`` (else ``active``) of ``nxd/serve/dispatch``; each program runs
one core a Mamba-2 layer (the ``M`` of ``hybrid_override_pattern``).  The
path apart from ``ssm_roofline``, which pools it with the chunk's: a step is
bound by the state's bytes, a chunk by its matmuls and its block arrays.
``None`` where nothing matched.

BENCHMARK.json holds this metric's entries (``ssm_step_roofline`` or ``ssm_step_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import ssm_flops, trace_scopes

SCOPES = ("ssm_step", "state_read", "state_write")


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev, cfg = sc.devices[0], r.cell.config
    layers = cfg.get("hybrid_override_pattern", "").count("M")
    lo, hi = sc.window
    by_program = {}
    for op in dev.ops:
        if set(trace_scopes.components(op.tf_op)) & set(SCOPES):
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
        least += layers * ssm_flops.scan_bytes(float(rows), float(rows), cfg) \
            / r.peak["hbm_bytes_per_s"]
        measured += sum(op.own for op in ops)
        n += 1
    if not measured or not layers:
        return None
    print(f"[ssm_step_roofline] {n} decode program(s): least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
