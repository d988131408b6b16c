"""retention_step_roofline — over the power-retention cores of the decode
programs that ran whole inside the traced window, the least time the chip
could take (``harness/retention_flops.py``: ONE read of the float32 state and
normaliser of every row stepped, at the minimal width of the symmetric
square, over the HBM bandwidth) summed, over the measured self time of the
cores' operations (scope ``retention_step``: the kernel and the XLA
operations that feed it) summed.  The rows of a program come from the host
span that launched it: ``state_rows`` (else ``active``) of
``nxd/serve/dispatch``; each program runs one core a layer.  A step that
reads AND writes the state reads at most 50% here.  ``None`` where nothing
matched.

BENCHMARK.json holds this metric's entries (``retention_step_roofline`` or ``retention_step_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import retention_flops, trace_scopes

SCOPES = ("retention_step",)


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev, cfg = sc.devices[0], r.cell.config
    layers = cfg["num_hidden_layers"]
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
        least += layers * retention_flops.step_bytes(float(rows), cfg) \
            / r.peak["hbm_bytes_per_s"]
        measured += sum(op.own for op in ops)
        n += 1
    if not measured:
        return None
    print(f"[retention_step_roofline] {n} decode program(s): least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
