"""ssm_roofline — over the Mamba-2 cores of the programs that ran whole inside the traced
window, the least time the chip could take (``harness/ssm_flops.py``: the
larger of the scan's operations over the bf16 peak, and the float32 state
and the convolution taps of every sequence the call continues, read and
written once, plus the x, B, C and y rows, over the HBM bandwidth) summed,
over the measured self time of the cores' operations (scopes ``ssm_conv`` /
``ssm_scan_chunk`` / ``ssm_step`` / ``state_read`` / ``state_write``) summed.
The rows and sequences of a program come from the host span that launched
it: ``active`` of ``nxd/serve/dispatch`` (a decode: one row a live slot, each
a sequence) or the valid rows of ``nxd/serve/prefill_chunk`` (one sequence);
each program runs one core a Mamba-2 layer (the ``M`` of
``hybrid_override_pattern``).  ``None`` where nothing matched.

BENCHMARK.json holds this metric's entries (``ssm_roofline`` or ``ssm_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import ssm_flops, trace_scopes

SCOPES = ("ssm_conv", "ssm_scan_chunk", "ssm_step", "state_read",
          "state_write")


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
    bounds = {}
    for index, ops in by_program.items():
        prog = dev.programs[index] if index >= 0 else None
        span = prog.span if prog is not None else None
        if (span is None or prog.start < lo or prog.end > hi
                or not {"active", "width"} & set(span.attrs)):
            continue
        if span.name.endswith("prefill_chunk"):
            rows, seqs = min(float(span.attrs["width"]),
                             float(span.attrs["ctx_tokens"])), 1.0
        else:
            rows = seqs = float(span.attrs["active"])
        t, bound = ssm_flops.scan_least_seconds(rows, seqs, cfg, r.peak)
        key = (span.name.rsplit("/", 1)[-1], bound)
        bounds[key] = bounds.get(key, 0) + 1
        least += t * layers
        measured += sum(op.own for op in ops)
    if not measured:
        return None
    print(f"[ssm_roofline] programs by span and bound {bounds}: least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
