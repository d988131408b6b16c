"""retention_chunk_roofline — over the power-retention cores of the prefill
chunks that ran whole inside the traced window, the least time the chip
could take (``harness/retention_flops.py``: each of the chunk's tokens counts
the cheaper of the quadratic form at its position and the state form at the
minimal width of the symmetric square, over the bf16 peak) summed, over the
measured self time of the cores' operations (scopes ``retention_chunk`` /
``state_read`` / ``state_write``) summed.  A chunk's tokens and their
positions come from the host span that launched it: ``chunk_tokens`` and
``ctx_tokens`` of ``nxd/serve/prefill_chunk`` (the chunk's own tokens; the
position of its last); each program runs one core a layer.  ``None`` where
nothing matched.

BENCHMARK.json holds this metric's entries (``retention_chunk_roofline`` or ``retention_chunk_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import retention_flops, trace_scopes

SCOPES = ("retention_chunk", "state_read", "state_write")


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
                or not span.name.endswith("prefill_chunk")
                or "ctx_tokens" not in span.attrs):
            continue
        ctx = int(float(span.attrs["ctx_tokens"]))
        tokens = int(float(span.attrs.get(
            "chunk_tokens", min(float(span.attrs.get("width", ctx)), ctx))))
        if tokens <= 0:
            continue
        least += layers * retention_flops.chunk_flops(
            ctx - tokens + 1, tokens, cfg) / r.peak["bf16_flops_per_s"]
        measured += sum(op.own for op in ops)
        n += 1
    if not measured:
        return None
    print(f"[retention_chunk_roofline] {n} chunk program(s): least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
