"""sparse_attn_roofline — over the sparse-attention kernel calls of the traced window
(``sparse_attention_decode`` / ``sparse_attention_chunk``, by their names in
the operation's name stack), the least time the chip could take
(``harness/sala_flops.py``: the larger of QK^T and PV over the keys a query
ATTENDS over the bf16 peak, and the K and V rows of those keys over the HBM
bandwidth) summed, over their measured time summed.  The keys come from
``selected_tokens`` of the host span that launched the call's program —
what the selection left, never ``ctx_tokens``: ``nxd/serve/dispatch`` gives
the decode's slots' selected keys as a sum (one query row a slot), and
``nxd/serve/prefill_chunk`` the selected keys of the chunk's LAST row, which
every row of the chunk is credited with (rows before it attend the same
number of blocks or one fewer, so this overstates the least by under 1% at
512 rows in 8k+ contexts; a chunk's rows share blocks, and the bytes are one
row's keys plus the chunk's own).  ``None`` where nothing matched, or the
span carries no ``selected_tokens`` (a program older than the selection).

BENCHMARK.json holds this metric's entries (``sparse_attn_roofline`` or ``sparse_attn_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import sala_flops, trace_scopes

KERNELS = ("sparse_attention_decode", "sparse_attention_chunk")


def least_seconds(span, cfg, peak):
    sel = float(span.attrs["selected_tokens"])
    if span.name.endswith("prefill_chunk"):
        rows = min(float(span.attrs["width"]), float(span.attrs["ctx_tokens"]))
        # every row attends about what the last does; the chunk's own rows
        # are causal among themselves
        pairs = rows * sel - rows * (rows - 1) / 2.0
        keys = sel
    else:
        pairs = keys = sel
    return sala_flops.sparse_attention_least_seconds(
        max(pairs, 0.0), keys, cfg, peak)


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev = sc.devices[0]
    least = measured = 0.0
    bounds = {}
    from benchmarks.harness import trace_reduce

    for op in dev.ops:
        parts = trace_scopes.components(op.tf_op)
        kernel = next((k for k in KERNELS if k in parts), None)
        span = dev.programs[op.program].span if op.program >= 0 else None
        if kernel is None or not trace_reduce.is_mosaic(op.text) \
                or span is None or "selected_tokens" not in span.attrs:
            continue
        t, bound = least_seconds(span, r.cell.config, r.peak)
        key = (kernel, bound)
        bounds[key] = bounds.get(key, 0) + 1
        least += t
        measured += op.end - op.start
    if not measured:
        return None
    print(f"[sparse_attn_roofline] calls by kernel and bound {bounds}: least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
