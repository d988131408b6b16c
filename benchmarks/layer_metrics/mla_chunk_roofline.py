"""mla_chunk_roofline — over the ``latent_attention_chunk`` kernel calls of the traced window,
the least time the chip could take (``harness/mla_flops.py``: the larger of
the EXPANDED operations over the bf16 peak — ``2 (qk_nope + qk_rope + v)`` a
(query, key, head) pair attended, causal, plus the up-projection of each
visible latent once a chunk — and each visible latent row read once over the
HBM bandwidth) summed, over their measured time summed: the same work
whatever path the program takes, so that a change of path is read on one
yardstick.  Rows and keys come from ``width`` and ``ctx_tokens`` of the
``nxd/serve/prefill_chunk`` span that launched the call's program.  ``None``
where no such call ran.

BENCHMARK.json holds this metric's entries (``mla_chunk_roofline`` or ``mla_chunk_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import mla_flops, trace_reduce, trace_scopes

KERNEL = "latent_attention_chunk"


def least_seconds(span, cfg, peak):
    ctx = float(span.attrs["ctx_tokens"])
    rows = min(float(span.attrs.get("width", 1)), ctx)
    return mla_flops.chunk_least_seconds(rows, ctx, cfg, peak)


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev = sc.devices[0]
    least = measured = 0.0
    bounds = {}
    for op in dev.ops:
        span = dev.programs[op.program].span if op.program >= 0 else None
        if KERNEL not in trace_scopes.components(op.tf_op) \
                or not trace_reduce.is_mosaic(op.text) or span is None \
                or "ctx_tokens" not in span.attrs:
            continue
        t, bound = least_seconds(span, r.cell.config, r.peak)
        bounds[bound] = bounds.get(bound, 0) + 1
        least += t
        measured += op.end - op.start
    if not measured:
        return None
    print(f"[mla_chunk_roofline] calls by bound {bounds}: least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
