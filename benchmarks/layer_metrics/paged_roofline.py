"""paged_roofline — over the paged-attention kernel calls of the traced window,
the least time the chip could take (the larger of: the K and V rows of the keys
a call attends, at the pool's dtype, over the HBM bandwidth; its QK^T and PV
over the bf16 peak) summed, over their measured time summed.  The keys come
from the host span that launched the call's program: ``ctx_tokens`` of
``nxd/serve/dispatch`` (a decode: one query row a slot) or of
``nxd/serve/prefill_chunk`` (``width`` rows, causal).  A sliding window caps
the keys a row attends; for a decode, whose span gives the slots' keys as a
sum, the cap is ``active`` x window, which overstates the bytes of a batch
whose slots straddle the window.

BENCHMARK.json holds this metric's entries (``paged_roofline`` or ``paged_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

import numpy as np

from benchmarks.harness import flops, trace_scopes

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def least_seconds(span, cfg, peak):
    """The least time of ONE kernel call of the program a span launched."""
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    window = cfg["program"]["kwargs"].get("sliding_window") or float("inf")
    ctx = float(span.attrs["ctx_tokens"])
    if span.name.endswith("prefill_chunk"):
        width = int(span.attrs["width"])
        rows = np.minimum(ctx - width + 1 + np.arange(width), window)
        pairs, keys = float(np.sum(np.maximum(rows, 0))), min(
            ctx, window + width)
    else:
        pairs = keys = min(ctx, window * int(span.attrs["active"]))
    kv_bytes = DTYPE_BYTES[cfg["serving"]["kv_cache_dtype"]]
    return flops.roofline_seconds(2 * 2.0 * nq * d * pairs,
                                  2.0 * keys * nkv * d * kv_bytes, peak)


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev = sc.devices[0]
    least = measured = 0.0
    bounds = {}
    for op in dev.ops:
        span = dev.programs[op.program].span if op.program >= 0 else None
        if op.group not in ("paged_decode", "paged_chunk") or span is None \
                or "ctx_tokens" not in span.attrs:
            continue
        t, bound = least_seconds(span, r.cell.config, r.peak)
        key = (op.group, bound)
        bounds[key] = bounds.get(key, 0) + 1
        least += t
        measured += op.end - op.start
    if not measured:
        return None
    print(f"[paged_roofline] calls by kernel and bound {bounds}: least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
