"""mla_decode_roofline — over the ``latent_attention_decode`` kernel calls of the traced window,
the least time the chip could take (``harness/mla_flops.py``: the larger of
the ABSORBED operations over the bf16 peak — ``2 x heads x (2 x kv_lora_rank
+ qk_rope_head_dim)`` a visible latent — and each visible latent row,
``(kv_lora_rank + qk_rope_head_dim) x 2`` bytes as published, read ONCE, over
the HBM bandwidth) summed, over their measured time summed.  The visible
latents come from ``ctx_tokens`` of the ``nxd/serve/dispatch`` span that
launched the call's program (the slots' keys before the new token, as a sum)
plus one a live slot (``active``: the token the call itself wrote).  ``None``
where no such call ran.

BENCHMARK.json holds this metric's entries (``mla_decode_roofline`` or ``mla_decode_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import mla_flops, trace_reduce, trace_scopes

KERNEL = "latent_attention_decode"


def least_seconds(span, cfg, peak):
    keys = float(span.attrs["ctx_tokens"]) + float(span.attrs.get("active", 0))
    return mla_flops.decode_least_seconds(keys, cfg, peak)


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
    print(f"[mla_decode_roofline] calls by bound {bounds}: least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
