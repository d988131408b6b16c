"""moe_roofline — over the routed expert blocks of the programs that ran whole inside
the traced window, the least time the chip could take for their grouped
matmuls (``harness/moe_flops.py``: the larger of the assignments' gate, up and
down operations over the bf16 peak, and the weights of the experts hit plus
the rows in and out over the HBM bandwidth) summed, over the measured time
of those matmuls summed.  The matmuls are the operations whose own name
stack passes through ``moe_gmm`` (the Pallas grouped-matmul kernel) or,
where a program has none, through ``moe_experts``.  The token rows of a
program come from the host span that launched it: ``active`` of
``nxd/serve/dispatch`` (a decode: one row a live slot) or the valid rows of
``nxd/serve/prefill_chunk`` (``width``, less the left pad of a prompt's first
chunk: ``min(width, ctx_tokens)``); each program runs one expert block a
layer.  The experts a program hit are the run's mean for its family, from
the program's counters ``moe/experts_hit_total/<family>`` over
``moe/layer_calls_total/<family>`` (``decode_pages``, ``prefill_chunk_pages``:
a skewed router hits fewer than a uniform one would, and the bytes follow
what was hit); without those counters, the uniform expectation.  ``None``
where nothing matched.

BENCHMARK.json holds this metric's entries (``moe_roofline`` or ``moe_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import moe_flops, trace_scopes


def rows_of(span):
    """Valid token rows of the program a serve span launched."""
    if span.name.endswith("prefill_chunk"):
        return int(min(float(span.attrs["width"]),
                       float(span.attrs["ctx_tokens"])))
    return int(span.attrs["active"])


FAMILY = {"prefill_chunk": "prefill_chunk_pages", "dispatch": "decode_pages"}


def experts_hit(span, counters):
    """Mean experts hit a layer call by the programs of the span's family,
    or ``None`` where the program does not count them."""
    family = FAMILY.get(span.name.rsplit("/", 1)[-1])
    calls = counters.get(f"moe/layer_calls_total/{family}")
    hit = counters.get(f"moe/experts_hit_total/{family}")
    return hit / calls if calls and hit is not None else None


def matmul_ops(dev):
    """Per program index, the expert blocks' matmul operations."""
    by_scope = {"moe_gmm": {}, "moe_experts": {}}
    for op in dev.ops:
        parts = set(trace_scopes.components(op.tf_op))
        for scope, acc in by_scope.items():
            if scope in parts:
                acc.setdefault(op.program, []).append(op)
    return by_scope["moe_gmm"] or by_scope["moe_experts"]


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev, cfg = sc.devices[0], r.cell.config
    lo, hi = sc.window
    least = measured = 0.0
    bounds = {}
    for index, ops in matmul_ops(dev).items():
        prog = dev.programs[index] if index >= 0 else None
        span = prog.span if prog is not None else None
        if (span is None or prog.start < lo or prog.end > hi
                or not {"active", "width"} & set(span.attrs)):
            continue
        t, bound = moe_flops.expert_block_least_seconds(
            rows_of(span), cfg, r.peak, experts_hit(span, r.counters))
        key = (span.name.rsplit("/", 1)[-1], bound)
        bounds[key] = bounds.get(key, 0) + 1
        least += t * cfg["num_hidden_layers"]
        measured += sum(op.end - op.start for op in ops)
    if not measured:
        return None
    print(f"[moe_roofline] programs by span and bound {bounds}: least "
          f"{least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
