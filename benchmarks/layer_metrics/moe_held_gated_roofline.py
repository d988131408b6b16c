"""moe_held_gated_roofline — over the routed expert blocks of the programs that ran whole
inside the traced window, the least time the chip could take for their
grouped matmuls (``harness/moe_held_gated_flops.py``: the larger of the HELD
assignments' gate, up and down operations over the bf16 peak, and the three
weights of the held experts hit plus the rows in and out over the HBM
bandwidth) summed, over the measured time of those matmuls summed — the
operations whose own name stack passes through ``moe_gmm`` (the Pallas
grouped-matmul kernel).  ``moe_held_roofline`` beside it is the same reading
for UNGATED held experts (two matmuls); this one is for a block whose held
experts are SwiGLU.  A program's token rows come from the host span that
launched it (``active`` of ``nxd/serve/dispatch``, or the valid rows of
``nxd/serve/prefill_chunk``), and of its rows x ``num_experts_per_tok``
assignments the held are the run's share for the program's family, the
counters ``moe/assignments_held_total/<family>`` over
``moe/assignments_total/<family>``; the held experts hit are the run's mean
for the family, ``moe/experts_hit_total/<family>`` over
``moe/layer_calls_total/<family>``.  Each program runs one block a routed
layer (``num_hidden_layers`` less ``first_k_dense_replace``).  ``None`` where
nothing matched or the program does not count held assignments.

BENCHMARK.json holds this metric's entries (``moe_held_gated_roofline`` or
``moe_held_gated_roofline.<tag>``, one per end-to-end metric it moves) with
their ``moves`` and ``workloads``; the three constants below must agree with
them (``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import moe_held_gated_flops, trace_scopes
from benchmarks.layer_metrics.moe_held_roofline import family_means


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev, cfg = sc.devices[0], r.cell.config
    layers = cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)
    lo, hi = sc.window
    by_program = {}
    for op in dev.ops:
        if "moe_gmm" in trace_scopes.components(op.tf_op):
            by_program.setdefault(op.program, []).append(op)
    least = measured = 0.0
    bounds = {}
    for index, ops in by_program.items():
        prog = dev.programs[index] if index >= 0 else None
        span = prog.span if prog is not None else None
        if (span is None or prog.start < lo or prog.end > hi
                or not {"active", "width"} & set(span.attrs)):
            continue
        means = family_means(span, r.counters)
        if means is None:
            continue
        rows = (min(float(span.attrs["width"]),
                    float(span.attrs["ctx_tokens"]))
                if span.name.endswith("prefill_chunk")
                else float(span.attrs["active"]))
        t, bound = moe_held_gated_flops.expert_block_least_seconds(
            rows * cfg["num_experts_per_tok"] * means[0], means[1], cfg,
            r.peak)
        key = (span.name.rsplit("/", 1)[-1], bound)
        bounds[key] = bounds.get(key, 0) + 1
        least += t * layers
        measured += sum(op.end - op.start for op in ops)
    if not measured:
        return None
    print(f"[moe_held_gated_roofline] programs by span and bound {bounds}: "
          f"least {least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
