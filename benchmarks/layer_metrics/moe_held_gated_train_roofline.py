"""moe_held_gated_train_roofline — over the whole train steps inside the traced window, the least
time the chip could take for the grouped matmuls of their routed expert
blocks (``harness/moe_train_flops.py``: NINE matmuls a HELD assignment —
gate, up and down, each forward, data gradient and weight gradient — over
the bf16 peak, or the weights of the held experts hit read twice and their
gradients written once plus the rows in and out over the HBM bandwidth,
whichever is larger) over the measured time of the operations whose own
name stack passes through ``moe_gmm`` (the megablox kernels ``gmm`` and
``tgmm`` and what ``parallel/moe.py`` wraps them in; a forward recomputed
under remat is measured and not counted).  The held assignments and the
held experts hit, a routed layer a step, are the run's means from the
program's counters, ``moe/assignments_held_total/train_step`` and
``moe/experts_hit_total/train_step`` over ``moe/layer_calls_total/
train_step`` — counted, so the count cannot go stale.  ``None`` where
nothing matched or the program does not count them.

BENCHMARK.json holds this metric's entries (``moe_held_gated_train_roofline`` or
``moe_held_gated_train_roofline.<tag>``, one per end-to-end metric it moves)
with their ``moves`` and ``workloads``; the three constants below must agree
with them (``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import moe_train_flops, trace_scopes


def read(r):
    sc = trace_scopes.of(r)
    runs = r.trace.dominant_runs() if r.trace is not None else []
    if sc is None or r.peak is None or not sc.devices or not runs:
        return None
    calls = r.counters.get("moe/layer_calls_total/train_step")
    held = r.counters.get("moe/assignments_held_total/train_step")
    hit = r.counters.get("moe/experts_hit_total/train_step")
    layers = r.notes.get("routed_layers")
    if not calls or held is None or hit is None or not layers:
        return None
    lo, hi = runs[0].start, runs[-1].end
    measured = sum(op.end - op.start for op in sc.devices[0].ops
                   if lo <= op.start < hi
                   and "moe_gmm" in trace_scopes.components(op.tf_op))
    if not measured:
        return None
    t, bound = moe_train_flops.expert_block_least_seconds(
        held / calls, hit / calls, r.cell.config, r.peak)
    least = t * layers * len(runs)
    print(f"[moe_held_gated_train_roofline] {len(runs)} steps of {layers} "
          f"routed layers, {held / calls:.0f} held assignments over "
          f"{hit / calls:.2f} experts a layer: least {least * 1e3:.3f} ms "
          f"({bound} bound) over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured
