"""flash_fwd_roofline — least time the chip could take for ONE call of the
forward flash kernel (2 of the 7 matmuls of ``flops.flash_train_flops`` over
the attended keys of one layer; q, k, v read and o written once) over the
measured time of a call.  Calls a step and the bound are printed on an earlier
line: with selective remat the forward is expected once a layer, twice if the
policy recomputed attention.

BENCHMARK.json holds this metric's entries (``flash_fwd_roofline`` or ``flash_fwd_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import flops, stats, trace_reduce, trace_scopes

FORWARD = ("flash_fwd", ("flash_fwd",), 2 / 7, 1 / 3)
BACKWARD = ("flash_bwd", ("flash_dq", "flash_dkv"), 5 / 7, 2 / 3)


def roofline(r, which):
    """``which``: the group, its kernels (a call is one run of the first),
    and the group's share of ``flops.flash_train_flops`` / ``_bytes``."""
    group, kernels, flop_share, byte_share = which
    sc = trace_scopes.of(r)
    runs = r.trace.dominant_runs() if r.trace is not None else []
    if sc is None or r.peak is None or not runs:
        return None
    ops = sc.ops_of(group, (runs[0].start, runs[-1].end))
    first = [op for op in ops
             if trace_reduce.hlo_name(op.text).startswith(kernels[0])]
    if not first:
        return None
    calls = len(first)
    # the device trace may start inside the first traced step: the count a
    # step is the median over the steps, the time a call is over all calls
    a_step = stats.median([sum(run.start <= op.start < run.end
                               for op in first) for run in runs])
    cfg, n = r.cell.config, r.notes
    layers = cfg["num_hidden_layers"]
    # a chip of a tp mesh runs its share of the heads
    least, bound = flops.roofline_seconds(
        flop_share * flops.flash_train_flops(cfg, n["batch"], n["seq_len"])
        / layers / r.chips,
        byte_share * flops.flash_train_bytes(cfg, n["batch"], n["seq_len"])
        / layers / r.chips, r.peak)
    a_call = sum(op.end - op.start for op in ops) / calls
    print(f"[{group}_roofline] {a_step:g} calls a step (median of "
          f"{len(runs)} steps) of {layers} layers ({' + '.join(kernels)}), "
          f"{a_call * 1e3:.3f} ms a call, least {least * 1e3:.3f} ms "
          f"({bound} bound)", flush=True)
    return 100.0 * least / a_call


def read(r):
    return roofline(r, FORWARD)
