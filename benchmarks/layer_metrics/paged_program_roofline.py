"""paged_program_roofline — ``paged_roofline``'s reading booked once a PROGRAM,
for a cell whose chunk is walked in PARTS: over the paged-attention kernel
calls of the programs that ran whole inside the traced window, the least
time the chip could take for each program's attention layers
(``paged_roofline.least_seconds`` of the host span that launched it — the K
and V rows of the keys attended over the HBM bandwidth, ``QK^T`` and ``PV``
at the configuration's ``head_dim`` over the bf16 peak — once a layer that
keeps pages) summed, over the measured time of the programs' kernel calls
summed, however many calls a layer's walk took.  ``paged_roofline`` books the
span's least time once a CALL; where a chunk's query rows a kv head pass
what one call walks (``ops/paged_attention.py``, ``_MAX_HEAD_ROWS``: a group
of 8 at 512 rows, a 1,024-row chunk of a group of 4) the walk is two calls
or more a layer and that reader counts the chunk's work once for each: it
read 134% in ``qwen3-next-80b-a3b.serve-longdocs`` (PERF.md, PR 59; mending
that reader is a ``benchmark`` PR's).  The layers that keep pages are the
configuration's layer list's — the ``"attention"`` entries of the program's
``mixer_types``, every layer where it has no list.  ``None`` where nothing
matched.

BENCHMARK.json holds this metric's entries (``paged_program_roofline`` or ``paged_program_roofline.<tag>``,
one per end-to-end metric it moves) with their ``moves`` and ``workloads``;
the three constants below must agree with them
(``benchmarks/tests/test_manifest.py``).
"""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"

from benchmarks.harness import trace_scopes
from benchmarks.layer_metrics.paged_roofline import least_seconds


def read(r):
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    dev, cfg = sc.devices[0], r.cell.config
    kwargs = cfg.get("program", {}).get("kwargs", {})
    kinds = kwargs.get("mixer_types")
    layers = (kwargs.get("num_layers", 0) if kinds is None
              else list(kinds).count("attention"))
    if not layers:
        return None
    lo, hi = sc.window
    by_program = {}
    for op in dev.ops:
        if op.group in ("paged_decode", "paged_chunk") and op.program >= 0:
            by_program.setdefault(op.program, []).append(op)
    least = measured = 0.0
    bounds = {}
    for index, ops in by_program.items():
        prog = dev.programs[index]
        span = prog.span
        if (span is None or prog.start < lo or prog.end > hi
                or "ctx_tokens" not in span.attrs):
            continue
        t, bound = least_seconds(span, cfg, r.peak)
        key = (ops[0].group, bound, len(ops))
        bounds[key] = bounds.get(key, 0) + 1
        least += t * layers
        measured += sum(op.end - op.start for op in ops)
    if not measured:
        return None
    print(f"[paged_program_roofline] programs by kernel, bound and calls "
          f"{bounds}: least {least * 1e3:.3f} ms over measured "
          f"{measured * 1e3:.3f} ms", flush=True)
    return 100.0 * least / measured
