#!/usr/bin/env python
"""ssm_chunk_probe.py — what a prefill chunk's Mamba-2 core costs a layer,
operation by operation, under each way of walking its blocks.

    chiprun -- python tools/ssm_chunk_probe.py [--shapes granite,nemotron]
    JAX_PLATFORMS=cpu python tools/ssm_chunk_probe.py --sites <cell> [--layers N]

On the chip (the default): ONE layer's chunk core ALONE at a cell's shapes
— the state row read from the donated ``[rows, NH, P, N]`` array,
``ops/ssm_scan.py::ssm_scan`` over the chunk's rows, the row written back,
the gated norm that consumes ``y`` — ``--steps`` calls in one profiler
trace.  A line a shape and a walk: ``layer_us`` (device time a call),
``ops_us`` (microseconds of self time a call by operation, the ``--top``
largest; an operation is an instruction's name less its number),
``least_us`` (the core's bytes — its inputs, ``y``, one read and one write
of the state row — over the HBM's peak, or its matmuls over the bf16 peak,
whichever is larger) and how far the scan's own float32 ``y`` and state are
from the other walk's (``0.0``: the same bits).  Walks: ``written_out`` (the library's, ``nb`` blocks in a loop at trace
time) and ``rolled`` (``lax.scan`` over the blocks, its stacked ``ys``: what
the library does past ``UNROLLED_BLOCKS``, forced here by setting that
constant to 1).  Shapes: ``granite`` (``[1, 512, 64, 64]``, one group,
state 128, blocks of 256), ``nemotron`` (eight groups, blocks of 128), or
``S,NH,P,G,N,c``.

``--sites`` (the sandbox, no chip): the serve programs of a benchmark cell
compiled for a described v5e by ``benchmarks/tools/granite_aot.py`` (which
serves Nemotron's pool too), both walks; a line a program and a walk with
the operations whose ``op_name`` holds ``ssm_scan_chunk`` / ``ssm_step`` by
opcode — count and the compiler's own ``estimated_cycles`` (a loop's body
counted once, whatever its trips) — the temporaries' bytes, the text's size
and a hash of it (a decode program's is the same under both walks).  A
compile, never a measurement.

``--trace-dir DIR`` (no chip either): a cell's ``--trace 1`` run as the
benchmark left it under ``chiprun_out/benchmarks/<cell>`` read by the
scan's five scopes — seconds of self time in the traced window by the host
span that launched the program (``prefill_chunk``: the chunk programs;
``dispatch``: the decodes) and scope, each scope's ``--top`` operations,
and the programs a span launched.

``--cpu --tiny`` rehearses the chip path at a toy shape (``layer_us`` None).
"""

import argparse
import collections
import contextlib
import hashlib
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SHAPES = {"granite": (512, 64, 64, 1, 128, 256),
          "nemotron": (512, 64, 64, 8, 128, 128),
          "tiny": (32, 8, 8, 2, 16, 8)}
WALKS = {"written_out": None, "rolled": 1}      # UNROLLED_BLOCKS, None: as is
SCOPES = ("ssm_scan_chunk", "ssm_step")
TRACED_SCOPES = ("ssm_conv", "ssm_scan_chunk", "ssm_step", "state_read",
                 "state_write")


@contextlib.contextmanager
def walking(walk):
    """``ops.ssm_scan.UNROLLED_BLOCKS`` set for ``walk``, then put back."""
    from neuronx_distributed_tpu.ops import ssm_scan as ssm

    mine = ssm.UNROLLED_BLOCKS
    if WALKS[walk] is not None:
        ssm.UNROLLED_BLOCKS = WALKS[walk]
    try:
        yield
    finally:
        ssm.UNROLLED_BLOCKS = mine


def operation(text):
    """An instruction's name less its number: ``%fusion.7 = ...`` ->
    ``fusion``."""
    from benchmarks.harness import trace_reduce

    return re.sub(r"[.\d]+$", "", trace_reduce.hlo_name(text))


def chunk_core(c, groups, eps=1e-5):
    """``(states, row, x, Bm, Cm, dt, A, D, z) -> (states, y)``: what a
    Mamba-2 layer does between its convolution and its output projection."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.hybrid import gated_group_norm
    from neuronx_distributed_tpu.ops import ssm_scan as ssm

    def core(states, row, x, Bm, Cm, dt, A, D, z):
        with jax.named_scope("state_read"):
            state = states[row]
        y, state = ssm.ssm_scan(x, Bm, Cm, dt, A, D, None, state, c)
        with jax.named_scope("state_write"):
            states = states.at[row].set(state)
        B, S = x.shape[:2]
        return states, gated_group_norm(
            y.reshape(B, S, -1), z, groups, eps).astype(x.dtype)

    return jax.jit(core, donate_argnums=(0,))


def least_us(S, NH, P, G, N, c, spec):
    """The least time of one call: its bytes once or its matmuls."""
    moved = (2 * S * NH * P * 2 + 2 * S * G * N * 2 + S * NH * 4
             + 2 * NH * P * N * 4)
    # C B^T and the masked product a block, the state's read and its update
    flops = 2 * S * (c * G * N + c * NH * P + 2 * NH * P * N)
    return 1e6 * max(moved / spec.hbm_bytes_per_s,
                     flops / spec.peak_flops)


def traced_ops(fn, steps, states, *xs):
    """``(layer_us, {operation: us a call})`` from a profiler trace of
    ``steps`` calls that hand the donated array on; ``(None, {})`` without a
    TPU to trace."""
    import jax

    from benchmarks.harness import trace_reduce

    states = jax.block_until_ready(fn(states, *xs))[0]
    if jax.devices()[0].platform != "tpu":
        return None, {}
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                states, y = fn(states, *xs)
            jax.block_until_ready(y)
        path = trace_reduce.find_xplane(trace_dir)
        events = trace_reduce.load(path, chips=1).devices[0].ops
    ops = collections.Counter()
    # self times: a loop's event spans its body's
    for e, own in zip(events, trace_reduce.self_times(events)):
        ops[operation(e.name)] += own * 1e6 / steps
    return sum(ops.values()), dict(ops.most_common())


def probe(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.ops import ssm_scan as ssm

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        sys.exit(f"ssm_chunk_probe measures a TPU; found {dev.platform} "
                 "(--cpu --tiny rehearses; --sites compiles in the sandbox)")
    spec = None
    if dev.platform == "tpu":
        from neuronx_distributed_tpu.utils.profiling import device_spec
        spec = device_spec()
    for name in ("tiny" if args.tiny else args.shapes).split(","):
        S, NH, P, G, N, c = SHAPES.get(name) or map(int, name.split(","))
        rs = np.random.RandomState(0)
        bf16, f32 = jnp.bfloat16, jnp.float32
        x = jnp.asarray(rs.randn(1, S, NH, P), bf16)
        Bm, Cm = (jnp.asarray(rs.randn(1, S, G, N), bf16) for _ in range(2))
        dt = jnp.asarray(np.log1p(np.exp(rs.randn(1, S, NH) - 3)), f32)
        A = jnp.asarray(-rs.uniform(1, 16, NH), f32)
        D = jnp.asarray(rs.randn(NH), f32)
        z = jnp.asarray(rs.randn(1, S, NH * P), bf16)
        row = jnp.asarray([args.rows // 2], jnp.int32)
        fresh = lambda: jax.random.normal(  # noqa: E731
            jax.random.PRNGKey(0), (args.rows, NH, P, N), f32)
        xs = (row, x, Bm, Cm, dt, A, D, z)
        want = None
        for walk in WALKS:
            line = dict(shape=name, S=S, heads=NH, groups=G, state=N,
                        block=c, blocks=-(-S // c), walk=walk,
                        device=str(dev.device_kind))
            with walking(walk):
                fn = chunk_core(c, G)
                # the scan's own outputs, before any reader rounds them
                got = jax.tree.map(np.asarray, jax.jit(
                    lambda *a: ssm.ssm_scan(*a, None, fresh()[:1], c))(*xs[1:-1]))
                if want is None:
                    want = got
                else:
                    line["y_diff"], line["state_diff"] = (
                        float(np.max(np.abs(a - b)))
                        for a, b in zip(got, want))
                us, ops = traced_ops(fn, args.steps, fresh(), *xs)
                line["layer_us"] = us and round(us, 2)
                line["ops_us"] = {k: round(v, 2) for k, v
                                  in list(ops.items())[:args.top]}
                if spec is not None:
                    line["least_us"] = round(
                        least_us(S, NH, P, G, N, c, spec), 2)
            print(json.dumps(line), flush=True)


def sites(args):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    from benchmarks.harness import manifest
    from benchmarks.tools import granite_aot
    from neuronx_distributed_tpu.parallel.mesh import destroy_model_parallel

    cell = manifest.Cell(args.sites)
    for walk in WALKS:
        with walking(walk):
            programs = granite_aot.compile_serve_programs(cell, args.layers)[0]
        destroy_model_parallel()
        for name, compiled in programs:
            text = compiled.as_text()
            m = compiled.memory_analysis()
            print(json.dumps(dict(
                cell=cell.name, layers=args.layers, program=name, walk=walk,
                text_bytes=len(text),
                text_sha=hashlib.sha256(text.encode()).hexdigest()[:12],
                temp_bytes=m.temp_size_in_bytes,
                whiles=len(re.findall(r" while\(", text)),
                ops=scoped_ops(text))), flush=True)


def scoped_ops(text):
    """``{scope:opcode: {count, estimated_cycles}}`` of the operations the
    device runs one by one (not those inside a fusion) whose ``op_name``
    holds one of ``SCOPES``, the costliest first."""
    by_op = collections.defaultdict(lambda: dict(count=0, estimated_cycles=0))
    inside = False
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", ln)
        if head:
            inside = re.search(r"fused_computation|fusion|sub_computation",
                               head.group(1)) is not None
        site = re.search(r'op_name="([^"]*)"', ln)
        op = re.match(r"\s*(?:ROOT )?%\S+ = \S+ ([\w-]+)\(", ln)
        scope = site and next((s for s in SCOPES if s in site.group(1)), None)
        if inside or not (op and scope) or op.group(1) in (
                "bitcast", "get-tuple-element", "constant", "tuple"):
            continue
        cycles = re.search(r'"estimated_cycles":"(\d+)"', ln)
        entry = by_op[f"{scope}:{op.group(1)}"]
        entry["count"] += 1
        entry["estimated_cycles"] += int(cycles.group(1)) if cycles else 0
    return dict(sorted(by_op.items(),
                       key=lambda kv: -kv[1]["estimated_cycles"]))


def traced_scopes(args):
    from benchmarks.harness import trace_reduce, trace_scopes

    path = trace_reduce.find_xplane(args.trace_dir)
    sc = trace_scopes.build(trace_scopes.read_space(path),
                            trace_reduce.load(path, chips=1))
    dev = sc.devices[0]
    seconds = collections.Counter()
    ops = collections.defaultdict(collections.Counter)
    for op in dev.ops:
        scope = next((c for c in trace_scopes.components(op.tf_op)
                      if c in TRACED_SCOPES), None)
        prog = dev.programs[op.program] if op.program >= 0 else None
        if scope is None or prog is None or prog.span is None:
            continue
        span = prog.span.name.rsplit("/", 1)[-1]
        seconds[span, scope] += op.own
        ops[span, scope][operation(op.text)] += op.own
    programs = collections.Counter(
        p.span.name.rsplit("/", 1)[-1] for p in dev.programs
        if p.span is not None)
    print(json.dumps(dict(trace=path, busy_s=round(sc.busy_s, 4),
                          programs=dict(programs))), flush=True)
    for (span, scope), s in sorted(seconds.items(), key=lambda kv: -kv[1]):
        print(json.dumps(dict(
            span=span, scope=scope, seconds=round(s, 4),
            share_of_busy=round(100 * s / sc.busy_s, 2),
            ops_s={k: round(v, 4) for k, v
                   in ops[span, scope].most_common(args.top)})), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="granite,nemotron")
    ap.add_argument("--rows", type=int, default=32,
                    help="rows of the donated state array")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--top", type=int, default=10,
                    help="operations a line names")
    ap.add_argument("--sites", default=None, metavar="CELL")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--trace-dir", default=None, metavar="DIR")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.sites:
        return sites(args)
    if args.trace_dir:
        return traced_scopes(args)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    probe(args)


if __name__ == "__main__":
    main()
