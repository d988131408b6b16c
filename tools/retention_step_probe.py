#!/usr/bin/env python
"""retention_step_probe.py — what holds ``retention_step`` off the speed of
its two copies.

    chiprun -- python tools/retention_step_probe.py [--variants a,b,...]

The decode step of a power-retention layer (``ops/power_retention.py``)
ALONE, at the Brumby cell's shape (16 rows, 8 key/value heads of 5 query
heads, d 128, ``D`` 9,216: 604 MB of state read and 604 written a call),
the state donated, ``--steps`` calls in one profiler trace.  A line a
variant: ``kernel_us`` (the median device time of the Mosaic call),
``other_us`` (every other device operation of the call: what XLA does to
feed it), ``gb_per_s`` of state read + written over ``kernel_us`` and its
share of the HBM's peak, and how far state and read are from the XLA form.

Variants (the knock-outs are this TOOL's kernels; the library has one path):

- ``library`` — ``retention_step(kernel=True)`` as the server calls it;
  ``library@C`` the same with blocks of ``C`` columns (the tool sets
  ``_STEP_BLOCK_BYTES``).
- ``READ:RxC`` — the step as it stood before PR 54 (``phi(k)``, ``phi(q)``
  and a lane-laid ``v`` made by XLA and read from HBM) over ``[R, C]``
  blocks of a head's ``[d, D]`` state, ``READ`` one of ``mxu`` (a float32
  ``dot_general`` at ``HIGHEST``: ``mxu:128x2304`` is that kernel),
  ``none`` (the read knocked out, ``num`` = zeros: the floor the two copies
  set for that blocking), ``load`` and ``store`` (the state only read, only
  written: the floor ONE stream sets, its GB/s of that stream alone) and
  ``vpu`` (whole rows only, ``C`` = ``D``: a row
  of ``phi(q)`` broadcast over the sublanes, 128-lane partial sums a query
  head, one lane reduction at the end).

- ``turns:H`` — the read knocked out and the two streams taking TURNS: the
  state left in HBM, ``H`` heads of a row read into one of two VMEM
  buffers by a DMA of the kernel's own, then the ``H`` before them written
  back while these are stepped, never both at once (``none:*`` is the same
  work with the pipeline's read and write in flight together).

``--cpu --tiny`` runs every variant through the interpreter at a toy shape
(no number of it is a device number; ``kernel_us`` reads None).
"""

import argparse
import functools
import glob
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DEFAULT = ("mxu:128x2304,none:128x2304,none:32x9216,none:128x9216,"
           "vpu:32x9216,load:128x2304,store:128x2304,turns:2,turns:8,library")
TINY = ("mxu:32x384,none:32x384,load:32x384,store:32x384,turns:2,vpu:16x768,"
        "library,library@384")


def _lanes(a):
    """A scalar or a column laid along 128 lanes, as the kernels take it."""
    import jax.numpy as jnp

    return jnp.broadcast_to(a[..., None], a.shape + (128,))


def probe_call(state, rows, keep, pk, pq, v, *, read, blk, interpret):
    """The step with ``phi`` read from HBM, over ``[blk[0], blk[1]]`` blocks;
    -> ``(state, num [B, NKV, G, d])``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    R, NKV, d, D = state.shape
    B, _, G, _ = pq.shape
    br, bc = blk
    assert d % br == 0 and D % bc == 0 and (read != "vpu" or bc == D), blk
    sub = min(br, 32)

    def kernel(rows_ref, s_in, keep_ref, pk_ref, pq_ref, v_ref, *outs):
        s_out, o_ref = outs if len(outs) == 2 else (None, outs[0])
        keep_ = keep_ref[0, 0][:, :1]                           # [1, 1]
        if read == "vpu":
            for lo in range(0, br, sub):
                vv = v_ref[0, 0, lo:lo + sub, :]
                acc = [jnp.zeros((sub, 128), f32) for _ in range(G)]
                for c in range(D // 128):
                    lanes = slice(128 * c, 128 * (c + 1))
                    s = s_in[0, 0, lo:lo + sub, lanes] * keep_ \
                        + vv * pk_ref[0, 0, :, lanes]
                    s_out[0, 0, lo:lo + sub, lanes] = s
                    for g in range(G):
                        acc[g] = acc[g] + s * pq_ref[0, 0, g:g + 1, lanes]
                for g in range(G):
                    o_ref[0, 0, lo:lo + sub, g:g + 1] = jnp.sum(
                        acc[g], axis=-1, keepdims=True)
            return
        j = pl.program_id(3)
        if read == "load":              # one stream: the state read, no more
            @pl.when(j == 0)
            def _():
                o_ref[0, 0, 0] = jnp.zeros((G, br), f32)
            o_ref[0, 0, 0] += s_in[0, 0, :G, :br]
            return
        if read == "store":             # the other stream: written, unread
            s_out[0, 0] = v_ref[0, 0][:, :1] * pk_ref[0, 0]
        else:
            s = s_in[0, 0] * keep_ + v_ref[0, 0][:, :1] * pk_ref[0, 0]
            s_out[0, 0] = s
        if read in ("none", "store"):
            @pl.when(j == 0)
            def _():
                o_ref[0, 0, 0] = jnp.zeros((G, br), f32)
            return
        part = jax.lax.dot_general(
            pq_ref[0, 0], s, (((1,), (1,)), ((), ())),
            preferred_element_type=f32,
            precision=jax.lax.Precision.HIGHEST)                # [G, br]

        @pl.when(j == 0)
        def _():
            o_ref[0, 0, 0] = part

        @pl.when(j > 0)
        def _():
            o_ref[0, 0, 0] += part

    block = lambda b, h, i, j, rows: (rows[b], h, i, j)  # noqa: E731
    if read == "vpu":
        o_shape, o_spec = (B, NKV, d, G), pl.BlockSpec(
            (1, 1, br, G), lambda b, h, i, j, rows: (b, h, i, 0))
    else:
        o_shape, o_spec = (B, NKV, d // br, G, br), pl.BlockSpec(
            (1, 1, 1, G, br), lambda b, h, i, j, rows: (b, h, i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, NKV, d // br, D // bc),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY) if read == "store"
            else pl.BlockSpec((1, 1, br, bc), block),
            pl.BlockSpec((1, 1, 1, 128), lambda b, h, i, j, rows: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bc), lambda b, h, i, j, rows: (b, h, 0, j)),
            pl.BlockSpec((1, 1, G, bc), lambda b, h, i, j, rows: (b, h, 0, j)),
            pl.BlockSpec((1, 1, br, 128), lambda b, h, i, j, rows: (b, h, i, 0)),
        ],
        out_specs=([] if read == "load" else
                   [pl.BlockSpec((1, 1, br, bc), block)]) + [o_spec],
    )
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 4,
        vmem_limit_bytes=max(16 << 20, 4 * br * bc * 4 + (8 << 20)))
    operands = (rows, state, _lanes(keep)[:, :, None], pk[:, :, None], pq,
                _lanes(v))
    if read == "load":
        o, = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(o_shape, f32)],
            compiler_params=params, interpret=interpret,
            name="retention_step_probe")(*operands)
    else:
        state, o = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(o_shape, f32)],
            input_output_aliases={1: 0}, compiler_params=params,
            interpret=interpret, name="retention_step_probe")(*operands)
    if read == "vpu":
        return state, o.swapaxes(2, 3)
    return state, o.transpose(0, 1, 3, 2, 4).reshape(B, NKV, G, d)


def turns_call(state, rows, keep, pk, v, *, heads, interpret):
    """The update alone (no read), ``heads`` heads of a row a turn: read,
    then step these while the turn before is written; -> ``state``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, NKV, d, D = state.shape
    per = NKV // heads                  # turns a row
    T = rows.shape[0] * per
    piece = D // 4 if D % 512 == 0 else D

    def kernel(rows_ref, s_in, keep_ref, pk_ref, v_ref, s_out, buf, rsem,
               wsem):
        t = pl.program_id(0)
        slot = t % 2

        def copy(tt, sl, back):
            hbm = (s_out if back else s_in).at[
                rows_ref[tt // per], pl.ds((tt % per) * heads, heads)]
            src, dst = (buf.at[sl], hbm) if back else (hbm, buf.at[sl])
            return pltpu.make_async_copy(src, dst,
                                         (wsem if back else rsem).at[sl])

        @pl.when(t == 0)
        def _():
            copy(0, 0, False).start()

        copy(t, slot, False).wait()

        @pl.when(t > 0)
        def _():
            copy(t - 1, 1 - slot, True).start()

        for h in range(heads):
            for c in range(0, D, piece):
                lanes = slice(c, c + piece)
                buf[slot, h, :, lanes] = (
                    buf[slot, h, :, lanes] * keep_ref[0, h][:, :1]
                    + v_ref[0, h][:, :1] * pk_ref[0, h, :, lanes])

        @pl.when(t > 0)
        def _():
            copy(t - 1, 1 - slot, True).wait()

        @pl.when(t + 1 < T)
        def _():
            copy(t + 1, 1 - slot, False).start()

        @pl.when(t == T - 1)
        def _():
            copy(t, slot, True).start()
            copy(t, slot, True).wait()

    turn = lambda t, rows: (t // per, t % per, 0, 0)  # noqa: E731
    state, = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, heads, 1, 128), turn),
                      pl.BlockSpec((1, heads, 1, D), turn),
                      pl.BlockSpec((1, heads, d, 128), turn)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[pltpu.VMEM((2, heads, d, D), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={1: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * heads * d * D * 4 + (16 << 20)),
        interpret=interpret, name="retention_step_probe",
    )(rows, state, _lanes(keep)[:, :, None], pk[:, :, None], _lanes(v))
    return state


def device_us(fn, steps, state, *xs):
    """``(kernel_us, other_us)`` of one call, from a profiler trace of
    ``steps`` calls that hand the donated state on: the median duration of
    the Mosaic call's events and the other device operations' time a call.
    ``(None, None)`` where there is no TPU to trace."""
    import jax

    state = jax.block_until_ready(fn(state, *xs))[0]
    if jax.devices()[0].platform != "tpu":
        return None, None
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                state, num = fn(state, *xs)
            jax.block_until_ready(num)
        [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
    events = [(e.name.lstrip("%"), e.duration_ns)
              for plane in data.planes
              if plane.name.startswith("/device:TPU:")
              for line in plane.lines if line.name == "XLA Ops"
              for e in line.events]
    ours = [ns for name, ns in events if name.startswith("retention_step")]
    other = sum(ns for name, ns in events
                if not name.startswith("retention_step"))
    return (statistics.median(ours) / 1e3 if ours else None,
            other / 1e3 / steps)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", default=None)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--group", type=int, default=5)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.ops import power_retention as pr

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        sys.exit(f"retention_step_probe measures a TPU; found {dev.platform} "
                 "(--cpu --tiny runs the interpreter on a toy shape)")
    if args.tiny:
        args.rows, args.kv_heads, args.group, args.head_dim = 2, 2, 3, 32
        args.steps = 1
    peak = None
    if dev.platform == "tpu":
        from neuronx_distributed_tpu.utils.profiling import device_spec
        peak = device_spec().hbm_bytes_per_s
    B, NKV, G, d = args.rows, args.kv_heads, args.group, args.head_dim
    D = pr.phi_dim(d)
    interpret = dev.platform != "tpu"
    rs = np.random.RandomState(0)
    # rows in an order of their own, one array row more than the call steps
    ids = jnp.asarray(rs.permutation(B + 1)[:B], jnp.int32)
    keep = jnp.asarray(rs.uniform(0.9, 1.0, (B, NKV)), jnp.float32)
    k, v = (jnp.asarray(rs.randn(B, NKV, d) / d ** 0.25, jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rs.randn(B, NKV, G, d), jnp.float32)
    fresh = lambda: jax.random.normal(  # noqa: E731
        jax.random.PRNGKey(0), (B + 1, NKV, d, D), jnp.float32)
    moved = 2 * B * NKV * d * D * 4
    want_state, want_num = pr.retention_step(fresh(), ids, keep, k, q, v,
                                             kernel=False)

    for variant in (args.variants or (TINY if args.tiny else DEFAULT)).split(","):
        row = dict(variant=variant, rows=B, kv_heads=NKV, group=G, head_dim=d,
                   device=str(dev.device_kind))
        block_bytes = pr._STEP_BLOCK_BYTES
        try:
            if variant.startswith("library"):
                if "@" in variant:
                    pr._STEP_BLOCK_BYTES = d * int(variant.split("@")[1]) * 4
                row["block"] = [d, pr._step_block(d, D)]
                pr._retention_step_impl.clear_cache()
                fn = jax.jit(functools.partial(pr.retention_step, kernel=True),
                             donate_argnums=(0,))
            elif variant.startswith("turns"):
                heads = int(variant.split(":")[1])
                row["block"] = [heads, d, D]
                fn = jax.jit(
                    lambda st, ids, keep, k, q, v, heads=heads: (turns_call(
                        st, ids, keep, pr.phi(k), v, heads=heads,
                        interpret=interpret), jnp.zeros_like(q)),
                    donate_argnums=(0,))
            else:
                read, _, blk = variant.partition(":")
                blk = tuple(int(x) for x in blk.split("x"))
                row["block"] = list(blk)
                # phi in HBM, made by XLA inside the call as the decode made it
                fn = jax.jit(
                    lambda st, ids, keep, k, q, v, read=read, blk=blk:
                    probe_call(st, ids, keep, pr.phi(k), pr.phi(q), v,
                               read=read, blk=blk, interpret=interpret),
                    donate_argnums=(0,))
            xs = (ids, keep, k, q, v)
            st, num = fn(fresh(), *xs)
            load = variant.startswith(("load", "store"))
            if not load:
                row["state_rel"] = float(jnp.max(jnp.abs(st - want_state))
                                         / jnp.max(jnp.abs(want_state)))
            if not variant.startswith(("none", "load", "store", "turns")):
                row["read_rel"] = float(jnp.max(jnp.abs(num - want_num))
                                        / jnp.max(jnp.abs(want_num)))
            del st, num
            us, other = device_us(fn, args.steps, fresh(), *xs)
            row["kernel_us"], row["other_us"] = us, other
            if us:
                row["gb_per_s"] = moved / (1 + load) / us / 1e3
                row["share_of_hbm_peak"] = 100e9 * row["gb_per_s"] / peak
        except Exception as e:  # a refused blocking is a row
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        finally:
            pr._STEP_BLOCK_BYTES = block_bytes
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
