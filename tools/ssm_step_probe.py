#!/usr/bin/env python
"""ssm_step_probe.py — what a Mamba-2 decode's scan step costs a layer, and
what it would cost if only the rows that decode were moved.

    chiprun -- python tools/ssm_step_probe.py [--variants a,b,...]

ONE layer's one-token step ALONE (``ops/ssm_scan.py``) over a state array
of ``--rows`` rows of ``[64, 64, 128]`` float32 (2 MiB a row: Granite's 32
rows at ``--groups 1``, Nemotron's 64 at ``--groups 8``), the state
donated, ``--steps`` calls in one profiler trace.  A line a variant:
``kernel_us`` (the median device time of the Mosaic call, None for the XLA
form), ``call_us`` (every device operation of the call), ``gb_per_s`` of the
LIVE rows' state read + written over ``call_us`` and its share of the HBM's
peak, and how far state and ``y`` are from the XLA form.

Variants, each ``NAME:LIVE`` (``LIVE`` of the rows are tokens, scattered):

- ``xla`` — the ``S == 1`` branch of ``ssm_scan`` on the whole array, ``dt``
  masked to 0 for the rows that are no token: what the decode ran before
  PR 58, every row read and written whatever ``LIVE``.
- ``copy@H`` — this TOOL's kernel: the live rows by scalar-prefetched id,
  ``H`` heads a block, the array aliased, the block handed back as it came
  (the ceiling the two copies set for that blocking).
- ``step@H`` — ``ops.ssm_scan.ssm_step``, the library's kernel, with blocks
  of ``H`` heads (the tool sets ``_STEP_BLOCK_BYTES``); ``step`` alone is
  the library's own blocking.  ``step!col`` knocks the ``dt x`` column's
  transpose out (the tool swaps ``_as_column`` for a lane broadcast of the
  row's first entry), ``step!read`` the read ``S C`` (``_read`` gives
  zeros), ``step!col!read`` both: what each costs beside the copies (their
  state and ``y`` are then not the step's, and are not compared);
  ``step!col8`` forms the column another way — eight sublanes of the row
  turned (one vreg through the transpose unit for sixteen), then its first
  lane broadcast — and is the step.

``--cpu --tiny`` runs every variant through the interpreter at a toy shape
(no number of it is a device number; the times read None).
"""

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DEFAULT = ("xla:32,xla:24,xla:16,copy@64:32,copy@64:24,copy@16:24,copy@32:24,"
           "copy@64:16,step:32,step:24,step:16,step@16:24,step@32:24,step:0,"
           "step!col:24,step!read:24,step!col!read:24,step!col8:24")
TINY = ("xla:3,xla:2,copy@2:2,copy@4:3,step:3,step:2,step@2:2,step:0,"
        "step!col!read:2")


def copy_call(state, ids, cnt, *, heads, interpret):
    """The live rows' blocks of ``heads`` heads read and written back as
    they came, the programs past the count on the last live block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, NH, P, N = state.shape
    NJ = NH // heads

    def kernel(ids_ref, cnt_ref, s_in, s_out):
        @pl.when((pl.program_id(0) < cnt_ref[0])
                 | ((pl.program_id(0) == 0) & (pl.program_id(1) == 0)))
        def _():
            s_out[...] = s_in[...]

    def block(i, j, ids, cnt):
        live = i < cnt[0]
        i = jnp.where(live, i, jnp.maximum(cnt[0] - 1, 0))
        return (ids[i], jnp.where(live, j, NJ - 1), 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(ids.shape[0], NJ),
            in_specs=[pl.BlockSpec((1, heads, P, N), block)],
            out_specs=[pl.BlockSpec((1, heads, P, N), block)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={2: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret, name="ssm_copy_probe",
    )(ids, cnt, state)[0]


def device_us(fn, steps, state, *xs):
    """``(kernel_us, call_us)`` of one call, from a profiler trace of
    ``steps`` calls that hand the donated state on: the median duration of
    the Mosaic call's events and every device operation's time a call.
    ``(None, None)`` where there is no TPU to trace."""
    import jax

    y, state = jax.block_until_ready(fn(state, *xs))
    if jax.devices()[0].platform != "tpu":
        return None, None
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                y, state = fn(state, *xs)
            jax.block_until_ready(y)
        [path] = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
    events = [(e.name.lstrip("%"), e.duration_ns)
              for plane in data.planes
              if plane.name.startswith("/device:TPU:")
              for line in plane.lines if line.name == "XLA Ops"
              for e in line.events]
    ours = [ns for name, ns in events if name.startswith("ssm_")]
    return (statistics.median(ours) / 1e3 if ours else None,
            sum(ns for _, ns in events) / 1e3 / steps)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", default=None)
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--groups", type=int, default=1)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.ops import ssm_scan as ss

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        sys.exit(f"ssm_step_probe measures a TPU; found {dev.platform} "
                 "(--cpu --tiny runs the interpreter on a toy shape)")
    if args.tiny:
        args.rows, args.groups, args.heads = 3, 2, 4
        args.head_dim, args.state, args.steps = 8, 16, 1
    peak = None
    if dev.platform == "tpu":
        from neuronx_distributed_tpu.utils.profiling import device_spec
        peak = device_spec().hbm_bytes_per_s
    B, G, NH, P, N = (args.rows, args.groups, args.heads, args.head_dim,
                      args.state)
    interpret = dev.platform != "tpu"
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(B, NH, P), jnp.bfloat16)
    Bm, Cm = (jnp.asarray(rs.randn(B, G, N), jnp.bfloat16) for _ in range(2))
    dt = jnp.asarray(rs.uniform(0.01, 0.1, (B, NH)), jnp.float32)
    A = -jnp.asarray(rs.uniform(1.0, 16.0, (NH,)), jnp.float32)
    D = jnp.asarray(rs.randn(NH), jnp.float32)
    fresh = lambda: jax.random.normal(  # noqa: E731
        jax.random.PRNGKey(0), (B, NH, P, N), jnp.float32)

    def xla(state, live):
        y, state = ss.ssm_scan(x[:, None], Bm[:, None], Cm[:, None],
                               dt[:, None], A, D, live[:, None], state)
        return y[:, 0], state

    variants = (args.variants or (TINY if args.tiny else DEFAULT)).split(",")
    for variant in variants:
        name, _, n = variant.partition(":")
        name, *knocked = name.split("!")
        name, _, heads = name.partition("@")
        n = int(n)
        live = np.zeros((B,), bool)
        live[rs.permutation(B)[:n]] = True
        live = jnp.asarray(live)
        row = dict(variant=variant, rows=B, live=n, groups=G, heads=NH,
                   head_dim=P, state=N, device=str(dev.device_kind))
        block_bytes, column, read = (ss._STEP_BLOCK_BYTES, ss._as_column,
                                     ss._read)
        try:
            want_y, want_state = xla(fresh(), live)
            if name == "xla":
                fn = jax.jit(xla, donate_argnums=(0,))
            elif name == "copy":
                order, cnt = ss.live_rows_first(live)
                fn = jax.jit(
                    lambda st, live, heads=int(heads): (
                        jnp.zeros((B, NH, P), jnp.float32), copy_call(
                            st, order, cnt, heads=heads,
                            interpret=interpret)),
                    donate_argnums=(0,))
            else:
                if heads:
                    ss._STEP_BLOCK_BYTES = int(heads) * P * N * 4
                row["block_heads"] = ss._step_heads(NH, P, N)
                if "col" in knocked:
                    ss._as_column = lambda r, N: jnp.broadcast_to(  # noqa: E731
                        r[:, :1], (r.shape[1], N))
                if "col8" in knocked:
                    ss._as_column = lambda r, N: jnp.broadcast_to(  # noqa: E731
                        jnp.broadcast_to(r, (8, r.shape[1])).T[:, :1],
                        (r.shape[1], N))
                if "read" in knocked:
                    ss._read = lambda c, s: jnp.zeros(  # noqa: E731
                        (1, s.shape[0]), jnp.float32)
                ss._ssm_step_impl.clear_cache()
                fn = jax.jit(
                    lambda st, live: ss.ssm_step(st, x, Bm, Cm, dt, A, D,
                                                 live),
                    donate_argnums=(0,))
            y, st = fn(fresh(), live)
            if name == "copy":
                row["state_kept"] = bool(jnp.all(st == fresh()))
            elif not set(knocked) - {"col8"}:
                row["state_rel"] = float(jnp.max(jnp.abs(st - want_state))
                                         / jnp.max(jnp.abs(want_state)))
                row["y_rel"] = float(
                    jnp.max(jnp.abs(jnp.where(live[:, None, None],
                                              y - want_y, 0.0)))
                    / jnp.max(jnp.abs(want_y)))
                row["idle_rows_kept"] = bool(jnp.all(jnp.where(
                    live[:, None, None, None], True, st == fresh())))
            del st, y
            us, call = device_us(fn, args.steps, fresh(), live)
            row["kernel_us"], row["call_us"] = us, call
            if call:
                row["gb_per_s"] = 2 * n * NH * P * N * 4 / call / 1e3
                row["share_of_hbm_peak"] = 100e9 * row["gb_per_s"] / peak
        except Exception as e:  # a refused blocking is a row
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        finally:
            ss._STEP_BLOCK_BYTES, ss._as_column, ss._read = (
                block_bytes, column, read)
            ss._ssm_step_impl.clear_cache()
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
