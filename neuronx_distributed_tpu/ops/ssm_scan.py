"""Mamba-2 (SSD) selective scan: the causal depthwise convolution with its
carried taps, the chunked form of the scan, and the one-token step — as XLA
operations on the rows a call holds (:func:`ssm_scan`) and as one Pallas
call on a layer's state array where it lies (:func:`ssm_step`).

Per head ``h`` (``P`` channels) of group ``g = h // (NH // G)`` the state
``S_h [P, N]`` follows, with ``dt`` already ``softplus(dt + dt_bias)`` and
``A_h = -exp(A_log_h)``::

    S_h(t) = exp(dt_h(t) A_h) S_h(t-1) + dt_h(t) x_h(t) (x) B_g(t)
    y_h(t) = S_h(t) C_g(t) + D_h x_h(t)

The decay is DATA dependent, so nothing is precomputed a head.  A row that
is not a token (a chunk's pad, a parked slot) is an identity step, which the
recurrence gives by itself at ``dt = 0`` (decay 1, no update): the scan
masks ``dt`` and needs nothing else.  The convolution does need the rows'
order: its window is the last ``K`` TOKENS, so a call's rows are compacted
(tokens first, in order), convolved behind the carried taps, and put back.

The chunked form is the exact rearrangement over blocks of ``c`` rows, with
``L_t`` the running sum of ``dt A`` inside the block (``<= 0``)::

    y     = ((C B^T) * exp(L_t - L_s) dt_s  [s <= t]) x + exp(L_t) C S_in^T
    S_out = exp(L_c) S_in + sum_s exp(L_c - L_s) dt_s x_s (x) B_s

Every exponent is ``<= 0``; the state, ``dt``, the decays and every
accumulation are float32, the matmul operands the activations' dtype.  The
one-token step is elementwise on the float32 state (an outer product and a
reduction over ``N``): nothing of it is rounded below float32, which is what
the benchmark's state check reads (``after - a * before`` is one outer
product across the heads of a group).  Scopes: ``ssm_conv``,
``ssm_scan_chunk`` (blocks of a prefill chunk), ``ssm_step`` (a decode).

What a decode reads and writes.  The XLA step (the ``S == 1`` branch of
:func:`ssm_scan`) passes over EVERY row it is handed: a decode launched
over all the slots reads and writes 2 MiB a slot a layer at Granite's and
Nemotron's sizes whether the slot decodes or waits for its chunk's turn
(``dt`` masked to 0: the row is rewritten with the bits it held) — 7.74 ms
a Granite decode whatever its live rows (PERF.md, PR 56).  :func:`ssm_step`
is the same arithmetic as a Mosaic call named ``ssm_step`` over the state
array ``[R, NH, P, N]`` itself, aliased input to output: the live rows are
compacted inside the program (:func:`live_rows_first`, no sort; once a
program, the layers' copies of it are one after CSE), their ids and their
count scalar-prefetched, the grid (compacted row, head block).  A program
past the count maps its blocks to the ones the program before it held —
an unchanged block index is no fetch and no write-back, the trick
``flash_attention``'s band uses — and runs no body: a row that is no token
keeps its BITS, costs no HBM traffic, and its ``y`` is exactly 0 (the first
program zeroes the resident ``y``).  A count of 0 and a count of ``B`` run
the same code.  The tokens' ``x``, ``B``, ``C`` and ``y`` stay in VMEM for
the whole call, the per-head scalars (decay, ``dt``, ``D``) in SMEM; a
pair of heads' ``dt x`` becomes a column by a ``[128, 128]`` transpose, the
update is three float32 vector operations an element, and the read ``S C``
a float32 ``dot_general`` at ``HIGHEST`` with the state stationary (as
``retention_step``'s).  ``models.hybrid.Mamba2Mixer`` takes the kernel for
a decode where the paged kernels run and tp = 1, and the XLA step — the
oracle the tests hold the kernel to — elsewhere.

How the blocks are walked: in a Python loop at trace time while a call has
at most ``UNROLLED_BLOCKS`` of them (the static ``ceil(S / c)``: 2 and 4 in
the served chunks), each block's ``y`` produced once and joined along the
row axis, which the compiler fuses into the reader.  NOT through
``lax.scan``'s outputs: the TPU compiler lays a loop's stacked ``ys`` out
with the BLOCK INDEX in the tiled second-minor dimension
(``f32[2,1,256,64,64]{2,0,4,3,1:T(2,128)}``), so every trip re-lays its
block and stores it one sublane a tile, and a physical reshape follows the
loop — 268 of a Granite layer's 385 us on the v5e, for two trips
(PERF.md, PR 56).  Past the constant the same ``_block`` is rolled into a
``lax.scan`` (thousands of uncached rows: program size wins there); the
two walks run the same blocks to the same bits.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.ops.flash_attention import (
    _compiler_params,
    run_kernel,
)

# rows of one block of the chunked form (the published ``chunk_size``): the
# [c, c] decay mask a head and the [c, P] x [P, N] products
CHUNK_ROWS = 128
# a call of at most this many blocks walks them in a loop written out at
# trace time, a longer one in a ``lax.scan`` (the module's docstring says
# why).  Read from compiles of one layer's core for a described v5e at 64
# heads x 64 (PERF.md, PR 56, step 0): written out, a block adds ~36 KB of
# text and ~0.2 s of compile to every layer that holds it and its least
# cycles stay level (67-88k a block of 256 rows from 2 blocks to 32), and
# through 8 blocks the temporaries stay under 1 MiB — at 16 they are 68 MiB
# a layer and the rolled form's text is 7 times smaller.  The benchmark's
# chunks are 2 blocks (Granite: 512 rows in 256s) and 4 (Nemotron: 128s); 8
# is a 1,024-row chunk of 128s.
UNROLLED_BLOCKS = 8


def causal_conv(x, taps, weight, bias, valid, silu=True, scope="ssm_conv"):
    """Depthwise causal convolution over the call's TOKENS, then ``silu``
    (Mamba-2's; ``silu=False`` and ``bias=None`` leave the taps' sum as it
    is — LFM2's short convolution — under the caller's own ``scope``).

    ``x [B, S, C]``; ``taps [B, K-1, C]`` the last ``K-1`` inputs of each
    row's sequence before this call (zeros start a sequence); ``weight [K,
    C]`` (tap ``K-1`` multiplies the current input), ``bias [C]``; ``valid
    [B, S]`` which rows are tokens (``None``: all).  Returns ``(y [B, S, C]
    in x.dtype, taps_out [B, K-1, C])``; the output of a row that is not a
    token is not meaningful, and such a row does not enter the taps."""
    B, S, C = x.shape
    K = weight.shape[0]
    f32 = jnp.float32
    w = weight.astype(f32)
    with jax.named_scope(scope):
        if S == 1:
            # the decode step: one dot over the window
            win = jnp.concatenate([taps, x], axis=1)           # [B, K, C]
            y = jnp.einsum("bkc,kc->bc", win.astype(f32), w)[:, None, :]
            live = (jnp.ones((B, 1), bool) if valid is None
                    else jnp.asarray(valid) > 0)
            taps_out = jnp.where(live[:, :, None], win[:, 1:], taps)
        else:
            if valid is None:
                order = n = None
                xc = x
            else:
                live = jnp.asarray(valid) > 0
                # tokens first, in their order; the rest behind them
                order = jnp.argsort(~live, axis=1, stable=True)
                n = jnp.sum(live, axis=1)
                xc = jnp.take_along_axis(x, order[:, :, None], axis=1)
            full = jnp.concatenate([taps.astype(x.dtype), xc], axis=1)
            y = sum(full[:, k:k + S].astype(f32) * w[k] for k in range(K))
            if order is None:
                taps_out = full[:, S:]
            else:
                # the K-1 rows that end at the last token
                taps_out = jax.vmap(
                    lambda f, i: jax.lax.dynamic_slice_in_dim(f, i, K - 1, 0)
                )(full, n)
                back = jnp.argsort(order, axis=1)
                y = jnp.take_along_axis(y, back[:, :, None], axis=1)
        if bias is not None:
            y = y + bias.astype(f32)
        if silu:
            y = jax.nn.silu(y)
        return y.astype(x.dtype), taps_out.astype(taps.dtype)


def _block(state, x, Bm, Cm, dt, A):
    """One block of the chunked form.  ``x [B, c, NH, P]``, ``Bm, Cm [B, c,
    G, N]``, ``dt [B, c, NH]`` float32 (0 where the row is not a token),
    ``A [NH]`` float32 (negative), ``state [B, NH, P, N]`` float32 ->
    ``(state, y [B, c, NH, P] float32)``, without the ``D`` skip."""
    f32 = jnp.float32
    Bsz, c, NH, P = x.shape
    G = Bm.shape[2]
    R = NH // G
    L = jnp.cumsum(dt * A[None, None, :], axis=1)              # [B, c, NH]
    tri = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    gap = L[:, :, None, :] - L[:, None, :, :]                  # [B, t, s, NH]
    dec = jnp.where(tri[None, :, :, None],
                    jnp.exp(jnp.minimum(gap, 0.0)) * dt[:, None, :, :], 0.0)
    cb = jnp.einsum("btgn,bsgn->btsg", Cm, Bm, preferred_element_type=f32)
    m = (jnp.repeat(cb, R, axis=3) * dec).astype(x.dtype)      # [B, t, s, NH]
    intra = jnp.einsum("btsh,bshp->bthp", m, x, preferred_element_type=f32)
    st = state.reshape(Bsz, G, R, P, state.shape[-1])
    inter = jnp.einsum("btgn,bgrpn->btgrp", Cm.astype(f32), st,
                       precision=jax.lax.Precision.HIGHEST).reshape(
        Bsz, c, NH, P) * jnp.exp(L)[..., None]
    L_c = L[:, -1]                                             # [B, NH]
    xd = (x.astype(f32) * (jnp.exp(L_c[:, None, :] - L) * dt)[..., None]
          ).astype(x.dtype)
    upd = jnp.einsum("bsgrp,bsgn->bgrpn", xd.reshape(Bsz, c, G, R, P), Bm,
                     preferred_element_type=f32).reshape(state.shape)
    state = jnp.exp(L_c)[:, :, None, None] * state + upd
    return state, intra + inter


def ssm_scan(x, Bm, Cm, dt, A, D, valid, state, chunk_rows: int = CHUNK_ROWS):
    """``x [B, S, NH, P]``, ``Bm, Cm [B, S, G, N]`` (the activations'
    dtype), ``dt [B, S, NH]`` float32 after its softplus, ``A, D [NH]``
    float32, ``valid [B, S]`` (which rows are tokens; ``None``: all),
    ``state [B, NH, P, N]`` float32 (the state the call continues; zeros
    start a sequence) -> ``(y [B, S, NH, P] float32, state_out)``.  The
    output of a row that is not a token is not meaningful; the state
    ignores such rows."""
    f32 = jnp.float32
    Bsz, S, NH, P = x.shape
    dt = dt.astype(f32)
    if valid is not None:
        dt = jnp.where(jnp.asarray(valid)[:, :, None] > 0, dt, 0.0)
    A, D = A.astype(f32), D.astype(f32)
    if S == 1:
        with jax.named_scope("ssm_step"):
            G = Bm.shape[2]
            R = NH // G
            xt, dtt = x[:, 0].astype(f32), dt[:, 0]            # [B,NH,P], [B,NH]
            Bt = jnp.repeat(Bm[:, 0].astype(f32), R, axis=1)   # [B, NH, N]
            Ct = jnp.repeat(Cm[:, 0].astype(f32), R, axis=1)
            a = jnp.exp(dtt * A[None, :])
            state = a[:, :, None, None] * state \
                + (dtt[:, :, None] * xt)[..., None] * Bt[:, :, None, :]
            y = jnp.sum(state * Ct[:, :, None, :], axis=-1)
            return (y + D[None, :, None] * xt)[:, None], state
    c = min(chunk_rows, S)
    pad = -S % c
    if pad:
        x, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                     for a in (x, Bm, Cm))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nb = (S + pad) // c
    with jax.named_scope("ssm_scan_chunk"):
        if nb <= UNROLLED_BLOCKS:
            # the incoming state is materialised once, as a loop's carry
            # would be: left free, the compiler fuses the caller's read of
            # the state row into every block that touches it, and then
            # copies a layer's WHOLE donated state array to write one row
            # of it (Granite's layer 39, 64 MiB twice a chunk; AOT, PR 56)
            state = jax.lax.optimization_barrier(state)
            ys = []
            for lo in range(0, nb * c, c):
                state, y = _block(state, x[:, lo:lo + c], Bm[:, lo:lo + c],
                                  Cm[:, lo:lo + c], dt[:, lo:lo + c], A)
                ys.append(y)
            y = jnp.concatenate(ys, axis=1)
        else:
            def blocks(a):
                return a.reshape(Bsz, nb, c, *a.shape[2:]).swapaxes(0, 1)

            state, y = jax.lax.scan(
                lambda st, xs: _block(st, *xs, A), state,
                (blocks(x), blocks(Bm), blocks(Cm), blocks(dt)))
            y = y.swapaxes(0, 1).reshape(Bsz, nb * c, NH, P)
        y = y[:, :S] + D[None, None, :, None] * x[:, :S].astype(f32)
    return y, state


# -- the one-token step on the state arrays where they lie -------------------

# heads of one program's block of a state row: a whole row where it fits (a
# grid step a row: what a skipped row costs is one step), else the largest
# divisor of the heads inside this many bytes
_STEP_BLOCK_BYTES = 2 << 20
# Mosaic's scoped VMEM for the call: a block in and out, each double
# buffered (8 MiB), beside the tokens' x, y, B and C (64 rows: 5 MiB)
_STEP_VMEM_BYTES = 32 << 20


def _step_heads(NH: int, P: int, N: int) -> int:
    best = 1
    for hb in range(1, NH + 1):
        if NH % hb == 0 and hb * P * N * 4 <= _STEP_BLOCK_BYTES:
            best = hb
    return best


def _tile_heads(HB: int, R: int, P: int) -> int:
    """Heads of one ``[T, N]`` tile of a block (``T = heads x P`` rows, 128
    where ``P`` divides it): whole heads of ONE group."""
    t = max(1, 128 // P)
    while HB % t or R % t:
        t -= 1
    return t


def _as_column(row, N: int):
    """``[1, T] -> [T, N]``, every lane of row ``r`` the entry ``r``: the
    row laid down the sublanes, turned (one ``[128, 128]`` transpose)."""
    return jnp.broadcast_to(row, (N, row.shape[1])).T


def _read(c, s):
    """``s [T, N] . c [1, N] -> [1, T]`` along the lanes: a float32 matmul at
    ``HIGHEST`` with the state as the stationary operand."""
    return jax.lax.dot_general(
        jnp.broadcast_to(c, (8, c.shape[1])), s, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)[:1]


def _step_kernel(src_ref, cnt_ref, rows_ref, fresh_ref, a_ref, dt_ref, d_ref,
                 s_in, x_ref, b_ref, c_ref, s_out, y_ref, *, R, NJ):
    """One program: ``HB`` heads of one LIVE row's state, ``s_in`` and
    ``s_out`` blocks of the one HBM buffer (aliased); the tokens' ``x``,
    ``B``, ``C`` and the ``y`` they leave stay in VMEM for the whole call,
    the scalars a head in SMEM.  A program past the count holds the block
    the last live one held and does nothing; of a call with no live row the
    first program hands its block back as it came."""
    del rows_ref                       # the index maps read the row ids
    f32 = jnp.float32
    i, j = pl.program_id(0), pl.program_id(1)
    cnt = cnt_ref[0]
    _, HB, P, N = s_in.shape
    hpt = _tile_heads(HB, R, P)
    T = hpt * P
    base = 0 if NJ == 1 else j * HB    # the block's first head

    @pl.when((i == 0) & (j == 0))
    def _():
        # a row no program visits reads exactly 0
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < cnt)
    def _():
        b = src_ref[i]
        keep = fresh_ref[b] == 0
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        # row b of x and y: a dynamic row is reached through its aligned
        # eight (Mosaic loads no single sublane at a traced index)
        eight = pl.ds(pl.multiple_of(b // 8 * 8, 8), 8)
        mine = jax.lax.broadcasted_iota(jnp.int32, (8, T), 0) == b % 8

        def along(ref, *at):
            """A scalar a head laid along its ``P`` lanes of the tile."""
            row = jnp.full((1, T), ref[at], f32)
            for hl in range(1, hpt):
                row = jnp.where(lane >= hl * P,
                                ref[at[:-1] + (at[-1] + hl,)], row)
            return row

        for t in range(HB // hpt):
            h0 = base + t * hpt                        # the tile's first head
            g = h0 // R                                # ... and its group
            lanes = pl.ds(h0 * P if NJ == 1 else pl.multiple_of(h0 * P, T), T)
            x = jnp.sum(jnp.where(mine, x_ref[eight, lanes], 0.0), axis=0,
                        keepdims=True)                             # [1, T]
            col = _as_column(x * along(dt_ref, b, h0), N)          # [T, N]
            brow = b_ref[b, pl.ds(g, 1), :]                        # [1, N]
            for hl in range(hpt):
                h = t * hpt + hl
                s = jnp.where(keep, s_in[0, h], 0.0)               # [P, N]
                s_out[0, h] = a_ref[b, base + h] * s \
                    + col[hl * P:(hl + 1) * P] * brow
            y = _read(c_ref[b, pl.ds(g, 1), :],
                      s_out[0, t * hpt:(t + 1) * hpt].reshape(T, N))
            y_ref[eight, lanes] = jnp.where(
                mine, y + along(d_ref, h0) * x, y_ref[eight, lanes])

    @pl.when((cnt == 0) & (i == 0) & (j == 0))
    def _():
        s_out[...] = s_in[...]


def _step_call(state, src, cnt, rows, fresh, a, dt, D, x, Bm, Cm, interpret):
    _, NH, P, N = state.shape
    HB = _step_heads(NH, P, N)
    NJ = NH // HB
    B, G = Bm.shape[:2]

    def block(i, j, src, cnt, rows, *_):
        # a program past the count: the last live program's block again,
        # which is no fetch and no write-back
        live = i < cnt[0]
        i = jnp.where(live, i, jnp.maximum(cnt[0] - 1, 0))
        return (rows[src[i]], jnp.where(live, j, NJ - 1), 0, 0)

    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, j, *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(B, NJ),
        in_specs=[pl.BlockSpec((1, HB, P, N), block), whole(x.shape),
                  whole(Bm.shape), whole(Cm.shape)],
        out_specs=[pl.BlockSpec((1, HB, P, N), block), whole(x.shape)],
    )

    def call(interp):
        return pl.pallas_call(
            functools.partial(_step_kernel, R=NH // G, NJ=NJ),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(x.shape, jnp.float32)],
            # operands count the seven scalar-prefetched ones: the state is
            # the 8th
            input_output_aliases={7: 0},
            # y is one block the whole call, zeroed by the first program
            compiler_params=_compiler_params(
                ("arbitrary", "arbitrary"), interp, _STEP_VMEM_BYTES),
            interpret=interp,
            name="ssm_step",
        )

    state, y = run_kernel(call, interpret, src, cnt, rows, fresh, a, dt, D,
                          state, x, Bm, Cm)
    return y, state


def live_rows_first(live):
    """``(order [B] int32, count [1] int32)``: the rows that are tokens
    first, in their order, the others behind them.  No sort: a row's place
    is a running count, and the inverse one comparison a pair."""
    B = live.shape[0]
    n = jnp.cumsum(live.astype(jnp.int32))
    place = jnp.where(live, n - 1, n[-1] + jnp.arange(B) - n)
    at = jnp.arange(B, dtype=jnp.int32)
    order = jnp.sum(jnp.where(place[None, :] == at[:, None], at[None, :], 0),
                    axis=1)
    return order.astype(jnp.int32), n[-1:].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step_impl(state, x, Bm, Cm, dt, A, D, live, fresh, rows,
                   interpret=None):
    f32 = jnp.float32
    B, NH, P = x.shape
    order, cnt = live_rows_first(live)
    # (x and y in whole eights of rows: the kernel reaches a row by its eight)
    y, state = _step_call(
        state, order, cnt, rows, fresh.astype(jnp.int32),
        jnp.exp(dt * A[None, :]), dt, D,
        jnp.pad(x.astype(f32).reshape(B, NH * P), ((0, -B % 8), (0, 0))),
        Bm.astype(f32), Cm.astype(f32), interpret)
    return y[:B].reshape(B, NH, P), state


def ssm_step(state, x, Bm, Cm, dt, A, D, live=None, fresh=None, rows=None, *,
             interpret: Optional[bool] = None):
    """The one-token step as ONE Pallas call (``ssm_step``) on the state
    array where it lies, visiting the rows that are tokens and no others.

    ``state [R, NH, P, N]`` float32 (the layer's whole array; given
    donated, the step is in place); ``x [B, NH, P]``, ``Bm, Cm [B, G, N]``,
    ``dt [B, NH]`` float32 after its softplus, ``A, D [NH]``; ``live [B]``
    which batch rows are tokens (``None``: all), ``fresh [B]`` which begin
    their sequence (their row counts as zeros; ``None``: none), ``rows [B]``
    the state row each batch row continues (``None``: row ``b``; distinct
    among the live).  Returns ``(y [B, NH, P] float32, state)``: for a live
    row ``S = a S + (dt x) (x) B`` and ``y = S C + D x`` as the ``S == 1``
    branch of :func:`ssm_scan` computes them (float32 throughout, the read a
    float32 matmul at ``HIGHEST``); a row that is no token keeps its state's
    BITS, costs no HBM traffic and yields ``y`` exactly 0.

    The grid is (compacted row, head block): the live rows' ids come first
    in a scalar-prefetched list with their count, and a program past the
    count maps every operand to the block the program before it held."""
    B = x.shape[0]
    f32 = jnp.float32
    with jax.named_scope("ssm_step"):
        return _ssm_step_impl(
            state, x, Bm, Cm, dt.astype(f32), A.astype(f32), D.astype(f32),
            jnp.ones((B,), bool) if live is None else jnp.asarray(live) > 0,
            jnp.zeros((B,), bool) if fresh is None else jnp.asarray(fresh),
            jnp.arange(B, dtype=jnp.int32) if rows is None
            else jnp.asarray(rows, jnp.int32), interpret=interpret)


def ssm_scan_reference(x, Bm, Cm, dt, A, D, valid, state):
    """The recurrence token by token, float32 throughout: the oracle the
    tests (and step 0 of PERF.md) hold the chunked form to."""
    f32 = jnp.float32
    Bsz, S, NH, P = x.shape
    R = NH // Bm.shape[2]
    m = (jnp.ones((Bsz, S), f32) if valid is None
         else (jnp.asarray(valid) > 0).astype(f32))
    A, D = A.astype(f32), D.astype(f32)

    def step(st, xs):
        xt, Bt, Ct, dtt, mt = xs
        dtt = dtt * mt[:, None]
        Bt, Ct = jnp.repeat(Bt, R, axis=1), jnp.repeat(Ct, R, axis=1)
        st = jnp.exp(dtt * A)[:, :, None, None] * st \
            + (dtt[:, :, None] * xt)[..., None] * Bt[:, :, None, :]
        return st, jnp.sum(st * Ct[:, :, None, :], -1) + D[None, :, None] * xt

    state, y = jax.lax.scan(
        step, state.astype(f32),
        tuple(a.astype(f32).swapaxes(0, 1) for a in (x, Bm, Cm, dt, m)))
    return y.swapaxes(0, 1), state
