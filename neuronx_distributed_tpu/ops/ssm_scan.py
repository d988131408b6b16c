"""Mamba-2 (SSD) selective scan: the causal depthwise convolution with its
carried taps, the chunked form of the scan, and the one-token step.

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

import jax
import jax.numpy as jnp

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
