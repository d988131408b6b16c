"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; Qwen3-Next's
linear-attention layers): the token recurrence, its chunked (WY) form over
rows of a layer's state array, and the one-token step on that array where it
lies.

Per head the state ``S [Dk, Dv]`` (float32) is DECAYED and CORRECTED by a
token: with ``g_t <= 0`` the log decay, ``beta_t`` in (0, 1), ``q_t, k_t``
L2-normalised (``q`` scaled by ``Dk ** -0.5``)::

    S   <- exp(g_t) S
    d_t  = beta_t (v_t - S^T k_t)           what the state gets wrong of v_t
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

Unlike the repo's other recurrent layers (``S <- g S + k v^T``: lightning,
Mamba-2, power retention) the update READS the state it writes, so a chunk
is no sum of outer products.  The chunked form over blocks of ``C`` rows is
exact (the WY representation): with ``gamma_i`` the running sum of ``g``
inside the block (``<= 0``) and ``Gamma_ij = exp(gamma_i - gamma_j)``::

    A  = tril(diag(beta) (K K^T * Gamma), -1)      strictly lower [C, C]
    T  = (I + A)^-1                                unit lower triangular
    W  = T (beta K e^gamma)        U = T (beta V)
    D  = U - W S_in                                the block's d_t, [C, Dv]
    O  = (Q e^gamma) S_in + tril(Q K^T * Gamma) D
    S_out = e^gamma_C S_in + (K e^(gamma_C - gamma))^T D

Every exponent is ``<= 0``.  ``A`` is nilpotent (``A^C = 0``), so ``T = (I -
A)(I + A^2)(I + A^4)...`` is ``log2 C`` squarings — matmuls, where forward
substitution is ``C`` dependent steps.

**Precision.**  The state, ``g``, ``gamma``, every decay, ``A``, ``T``, ``W``
and ``U`` are float32 (the ``[C, C]`` products at ``HIGHEST``: their errors
pass through an inverse).  The matmuls AGAINST THE STATE and the ``[C, C]``
scores take their operands in the activations' dtype (bfloat16 on the served
path) and accumulate in float32 — the reference implementation's choice
(flash-linear-attention rounds ``S``, ``W`` and the scores to bfloat16 for
its dots): q, k and v come out of a bfloat16 convolution, so operands kept
wider would carry rounding they were born with; what decides it is the
benchmark's tolerances (the state after 20k tokens against the float32
recurrence, ``benchmarks/harness/serve_gdn_runner.py``).  Float32 operands
(the tests) run at ``HIGHEST``.  The one-token step is float32 throughout:
two ``[1, Dk] x [Dk, Dv]`` reads at ``HIGHEST`` and an outer product.

**What a prefill chunk runs** (:func:`gdn_chunk`, ``S > 1``): everything
that is parallel over blocks — ``A``, ``T``, ``W``, ``U``, the scores — as
XLA operations over ``[B, NH, blocks, C, .]``, then the walk over the
blocks, which is sequential in the state, in a loop WRITTEN OUT at trace
time (no ``lax.scan`` with stacked outputs: ``ops/ssm_scan.py`` says what
that cost) on the ONE row of the layer's array ``[R, NH, Dk, Dv]`` the
chunk continues, sliced out by its id and written back where it lay.  ONE
walk: a Mosaic call that kept a head's state in VMEM over the blocks read
45 us a layer SLOWER than this one at the served sizes (425 us against 379
on the donated array: PERF.md section 6, PR 59) — the chunk's cost is the
prologue, and a kernel belongs where it takes that in.

**What a decode runs** (:func:`gdn_step`, one token a row): ONE Pallas call
(``gdn_step``) over the state array itself, ``ops.ssm_scan.ssm_step``'s
design — the live rows compacted, their ids and count scalar-prefetched, a
program a live row (all its heads: 2 MiB), a program past the count mapped
to the block before it (no fetch, no write-back): a row that is no token
keeps its BITS, costs no HBM traffic and reads ``o`` exactly 0.  Elsewhere
the XLA step passes over every row it is handed.

A row that is not a token (a chunk's pad, a parked slot) is an identity
step: ``g = 0`` and ``beta = 0`` give decay 1 and no correction.
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
from neuronx_distributed_tpu.ops.ssm_scan import live_rows_first

# rows of one block of the chunked form (the published kernels' chunk size)
CHUNK_ROWS = 64
# Mosaic's scoped VMEM for the step: a row's 2 MiB block in and out, each
# double buffered, beside the tokens' q, k, v and o
_STEP_VMEM_BYTES = 32 << 20
_HI = jax.lax.Precision.HIGHEST


def l2_normalise(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32 (the
    published kernels' ``l2norm``)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gdn_reference(q, k, v, g, beta, valid, state):
    """The recurrence token by token, float32 throughout: the oracle the
    tests hold the chunked form and the step to.  ``q, k [B, S, NH, Dk]``,
    ``v [B, S, NH, Dv]``, ``g, beta [B, S, NH]``, ``valid [B, S]`` (None:
    every row is a token), ``state [B, NH, Dk, Dv]`` -> ``(o [B, S, NH, Dv]
    float32, state)``."""
    f32 = jnp.float32
    B, S = q.shape[:2]
    m = (jnp.ones((B, S), f32) if valid is None
         else (jnp.asarray(valid) > 0).astype(f32))

    def step(st, xs):
        qt, kt, vt, gt, bt, mt = xs
        gt, bt = gt * mt[:, None], bt * mt[:, None]
        st = jnp.exp(gt)[..., None, None] * st
        d = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", st, kt,
                                             precision=_HI))
        st = st + kt[..., :, None] * d[..., None, :]
        return st, jnp.einsum("bhkv,bhk->bhv", st, qt, precision=_HI)

    state, o = jax.lax.scan(
        step, state.astype(f32),
        tuple(a.astype(f32).swapaxes(0, 1) for a in (q, k, v, g, beta, m)))
    return o.swapaxes(0, 1), state


# -- the chunked form ----------------------------------------------------------


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of strictly lower triangular ``A [..., C, C]``
    (float32): ``A`` is nilpotent, so the Neumann series ends, and factors
    as ``(I - A)(I + A^2)(I + A^4)...`` — one squaring and one product a
    doubling."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=A.dtype)
    mm = functools.partial(jnp.matmul, precision=_HI)
    T, P, span = eye - A, A, 2
    while span < C:
        P = mm(P, P)
        T = mm(T, eye + P)
        span *= 2
    return T


def _block_operands(q, k, v, g, beta, op_dtype):
    """What is parallel over blocks.  ``q, k [B, NH, nb, C, Dk]``, ``v [B,
    NH, nb, C, Dv]`` (float32 or the operands' dtype), ``g, beta [B, NH, nb,
    C]`` float32 (``g = beta = 0`` where a row is no token) -> ``w [.., C,
    Dk]`` and ``qg, kd [.., C, Dk]`` and the scores ``qk [.., C, C]`` in
    ``op_dtype``, ``u [.., C, Dv]`` float32, ``decay [B, NH, nb]`` float32
    (``e^gamma_C``)."""
    f32 = jnp.float32
    C = q.shape[-2]
    qf, kf, vf = (a.astype(f32) for a in (q, k, v))
    # the running sum of the log decays inside a block: float32 (bfloat16
    # sums read nine times the served error in the first layer's state)
    gamma = jnp.cumsum(g, axis=-1)                             # [.., C]
    gap = gamma[..., :, None] - gamma[..., None, :]            # [.., i, j]
    lower = jnp.tril(jnp.ones((C, C), bool))
    Gamma = jnp.where(lower, jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    kb = kf * beta[..., None]
    # operands of the [C, C] products as the activations hold them
    prec = _HI if jnp.dtype(op_dtype) == jnp.float32 else None
    scores = lambda a, b: jnp.einsum(  # noqa: E731
        "...id,...jd->...ij", a.astype(op_dtype), b.astype(op_dtype),
        preferred_element_type=f32, precision=prec)
    A = jnp.where(strict, scores(kb, kf) * Gamma, 0.0)
    T = _unit_lower_inverse(A)
    e = jnp.exp(gamma)[..., None]
    w = jnp.matmul(T, kb * e, precision=_HI)
    u = jnp.matmul(T, vf * beta[..., None], precision=_HI)
    qk = scores(qf, kf) * Gamma
    tail = jnp.exp(gamma[..., -1:] - gamma)[..., None]
    return (w.astype(op_dtype), u, (qf * e).astype(op_dtype),
            (kf * tail).astype(op_dtype), qk.astype(op_dtype),
            jnp.exp(gamma[..., -1]))


def _walk_block(state, w, u, qg, kd, qk, decay, op_dtype):
    """One block of the walk, XLA: ``state [B, NH, Dk, Dv]`` float32 and the
    block's operands (:func:`_block_operands`, without the block axis) ->
    ``(state, o [B, NH, C, Dv] float32)``."""
    f32 = jnp.float32
    prec = _HI if jnp.dtype(op_dtype) == jnp.float32 else None
    mm = lambda spec, a, b: jnp.einsum(  # noqa: E731
        spec, a, b, preferred_element_type=f32, precision=prec)
    s_op = state.astype(op_dtype)
    d = u - mm("bhck,bhkv->bhcv", w, s_op)
    d_op = d.astype(op_dtype)
    o = mm("bhck,bhkv->bhcv", qg, s_op) + mm("bhij,bhjv->bhiv", qk, d_op)
    state = decay[..., None, None] * state + mm("bhck,bhcv->bhkv", kd, d_op)
    return state, o


def _blocked(a, nb, C):
    """``[B, S, NH, ...] -> [B, NH, nb, C, ...]``."""
    B, _, NH = a.shape[:3]
    a = a.reshape(B, nb, C, NH, *a.shape[3:])
    return jnp.moveaxis(a, 3, 1)


def _prepare(q, k, v, g, beta, valid, chunk_rows):
    """Pads the rows to whole blocks, masks ``g`` and ``beta`` of rows that
    are no tokens, and runs :func:`_block_operands`."""
    f32 = jnp.float32
    S = q.shape[1]
    g, beta = g.astype(f32), beta.astype(f32)
    if valid is not None:
        live = (jnp.asarray(valid) > 0)[:, :, None]
        g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    C = min(chunk_rows, S)
    pad = -S % C
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    nb = (S + pad) // C
    return nb, C, _block_operands(
        *(_blocked(a, nb, C) for a in (q, k, v, g, beta)), op_dtype=v.dtype)


def gdn_scan(q, k, v, g, beta, valid, state,
             chunk_rows: int = CHUNK_ROWS):
    """The chunked form as XLA operations on the rows a call holds.  ``q, k
    [B, S, NH, Dk]`` (L2-normalised, ``q`` scaled; float32 or the
    activations' dtype), ``v [B, S, NH, Dv]`` (its dtype is the operands'),
    ``g, beta [B, S, NH]``, ``valid [B, S]`` (None: all), ``state [B, NH,
    Dk, Dv]`` float32 (zeros start a sequence) -> ``(o [B, S, NH, Dv]
    float32, state)``.  The blocks are walked in a loop written out at
    trace time, however many (a served chunk is eight; an uncached forward
    of thousands of rows pays in program size).  The output of a row that
    is not a token is not meaningful; the state ignores such rows."""
    S = q.shape[1]
    with jax.named_scope("gdn_chunk"):
        nb, C, ops = _prepare(q, k, v, g, beta, valid, chunk_rows)
        # (materialised once, as a loop's carry would be: ops/ssm_scan.py)
        state = jax.lax.optimization_barrier(state)
        outs = []
        for n in range(nb):
            state, o = _walk_block(state, *(a[:, :, n] for a in ops),
                                   op_dtype=v.dtype)
            outs.append(o)
        return jnp.moveaxis(jnp.concatenate(outs, axis=2), 1, 2)[:, :S], state


@jax.jit
def _gdn_chunk_impl(q, k, v, g, beta, m, fresh, states, rows):
    one = rows.shape[0] == 1
    # ONE row (a prefill chunk): a slice, never a gather of the array
    state = (jax.lax.dynamic_index_in_dim(states, rows[0], axis=0)
             if one else states[rows])
    state = jnp.where(fresh[:, None, None, None], 0.0, state)
    o, state = gdn_scan(q, k, v, g, beta, m, state)
    return o, (jax.lax.dynamic_update_index_in_dim(
        states, state[0], rows[0], axis=0) if one
        else states.at[rows].set(state))


def gdn_chunk(q, k, v, g, beta, valid, fresh, states, rows):
    """:func:`gdn_scan` over rows of the layer's state array ``states [R,
    NH, Dk, Dv]`` float32: batch row ``b`` continues row ``rows[b]``
    (distinct), from zeros where ``fresh[b]`` (the call holds the sequence's
    position 0).  Returns ``(o [B, S, NH, Dv] float32, states)``; every
    other row keeps its bits, and given the array donated the one row of a
    prefill chunk is written where it lay."""
    B, S = q.shape[:2]
    m = (jnp.ones((B, S), jnp.int32) if valid is None
         else jnp.asarray(valid).astype(jnp.int32))
    return _gdn_chunk_impl(q, k, v, g, beta, m, jnp.asarray(fresh), states,
                           rows.astype(jnp.int32))


# -- the one-token step on the state array where it lies -----------------------


def _as_column(row, n: int):
    """``[1, T] -> [T, n]``, every lane of row ``r`` the entry ``r``: the
    row laid down the sublanes, turned (one ``[128, 128]`` transpose)."""
    return jnp.broadcast_to(row, (n, row.shape[1])).T


def _step_kernel(src_ref, cnt_ref, rows_ref, fresh_ref, decay_ref, beta_ref,
                 s_in, q_ref, k_ref, v_ref, s_out, o_ref):
    """One program: every head of one LIVE row's state, ``s_in`` and
    ``s_out`` blocks of the one HBM buffer (aliased); the tokens' q, k, v
    and the ``o`` they leave stay in VMEM for the whole call, the scalars a
    head in SMEM.  A program past the count holds the block the last live
    one held and does nothing; of a call with no live row the first program
    hands its block back as it came."""
    del rows_ref                       # the index maps read the row ids
    f32 = jnp.float32
    i = pl.program_id(0)
    cnt = cnt_ref[0]
    _, NH, Dk, Dv = s_in.shape

    @pl.when(i == 0)
    def _():
        # a row no program visits reads exactly 0
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < cnt)
    def _():
        b = src_ref[i]
        keep = fresh_ref[b] == 0
        # row b of q, k, v and o: a dynamic row is reached through its
        # aligned eight (Mosaic loads no single sublane at a traced index)
        eight = pl.ds(pl.multiple_of(b // 8 * 8, 8), 8)
        read = functools.partial(jnp.dot, precision=_HI,
                                 preferred_element_type=f32)
        mine = jax.lax.broadcasted_iota(jnp.int32, (8, Dk), 0) == b % 8
        minev = jax.lax.broadcasted_iota(jnp.int32, (8, Dv), 0) == b % 8
        for h in range(NH):
            kl, vl = pl.ds(h * Dk, Dk), pl.ds(h * Dv, Dv)
            k8 = jnp.where(mine, k_ref[eight, kl], 0.0)        # [8, Dk]
            q8 = jnp.where(mine, q_ref[eight, kl], 0.0)
            v1 = jnp.sum(jnp.where(minev, v_ref[eight, vl], 0.0), axis=0,
                         keepdims=True)                        # [1, Dv]
            s = decay_ref[b, h] * jnp.where(keep, s_in[0, h], 0.0)
            # (seven of the eight rows are zeros: their sum is row b's)
            sk = jnp.sum(read(k8, s), axis=0, keepdims=True)   # [1, Dv]
            d = beta_ref[b, h] * (v1 - sk)
            k1 = jnp.sum(k8, axis=0, keepdims=True)            # [1, Dk]
            s = s + _as_column(k1, Dv) * d
            s_out[0, h] = s
            o = jnp.sum(read(q8, s), axis=0, keepdims=True)
            o_ref[eight, vl] = jnp.where(minev, o, o_ref[eight, vl])

    @pl.when((cnt == 0) & (i == 0))
    def _():
        s_out[...] = s_in[...]


def _step_call(state, src, cnt, rows, fresh, decay, beta, q, k, v,
               interpret):
    _, NH, Dk, Dv = state.shape
    B = q.shape[0]

    def block(i, src, cnt, rows, *_):
        # a program past the count: the last live program's block again,
        # which is no fetch and no write-back
        i = jnp.where(i < cnt[0], i, jnp.maximum(cnt[0] - 1, 0))
        return (rows[src[i]], 0, 0, 0)

    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, NH, Dk, Dv), block), whole(q.shape),
                  whole(k.shape), whole(v.shape)],
        out_specs=[pl.BlockSpec((1, NH, Dk, Dv), block), whole(v.shape)],
    )

    def call(interp):
        return pl.pallas_call(
            _step_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct(v.shape, jnp.float32)],
            # operands count the six scalar-prefetched ones: the state is
            # the 7th
            input_output_aliases={6: 0},
            # o is one block the whole call, zeroed by the first program
            compiler_params=_compiler_params(("arbitrary",), interp,
                                             _STEP_VMEM_BYTES),
            interpret=interp,
            name="gdn_step",
        )

    state, o = run_kernel(call, interpret, src, cnt, rows, fresh, decay, beta,
                          state, q, k, v)
    return o, state


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def _gdn_step_impl(state, q, k, v, g, beta, live, fresh, rows, kernel=False,
                   interpret=None):
    f32 = jnp.float32
    B, NH = q.shape[:2]
    Dv = v.shape[-1]
    m = live.astype(f32)[:, None]
    decay, beta = jnp.exp(g * m), beta * m
    if not kernel:
        s = jnp.where(fresh[:, None, None, None], 0.0, state[rows]) \
            * decay[..., None, None]
        d = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                              precision=_HI))
        s = s + k[..., :, None] * d[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
        return jnp.where(live[:, None, None], o, 0.0), state.at[rows].set(s)
    order, cnt = live_rows_first(live)
    # (q, k, v and o in whole eights of rows: the kernel reaches a row by
    # its eight)
    flat = lambda a: jnp.pad(a.reshape(B, -1), ((0, -B % 8), (0, 0)))  # noqa: E731
    o, state = _step_call(state, order, cnt, rows, fresh.astype(jnp.int32),
                          decay, beta, flat(q), flat(k), flat(v), interpret)
    return o[:B].reshape(B, NH, Dv), state


def gdn_step(state, q, k, v, g, beta, live=None, fresh=None, rows=None, *,
             kernel: bool = False, interpret: Optional[bool] = None):
    """One token a row on the layer's state array ``state [R, NH, Dk, Dv]``
    float32 where it lies (given donated, in place).  ``q, k [B, NH, Dk]``
    (L2-normalised, ``q`` scaled), ``v [B, NH, Dv]``, ``g, beta [B, NH]``,
    all taken as float32; ``live [B]`` which batch rows are tokens (None:
    all), ``fresh [B]`` which begin their sequence (their row counts as
    zeros; None: none), ``rows [B]`` the state row each batch row continues
    (None: row ``b``; distinct among the live).  Returns ``(o [B, NH, Dv]
    float32, state)``.

    ``kernel`` (the caller's resolved ``paged_kernel``) takes the Pallas
    call ``gdn_step``: the rows that are tokens by their ids and no byte of
    any other — such a row keeps its state's BITS and reads ``o`` exactly
    0.  Else the XLA form: a gather of the rows, the step (an identity step
    for a row that is no token) and a scatter; its ``o`` of such a row is 0
    too.  ``interpret`` as in ``ops.paged_attention``."""
    B = q.shape[0]
    f32 = jnp.float32
    with jax.named_scope("gdn_step"):
        return _gdn_step_impl(
            state, q.astype(f32), k.astype(f32), v.astype(f32),
            g.astype(f32), beta.astype(f32),
            jnp.ones((B,), bool) if live is None else jnp.asarray(live) > 0,
            jnp.zeros((B,), bool) if fresh is None else jnp.asarray(fresh),
            jnp.arange(B, dtype=jnp.int32) if rows is None
            else jnp.asarray(rows, jnp.int32), kernel=kernel,
            interpret=interpret)
