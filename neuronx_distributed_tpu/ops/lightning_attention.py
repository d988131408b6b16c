"""Lightning (decayed linear) attention: the chunked form and its one-row
step.

Per head ``h`` with decay ``lambda_h = exp(-s_h)`` a position, ``s_h =
2^(-8 (h + 1) / NH)`` (the ALiBi-slope convention of Lightning Attention /
TransNormer), the recurrence is::

    S_t = lambda_h S_{t-1} + k_t^T v_t          o_t = q_t S_t / sqrt(d)

A row that is not a token (a left pad, a parked slot, a cell past the
prompt) is an IDENTITY step: no decay, no update.  So the exponent between
two rows is the number of TOKENS between them, ``n_t - n_s`` with ``n`` the
running count of valid rows, and a call's rows may be ragged.

The chunked form is an exact rearrangement over blocks of ``c`` rows (the
state enters a block as ``S_in``)::

    o    = ((Q K^T) * D) V + (Q * lambda^n) S_in      D[t, s] = lambda^(n_t - n_s), s <= t, s valid
    S_out = lambda^(n_c) S_in + (K * lambda^(n_c - n))^T V

Every exponent is <= 0 (nothing is factored into ``lambda^n`` times
``lambda^-n``: at 512 rows the fastest head's ``lambda^-n`` overflows), the
state and every accumulation are float32, the matmul operands are the
activations' dtype.  One row a call (``S == 1``) is the decode step — the
same code with a block of one.  The scan over blocks runs under the scope
``lightning_chunk`` (``lightning_decode`` at one row), which is what the
device trace and the benchmark's readers see.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# rows of one block of the chunked form: the [c, c] decay mask of a block
# and its two [c, D] x [D, D] products a head
CHUNK_ROWS = 128


def decay_slopes(num_heads: int) -> jax.Array:
    """``s_h [NH]`` float32; head ``h`` decays by ``exp(-s_h)`` a token."""
    return 2.0 ** (-8.0 * jnp.arange(1, num_heads + 1, dtype=jnp.float32)
                   / num_heads)


def _block(state, q, k, v, m, slopes, scale):
    """One block: ``q, k, v [B, c, NH, D]``, ``m [B, c]`` (1 = a token),
    ``state [B, NH, D, D]`` float32 -> ``(state, o [B, c, NH, D] f32)``."""
    f32 = jnp.float32
    n = jnp.cumsum(m.astype(f32), axis=1)                      # [B, c]
    s = slopes[None, :, None]                                  # [1, NH, 1]
    gap = n[:, None, :, None] - n[:, None, None, :]            # [B, 1, t, s]
    c = q.shape[1]
    tri = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :])
    keep = tri[None, None] & (m[:, None, None, :] > 0)
    dec = jnp.where(keep, jnp.exp(-s[..., None] * jnp.maximum(gap, 0.0)), 0.0)
    qk = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=f32)
    intra = jnp.einsum("bhts,bshd->bthd", (qk * dec).astype(v.dtype), v,
                       preferred_element_type=f32)
    qd = q.astype(f32) * jnp.exp(-s * n[:, None, :]).transpose(0, 2, 1)[..., None]
    inter = jnp.einsum("bthd,bhde->bthe", qd, state,
                       precision=jax.lax.Precision.HIGHEST)
    n_c = n[:, -1]                                             # [B]
    kd = (k.astype(f32)
          * (jnp.exp(-s * (n_c[:, None, None] - n[:, None, :]))
             * m[:, None, :].astype(f32)).transpose(0, 2, 1)[..., None])
    upd = jnp.einsum("bshd,bshe->bhde", kd.astype(k.dtype), v,
                     preferred_element_type=f32)
    state = jnp.exp(-slopes[None, :] * n_c[:, None])[..., None, None] * state \
        + upd
    return state, (intra + inter) * scale


def lightning_attention(q, k, v, valid, state, chunk_rows: int = CHUNK_ROWS):
    """``q, k, v [B, S, NH, D]``, ``valid [B, S]`` (which rows are tokens;
    ``None``: all), ``state [B, NH, D, D]`` float32 (the state the call
    continues; zeros start a sequence) -> ``(o [B, S, NH, D]`` in
    ``q.dtype``, ``state_out)``.  The output of a row that is not a token
    is not meaningful; the state ignores such rows."""
    B, S, NH, D = q.shape
    m = (jnp.ones((B, S), jnp.int32) if valid is None
         else jnp.asarray(valid).astype(jnp.int32))
    slopes = decay_slopes(NH)
    scale = D ** -0.5
    c = min(chunk_rows, S)
    pad = -S % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        m = jnp.pad(m, ((0, 0), (0, pad)))
    nb = (S + pad) // c
    with jax.named_scope("lightning_decode" if S == 1 else "lightning_chunk"):
        if nb == 1:
            state, o = _block(state, q, k, v, m, slopes, scale)
        else:
            def blocks(a):
                return a.reshape(B, nb, c, *a.shape[2:]).swapaxes(0, 1)

            state, o = jax.lax.scan(
                lambda st, x: _block(st, *x, slopes, scale), state,
                (blocks(q), blocks(k), blocks(v), blocks(m)))
            o = o.swapaxes(0, 1).reshape(B, nb * c, NH, D)
    return o[:, :S].astype(q.dtype), state


def lightning_scan_reference(q, k, v, valid, state):
    """The recurrence token by token, float32 throughout: the oracle the
    tests (and step 0 of PERF.md) hold the chunked form to."""
    B, S, NH, D = q.shape
    m = (jnp.ones((B, S), jnp.float32) if valid is None
         else jnp.asarray(valid).astype(jnp.float32))
    lam = jnp.exp(-decay_slopes(NH))[None, :, None, None]
    f32 = jnp.float32

    def step(st, x):
        qt, kt, vt, mt = x
        live = mt[:, None, None, None] > 0
        new = lam * st + kt[..., :, None] * vt[..., None, :]
        st = jnp.where(live, new, st)
        return st, jnp.einsum("bhd,bhde->bhe", qt, st) * D ** -0.5

    state, o = jax.lax.scan(
        step, state.astype(f32),
        (q.astype(f32).swapaxes(0, 1), k.astype(f32).swapaxes(0, 1),
         v.astype(f32).swapaxes(0, 1), m.swapaxes(0, 1)))
    return o.swapaxes(0, 1), state
