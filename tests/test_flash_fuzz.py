"""Randomized property sweep for the flash kernel: every feature
combination (GQA grouping x sliding window x softcap x packed segments x
non-divisible-ish blocks) must match the dense oracle for values AND input
gradients.  Complements the targeted tests in test_attention/test_swa —
this is the combinatorial net that catches feature-interaction bugs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import square_flash_grid
from neuronx_distributed_tpu.ops.flash_attention import (
    NEG_INF,
    flash_attention,
    flash_attention_segmented,
    mha_reference,
)

CASES = [
    # (seed, B, HKV, G, S, D, bq, bk, window, softcap, segmented)
    (0, 1, 2, 1, 64, 8, 16, 16, None, None, False),
    (1, 2, 1, 4, 64, 16, 32, 16, None, None, False),
    (2, 1, 2, 2, 96, 8, 32, 32, None, None, False),   # S % 64 != 0 fit path
    (3, 1, 2, 1, 64, 8, 16, 16, 10, None, False),
    (4, 1, 1, 2, 64, 8, 16, 32, 33, None, False),     # window > block
    (5, 1, 2, 2, 64, 8, 16, 16, None, 7.0, False),
    (6, 1, 2, 1, 64, 8, 32, 16, 17, 3.0, False),      # window + cap
    (7, 1, 2, 1, 64, 8, 16, 16, None, None, True),
    (8, 1, 1, 2, 64, 8, 16, 16, 12, None, True),      # window + segments
    (9, 1, 2, 1, 64, 8, 16, 16, None, 5.0, True),     # cap + segments
    (10, 2, 2, 2, 64, 8, 16, 16, 9, 4.0, True),       # everything at once
    (11, 1, 2, 1, 64, 8, 64, 64, 5, 2.0, False),      # single-block grid
]


def _oracle(q, k, v, window, softcap, segs):
    """Dense oracle with all three masks/transforms composed."""
    G = q.shape[1] // k.shape[1]
    S = q.shape[2]
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, kk,
                   preferred_element_type=jnp.float32) / jnp.sqrt(q.shape[-1])
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    mask = jnp.broadcast_to(mask[None, None], s.shape[:2] + mask.shape)
    if segs is not None:
        same = (segs[:, None, :, None] == segs[:, None, None, :])
        live = (segs > 0)[:, None, :, None]
        mask = jnp.logical_and(mask, jnp.broadcast_to(same & live, mask.shape))
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, vv)


@pytest.mark.parametrize("case", CASES, ids=[f"case{c[0]}" for c in CASES])
def test_flash_feature_matrix_matches_oracle(case):
    seed, B, HKV, G, S, D, bq, bk, window, softcap, segmented = case
    key = jax.random.PRNGKey(seed)
    kq, kk_, kv, ks = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, HKV * G, S, D), jnp.float32)
    k = jax.random.normal(kk_, (B, HKV, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, HKV, S, D), jnp.float32)
    segs = None
    if segmented:
        # 2-3 random documents plus a padding tail
        cuts = sorted(jax.random.randint(ks, (2,), 8, S - 8).tolist())
        seg_row = np.zeros(S, np.int32)
        seg_row[:cuts[0]] = 1
        seg_row[cuts[0]:cuts[1]] = 2
        seg_row[cuts[1]:S - 4] = 3
        segs = jnp.broadcast_to(jnp.asarray(seg_row), (B, S))

    def run_flash(q, k, v):
        if segmented:
            return flash_attention_segmented(
                q, k, v, segs, segs, True, None, bq, bk, None, window, softcap)
        return flash_attention(q, k, v, True, None, bq, bk, None, window, softcap)

    out = run_flash(q, k, v)
    ref = _oracle(q, k, v, window, softcap, segs)
    if segmented:
        # padding rows (seg 0) produce garbage in both paths by convention;
        # compare live rows only
        live = np.asarray(segs[0] > 0)
        np.testing.assert_allclose(
            np.asarray(out)[:, :, live], np.asarray(ref)[:, :, live],
            rtol=2e-5, atol=2e-5)
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    # input gradients (mask padding rows out of the loss for segmented)
    w = jnp.ones((S,), jnp.float32) if segs is None else (segs[0] > 0).astype(jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum((run_flash(q, k, v) * w[None, None, :, None]) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum((_oracle(q, k, v, window, softcap, segs)
                        * w[None, None, :, None]) ** 2)

    g_f = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b, name in zip(g_f, g_r, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("case", CASES, ids=[f"case{c[0]}" for c in CASES])
def test_flash_feature_matrix_band_equals_square_grid(case):
    """The same matrix, the banded grids against the square ones: blocks are
    visited in the same order with the same bodies, so values and input
    gradients are equal bit for bit whatever features compose."""
    seed, B, HKV, G, S, D, bq, bk, window, softcap, segmented = case
    kq, kk_, kv = jax.random.split(jax.random.PRNGKey(100 + seed), 3)
    q = jax.random.normal(kq, (B, HKV * G, S, D), jnp.float32)
    k = jax.random.normal(kk_, (B, HKV, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, HKV, S, D), jnp.float32)
    seg_row = np.zeros(S, np.int32)
    seg_row[:S // 4] = 1
    seg_row[S // 4:S - 4] = 2
    segs = jnp.broadcast_to(jnp.asarray(seg_row), (B, S))

    def run_flash(q, k, v):
        if segmented:
            return flash_attention_segmented(
                q, k, v, segs, segs, True, None, bq, bk, None, window, softcap)
        return flash_attention(q, k, v, True, None, bq, bk, None, window, softcap)

    def everything():
        return (run_flash(q, k, v),) + jax.grad(
            lambda q, k, v: jnp.sum(run_flash(q, k, v) ** 2), (0, 1, 2))(q, k, v)

    banded = everything()
    with square_flash_grid():
        square = everything()
    for a, b, name in zip(banded, square, ("o", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_flash_softcap_bounds_scores():
    """Numerical-stability property: with huge-magnitude inputs the capped
    kernel stays finite in values and grads (uncapped fp32 scores would be
    ~1e4); and the cap really binds: outputs differ from uncapped."""
    q = 100.0 * jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 8))
    k = 100.0 * jax.random.normal(jax.random.PRNGKey(1), (1, 2, 64, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 64, 8))
    capped = flash_attention(q, k, v, True, None, 16, 16, None, None, 20.0)
    assert np.isfinite(np.asarray(capped)).all()
    g = jax.grad(lambda a: jnp.sum(
        flash_attention(a, k, v, True, None, 16, 16, None, None, 20.0) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()
    uncapped = flash_attention(q, k, v, True, None, 16, 16)
    assert float(jnp.abs(capped - uncapped).max()) > 1e-3
