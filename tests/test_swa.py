"""Sliding-window (Mistral-style) attention parity tests.

Same dense-vs-kernel methodology as test_attention.py: the pallas kernels
run in interpreter mode on CPU, and every windowed path must match the
dense oracle with the identical band mask.  Window sizes are chosen to
cross block boundaries (window < block, == block, spanning several blocks,
>= sequence) so both the in-block band mask and the out-of-band block-skip
condition are exercised.

The reference has no sliding-window support anywhere (its CoreAttention is
plain causal, ``examples/training/llama2/modeling_llama_nxd.py:193-214``) —
this is capability beyond the reference, following the Mistral-7B family
definition (window W: query p attends keys [p-W+1, p]).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import square_flash_grid
from neuronx_distributed_tpu.ops import (
    flash_attention,
    flash_attention_segmented,
    mha_reference,
    ring_attention,
    ulysses_attention,
)
from neuronx_distributed_tpu.ops.flash_attention import (
    band_blocks,
    band_mask,
    flash_attention_segmented_with_lse,
    flash_attention_with_lse,
)
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel


def _qkv(key, B, HQ, HKV, S, T, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, HQ, S, D), dtype)
    k = jax.random.normal(kk, (B, HKV, T, D), dtype)
    v = jax.random.normal(kv, (B, HKV, T, D), dtype)
    return q, k, v


def _t(x):
    return x.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("gqa", [1, 2], ids=["mha", "gqa2"])
@pytest.mark.parametrize("window", [1, 7, 16, 24, 100])
def test_swa_forward_matches_dense(window, gqa):
    B, HKV, S, D = 1, 2, 64, 8
    q, k, v = _qkv(jax.random.PRNGKey(0), B, HKV * gqa, HKV, S, S, D)
    out = flash_attention(q, k, v, True, None, 16, 16, None, window)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_swa_full_window_equals_unwindowed():
    """window >= S covers every causal key: identical to plain causal."""
    B, HKV, S, D = 1, 2, 64, 8
    q, k, v = _qkv(jax.random.PRNGKey(1), B, 2, HKV, S, S, D)
    out_w = flash_attention(q, k, v, True, None, 16, 16, None, S)
    out = flash_attention(q, k, v, True, None, 16, 16, None, None)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(out), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("window", [9, 24])
def test_swa_grads_match_dense(window):
    B, HKV, S, D = 1, 2, 64, 8
    q, k, v = _qkv(jax.random.PRNGKey(2), B, 4, HKV, S, S, D)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 16, 16, None, window) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True, window=window) ** 2)

    g_f = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b, name in zip(g_f, g_r, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}"
        )


def test_swa_segmented_matches_oracle():
    """Band mask AND document mask compose: neither cross-document nor
    out-of-window keys are visible."""
    B, HKV, S, D, W = 1, 2, 64, 8, 12
    q, k, v = _qkv(jax.random.PRNGKey(3), B, 2, HKV, S, S, D)
    segs = jnp.concatenate(
        [jnp.full((B, S // 2), 1, jnp.int32), jnp.full((B, S // 2), 2, jnp.int32)],
        axis=1,
    )
    out = flash_attention_segmented(q, k, v, segs, segs, True, None, 16, 16, None, W)

    qpos = np.arange(S)[:, None]
    kpos = np.arange(S)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - W)
    mask &= np.asarray(segs)[0][:, None] == np.asarray(segs)[0][None, :]
    s = jnp.einsum("bhsd,bhtd->bhst", q, k, preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(D)
    s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhst,bhtd->bhsd", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_swa_requires_causal():
    B, HKV, S, D = 1, 2, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(4), B, 2, HKV, S, S, D)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, 16, 16, None, 8)
    with pytest.raises(ValueError, match="causal"):
        mha_reference(q, k, v, causal=False, window=8)


def test_swa_window_zero_rejected():
    """window < 1 must raise on every path — a silent all-False mask would
    degenerate softmax to uniform attention with no error."""
    from neuronx_distributed_tpu.models.llama import _causal_mask

    initialize_model_parallel()
    B, HKV, S, D = 1, 2, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(11), B, 2, HKV, S, S, D)
    with pytest.raises(ValueError, match=">= 1"):
        _causal_mask(S, S, 0, window=0)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, k, v, True, None, 16, 16, None, 0)
    with pytest.raises(ValueError, match=">= 1"):
        mha_reference(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match=">= 1"):
        ring_attention(_t(q), _t(k), _t(v), causal=True, window=0)


# ---------------------------------------------------------------------------
# the grids walk the band, not the square
# ---------------------------------------------------------------------------

BAND_CASES = {  # (S, T, block_q, block_k, window)
    "train_cells_16th": (512, 512, 32, 32, 256),   # 8192 / 4096 / 512 scaled
    "window_not_block_multiple": (256, 256, 32, 32, 100),
    "window_under_one_block": (256, 256, 32, 32, 7),
    "window_of_one": (128, 128, 32, 32, 1),
    "window_equals_seq": (256, 256, 32, 32, 256),
    "window_past_seq": (256, 256, 32, 32, 1000),
    "wide_q_blocks": (256, 256, 64, 16, 96),
    "wide_kv_blocks": (256, 256, 16, 64, 96),
    "kv_longer": (128, 384, 32, 32, 80),           # T > S: chunked prefill, ring
    "kv_longer_unequal_blocks": (128, 384, 32, 64, 150),
    "kv_longer_no_window": (128, 384, 64, 32, None),
    "no_window": (256, 256, 32, 32, None),
    "kv_shorter": (256, 128, 32, 32, 40),          # T < S: rows with no key
    "one_block": (64, 64, 64, 64, 5),
    "blocks_no_power_of_two": (192, 384, 48, 96, 70),  # the `//` arm
}


@pytest.mark.parametrize("by_kv", [False, True], ids=["fwd_dq", "dkv"])
@pytest.mark.parametrize("case", BAND_CASES.values(), ids=BAND_CASES.keys())
def test_band_blocks_match_brute_force(case, by_kv):
    """``band_blocks`` against the mask itself: a block pair is live when
    ``band_mask`` shows a key in it.  The inner axis is as wide as the
    widest row of live blocks; walking it visits every live block of a row
    once, in ascending order, and a step before a short row's turn stands on
    the row's first block (so the pipeline fetches nothing for it)."""
    S, T, bq, bk, window = case
    mask = np.asarray(band_mask(S, T, T - S, window))
    seen = mask.reshape(S // bq, bq, T // bk, bk).any(axis=(1, 3))  # [nq, nk]
    if by_kv:
        seen = seen.T  # [outer, inner]
    band = band_blocks(S, T, bq, bk, True, window, by_kv)
    assert band.by_kv == by_kv
    assert band.live == seen.sum()
    assert band.width == max(1, seen.sum(axis=1).max())
    assert band.stepped == seen.shape[0] * band.width
    for outer, row in enumerate(seen):
        steps = [band.step(outer, j, np) for j in range(band.width)]
        ran = [i for i, fresh in steps if fresh and row[i]]
        assert ran == list(np.flatnonzero(row)), outer
        # a step that visits nothing new stands on a block that a live step
        # of this row fetches anyway, and a row with live blocks ends on one
        assert all(steps[j][0] == steps[j + 1][0]
                   for j in range(band.width - 1) if not steps[j][1]), outer
        assert steps[-1][1], outer


def test_band_blocks_at_the_training_cells_shape():
    """Sequence 8192 under a window of 4096 in blocks of 512 x 512: 108
    live block pairs a (batch, head) in 144 grid steps where the square had
    256; causal without a window keeps the width (the last q block sees
    every key); a non-causal call keeps the square."""
    for by_kv in (False, True):
        band = band_blocks(8192, 8192, 512, 512, True, 4096, by_kv)
        assert (band.width, band.live, band.stepped) == (9, 108, 144)
        causal = band_blocks(8192, 8192, 512, 512, True, None, by_kv)
        assert (causal.width, causal.live, causal.stepped) == (16, 136, 256)
        square = band_blocks(8192, 8192, 512, 512, False, None, by_kv)
        assert (square.width, square.live, square.stepped) == (16, 256, 256)
        assert square.reach is None


def _segments(B, S):
    row = np.zeros(S, np.int32)
    row[: S // 3] = 1
    row[S // 3: S - 5] = 2  # tail stays 0 = padding
    return jnp.broadcast_to(jnp.asarray(row), (B, S))


KERNEL_CASES = {  # (HQ, HKV, S, T, block_q, block_k, window, softcap, segmented)
    "plain": (2, 2, 128, 128, 32, 32, 40, None, False),
    "gqa4": (4, 1, 128, 128, 32, 16, 70, None, False),
    "kv_longer": (2, 2, 64, 192, 32, 32, 50, None, False),
    "softcap": (2, 1, 128, 128, 16, 32, 24, 5.0, False),
    "segmented": (2, 2, 128, 128, 32, 32, 40, None, True),
    "segmented_softcap_gqa4": (4, 1, 128, 128, 32, 32, 33, 3.0, True),
    "no_window": (2, 2, 128, 128, 32, 32, None, None, False),
    "blocks_no_power_of_two": (2, 1, 192, 192, 48, 96, 70, None, False),
}


@pytest.mark.parametrize("case", KERNEL_CASES.values(), ids=KERNEL_CASES.keys())
def test_band_grid_matches_dense_and_the_square_grid(case):
    """Forward, ``lse`` and all three gradients (the ``lse`` cotangent
    included) of the banded grids: bit-equal to the same kernels stepping
    the whole square, and equal to the dense oracle."""
    HQ, HKV, S, T, bq, bk, window, softcap, segmented = case
    B, D = 1, 16
    q, k, v = _qkv(jax.random.PRNGKey(S + T + HQ), B, HQ, HKV, S, T, D)
    r_o = jax.random.normal(jax.random.PRNGKey(7), (B, HQ, S, D))
    r_lse = jax.random.normal(jax.random.PRNGKey(8), (B, HQ, S))
    segs = _segments(B, S) if segmented else None
    rows = (np.asarray(segs[0]) > 0) if segmented else np.ones(S, bool)
    w = jnp.asarray(rows, jnp.float32)  # padding rows hold garbage by contract

    def flash(q, k, v):
        if segmented:
            return flash_attention_segmented_with_lse(
                q, k, v, segs, segs, True, None, bq, bk, None, window, softcap)
        return flash_attention_with_lse(
            q, k, v, True, None, bq, bk, None, window, softcap)

    def dense(q, k, v):
        G = HQ // HKV
        s = jnp.einsum("bhsd,bhtd->bhst", q, jnp.repeat(k, G, axis=1)) * D ** -0.5
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = band_mask(S, T, T - S, window)[None, None]
        if segmented:
            same = segs[:, None, :, None] == segs[:, None, None, :]
            mask = mask & same & (segs > 0)[:, None, :, None]
        s = jnp.where(mask, s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        return jnp.einsum("bhst,bhtd->bhsd", p, jnp.repeat(v, G, axis=1)), lse

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return (jnp.sum(o * r_o * w[None, None, :, None])
                    + jnp.sum(lse * r_lse * w[None, None, :]))
        return f

    def everything(fn):
        o, lse = fn(q, k, v)
        return (o, lse) + jax.grad(loss(fn), (0, 1, 2))(q, k, v)

    banded = everything(flash)
    with square_flash_grid():
        square = everything(flash)
    oracle = everything(dense)
    for name, a, b, c in zip(("o", "lse", "dq", "dk", "dv"), banded, square, oracle):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
        a, c = np.asarray(a), np.asarray(c)
        if name in ("o", "lse"):
            a, c = a[:, :, rows], c[:, :, rows]
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-4, err_msg=name)


# ---------------------------------------------------------------------------
# context-parallel composition
# ---------------------------------------------------------------------------


@pytest.fixture
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


def test_swa_ulysses_matches_dense(devices8):
    """Under ulysses every device holds the full sequence post-a2a, so the
    band composes with cp > 1 unmodified."""
    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=2, devices=devices8
    )
    B, HKV, S, D, W = 1, 2, 64, 8, 20
    q, k, v = _qkv(jax.random.PRNGKey(5), B, 4, HKV, S, S, D)
    ref = mha_reference(q, k, v, causal=True, window=W)
    out = jax.jit(
        lambda a, b, c: ulysses_attention(
            a, b, c, causal=True, block_q=16, block_k=16, window=W
        )
    )(_t(q), _t(k), _t(v))
    np.testing.assert_allclose(
        np.asarray(_t(out)), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_swa_ring_matches_oracle(devices8):
    """Sliding window under the contiguous ring (W <= S/cp): the
    one-neighbor schedule — a single ppermute + one [left|own] 2C-timeline
    kernel call — matches the global dense oracle for values and grads.
    Device 0's wrapped 'left' chunk (future tokens) must contribute
    nothing, which value parity pins."""
    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=4, devices=devices8
    )
    B, HKV, S, D, W = 1, 2, 64, 8, 12  # C = 16, W < C
    q, k, v = _qkv(jax.random.PRNGKey(6), B, 4, HKV, S, S, D)
    ref = mha_reference(q, k, v, causal=True, window=W)
    fn = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, causal=True, block_q=16, block_k=16, window=W))
    out = fn(_t(q), _t(k), _t(v))
    np.testing.assert_allclose(
        np.asarray(_t(out)), np.asarray(ref), rtol=1e-5, atol=1e-5)

    g_r = jax.grad(lambda a, b, c: jnp.sum(fn(_t(a), _t(b), _t(c)) ** 2),
                   (0, 1, 2))(q, k, v)
    g_o = jax.grad(lambda a, b, c: jnp.sum(
        _t(mha_reference(a, b, c, causal=True, window=W)) ** 2), (0, 1, 2))(q, k, v)
    for a, b, n in zip(g_r, g_o, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{n}")


def test_swa_ring_window_equals_chunk(devices8):
    """W == S/cp exactly (the Mistral-32k-at-cp-8 shape) also holds."""
    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=4, devices=devices8
    )
    B, HKV, S, D = 1, 2, 64, 8
    W = 16  # == C
    q, k, v = _qkv(jax.random.PRNGKey(16), B, 2, HKV, S, S, D)
    ref = mha_reference(q, k, v, causal=True, window=W)
    out = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, causal=True, block_q=16, block_k=16, window=W))(_t(q), _t(k), _t(v))
    np.testing.assert_allclose(
        np.asarray(_t(out)), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_swa_ring_band_equals_square_grid(devices8):
    """The ring's windowed schedule calls the kernels on a ``[left | own]``
    timeline (``T = 2 S``, the window a chunk or less): values and grads of
    the banded grids equal the square grid's bit for bit."""
    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=4, devices=devices8
    )
    B, HKV, S, D, W = 1, 2, 128, 8, 20  # C = 32 in blocks of 16
    q, k, v = _qkv(jax.random.PRNGKey(18), B, 4, HKV, S, S, D)

    def run():
        fn = jax.jit(lambda a, b, c: ring_attention(
            a, b, c, causal=True, block_q=16, block_k=16, window=W))
        out = fn(_t(q), _t(k), _t(v))
        grads = jax.grad(lambda a, b, c: jnp.sum(fn(_t(a), _t(b), _t(c)) ** 2),
                         (0, 1, 2))(q, k, v)
        return (out,) + grads

    banded = run()
    with square_flash_grid():
        square = run()
    for a, b, n in zip(banded, square, ("o", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=n)
    ref = mha_reference(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(_t(banded[0])), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_swa_ring_packed_matches_oracle(devices8):
    """Packed documents + sliding window + contiguous ring: the left-
    neighbor schedule carries both the document mask and the band."""
    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=4, devices=devices8
    )
    B, HKV, S, D, W = 1, 2, 64, 8, 10
    q, k, v = _qkv(jax.random.PRNGKey(17), B, 2, HKV, S, S, D)
    seg_row = np.zeros(S, np.int32)
    seg_row[:30] = 1
    seg_row[30:58] = 2  # tail [58:] stays 0 = padding
    segs = jnp.broadcast_to(jnp.asarray(seg_row), (B, S))

    qpos = np.arange(S)[:, None]
    kpos = np.arange(S)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - W)
    mask &= (seg_row[:, None] == seg_row[None, :]) & (seg_row > 0)[:, None]
    kk = jnp.repeat(k, 1, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, k) / jnp.sqrt(D)
    s = jnp.where(jnp.asarray(mask)[None, None], s, -1e30)
    ref = jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(s, axis=-1), v)

    out = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, causal=True, segment_ids=segs, block_q=16, block_k=16,
        window=W))(_t(q), _t(k), _t(v))
    out = _t(out)
    live = seg_row > 0
    np.testing.assert_allclose(
        np.asarray(out)[:, :, live], np.asarray(ref)[:, :, live],
        rtol=1e-5, atol=1e-5)


def test_swa_ring_cp_raises(devices8):
    """Out-of-contract ring+window cases reject with guidance: W > S/cp
    (one-neighbor schedule can't see far enough) and zigzag (band already
    balances the contiguous layout)."""
    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=4, devices=devices8
    )
    B, HKV, S, D = 1, 2, 64, 8
    q, k, v = _qkv(jax.random.PRNGKey(6), B, 2, HKV, S, S, D)
    with pytest.raises(ValueError, match="ulysses"):
        ring_attention(_t(q), _t(k), _t(v), causal=True, window=17)  # > C=16
    with pytest.raises(ValueError, match="contiguous"):
        ring_attention(_t(q), _t(k), _t(v), causal=True, window=8,
                       layout="zigzag")


# ---------------------------------------------------------------------------
# model level (Mistral = Llama + sliding window)
# ---------------------------------------------------------------------------


def test_mistral_preset():
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.mistral_7b()
    assert cfg.sliding_window == 4096
    assert cfg.num_kv_heads == 8 and cfg.intermediate_size == 14336


def test_llama_swa_flash_matches_dense(devices8):
    """Full-model parity: tiny Llama with sliding_window, flash kernel core
    vs dense GSPMD core on a tp=2 mesh — same params, same logits, same
    grads.  Both cores apply the same band, so agreement pins the kernel's
    band against the mask-based dense implementation."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    base = dict(sequence_parallel=True, dtype=jnp.float32, param_dtype=jnp.float32,
                max_seq_len=32, sliding_window=10)
    cfg_d = LlamaConfig.tiny(attention_impl="dense", **base)
    cfg_f = LlamaConfig.tiny(attention_impl="flash", **base)
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0, cfg_d.vocab_size)

    model_d = LlamaForCausalLM(cfg_d)
    model_f = LlamaForCausalLM(cfg_f)
    params = sharded_params(model_d.init(jax.random.PRNGKey(8), ids))

    logits_d = jax.jit(model_d.apply)(params, ids)
    logits_f = jax.jit(model_f.apply)(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits_f), np.asarray(logits_d), rtol=2e-4, atol=2e-4
    )

    def loss(m):
        def f(p):
            lg = m.apply(p, ids)
            return jnp.mean(lg.astype(jnp.float32) ** 2)
        return f

    g_d = jax.jit(jax.grad(loss(model_d)))(params)
    g_f = jax.jit(jax.grad(loss(model_f)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        ),
        g_d, g_f,
    )


def test_swa_cached_decode_matches_teacher_forcing(devices8):
    """Serving with a sliding window: the cached decode path (dense core +
    band mask over the full cache) must reproduce the cacheless model's
    greedy continuation at every step.  window=5 < generated length, so the
    band genuinely bites mid-decode."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

    initialize_model_parallel(tensor_parallel_size=8, devices=devices8)
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none", sliding_window=5,
    )
    module = LlamaForCausalLM(cfg)
    params = sharded_params(
        module.init(jax.random.PRNGKey(12), jnp.zeros((2, 8), jnp.int32)))
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=2, context_len=8, max_total_len=16))
    prompt = jax.random.randint(jax.random.PRNGKey(13), (2, 8), 0, cfg.vocab_size)
    out = model.generate(prompt, max_new_tokens=6)
    full_logits = jax.jit(module.apply)(params, out)
    for t in range(8, 14):
        pred = np.asarray(jnp.argmax(full_logits[:, t - 1, :], axis=-1))
        np.testing.assert_array_equal(pred, np.asarray(out[:, t]), err_msg=f"pos {t}")


def test_llama_swa_cp_ring_matches_dense(devices8):
    """Model-level long-context SWA: tiny Llama with sliding_window on a
    tp=2 x cp=2 mesh, flash (one-neighbor ring) vs the dense core."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=2, devices=devices8)
    base = dict(sequence_parallel=True, dtype=jnp.float32,
                param_dtype=jnp.float32, max_seq_len=32, sliding_window=10)
    cfg_d = LlamaConfig.tiny(attention_impl="dense", **base)
    cfg_f = LlamaConfig.tiny(attention_impl="flash", **base)
    ids = jax.random.randint(jax.random.PRNGKey(18), (2, 32), 0, cfg_d.vocab_size)
    model_d = LlamaForCausalLM(cfg_d)
    model_f = LlamaForCausalLM(cfg_f)
    params = sharded_params(model_d.init(jax.random.PRNGKey(19), ids))
    logits_d = jax.jit(model_d.apply)(params, ids)
    logits_f = jax.jit(model_f.apply)(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits_f), np.asarray(logits_d), rtol=2e-4, atol=2e-4)

    def loss(m):
        def f(p):
            return jnp.mean(m.apply(p, ids).astype(jnp.float32) ** 2)
        return f

    g_d = jax.jit(jax.grad(loss(model_d)))(params)
    g_f = jax.jit(jax.grad(loss(model_f)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5),
        g_d, g_f)


def test_llama_swa_moe_flash_matches_dense(devices8):
    """Mistral-MoE-shaped config: sliding window + expert-parallel MoE
    compose — flash core matches the dense core for logits."""
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.trainer import initialize_parallel_model

    nxd.initialize_model_parallel(tensor_parallel_size=2, expert_parallel_size=2,
                                  devices=devices8)
    base = dict(sequence_parallel=False, dtype=jnp.float32,
                param_dtype=jnp.float32, max_seq_len=32, sliding_window=10,
                num_experts=4, moe_top_k=2, moe_dispatch="einsum")
    cfg_d = LlamaConfig.tiny(attention_impl="dense", **base)
    cfg_f = LlamaConfig.tiny(attention_impl="flash", **base)
    ids = jax.random.randint(jax.random.PRNGKey(14), (2, 32), 0, cfg_d.vocab_size)
    config = nxd.training_config(tensor_parallel_size=2, expert_parallel_size=2,
                                 compute_dtype="float32")
    model_d = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg_d), (jnp.zeros((1, 32), jnp.int32),))
    model_f = LlamaForCausalLM(cfg_f)
    logits_d = jax.jit(model_d.module.apply)(model_d.params, ids)
    logits_f = jax.jit(model_f.apply)(model_d.params, ids)
    np.testing.assert_allclose(
        np.asarray(logits_f), np.asarray(logits_d), rtol=2e-4, atol=2e-4)


def test_llama_swa_pipelined_matches_dense(devices8):
    """Mistral under the PP engine: sliding_window rides the pipelined
    blocks (pp=2 x tp=2, sync-1F1B) and the whole-schedule loss equals the
    dense oracle with the same band."""
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
        build_pipelined_llama,
        causal_lm_loss,
    )

    nxd.initialize_model_parallel(
        tensor_parallel_size=2, pipeline_parallel_size=2, devices=devices8)
    cfg = LlamaConfig.tiny(
        num_layers=4, num_heads=8, num_kv_heads=8, sequence_parallel=False,
        remat="none", dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=16, sliding_window=6)
    pmodel = build_pipelined_llama(cfg, num_microbatches=2, seed=3)
    ids = jax.random.randint(jax.random.PRNGKey(20), (4, 16), 0, cfg.vocab_size)
    labels = jnp.roll(ids, -1, axis=1)
    loss_sum, tok = jax.jit(pmodel.loss_fn)(pmodel.params, ids, labels)
    pp_loss = float(loss_sum) / float(tok)

    from test_pipeline import _dense_params_from_pipelined

    dense = LlamaForCausalLM(cfg)
    dparams = _dense_params_from_pipelined(pmodel, cfg)
    dense_loss = float(jax.jit(lambda p: causal_lm_loss(
        dense, p, {"ids": ids, "labels": labels}))(dparams))
    assert pp_loss == pytest.approx(dense_loss, rel=2e-4), (pp_loss, dense_loss)

    # and the window genuinely bites: an unwindowed dense loss differs
    cfg_n = LlamaConfig.tiny(
        num_layers=4, num_heads=8, num_kv_heads=8, sequence_parallel=False,
        remat="none", dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=16)
    plain_loss = float(jax.jit(lambda p: causal_lm_loss(
        LlamaForCausalLM(cfg_n), p, {"ids": ids, "labels": labels}))(dparams))
    assert abs(plain_loss - dense_loss) > 1e-5


def test_llama_swa_changes_logits(devices8):
    """The window must actually change attention for sequences longer than
    the window (guards against the flag silently not reaching the core)."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    base = dict(sequence_parallel=False, dtype=jnp.float32,
                param_dtype=jnp.float32, max_seq_len=32)
    cfg_w = LlamaConfig.tiny(attention_impl="dense", sliding_window=4, **base)
    cfg_n = LlamaConfig.tiny(attention_impl="dense", **base)
    ids = jax.random.randint(jax.random.PRNGKey(9), (1, 32), 0, cfg_w.vocab_size)
    model_w = LlamaForCausalLM(cfg_w)
    model_n = LlamaForCausalLM(cfg_n)
    params = sharded_params(model_n.init(jax.random.PRNGKey(10), ids))
    lw = jax.jit(model_w.apply)(params, ids)
    ln = jax.jit(model_n.apply)(params, ids)
    # early tokens (inside the window) identical; late tokens differ
    np.testing.assert_allclose(
        np.asarray(lw[:, :4]), np.asarray(ln[:, :4]), rtol=1e-5, atol=1e-5
    )
    assert float(jnp.abs(lw[:, 8:] - ln[:, 8:]).max()) > 1e-3
