"""Flash + ring attention parity tests against the dense oracle.

Methodology mirrors the reference's dense-vs-sharded integration tests
(``test/integration/parallel_layers/test_layers.py:42-84``): same inputs,
forward values and input gradients must match the unsharded reference.  The
pallas kernels run in interpreter mode on CPU (`_auto_interpret`), so this
exercises the real kernel code paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import split_flash_backward, square_flash_grid
from neuronx_distributed_tpu.ops import (
    flash_attention,
    flash_attention_segmented,
    flash_attention_with_lse,
    mha_reference,
    ring_attention,
)
from neuronx_distributed_tpu.ops.flash_attention import (
    band_blocks,
    flash_attention_segmented_with_lse,
)
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel


def _qkv(key, B, HQ, HKV, S, T, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, HQ, S, D), dtype)
    k = jax.random.normal(kk, (B, HKV, T, D), dtype)
    v = jax.random.normal(kv, (B, HKV, T, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("gqa", [1, 2], ids=["mha", "gqa2"])
def test_flash_forward_matches_dense(causal, gqa):
    B, HKV, S, D = 1, 2, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(0), B, HKV * gqa, HKV, S, S, D)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_bf16_matches_fp32_reference():
    """The TPU bench ladder's hot rungs run bf16 operands with fp32
    accumulation (preferred_element_type): the kernel's bf16 path must
    track the fp32 dense oracle within bf16 resolution — a dtype-handling
    bug here would silently poison every silicon measurement."""
    B, HKV, S, D = 2, 2, 64, 16
    q, k, v = _qkv(jax.random.PRNGKey(9), B, HKV * 2, HKV, S, S, D)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(q, k, v, causal=True)  # fp32 oracle
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=2e-2, atol=2e-2)
    # gradients flow at bf16 without NaN/inf
    g = jax.grad(lambda a: jnp.sum(
        flash_attention(a, kb, vb, True, None, 16, 16).astype(jnp.float32) ** 2
    ))(qb)
    assert g.dtype == jnp.bfloat16 and np.isfinite(np.asarray(g, np.float32)).all()


def test_flash_decode_offset():
    """T > S: queries occupy the last S positions of the kv timeline."""
    B, H, S, T, D = 1, 2, 8, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(1), B, H, H, S, T, D)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


CAUSAL_GRIDS = {  # (S, T, block_q, block_k, causal)
    "causal": (64, 64, 16, 16, True),
    "causal_unequal_blocks": (64, 64, 32, 16, True),
    "causal_decode_offset": (32, 96, 16, 16, True),
    "full": (64, 64, 16, 16, False),
}


@pytest.mark.parametrize("case", CAUSAL_GRIDS.values(), ids=CAUSAL_GRIDS.keys())
def test_flash_causal_band_equals_square_grid(case):
    """Causal WITHOUT a window: the inner axis stays as wide as the widest
    row, the steps above the diagonal repeat the diagonal's block (they stop
    fetching) — and values and gradients equal the square grid's bit for
    bit.  A non-causal call keeps the square itself."""
    S, T, bq, bk, causal = case
    B, HQ, HKV, D = 1, 4, 2, 8
    q, k, v = _qkv(jax.random.PRNGKey(S + T), B, HQ, HKV, S, T, D)
    for by_kv in (False, True):
        band = band_blocks(S, T, bq, bk, causal, None, by_kv)
        n_outer, n_inner = (T // bk, S // bq) if by_kv else (S // bq, T // bk)
        if not causal:
            assert band.reach is None
            assert band.live == band.stepped == n_outer * n_inner
        else:
            assert band.live < band.stepped <= n_outer * n_inner

    def everything():
        f = lambda q, k, v: flash_attention(q, k, v, causal, None, bq, bk)  # noqa: E731
        return (f(q, k, v),) + jax.grad(
            lambda q, k, v: jnp.sum(f(q, k, v) ** 2), (0, 1, 2))(q, k, v)

    banded = everything()
    with square_flash_grid():
        square = everything()
    for a, b, name in zip(banded, square, ("o", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(banded[0]), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


FUSED_BACKWARD_CASES = {
    # name: (S, T, bq, bk, causal, window, gqa, head_dim, dtype, softcap,
    #        segmented, lse cotangent)
    "causal_mha_d64_f32": (64, 64, 16, 16, True, None, 1, 64, "float32", None, False, False),
    "causal_gqa2_d128_bf16": (64, 64, 16, 16, True, None, 2, 128, "bfloat16", None, False, False),
    "causal_gqa4_d64_bf16": (64, 64, 16, 16, True, None, 4, 64, "bfloat16", None, False, False),
    "window_gqa2_d64_bf16": (64, 64, 16, 16, True, 24, 2, 64, "bfloat16", None, False, False),
    "window_mha_d128_f32": (64, 64, 16, 16, True, 40, 1, 128, "float32", None, False, False),
    "window_unequal_blocks_gqa2": (64, 64, 32, 16, True, 24, 2, 64, "bfloat16", None, False, False),
    "full_gqa4_d64_f32": (64, 64, 16, 16, False, None, 4, 64, "float32", None, False, False),
    "full_mha_d128_bf16": (64, 64, 16, 16, False, None, 1, 128, "bfloat16", None, False, False),
    "decode_offset_gqa2_bf16": (32, 96, 16, 16, True, None, 2, 64, "bfloat16", None, False, False),
    "decode_offset_window_f32": (32, 96, 16, 16, True, 40, 1, 64, "float32", None, False, False),
    "softcap_causal_gqa2_bf16": (64, 64, 16, 16, True, None, 2, 64, "bfloat16", 30.0, False, False),
    "softcap_window_f32": (64, 64, 16, 16, True, 24, 1, 64, "float32", 20.0, False, False),
    "segmented_gqa2_bf16": (64, 64, 16, 16, True, None, 2, 64, "bfloat16", None, True, False),
    "segmented_window_softcap_f32": (64, 64, 16, 16, True, 24, 1, 128, "float32", 30.0, True, False),
    "lse_cotangent_gqa4_bf16": (64, 64, 16, 16, True, None, 4, 64, "bfloat16", None, False, True),
    "lse_cotangent_window_f32": (64, 64, 16, 16, True, 24, 2, 128, "float32", None, False, True),
    "segmented_lse_cotangent_bf16": (64, 64, 16, 16, True, None, 2, 64, "bfloat16", None, True, True),
}


def _backward_kernels(loss, *args):
    """The backward flash kernels' names in the traced text of ``loss``'s
    gradient (nothing runs)."""
    import re

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(*args))
    return set(re.findall(r"name=(flash_\w+)", text)) - {"flash_fwd"}


@pytest.mark.parametrize("case", FUSED_BACKWARD_CASES.values(),
                         ids=FUSED_BACKWARD_CASES.keys())
def test_flash_fused_backward_equals_split(case):
    """``flash_dq_dkv`` (one call, five matmuls a tile, a head's dq rows in
    VMEM) against ``flash_dq`` + ``flash_dkv`` (the path past the budget):
    kv blocks ascending into a dq row, q blocks ascending into a dk / dv
    block, in both — dq, dk and dv bit for bit, through all four entries."""
    (S, T, bq, bk, causal, window, gqa, D, dtype, softcap, segmented,
     with_lse) = case
    B, HKV = 2, 2
    q, k, v = _qkv(jax.random.PRNGKey(S + T + D), B, HKV * gqa, HKV, S, T, D,
                   jnp.dtype(dtype))
    # documents of 24 keys, the q rows at the end of the kv timeline
    kv_seg = jnp.broadcast_to(1 + jnp.arange(T) // 24, (B, T))
    segs = (kv_seg[:, T - S:], kv_seg) if segmented else ()
    entry = {(False, False): flash_attention,
             (False, True): flash_attention_with_lse,
             (True, False): flash_attention_segmented,
             (True, True): flash_attention_segmented_with_lse}[segmented, with_lse]

    def loss(q, k, v):
        out = entry(q, k, v, *segs, causal, None, bq, bk, None, window, softcap)
        if not with_lse:
            return jnp.sum(out.astype(jnp.float32) ** 2)
        o, lse = out  # a non-zero cotangent into the lse too
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(jnp.sin(lse))

    fused = jax.grad(loss, (0, 1, 2))(q, k, v)
    assert _backward_kernels(loss, q, k, v) == {"flash_dq_dkv"}
    with split_flash_backward():
        split = jax.grad(loss, (0, 1, 2))(q, k, v)
        assert _backward_kernels(loss, q, k, v) == {"flash_dq", "flash_dkv"}
    for a, b, name in zip(fused, split, ("dq", "dk", "dv")):
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        assert np.any(np.asarray(a, np.float32) != 0), name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)


@pytest.mark.parametrize("case", [
    # (S, head_dim, dtype, the backward's kernels)
    (8192, 128, "bfloat16", {"flash_dq_dkv"}),          # the Mistral cells
    (8192, 64, "bfloat16", {"flash_dq_dkv"}),           # LFM2's
    (16384, 128, "float32", {"flash_dq_dkv"}),          # 8 MiB: the budget
    (16384, 64, "bfloat16", {"flash_dq_dkv"}),
    # a row of 64 takes a whole lane tile in VMEM: 16 MiB, as at 128
    (32768, 64, "bfloat16", {"flash_dq", "flash_dkv"}),
    (32768, 128, "bfloat16", {"flash_dq", "flash_dkv"}),  # a ring's shard
    (16384, 256, "bfloat16", {"flash_dq", "flash_dkv"}),
], ids=lambda c: f"s{c[0]}_d{c[1]}_{c[2]}")
def test_flash_backward_kernels_follow_the_budget(case):
    """Which calls take the one kernel is read from the shapes: a head's
    float32 dq rows (``S * D * 4`` bytes with ``D`` padded to whole lanes,
    whatever the operands' dtype) within ``_FUSED_DQ_BYTES`` ->
    ``flash_dq_dkv`` alone; past it -> the two kernels.  Read off the traced program's text (nothing runs)."""
    S, D, dtype, kernels = case
    q = jax.ShapeDtypeStruct((1, 4, S, D), jnp.dtype(dtype))
    kv = jax.ShapeDtypeStruct((1, 2, S, D), jnp.dtype(dtype))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 512, 512, None,
                                       4096).astype(jnp.float32))

    assert _backward_kernels(loss, q, kv, kv) == kernels


def test_flash_autotune_prints_live_of_stepped():
    """`tools/flash_autotune.py --cpu --tiny --window W`: a line a block
    pair with the kernels' `kernel_us` (None off the chip) — the one
    backward call beside the pair it replaces, each from its own program —
    and, beside them, the live block pairs of the steps each grid makes."""
    from conftest import run_cli

    proc = run_cli("tools/flash_autotune.py", "--cpu", "--tiny", "--window", "24")
    assert proc.returncode == 0, proc.stderr[-2000:]
    import json

    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    sweeps = [r for r in lines if "live_of_stepped" in r and "best" not in r]
    assert len(sweeps) == 4 and "best" in lines[-1]
    for rec in sweeps:
        assert rec["shape"]["window"] == 24 and rec["kernel_us"] is None
        S, bq, bk = rec["shape"]["seq"], rec["block_q"], rec["block_k"]
        assert rec["fwd_bwd_split_ms"] > 0  # under the budget: both timed
        for kernel, by_kv in (("flash_fwd", False), ("flash_dq", False),
                              ("flash_dkv", True), ("flash_dq_dkv", True)):
            band = band_blocks(S, S, bq, bk, True, 24, by_kv)
            assert rec["live_of_stepped"][kernel] == [band.live, band.stepped]
            assert band.live < band.stepped <= (S // bq) * (S // bk)
    [fine] = [r for r in sweeps if r["block_q"] == r["block_k"] == 16]
    assert fine["live_of_stepped"]["flash_dq_dkv"] == [9, 12]  # the square: 16


@pytest.mark.parametrize("gqa", [1, 2], ids=["mha", "gqa2"])
def test_flash_grads_match_dense(gqa):
    B, HKV, S, D = 1, 2, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(2), B, HKV * gqa, HKV, S, S, D)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 16, 16) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_f, g_d, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}"
        )


def test_flash_lse_cotangent():
    """The lse output's vjp must be correct — ring attention differentiates
    through the lse-weighted combine.  Oracle: dense logsumexp."""
    B, H, S, D = 1, 1, 16, 8
    q, k, v = _qkv(jax.random.PRNGKey(3), B, H, H, S, S, D)

    def f_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, True, None, 8, 8)
        return jnp.sum(o) + jnp.sum(jnp.sin(lse))

    def f_dense(q, k, v):
        scale = D ** -0.5
        s = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        o = jnp.einsum("bhst,bhtd->bhsd", p, v)
        return jnp.sum(o) + jnp.sum(jnp.sin(lse))

    np.testing.assert_allclose(f_flash(q, k, v), f_dense(q, k, v), rtol=1e-5)
    g_f = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_f, g_d, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}"
        )


# ---------------------------------------------------------------------------
# ring attention (cp > 1)
# ---------------------------------------------------------------------------


@pytest.fixture
def cp_mesh(devices8):
    return initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=4, devices=devices8
    )


def _model_layout(q, k, v):
    """[B,H,S,D] -> [B,S,H,D] (ring_attention's model layout)."""
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(q), t(k), t(v)


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense-chunk", "flash-chunk"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_ring_forward_matches_dense(cp_mesh, causal, use_flash):
    B, HKV, S, D = 1, 2, 64, 8
    G = 2
    q, k, v = _qkv(jax.random.PRNGKey(4), B, HKV * G, HKV, S, S, D)
    ref = mha_reference(q, k, v, causal=causal)
    qm, km, vm = _model_layout(q, k, v)
    out = jax.jit(
        lambda a, b, c: ring_attention(
            a, b, c, causal=causal, use_flash=use_flash, block_q=16, block_k=16
        )
    )(qm, km, vm)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3)), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense-chunk", "flash-chunk"])
def test_ring_grads_match_dense(cp_mesh, use_flash):
    B, HKV, S, D = 1, 2, 32, 8
    G = 2
    q, k, v = _qkv(jax.random.PRNGKey(5), B, HKV * G, HKV, S, S, D)

    def loss_ring(q, k, v):
        qm, km, vm = _model_layout(q, k, v)
        o = ring_attention(qm, km, vm, causal=True, use_flash=use_flash,
                           block_q=8, block_k=8)
        return jnp.sum(o ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_r = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_r, g_d, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}"
        )


def test_ring_cp1_degenerates(devices8):
    """cp == 1 must behave exactly like plain flash attention."""
    initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    B, H, S, D = 1, 2, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(6), B, H, H, S, S, D)
    qm, km, vm = _model_layout(q, k, v)
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, block_q=16, block_k=16))(qm, km, vm)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3)), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_llama_flash_ring_matches_dense(devices8):
    """Full-model parity: Llama tiny with the flash/ring attention core on a
    cp=2 x tp=2 x dp=2 mesh must match the dense GSPMD core."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=2, devices=devices8
    )
    base = dict(sequence_parallel=True, dtype=jnp.float32, param_dtype=jnp.float32,
                max_seq_len=32)
    cfg_d = LlamaConfig.tiny(attention_impl="dense", **base)
    cfg_f = LlamaConfig.tiny(attention_impl="flash", **base)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, cfg_d.vocab_size)

    model_d = LlamaForCausalLM(cfg_d)
    model_f = LlamaForCausalLM(cfg_f)
    params = sharded_params(model_d.init(jax.random.PRNGKey(1), ids))

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


# ---------------------------------------------------------------------------
# zigzag layout
# ---------------------------------------------------------------------------


def test_zigzag_permute_roundtrip():
    from neuronx_distributed_tpu.ops import zigzag_permute, zigzag_unpermute

    x = jnp.arange(2 * 32 * 3).reshape(2, 32, 3)
    z = zigzag_permute(x, cp=4, axis=1)
    assert not np.array_equal(np.asarray(z), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(zigzag_unpermute(z, cp=4, axis=1)),
                                  np.asarray(x))


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense-chunk", "flash-chunk"])
def test_zigzag_ring_matches_dense(cp_mesh, use_flash):
    from neuronx_distributed_tpu.ops import zigzag_permute, zigzag_unpermute

    B, HKV, S, D = 1, 2, 64, 8
    G = 2
    q, k, v = _qkv(jax.random.PRNGKey(7), B, HKV * G, HKV, S, S, D)
    ref = mha_reference(q, k, v, causal=True)
    qm, km, vm = _model_layout(q, k, v)
    qz = zigzag_permute(qm, cp=4, axis=1)
    kz = zigzag_permute(km, cp=4, axis=1)
    vz = zigzag_permute(vm, cp=4, axis=1)
    out = jax.jit(
        lambda a, b, c: ring_attention(a, b, c, causal=True, use_flash=use_flash,
                                       block_q=8, block_k=8, layout="zigzag")
    )(qz, kz, vz)
    out = zigzag_unpermute(out, cp=4, axis=1)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3)), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_zigzag_ring_grads_match_dense(cp_mesh):
    from neuronx_distributed_tpu.ops import zigzag_permute, zigzag_unpermute

    B, H, S, D = 1, 2, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(8), B, H, H, S, S, D)

    def loss_zig(q, k, v):
        qm, km, vm = _model_layout(q, k, v)
        qz, kz, vz = (zigzag_permute(x, cp=4, axis=1) for x in (qm, km, vm))
        o = ring_attention(qz, kz, vz, causal=True, use_flash=False, layout="zigzag")
        o = zigzag_unpermute(o, cp=4, axis=1)
        return jnp.sum(o ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_z = jax.jit(jax.grad(loss_zig, argnums=(0, 1, 2)))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_z, g_d, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


def test_llama_zigzag_matches_dense(devices8):
    """Full model in zigzag layout: permuted ids/positions through the
    flash+zigzag core must reproduce the dense model's logits (unpermuted)."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.ops import zigzag_permute, zigzag_unpermute

    initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=2, devices=devices8)
    base = dict(sequence_parallel=True, dtype=jnp.float32, param_dtype=jnp.float32,
                max_seq_len=32)
    cfg_d = LlamaConfig.tiny(attention_impl="dense", **base)
    cfg_z = LlamaConfig.tiny(attention_impl="flash", cp_zigzag=True, **base)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, cfg_d.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(32), ids.shape)

    model_d = LlamaForCausalLM(cfg_d)
    model_z = LlamaForCausalLM(cfg_z)
    params = sharded_params(model_d.init(jax.random.PRNGKey(1), ids))

    logits_d = jax.jit(model_d.apply)(params, ids)
    ids_z = zigzag_permute(ids, cp=2, axis=1)
    pos_z = zigzag_permute(positions, cp=2, axis=1)
    logits_z = jax.jit(model_z.apply)(params, ids_z, pos_z)
    logits_z = zigzag_unpermute(logits_z, cp=2, axis=1)
    np.testing.assert_allclose(
        np.asarray(logits_z), np.asarray(logits_d), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ulysses (all-to-all) context parallelism
# ---------------------------------------------------------------------------


@pytest.fixture
def cp2_mesh(devices8):
    return initialize_model_parallel(
        tensor_parallel_size=2, context_parallel_size=2, devices=devices8
    )


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense-chunk", "flash-chunk"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("gqa", [1, 2], ids=["mha", "gqa2"])
def test_ulysses_forward_matches_dense(cp2_mesh, causal, use_flash, gqa):
    """gqa=1 exercises the kv all-to-all path (local kv heads % cp == 0);
    gqa=2 leaves 1 local kv head so the repeat-then-a2a fallback runs."""
    from neuronx_distributed_tpu.ops import ulysses_attention

    B, S, D = 1, 64, 8
    HKV = 4 // gqa
    q, k, v = _qkv(jax.random.PRNGKey(9), B, 4, HKV, S, S, D)
    ref = mha_reference(q, k, v, causal=causal)
    qm, km, vm = _model_layout(q, k, v)
    out = jax.jit(
        lambda a, b, c: ulysses_attention(
            a, b, c, causal=causal, use_flash=use_flash, block_q=16, block_k=16
        )
    )(qm, km, vm)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3)), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense-chunk", "flash-chunk"])
def test_ulysses_grads_match_dense(cp2_mesh, use_flash):
    from neuronx_distributed_tpu.ops import ulysses_attention

    B, HKV, S, D = 1, 2, 32, 8
    G = 2
    q, k, v = _qkv(jax.random.PRNGKey(10), B, HKV * G, HKV, S, S, D)

    def loss_uly(q, k, v):
        qm, km, vm = _model_layout(q, k, v)
        o = ulysses_attention(qm, km, vm, causal=True, use_flash=use_flash,
                              block_q=8, block_k=8)
        return jnp.sum(o ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_u = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_u, g_d, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}"
        )


def test_ulysses_head_starved_raises(cp_mesh):
    """cp=4 with 2 q heads per tp shard cannot split heads over cp."""
    from neuronx_distributed_tpu.ops import ulysses_attention

    q, k, v = _qkv(jax.random.PRNGKey(11), 1, 4, 4, 64, 64, 8)
    qm, km, vm = _model_layout(q, k, v)
    with pytest.raises(ValueError, match="divisible by cp"):
        ulysses_attention(qm, km, vm, use_flash=False)


def test_llama_flash_ulysses_matches_dense(cp2_mesh):
    """Full-model parity: the ulysses cp_impl on a cp=2 x tp=2 x dp=2 mesh
    must match the dense GSPMD core."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    base = dict(sequence_parallel=True, dtype=jnp.float32, param_dtype=jnp.float32,
                max_seq_len=32)
    cfg_d = LlamaConfig.tiny(attention_impl="dense", **base)
    cfg_u = LlamaConfig.tiny(attention_impl="flash", cp_impl="ulysses", **base)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, cfg_d.vocab_size)

    model_d = LlamaForCausalLM(cfg_d)
    model_u = LlamaForCausalLM(cfg_u)
    params = sharded_params(model_d.init(jax.random.PRNGKey(1), ids))

    logits_d = jax.jit(model_d.apply)(params, ids)
    logits_u = jax.jit(model_u.apply)(params, ids)
    np.testing.assert_allclose(
        np.asarray(logits_u), np.asarray(logits_d), rtol=2e-4, atol=2e-4
    )

    def loss(m):
        def f(p):
            lg = m.apply(p, ids)
            return jnp.mean(lg.astype(jnp.float32) ** 2)
        return f

    g_d = jax.jit(jax.grad(loss(model_d)))(params)
    g_u = jax.jit(jax.grad(loss(model_u)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        ),
        g_d, g_u,
    )


# ---------------------------------------------------------------------------
# segmented (packed) flash attention
# ---------------------------------------------------------------------------


def _seg_oracle(q, k, v, seg):
    """Dense causal+segment-masked oracle (packing semantics: id 0 blocked)."""
    G = q.shape[1] // k.shape[1]
    D = q.shape[-1]
    S = q.shape[2]
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, kk, preferred_element_type=jnp.float32) * (D ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    same = (seg[:, :, None] == seg[:, None, :]) & (seg > 0)[:, :, None]
    s = jnp.where((causal[None] & same)[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, vv)


def _packed_segs(B, S):
    seg = np.zeros((B, S), np.int32)
    seg[0, : S // 3] = 1
    seg[0, S // 3: S - 5] = 2
    seg[1, : S // 2] = 1
    seg[1, S // 2:] = 2
    return jnp.asarray(seg)


@pytest.mark.parametrize("gqa", [1, 2], ids=["mha", "gqa2"])
def test_segmented_flash_matches_oracle(gqa):
    from neuronx_distributed_tpu.ops import flash_attention_segmented

    B, HKV, S, D = 2, 2, 64, 8
    q, k, v = _qkv(jax.random.PRNGKey(20), B, HKV * gqa, HKV, S, S, D)
    seg = _packed_segs(B, S)
    live = jnp.asarray((np.asarray(seg) > 0)[:, None, :, None].astype(np.float32))
    out = flash_attention_segmented(q, k, v, seg, seg, True, None, 16, 16)
    ref = _seg_oracle(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out * live), np.asarray(ref * live),
                               rtol=1e-5, atol=1e-5)

    def loss_f(q, k, v):
        o = flash_attention_segmented(q, k, v, seg, seg, True, None, 16, 16)
        return jnp.sum((o * live) ** 2)

    def loss_d(q, k, v):
        return jnp.sum((_seg_oracle(q, k, v, seg) * live) ** 2)

    g_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_f, g_d, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=f"d{name}")


def test_llama_packed_flash_matches_dense(devices8):
    """Packed batch through the FLASH path (segmented kernel) must match the
    dense core's segment masking — the packed-pretraining hot path no longer
    falls back to O(S^2) scores."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    base = dict(sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
                max_seq_len=64, remat="none")
    cfg_d = LlamaConfig.tiny(attention_impl="dense", **base)
    cfg_f = LlamaConfig.tiny(attention_impl="flash", **base)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, cfg_d.vocab_size)
    seg = _packed_segs(2, 64)
    positions = jnp.broadcast_to(jnp.arange(64), ids.shape)

    model_d = LlamaForCausalLM(cfg_d)
    model_f = LlamaForCausalLM(cfg_f)
    params = sharded_params(model_d.init(jax.random.PRNGKey(1), ids))

    lg_d = jax.jit(lambda p, i: model_d.apply(p, i, positions, segment_ids=seg))(params, ids)
    lg_f = jax.jit(lambda p, i: model_f.apply(p, i, positions, segment_ids=seg))(params, ids)
    live = np.asarray(seg)[:, :, None] > 0
    np.testing.assert_allclose(np.asarray(lg_f) * live, np.asarray(lg_d) * live,
                               rtol=2e-4, atol=2e-4)

    def loss(m):
        def f(p):
            lg = m.apply(p, ids, positions, segment_ids=seg)
            mask = (seg > 0).astype(jnp.float32)[:, :, None]
            return jnp.mean((lg.astype(jnp.float32) * mask) ** 2)
        return f

    g_d = jax.jit(jax.grad(loss(model_d)))(params)
    g_f = jax.jit(jax.grad(loss(model_f)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5),
        g_d, g_f,
    )


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_segmented_ring_matches_oracle(cp_mesh, layout):
    """Packed (segment-masked) attention under cp=4 — ring and zigzag
    schedules — must match the dense causal+segment oracle on live rows
    (VERDICT r4 next-step #4: packed long-context and CP now compose)."""
    from neuronx_distributed_tpu.ops import (
        ring_attention, zigzag_permute, zigzag_unpermute,
    )

    B, HKV, S, D = 2, 2, 64, 8
    q, k, v = _qkv(jax.random.PRNGKey(22), B, HKV * 2, HKV, S, S, D)
    seg = _packed_segs(B, S)
    ref = _seg_oracle(q, k, v, seg)
    live = np.asarray(seg)[:, None, :, None] > 0

    qm, km, vm = _model_layout(q, k, v)
    if layout == "zigzag":
        qm, km, vm = (zigzag_permute(x, cp=4, axis=1) for x in (qm, km, vm))
        seg_in = zigzag_permute(seg, cp=4, axis=1)
    else:
        seg_in = seg
    out = jax.jit(lambda a, b, c, s: ring_attention(
        a, b, c, segment_ids=s, layout=layout, block_q=8, block_k=8
    ))(qm, km, vm, seg_in)
    if layout == "zigzag":
        out = zigzag_unpermute(out, cp=4, axis=1)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3)) * live, np.asarray(ref) * live,
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_segmented_ring_grads_match_oracle(cp_mesh, layout):
    from neuronx_distributed_tpu.ops import ring_attention, zigzag_permute

    B, HKV, S, D = 2, 2, 32, 8
    q, k, v = _qkv(jax.random.PRNGKey(23), B, HKV * 2, HKV, S, S, D)
    seg = _packed_segs(B, S)
    live = jnp.asarray((np.asarray(seg) > 0)[:, None, :, None].astype(np.float32))

    def loss_ring(q, k, v):
        qm, km, vm = _model_layout(q, k, v)
        lv = live.transpose(0, 2, 1, 3)
        sin = seg
        if layout == "zigzag":
            qm, km, vm = (zigzag_permute(x, cp=4, axis=1) for x in (qm, km, vm))
            sin = zigzag_permute(seg, cp=4, axis=1)
            lv = zigzag_permute(lv, cp=4, axis=1)
        o = ring_attention(qm, km, vm, segment_ids=sin, layout=layout,
                           block_q=8, block_k=8)
        return jnp.sum((o * lv) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum((_seg_oracle(q, k, v, seg) * live) ** 2)

    g_r = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_r, g_d, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4, err_msg=f"d{name}"
        )


def test_segmented_ulysses_matches_oracle(cp2_mesh):
    from neuronx_distributed_tpu.ops import ring_attention

    B, HKV, S, D = 2, 2, 64, 8
    q, k, v = _qkv(jax.random.PRNGKey(24), B, HKV * 2, HKV, S, S, D)
    seg = _packed_segs(B, S)
    ref = _seg_oracle(q, k, v, seg)
    live = np.asarray(seg)[:, None, :, None] > 0
    qm, km, vm = _model_layout(q, k, v)
    out = jax.jit(lambda a, b, c, s: ring_attention(
        a, b, c, segment_ids=s, cp_impl="ulysses", block_q=8, block_k=8
    ))(qm, km, vm, seg)
    np.testing.assert_allclose(
        np.asarray(out.transpose(0, 2, 1, 3)) * live, np.asarray(ref) * live,
        rtol=1e-5, atol=1e-5,
    )


def test_llama_packed_cp_matches_dense(cp2_mesh):
    """Packed batch through the FLASH path under cp=2 (segmented ring) must
    match the dense core's segment masking — packed long-context and CP
    compose (VERDICT r4 next-step #4)."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    S = 256  # model flash gate needs S % (128 * cp) == 0
    base = dict(sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
                max_seq_len=S, remat="none", num_layers=1)
    cfg_d = LlamaConfig.tiny(attention_impl="dense", **base)
    cfg_f = LlamaConfig.tiny(attention_impl="flash", **base)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, S), 0, cfg_d.vocab_size)
    seg = _packed_segs(2, S)
    positions = jnp.broadcast_to(jnp.arange(S), ids.shape)

    model_d = LlamaForCausalLM(cfg_d)
    model_f = LlamaForCausalLM(cfg_f)
    params = sharded_params(model_d.init(jax.random.PRNGKey(1), ids))
    lg_d = jax.jit(lambda p, i: model_d.apply(p, i, positions, segment_ids=seg))(params, ids)
    lg_f = jax.jit(lambda p, i: model_f.apply(p, i, positions, segment_ids=seg))(params, ids)
    live = np.asarray(seg)[:, :, None] > 0
    np.testing.assert_allclose(np.asarray(lg_f) * live, np.asarray(lg_d) * live,
                               rtol=2e-4, atol=2e-4)


def test_packed_zigzag_odd_chunk_raises_shape_rule(devices8):
    """cp_zigzag packed gate: S=768 at cp=2 divides over 128*cp but the
    zigzag CHUNK is 192 rows — not kernel-tileable — so the kernel raises
    its shape rule; a flash config is never handed to the dense core."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    initialize_model_parallel(tensor_parallel_size=2, context_parallel_size=2,
                              devices=devices8)
    cfg = LlamaConfig.tiny(attention_impl="flash", cp_zigzag=True,
                           sequence_parallel=False, num_layers=1,
                           dtype=jnp.float32, param_dtype=jnp.float32,
                           max_seq_len=768, remat="none")
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 768), 0, cfg.vocab_size)
    seg = jnp.concatenate([jnp.ones((2, 400), jnp.int32),
                           2 * jnp.ones((2, 368), jnp.int32)], axis=1)
    positions = jnp.broadcast_to(jnp.arange(768), ids.shape)
    model = LlamaForCausalLM(cfg)
    with pytest.raises(ValueError, match="multiple of 128"):
        model.init(jax.random.PRNGKey(1), ids, positions, segment_ids=seg)


def test_ring_batch_indivisible_raises(devices8):
    """A real batch (B > dp) not divisible by the dp degree must be a hard
    error, not a silent dp-fold replication cliff (VERDICT r4 #4);
    probe-scale batches (B < dp, init-time tracing) still trace with a
    warning."""
    initialize_model_parallel(tensor_parallel_size=2, devices=devices8)  # dp=4
    S, D = 32, 8
    q6, k6, v6 = _qkv(jax.random.PRNGKey(25), 6, 2, 2, S, S, D)
    with pytest.raises(ValueError, match="not divisible by the dp degree"):
        ring_attention(*_model_layout(q6, k6, v6), block_q=8, block_k=8)
    q1, k1, v1 = _qkv(jax.random.PRNGKey(26), 1, 2, 2, S, S, D)
    out = ring_attention(*_model_layout(q1, k1, v1), block_q=8, block_k=8)
    ref = mha_reference(q1, k1, v1, causal=True)
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_packed_flash_short_odd_seq_runs_the_kernel(devices8):
    """A packed batch shorter than one 128-row tile is one kernel block:
    the segmented kernel serves it (matching the dense core), it is not
    swapped for the dense core behind the caller's back."""
    from conftest import sharded_params
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    base = dict(sequence_parallel=False, dtype=jnp.float32,
                param_dtype=jnp.float32, max_seq_len=96, remat="none")
    model_d = LlamaForCausalLM(LlamaConfig.tiny(attention_impl="dense", **base))
    model_f = LlamaForCausalLM(LlamaConfig.tiny(attention_impl="flash", **base))
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 0, 256)
    seg = jnp.concatenate([jnp.ones((2, 40), jnp.int32),
                           2 * jnp.ones((2, 56), jnp.int32)], axis=1)
    positions = jnp.broadcast_to(jnp.arange(96), ids.shape)
    params = sharded_params(model_d.init(jax.random.PRNGKey(1), ids))
    lg_f = jax.jit(lambda p, i: model_f.apply(p, i, positions, segment_ids=seg))(params, ids)
    lg_d = jax.jit(lambda p, i: model_d.apply(p, i, positions, segment_ids=seg))(params, ids)
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_d),
                               rtol=2e-4, atol=2e-4)
