"""Heads of 64 in the page pool (Granite-4.0-H's attention layers), under the
Pallas interpreter on the CPU: the pool keeps two such heads to a 128-lane
row (``kvcache.pool.page_layout``), the writer lays a step's rows the same
way, and the walk hands the kernel a pair's query heads as ``[q | 0]`` and
``[0 | q]`` — all of it held, bit for bit where it can be, to the plain
layout ``[pages, kv heads, page, 64]`` read through the gather form.  An ODD
count of 64-wide heads is not paired: the pool keeps it as it is and the
same walk runs on it (the interpreter and the gather path take it; a
compiled walk needs rows of whole lanes).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kvcache.pool import (
    LANES,
    init_page_pool_caches,
    page_layout,
)
from neuronx_distributed_tpu.kvcache.quant import quantize_page
from neuronx_distributed_tpu.ops.kv_pool_write import write_pool_rows
from neuronx_distributed_tpu.ops.paged_attention import (
    gather_page_chain,
    paged_attention,
    paged_attention_reference,
)

pytestmark = pytest.mark.paged_kernel

D, PAGE, PP, NP_, B = 64, 8, 6, 40, 3
T = PP * PAGE


def _pools(nkv, dtype=jnp.float32):
    """An empty pool as the program builds it, and the plain layout."""
    (k, v), = init_page_pool_caches(1, NP_, PAGE, nkv, D, dtype)
    plain = jnp.zeros((NP_, nkv, PAGE, D), dtype)
    return (k, v), (plain, plain)


def _filled(rs, nkv, kernel, rows=T - 5):
    """Both layouts after the same write of ``rows`` cells a slot, and the
    block table."""
    pool, plain = _pools(nkv)
    bt = jnp.asarray(rs.permutation(np.arange(1, NP_))[:B * PP].reshape(
        B, PP), jnp.int32)
    new = [jnp.asarray(rs.standard_normal((B, rows, nkv, D)), jnp.float32)
           for _ in range(2)]
    idx = jnp.broadcast_to(jnp.arange(rows)[None, :], (B, rows))
    phys = jnp.take_along_axis(bt, idx // PAGE, axis=1)
    write = lambda p, x: write_pool_rows(p, x, phys, idx % PAGE,  # noqa: E731
                                         kernel=kernel)
    return (tuple(write(p, x) for p, x in zip(pool, new)),
            tuple(write(p, x) for p, x in zip(plain, new)), bt)


@pytest.mark.parametrize("nkv", [1, 2, 3, 4, 8])
def test_the_pool_pairs_an_even_count_of_half_row_heads(nkv):
    (k, _), _ = _pools(nkv)
    if nkv % 2:
        # an odd count is kept one head to a row
        assert page_layout(nkv, D) == (nkv, D)
        assert k.shape == (NP_, nkv, PAGE, D)
    else:
        assert page_layout(nkv, D) == (nkv // 2, LANES)
        assert k.shape == (NP_, nkv // 2, PAGE, LANES)
    assert k.size == NP_ * nkv * PAGE * D        # never a padded element


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("first,rows", [(0, 1), (5, 1), (0, 17), (3, 20)])
@pytest.mark.parametrize("nkv", [2, 3, 4])
def test_pool_write_lays_the_rows_as_the_pool_keeps_them(nkv, first, rows,
                                                         kernel):
    """A decode's row and a chunk's rows, page-aligned and not: every cell
    of the paired pool holds the bits the plain pool holds, read back
    through the gather form; a row routed past the pool is dropped."""
    rs = np.random.RandomState(nkv * 100 + rows)
    pool, plain = _pools(nkv)
    bt = jnp.asarray(rs.permutation(np.arange(1, NP_))[:B * PP].reshape(
        B, PP), jnp.int32)
    new = jnp.asarray(rs.standard_normal((B, rows, nkv, D)), jnp.float32)
    idx = first + jnp.broadcast_to(jnp.arange(rows)[None, :], (B, rows))
    phys = jnp.take_along_axis(bt, idx // PAGE, axis=1)
    phys = phys.at[1].set(NP_)                    # slot 1 is parked
    got = write_pool_rows(pool[0], new, phys, idx % PAGE, kernel=kernel)
    want = write_pool_rows(plain[0], new, phys, idx % PAGE, kernel=False)
    a, _ = gather_page_chain((got, got), bt, jnp.float32, D)
    b, _ = gather_page_chain((want, want), bt, jnp.float32)
    assert a.shape == b.shape == (B, T, nkv, D)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.asarray(a[1]).any()


@pytest.mark.parametrize("S", [1, 5, 16], ids=["decode", "verify", "chunk"])
@pytest.mark.parametrize("group", [1, 4, 7])
@pytest.mark.parametrize("nkv", [2, 3, 4])
def test_the_walk_over_paired_heads_is_the_gather_form(nkv, group, S):
    """Decode, verify and chunk rows over a paired pool (an odd head count:
    the unpaired one) against the gather form on the plain layout, ragged
    offsets, a left pad and a parked slot among them — and bit for bit
    against the same walk over the plain layout: the lanes a head does not
    own meet exact zeros."""
    rs = np.random.RandomState(nkv * 1000 + group * 10 + S)
    pool, plain, bt = _filled(rs, nkv, kernel=False)
    q = jnp.asarray(rs.standard_normal((B, S, nkv * group, D)), jnp.float32)
    off = jnp.asarray([T - 5 - S, 9, T], jnp.int32)
    start = jnp.asarray([0, 3, 0], jnp.int32)
    kw = dict(sm_scale=1.0 / 64)
    out = paged_attention(q, pool, bt, off, start, interpret=True, **kw)
    ref = paged_attention_reference(q, plain, bt, off, start, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    same = paged_attention(q, plain, bt, off, start, interpret=True, **kw)
    assert np.array_equal(np.asarray(out), np.asarray(same))
    assert not np.asarray(out[2]).any()           # the parked slot: zeros


@pytest.mark.parametrize("window", [None, 11])
def test_paired_heads_under_a_window_a_softcap_and_the_default_scale(window):
    """The walk's other arguments pass through the pairing: a window, a
    softcap, and the default scale, which is the HEAD's ``64 ** -0.5`` and
    not the row's."""
    rs = np.random.RandomState(7)
    pool, plain, bt = _filled(rs, 4, kernel=True)
    q = jnp.asarray(rs.standard_normal((B, 3, 16, D)), jnp.float32)
    off = jnp.asarray([T - 8, 12, 30], jnp.int32)
    kw = dict(window=window, softcap=20.0)
    out = paged_attention(q, pool, bt, off, interpret=True, **kw)
    ref = paged_attention_reference(q, plain, bt, off, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_int8_pages_of_paired_heads_dequantize_in_the_walk():
    """An int8 six-tuple pool in the paired layout: a page's scale and zero
    are the page's whatever the heads' order inside it."""
    rs = np.random.RandomState(11)
    fp, plain, bt = _filled(rs, 4, kernel=False)
    six = sum((quantize_page(p) for p in fp), ())           # k.., v..
    six = (six[0], six[3], six[1], six[2], six[4], six[5])
    q = jnp.asarray(rs.standard_normal((B, 1, 8, D)), jnp.float32)
    off = jnp.asarray([T - 6, 9, 20], jnp.int32)
    out = paged_attention(q, six, bt, off, interpret=True)
    ref = paged_attention_reference(q, six, bt, off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # ... and the quantized pool is the plain one's within a code's step
    near = paged_attention_reference(q, plain, bt, off)
    assert float(jnp.max(jnp.abs(out - near))) < 0.05
