"""The block selection chooses without sorting (``ops/block_select.py``): the
threshold at the exact k-th largest score gives ``lax.top_k``'s set bit for
bit, ties to the lower page included; the ranked table gives the stable
``argsort``'s ``count`` and first ``count`` entries; and no program built
from them holds a sort."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.ops import block_select as bs

SPEC = bs.SparseSpec(block_size=4, kernel_size=2, kernel_stride=1,
                     init_blocks=1, window_size=6, topk=4, dense_len=16)
PP = 24


def choose_by_top_k(scores, n_row, spec):
    """The sorting form this module held until PR 40, kept as the oracle."""
    pp = scores.shape[-1]
    visible = scores > -jnp.inf
    if spec.topk >= pp:
        chosen = visible
    else:
        _, idx = jax.lax.top_k(scores, spec.topk)
        chosen = jnp.any(idx[..., None] == jnp.arange(pp), axis=-2) & visible
    dense = (n_row < spec.dense_len)[:, None, None, None]
    return jnp.where(dense, visible, chosen)


def table_by_argsort(chosen, block_table, width, nkv):
    """The stable-sort form of ``_chosen_table`` until PR 40, as the oracle."""
    B, NKV, _ = chosen.shape
    order = jnp.argsort(~chosen, axis=-1, stable=True)[..., :width]
    if order.shape[-1] < width:
        order = jnp.pad(order, ((0, 0), (0, 0), (0, width - order.shape[-1])))
    phys = jnp.take_along_axis(
        jnp.broadcast_to(block_table[:, None, :], chosen.shape), order, axis=-1)
    phys = phys * nkv + jnp.arange(nkv)[None, :, None]
    return (phys.reshape(B * NKV, width).astype(jnp.int32),
            jnp.sum(chosen, axis=-1).reshape(B * NKV).astype(jnp.int32))


def _visible_prefix(rs, shape):
    """Scores as ``block_scores`` leaves them: a visible prefix of random
    length a row, ``-inf`` beyond it."""
    vis = rs.randint(1, shape[-1] + 1, size=shape[:-1] + (1,))
    return np.arange(shape[-1]) < vis


def _random(rs, shape):
    return rs.standard_normal(shape).astype(np.float32) ** 2


def _zeros_and_ties_at_k(rs, shape):
    """Most visible scores exactly 0.0 and a few values many times over, so
    the k-th place is tied in nearly every row."""
    x = rs.choice(np.asarray([0.0, 0.0, 0.0, 0.25, 0.25, 0.5], np.float32),
                  size=shape)
    return np.where(_visible_prefix(rs, shape), x, -np.inf)


def _forced_beside_invisible(rs, shape):
    """``+inf`` forced blocks beside ``-inf`` invisible ones; many rows see
    fewer than ``topk`` blocks."""
    x = _random(rs, shape)
    vis = rs.randint(1, 2 * SPEC.topk, size=shape[:-1] + (1,))
    b = np.arange(shape[-1])
    x = np.where((b < 1) | ((b >= vis - 2) & (b < vis)), np.inf, x)
    return np.where(b < vis, x, -np.inf)


def _negative_and_minus_zero(rs, shape):
    x = rs.choice(np.asarray([-0.0, 0.0, -1.5, -1.5, 2.0, -3e38, 1e-45,
                              -1e-45], np.float32), size=shape)
    return np.where(rs.random_sample(shape) < 0.3, -_random(rs, shape), x)


CASES = {
    # name: (scores from (rs, shape), PP, rows' lengths)
    "random": (lambda rs, s: np.where(_visible_prefix(rs, s), _random(rs, s),
                                      -np.inf), PP, (64, 40)),
    "zeros_and_ties_at_k": (_zeros_and_ties_at_k, PP, (64, 40)),
    "forced_inf_beside_invisible": (_forced_beside_invisible, PP, (64, 40)),
    "a_row_under_dense_len": (_zeros_and_ties_at_k, PP, (12, 40)),
    "topk_covers_every_page": (_forced_beside_invisible, SPEC.topk, (64, 40)),
    "negative_and_minus_zero": (_negative_and_minus_zero, PP, (64, 40)),
}


@pytest.mark.parametrize("S", [1, 7], ids=["decode", "chunk"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_choose_blocks_is_top_ks_set(case, S):
    make, pp, n_row = CASES[case]
    rs = np.random.RandomState(sorted(CASES).index(case))
    choose = jax.jit(functools.partial(bs.choose_blocks, spec=SPEC))
    n = jnp.asarray(n_row, jnp.int32)
    for _ in range(3):
        scores = jnp.asarray(make(rs, (2, 2, S, pp)), jnp.float32)
        got = choose(scores, n)
        assert got.dtype == jnp.bool_
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(choose_by_top_k(scores, n, SPEC)))


def test_choose_blocks_at_the_published_sizes():
    """top-64 of 328 pages, a window of 33 forced blocks: the sizes of
    ``minicpm-sala.serve-1chip``, a few rows."""
    spec = bs.SparseSpec()
    rs = np.random.RandomState(7)
    shape = (2, 2, 5, 328)
    x = np.where(rs.random_sample(shape) < 0.4, 0.0, _random(rs, shape))
    vis = rs.randint(1, 329, size=shape[:-1] + (1,))
    b = np.arange(328)
    x = np.where((b < 1) | ((b >= vis - 33) & (b < vis)), np.inf, x)
    scores = jnp.asarray(np.where(b < vis, x, -np.inf), jnp.float32)
    n = jnp.asarray((20000, 9000), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(bs.choose_blocks(scores, n, spec)),
        np.asarray(choose_by_top_k(scores, n, spec)))


@pytest.mark.parametrize("width", [4, 8, PP, PP + 4],
                         ids=["topk", "table_width", "every_page", "past_pp"])
def test_chosen_table_is_the_stable_sorts_first_count_entries(width):
    rs = np.random.RandomState(width)
    B, NKV = 4, 2
    chosen = np.zeros((B, NKV, PP), bool)
    counts = [0, min(width, PP), 1, 3, min(width, PP), 0, 2, min(width, PP)]
    for i, n in enumerate(counts):
        chosen[i // NKV, i % NKV, rs.choice(PP, n, replace=False)] = True
    block_table = jnp.asarray(rs.randint(1, 90, (B, PP)), jnp.int32)
    table, count = jax.jit(functools.partial(
        bs._chosen_table, width=width, nkv=NKV))(jnp.asarray(chosen),
                                                 block_table)
    want, want_count = table_by_argsort(jnp.asarray(chosen), block_table,
                                        width, NKV)
    assert table.shape == (B * NKV, width) and table.dtype == jnp.int32
    assert count.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(count), counts)
    np.testing.assert_array_equal(np.asarray(count), np.asarray(want_count))
    live = np.arange(width)[None, :] < np.asarray(count)[:, None]
    np.testing.assert_array_equal(np.where(live, np.asarray(table), -1),
                                  np.where(live, np.asarray(want), -1))
    # what lies past ``count`` is unspecified, but a page of the pool
    assert (np.asarray(table) >= 0).all()
    assert (np.asarray(table) < 90 * NKV).all()


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernels"])
@pytest.mark.parametrize("S", [1, 8], ids=["decode", "chunk"])
def test_no_program_of_the_sparse_layer_sorts(S, kernel):
    """The regression guard of PR 40's finding: ``sort`` was an eighth of
    the device's time in ``minicpm-sala.serve-longdocs`` (the compiler makes
    ``lax.top_k`` of 64 in 328 a stable sort of pairs, 1.96 ms a chunk layer
    in the layout the program gives it).  Neither the traced program nor
    what the compiler makes of it may hold a sort or a top-k."""
    B, NQ, NKV, D, page, NP = 2, 4, 2, 16, SPEC.block_size, 40
    T = PP * page
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    cache = (f32((NP, NKV, page, D)), f32((NP, NKV, page, D)),
             f32((NP, page // SPEC.kernel_stride, NKV, D)))
    lowered = jax.jit(functools.partial(
        bs.sparse_paged_attention, spec=SPEC, paged_kernel=kernel)).lower(
        f32((B, S, NQ, D)), f32((B, S, NKV, D)), f32((B, S, NKV, D)), cache,
        i32((B, PP)), i32((B,)), i32((B, T)))
    # the operations by name, StableHLO's and HLO's (metadata holds this
    # test's own name, and a gather's ``indices_are_sorted``)
    ops = re.compile(r"stablehlo\.sort|chlo\.top_k| sort\(| topk\(|\"TopK\"")
    for text in (lowered.as_text(), lowered.compile().as_text()):
        assert not ops.search(text), ops.findall(text)[:3]
    # the scope the trace's readers go by is in the program
    assert "sparse_topk" in lowered.as_text(debug_info=True)
