"""A held expert share passes over the rows it holds (PR 45,
``parallel.moe._walk_held_rows``): the grouped matmuls once over the whole
sorted array (their kernel visits its groups' row tiles alone) and everything
between them in two spans of static shape, each skipped when it holds no held
row, give the whole-array block's output and every gradient for ANY count of
held rows — none, one, the first span exactly, the first span and one,
several, all ``N * K`` — with a ``valid`` mask, for gated and relu2 experts,
through megablox in interpret mode with a span that starts inside a group and
one whose groups are empty; an expert's weight gradient is ONE float32
accumulation over all its rows wherever the spans' edge falls; and the rule
that decides where a block does so (``held_rows_slab``) leaves every serving
shape the program it had.  Tiny sizes, the CPU."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.parallel import moe

N, K, H, I, E = 64, 4, 16, 24, 4      # 256 assignment rows over 4 held experts


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def sorted_block(held_rows, activation="silu", dtype=jnp.float32, seed=0,
                 n=N, h=H, i=I, straddled=None):
    """What ``_dropless`` hands the block after its sort when ``held_rows`` of
    the ``n * K`` assignments fall to the ``E`` held experts (seeded places,
    seeded experts; ``straddled``: ALL of them to that one expert): ``(xt,
    order, gates, load, wi, wo)``."""
    rs = np.random.RandomState(seed + held_rows)
    group = np.full(n * K, E, np.int32)
    group[rs.permutation(n * K)[:held_rows]] = (
        rs.randint(0, E, held_rows) if straddled is None else straddled)
    order = jnp.argsort(jnp.asarray(group), stable=True)
    load = jnp.asarray(np.bincount(group, minlength=E + 1)[:E], jnp.int32)
    gates = jnp.asarray(np.where(group < E, rs.rand(n * K) + 0.1, 0.0)
                        .reshape(n, K), jnp.float32)
    xt = jnp.asarray(rs.randn(n, h), dtype)
    shapes = ([(E, i, h)] if activation == "relu2" else [(E, h, i)] * 2)
    wi = tuple(jnp.asarray(rs.randn(*s) / np.sqrt(h), dtype) for s in shapes)
    wo = jnp.asarray(rs.randn(E, i, h) / np.sqrt(i), dtype)
    return xt, order, gates, load, wi, wo


def whole_rows(xt, order, gates, load, wi, wo, activation, dtype):
    """The block once over all ``N * K`` sorted rows, as ``_dropless`` has it
    where no share walks."""
    nk = order.shape[0]
    xs = moe._dispatch_rows(xt, order)
    pre = tuple(moe.grouped_matmul(xs, w, load, dtype,
                                   transpose_rhs=activation == "relu2")
                for w in wi)
    ys = moe.grouped_matmul(moe._expert_activation(activation)(pre), wo, load,
                            dtype)
    ys = jnp.where((jnp.arange(nk) < jnp.sum(load))[:, None], ys, 0)
    back = jnp.zeros((nk,), jnp.int32).at[order].set(
        jnp.arange(nk, dtype=jnp.int32))
    return moe._combine_rows(ys, back, order, gates)


def both(block, slab, activation="silu", dtype=jnp.float32):
    """``(y, gradients)`` of the walk and of the whole-array block under one
    seeded cotangent."""
    xt, order, gates, load, wi, wo = block
    cot = jnp.asarray(np.random.RandomState(7).randn(*xt.shape), jnp.float32)

    def of(fn):
        def loss(xt, gates, wi, wo):
            y = fn(xt, order, gates, load, wi, wo)
            return jnp.sum(y * cot), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True))(xt, gates, wi, wo)
        return y, grads

    walked = of(lambda *a: moe._walk_held_rows(*a, slab, activation, dtype))
    whole = of(lambda *a: whole_rows(*a, activation, dtype))
    return walked, whole


@pytest.mark.parametrize("activation", ["silu", "relu2"])
@pytest.mark.parametrize("slab", [32, 48], ids=["span32", "span48"])
@pytest.mark.parametrize("held_rows", [0, 1, 32, 33, 100, N * K],
                         ids=["none", "one", "the-first-span",
                              "the-first-span-and-one", "several", "all"])
def test_the_walk_is_the_whole_block_for_any_count_of_held_rows(
        held_rows, slab, activation):
    """Output and the gradients of the rows, the gates (through which the
    router's come), gate / up / down; the second span is whatever the first
    leaves of the 256 rows (224 or 208)."""
    (y, grads), (y0, grads0) = both(sorted_block(held_rows, activation),
                                    slab, activation)
    assert y.dtype == jnp.float32 and y.shape == (N, H)
    if held_rows:
        assert rel(y, y0) < 1e-5
        for g, g0 in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
            assert rel(g, g0) < 1e-5
    else:       # nothing held: every row masked, zeros all the way
        for a in jax.tree.leaves((y, grads, y0, grads0)):
            assert not np.any(np.asarray(a))


def layer(activation="silu", held=(2, 4), routed=16, dtype=jnp.float32):
    return moe.ExpertParallelMLP(
        num_experts=held[1], intermediate_size=I, top_k=K,
        dispatch="dropless", fused_gate_up=False, num_experts_global=routed,
        first_expert=held[0], router_scores="sigmoid", router_bias=True,
        activation=activation, dtype=dtype, param_dtype=jnp.float32,
        kernel_init=moe.per_expert_lecun)


def applied(block, params, x, valid, slab, monkeypatch):
    """``(y, sown stats, gradients of parameters and rows)`` of the layer
    with ``held_rows_slab`` answering ``slab``."""
    monkeypatch.setattr(moe, "held_rows_slab", lambda *a: slab)
    cot = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def loss(p, x):
        (y, _), sown = block.apply(p, x, valid, mutable=["moe_stats"])
        return jnp.sum(y * cot), (y, sown["moe_stats"])

    (_, (y, stats)), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(params, x)
    return y, stats, grads


@pytest.mark.parametrize("activation", ["silu", "relu2"])
@pytest.mark.parametrize("router", ["seeded", "all-held"])
def test_the_layer_walks_under_a_valid_mask_and_counts_its_slabs(
        activation, router, monkeypatch):
    """Through ``ExpertParallelMLP``: the router's own gradient, a ``valid``
    mask (a third of the rows are no token), a bias that forces EVERY live
    row onto the held experts, and the sown statistics — the parent's, with
    ``computed`` (the rows the spans that ran passed over) beside them."""
    from flax.core import meta

    block = layer(activation)
    x = jax.random.normal(jax.random.PRNGKey(1), (N, H), jnp.float32)
    valid = jnp.arange(N) % 3 != 0
    params = meta.unbox(block.init(jax.random.PRNGKey(0), x))
    if router == "all-held":
        params["params"]["router_bias"] = jnp.where(
            (jnp.arange(16) >= 2) & (jnp.arange(16) < 6), 10.0, 0.0)
    y0, stats0, grads0 = applied(block, params, x, valid, 0, monkeypatch)
    y, stats, grads = applied(block, params, x, valid, 32, monkeypatch)
    held = int(jnp.sum(stats["load"][-1]))
    assert held == (int(jnp.sum(valid)) * K if router == "all-held" else held)
    assert 0 < held < N * K
    assert rel(y, y0) < 1e-5 and not np.any(np.asarray(y[~valid]))
    for g, g0 in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        assert rel(g, g0) < 1e-5
    for key in ("load", "choice", "assigned"):
        np.testing.assert_array_equal(stats[key][-1], stats0[key][-1])
    assert "computed" not in stats0
    assert int(stats["computed"][-1]) == (32 if held <= 32 else N * K)


@pytest.fixture
def megablox_interpreted(monkeypatch):
    """The kernel arm of ``grouped_matmul`` and of its backward — their
    tiles, their padding, the rows they leave unwritten — on the CPU:
    megablox in interpret mode, and ``platform_dependent`` taking its ``tpu``
    arm."""
    real = moe._megablox()
    shim = types.SimpleNamespace(
        gmm=lambda *a, **kw: real.gmm(*a, **{**kw, "interpret": True}),
        tgmm=lambda *a, **kw: real.tgmm(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(moe, "_megablox", lambda: shim)
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))


@pytest.mark.parametrize("taken", [(170, 130), (60, 40)],
                         ids=["a-span-from-inside-a-group",
                              "a-span-of-empty-groups"])
def test_the_walk_through_megablox_with_a_slab_inside_a_group_and_an_empty_one(
        taken, megablox_interpreted, monkeypatch):
    """A first span of 128 of 512 assignment rows, 300 of them held by
    experts 0 and 2 of four (1 and 3 are empty): the second span starts
    inside expert 0's rows and holds the end of them, all of expert 2's and
    rows of no group, which the kernels leave unwritten and the spans mask;
    with 100 held rows the second span lies past every held row (all its
    groups empty) and is skipped."""
    n, h, i = 128, 128, 128
    rs = np.random.RandomState(3)
    group = np.full(n * K, E, np.int32)
    group[rs.permutation(n * K)[:sum(taken)]] = np.repeat([0, 2], taken)
    order = jnp.argsort(jnp.asarray(group), stable=True)
    load = jnp.asarray([taken[0], 0, taken[1], 0], jnp.int32)
    gates = jnp.asarray(np.where(group < E, rs.rand(n * K) + 0.1, 0.0)
                        .reshape(n, K), jnp.float32)
    xt = jnp.asarray(rs.randn(n, h), jnp.float32)
    wi = tuple(jnp.asarray(rs.randn(E, h, i) / np.sqrt(h), jnp.float32)
               for _ in range(2))
    wo = jnp.asarray(rs.randn(E, i, h) / np.sqrt(i), jnp.float32)
    assert moe._spans(n * K, 128) == ((0, 128), (128, 384))

    cot = jnp.asarray(rs.randn(n, h), jnp.float32)

    def walked(xt, gates, wi, wo):
        return jnp.sum(moe._walk_held_rows(xt, order, gates, load, wi, wo,
                                           128, "silu", jnp.float32) * cot)

    def whole(xt, gates, wi, wo):
        return jnp.sum(whole_rows(xt, order, gates, load, wi, wo, "silu",
                                  jnp.float32) * cot)

    got = jax.grad(walked, argnums=(0, 1, 2, 3))(xt, gates, wi, wo)
    # the reference side is ``ragged_dot``'s: leave the interpreted arms
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: default(*args))
    want = jax.grad(whole, argnums=(0, 1, 2, 3))(xt, gates, wi, wo)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert rel(g, w) < 1e-5
    for w in jax.tree.leaves(got[2:]):       # experts 1 and 3 took no row
        assert not np.any(np.asarray(w[1])) and not np.any(np.asarray(w[3]))


def test_a_weight_gradient_is_one_float32_sum_wherever_the_spans_edge_falls(
        monkeypatch):
    """bfloat16, 1,024 assignment rows ALL of one expert, so that its rows
    lie in both spans: each weight gradient is the whole-array block's — ONE
    grouped call over all of the expert's rows, float32 accumulation, one
    rounding (the spans cut what lies between the kernels, never a kernel) —
    as close to the float32 answer as the whole-array block's.  A walk that
    cut the weight gradient's KERNEL at the spans' edge and added the parts
    as they come (each span's sum rounded to bfloat16, the two added in
    bfloat16: what JAX's transpose of two uses of a bfloat16 weight does) is
    a rounding further off, and the same reading shows it."""
    n, slab = 256, 512
    block = sorted_block(n * K, dtype=jnp.bfloat16, n=n, straddled=1)
    (_, grads), (_, grads0) = both(block, slab, dtype=jnp.bfloat16)
    exact = both(tuple(jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        block)), slab)[1][1]

    real = moe.grouped_matmul_dw

    def cut_at_the_edge(x, g, w, sizes, transpose_rhs=False):
        ends = jnp.cumsum(sizes)
        return sum(
            real(x[lo:hi], g[lo:hi], w, jnp.clip(ends, lo, hi) - jnp.clip(
                ends - sizes, lo, hi), transpose_rhs=transpose_rhs)
            for lo, hi in ((0, slab), (slab, x.shape[0])))

    monkeypatch.setattr(moe, "grouped_matmul_dw", cut_at_the_edge)
    (_, faulty), _ = both(block, slab, dtype=jnp.bfloat16)
    # the float32 block differs from the bfloat16 one in every rounding of
    # the forward too: hold the walk to the whole-array block's distance
    # from it, and the two to each other by one rounding of the sum
    for g, g0, bad, want in zip(*(jax.tree.leaves(t[2:]) for t in (
            grads, grads0, faulty, exact))):
        assert g.dtype == jnp.bfloat16
        assert rel(g[1], want[1]) < 1.5 * rel(g0[1], want[1])
        assert rel(g, g0) < 6e-3
        # one more rounding, of each partial sum: a bfloat16 step on a
        # share of the elements, and further from the float32 answer
        assert rel(bad[1], g[1]) > 2.0 ** -10
        assert rel(bad[1], want[1]) > rel(g[1], want[1])


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["k-n", "n-k"])
@pytest.mark.parametrize("arm", ["ragged_dot", "megablox"])
def test_the_weight_gradient_alone_is_the_grouped_matmuls_own(
        arm, transpose_rhs, request):
    """``grouped_matmul_dw`` — what the walk's backward calls, so that no
    forward kernel and no data gradient is lowered for the compiler to drop
    — against the weight's half of ``jax.vjp(grouped_matmul)``: groups of
    100, 0, 129 and 0 of 384 rows, the rest in no group."""
    if arm == "megablox":
        request.getfixturevalue("megablox_interpreted")
    rs = np.random.RandomState(5)
    m, k, n = 384, 128, 256
    sizes = jnp.asarray([100, 0, 129, 0], jnp.int32)
    x = jnp.asarray(rs.randn(m, k), jnp.float32)
    g = jnp.asarray(rs.randn(m, n), jnp.float32)
    w = jnp.asarray(rs.randn(*((E, n, k) if transpose_rhs else (E, k, n))),
                    jnp.float32)
    got = moe.grouped_matmul_dw(x, g, w, sizes, transpose_rhs=transpose_rhs)
    want = jax.vjp(lambda w: moe.grouped_matmul(
        x, w, sizes, jnp.float32, transpose_rhs=transpose_rhs), w)[1](g)[0]
    assert got.shape == w.shape and got.dtype == w.dtype
    assert rel(got, want) < 1e-6
    assert not np.any(np.asarray(got[1])) and not np.any(np.asarray(got[3]))


SERVING_SHAPES = {
    # cell: (rows N * K a program lays out at most, held, routed)
    "olmoe-all-held": (512 * 8, 64, 64),
    "xing-all-held": (520 * 4, 64, 64),
    "nemotron-chunk": ((512 + 64) * 6, 64, 128),
    "nemotron-decode": (64 * 6, 64, 128),
    "deepseek-v2-chunk": ((512 + 32) * 6, 20, 160),
    "deepseek-v2-decode": (32 * 6, 20, 160),
}


@pytest.mark.parametrize("cell", sorted(SERVING_SHAPES))
def test_no_serving_shape_walks(cell):
    assert moe.held_rows_slab(*SERVING_SHAPES[cell]) == 0


def test_the_train_cells_first_span_is_whole_backward_row_tiles():
    """LFM2-8B-A1B's step: 65,536 rows, 8 of 32 held — the first span takes a
    balanced router's 16,384 with an eighth to spare, the second the other
    47,104, both whole numbers of the backward's 512-row tiles."""
    slab = moe.held_rows_slab(16384 * 4, 8, 32)
    assert slab == 18432 and slab >= 1.12 * 16384
    assert [size % moe.GMM_BACKWARD_ROWS for _, size in moe._spans(
        65536, slab)] == [0, 0]
    assert moe._spans(65536, slab) == ((0, 18432), (18432, 47104))
    # a share too large for a first span shorter than the array: whole
    assert moe.held_rows_slab(moe.HELD_WALK_FLOOR, 15, 16) == 0


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs under it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def primitives(jaxpr):
    """Every primitive's name in ``jaxpr`` and the jaxprs under it."""
    return [eqn.primitive.name for eqn in _eqns(jaxpr)]


@pytest.mark.parametrize("shape", ["all-held", "held-3264-rows",
                                   "held-384-rows", "held-long-array"])
def test_a_bypass_is_a_bypass_and_a_walk_holds_each_grouped_matmul_once(
        shape, monkeypatch):
    """Where the rule declines — every expert held, or a held share at a
    serving program's few thousand rows — the block's jaxpr holds no loop
    and no branch and is the whole-array form (three grouped matmuls, the
    un-sort's row gather, no row scatter).  At a long array it holds the
    SAME three grouped matmuls, once each and outside every branch — no
    second copy of a kernel for the compiler — and three ``cond``s, the
    second span's gather, activation and mask (the first span's stand in
    the open), NO loop (a ``while`` around these kernels made the compiled
    train step round differently from its check: PERF.md, PR 45); the
    backward, written out, holds eight more (the two pre-activations again,
    ``dy wo^T``, two for the rows' gradient, one ``tgmm`` a weight) and
    three more ``cond``s.  (Each grouped matmul taken by its ``ragged_dot``
    arm: megablox's own tables hold a ``scan`` and two ``cond``s a call.)"""
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: default(*args))
    n, held, routed = {"all-held": (4096, 8, 8),
                       "held-3264-rows": (816, 4, 32),
                       "held-384-rows": (96, 4, 32),
                       "held-long-array": (4096, 4, 16)}[shape]
    block = moe.ExpertParallelMLP(
        num_experts=held, intermediate_size=I, top_k=K, dispatch="dropless",
        fused_gate_up=False, num_experts_global=routed, first_expert=0,
        dtype=jnp.float32, param_dtype=jnp.float32)
    x = jnp.zeros((n, H), jnp.float32)
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)

    def dots(names):
        return names.count("ragged_dot_general") + names.count("ragged_dot")

    forward = jax.make_jaxpr(lambda p, x: block.apply(p, x)[0])(params, x)
    names = primitives(forward.jaxpr)
    walks = shape == "held-long-array"
    assert dots(names) == 3
    assert names.count("cond") == (3 if walks else 0)
    assert "while" not in names and "scan" not in names
    assert "scatter-add" not in names       # rows move by gathers alone
    # no grouped matmul under a branch: each stands once, in the open
    inside = [name for eqn in _eqns(forward.jaxpr) if eqn.primitive.name
              == "cond" for sub in jax.core.jaxprs_in_params(eqn.params)
              for name in primitives(sub)]
    assert dots(inside) == 0 and "custom_vjp_call" not in inside
    if walks:
        both_ways = primitives(jax.make_jaxpr(jax.grad(
            lambda p, x: jnp.sum(block.apply(p, x)[0]), argnums=(0, 1)))(
                params, x).jaxpr)
        assert dots(both_ways) == 3 + 8
        assert both_ways.count("cond") == 3 + 3
        assert "while" not in both_ways and "scan" not in both_ways


@pytest.mark.parametrize("computed, want", [(None, 60), ([16, 8], 24)],
                         ids=["whole", "walked"])
def test_the_rows_computed_are_booked_beside_the_assignments(computed, want):
    """``moe/rows_computed_total[/<program>]``: every assignment made where
    the blocks run whole, the slabs' rows where they walked."""
    from neuronx_distributed_tpu.obs.registry import MetricRegistry

    reg = MetricRegistry()
    moe.book_expert_loads(reg, "train_step", {
        "load": np.asarray([[3, 4, 0], [1, 2, 3]]), "assigned": [40, 20],
        "computed": computed}, None)
    snap = reg.snapshot()
    assert snap["moe/assignments_total"] == 60
    assert snap["moe/assignments_held_total"] == 13
    assert snap["moe/rows_computed_total"] == want
    assert snap["moe/rows_computed_total/train_step"] == want
