"""Power retention of degree 2 (``ops/power_retention.py``) at toy sizes on
the CPU: the symmetric square's identity, the chunk form against the token
recurrence and against the reference's quadratic form
(``benchmarks/reference/brumby_f32.py``), a state carried over calls, and
the two Pallas calls (interpreted) stepping rows of the state array in place
by row id."""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.models import hybrid
from neuronx_distributed_tpu.ops import power_retention as pr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("retention_test_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("brumby_f32")


def _inputs(seed, B, S, NQ, NKV, d):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, S, NQ, d), jnp.float32)
    k, v = (jnp.asarray(rs.randn(B, S, NKV, d), jnp.float32)
            for _ in range(2))
    # half-lives of 3 to 300 tokens: the decay matters inside a chunk
    lg = jnp.asarray(-np.log(2.0) / np.exp(rs.uniform(
        np.log(3.0), np.log(300.0), size=(B, S, NKV))), jnp.float32)
    return q, k, v, lg


@pytest.mark.parametrize("d", [16, 32, 128])
def test_phi_is_the_symmetric_square(d):
    """``phi(q) . phi(k) == (q . k)^2``, at the tile layout's width: 16 x 16
    tiles of the outer product, the upper triangle of tiles."""
    rs = np.random.RandomState(d)
    q, k = (rs.randn(7, d).astype(np.float32) for _ in range(2))
    tiles = d // pr.TILE
    assert pr.phi_dim(d) == 256 * tiles * (tiles + 1) // 2
    assert pr.phi_dim(128) == 9216
    pq, pk = np.asarray(hybrid.phi(q), np.float64), np.asarray(pr.phi(k),
                                                               np.float64)
    assert pq.shape == (7, pr.phi_dim(d))
    want = (q.astype(np.float64) * k).sum(-1) ** 2
    np.testing.assert_allclose((pq * pk).sum(-1), want, rtol=2e-5, atol=1e-5)
    # the columns a kernel forms in VMEM are phi's, 128 lanes at a time
    rep, lay = (np.asarray(x) for x in pr._column_operands(jnp.asarray(q)))
    cols = np.concatenate(
        [rep[:, 128 * r:128 * (r + 1)] * lay[:, 128 * a:128 * (a + 1)] * w
         for a, r, w in pr._columns(d)], axis=-1)
    np.testing.assert_allclose(cols, np.asarray(pr.phi(q)), rtol=1e-6)
    with pytest.raises(ValueError, match="multiple of 16"):
        pr.phi_dim(24)


@pytest.mark.parametrize("holes", [False, True], ids=["whole", "invalid_cells"])
@pytest.mark.parametrize("chunk", [1, 5, 8, 21, 64])
def test_chunk_form_is_the_token_recurrence(chunk, holes):
    """Blocks of several widths (two that do not divide the rows, one wider
    than they are) against the token-by-token recurrence with ``phi``
    formed, from a state that is not zero, with invalid cells inside a block
    (identity steps: no decay, no update)."""
    B, S, NQ, NKV, d = 2, 21, 4, 2, 16
    q, k, v, lg = _inputs(chunk, B, S, NQ, NKV, d)
    rs = np.random.RandomState(99)
    state = jnp.asarray(rs.randn(B, NKV, d, pr.phi_dim(d)), jnp.float32)
    kk = rs.randn(B, NKV, 40, d).astype(np.float32)
    z = jnp.einsum("bkti,bktj->bkij", kk, kk)       # a second moment
    valid = None
    if holes:
        valid = np.ones((B, S), np.int32)
        valid[0, :5] = 0
        valid[1, [2, 3, 11, 20]] = 0
    with jax.default_matmul_precision("highest"):
        o, st, zz = pr.power_retention(q, k, v, lg, valid, state, z,
                                       chunk_rows=chunk)
        o2, st2, zz2 = pr.retention_scan_reference(q, k, v, lg, valid, state,
                                                   z)
    live = np.ones((B, S), bool) if valid is None else valid > 0
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o2)[live],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(st, st2, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(zz, zz2, rtol=2e-5, atol=2e-5)


def test_recurrence_is_the_references_quadratic_form():
    """The program's chunk form, its token recurrence and the reference's
    quadratic form (no ``phi``, no state) give the same numbers from a zero
    state."""
    S, NQ, NKV, d = 37, 4, 2, 16
    q, k, v, lg = _inputs(0, 1, S, NQ, NKV, d)
    shape = ref.Shape(vocab=1, hidden=1, inter=1, layers=1, heads=NQ,
                      kv_heads=NKV, head_dim=d, eps=1e-6, theta=1e6)
    zero = (jnp.zeros((1, NKV, d, pr.phi_dim(d))), jnp.zeros((1, NKV, d, d)))
    with jax.default_matmul_precision("highest"):
        quad = ref.power_retention(q[0], k[0], v[0], lg[0], shape)
        scan, _, _ = pr.retention_scan_reference(q, k, v, lg, None, *zero)
        ours, _, _ = pr.power_retention(q, k, v, lg, None, *zero,
                                        chunk_rows=8)
    np.testing.assert_allclose(scan[0], quad, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(ours[0], quad, rtol=2e-4, atol=2e-5)


def test_a_state_carried_over_three_calls_is_one_call():
    """Rows 0-9, 10-24 and 25-36 in three calls, each continuing what the
    one before left, against all 37 at once."""
    B, S, NQ, NKV, d = 1, 37, 4, 2, 16
    q, k, v, lg = _inputs(5, B, S, NQ, NKV, d)
    carry = (jnp.zeros((B, NKV, d, pr.phi_dim(d))), jnp.zeros((B, NKV, d, d)))
    with jax.default_matmul_precision("highest"):
        whole, st, z = pr.power_retention(q, k, v, lg, None, *carry)
        parts = []
        for lo, hi in ((0, 10), (10, 25), (25, 37)):
            o, *carry = pr.power_retention(
                q[:, lo:hi], k[:, lo:hi], v[:, lo:hi], lg[:, lo:hi], None,
                *carry, chunk_rows=6)
            parts.append(o)
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1), whole,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(carry[0], st, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(carry[1], z, rtol=2e-5, atol=2e-5)


def _array(seed, R, NKV, d):
    rs = np.random.RandomState(seed)
    states = jnp.asarray(rs.randn(R, NKV, d, pr.phi_dim(d)), jnp.float32)
    kk = rs.randn(R, NKV, 30, d).astype(np.float32)
    return states, jnp.einsum("rkti,rktj->rkij", kk, kk)


@pytest.mark.parametrize("d,G", [(16, 1), (16, 2), (16, 5), (32, 1), (32, 2),
                                 (32, 5), (128, 5)])
def test_decode_step_is_in_place_by_row_id(d, G):
    """One token a row, handed over as the token's own ``q``, ``k``, ``v``:
    of a five-row state array row 2 carries on, row 0 begins its sequence
    (keep 0: whatever it held is gone) and row 4 is parked (no token: keep
    1, ``k`` zero); rows 1 and 3 are not named.  The Pallas call
    (interpreted; ``phi`` formed inside, 5 query heads a group is the
    cell's and no power of two) and the XLA form against the token
    recurrence; the read is of what the step LEFT.  At d 128, the cell's,
    one row of two."""
    B, R, NKV = (1, 2, 1) if d == 128 else (3, 5, 2)
    rows = jnp.asarray([2, 0, 4][:B]) % R
    states, zs = _array(d, R, NKV, d)
    q, k, v, lg = _inputs(G, B, 1, NKV * G, NKV, d)
    live = np.asarray([1, 1, 0][:B])
    fresh = np.asarray([False, True, False][:B])
    m = jnp.asarray(live, jnp.float32)
    keep = jnp.where(fresh[:, None], 0.0, jnp.exp(lg[:, 0] * m[:, None]))
    qg = q[:, 0].reshape(B, NKV, G, d)
    got = {kernel: pr.retention_step(
        states, rows, keep, k[:, 0] * m[:, None, None], qg, v[:, 0],
        kernel=kernel) for kernel in (False, True)}
    begins = fresh[:, None, None, None]
    o_ref, s_ref, z_ref = pr.retention_scan_reference(
        q, k, v, lg, live[:, None], jnp.where(begins, 0.0, states[rows]),
        jnp.where(begins, 0.0, zs[rows]))
    den = np.einsum("bkgi,bkij,bkgj->bkg", qg, z_ref, qg)
    others = [r for r in range(R) if r not in np.asarray(rows)]
    for kernel, (st, num) in got.items():
        np.testing.assert_allclose(np.asarray(st)[rows], s_ref, rtol=1e-6,
                                   atol=1e-6, err_msg=f"kernel={kernel}")
        np.testing.assert_array_equal(np.asarray(st)[others],
                                      np.asarray(states)[others])
        # the parked row keeps its BITS
        np.testing.assert_array_equal(np.asarray(st)[rows][live == 0],
                                      np.asarray(states)[rows][live == 0])
        want = np.einsum("bkgr,bker->bkge", np.asarray(pr.phi(qg), np.float64),
                         np.asarray(s_ref, np.float64))
        np.testing.assert_allclose(num, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
        o = np.asarray(pr._normalise(num, den, d)).reshape(B, 1, NKV * G, d)
        np.testing.assert_allclose(o[live > 0], np.asarray(o_ref)[live > 0],
                                   rtol=1e-5, atol=1e-5)


def test_both_kernels_form_phi_by_the_one_column_helper(monkeypatch):
    """The step's and the chunk's Pallas calls take a column of ``phi`` from
    the SAME function, ``_column`` — which is ``phi()``'s, column by column —
    and the step's 0/1 matrix lays ``u`` out as ``_column_operands`` does,
    entry for entry."""
    d, NKV, G = 32, 1, 2
    u = jnp.asarray(np.random.RandomState(0).randn(3, d), jnp.float32)
    rep, lay = pr._column_operands(u)
    for c, col in enumerate(pr._columns(d)):
        np.testing.assert_allclose(
            pr._column(rep, lay, col, jnp.float32),
            pr.phi(u)[:, 128 * c:128 * (c + 1)], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(u) @ pr._expansion(d),
                                  np.concatenate([rep, lay], axis=-1))
    seen, column = [], pr._column

    def counted(rep, lay, col, dtype):
        seen.append(jnp.dtype(dtype))
        return column(rep, lay, col, dtype)

    monkeypatch.setattr(pr, "_column", counted)
    states, zs = _array(1, 2, NKV, d)
    q, k, v, lg = _inputs(3, 1, 8, NKV * G, NKV, d)
    ids = jnp.asarray([1])
    # the ops' own jits keep what they traced: a patch is seen by a new trace
    for fn in (pr._retention_chunk_impl, pr._retention_step_impl):
        fn.clear_cache()
    try:
        pr.retention_step(states, ids, jnp.ones((1, NKV)), k[:, 0],
                          q[:, 0].reshape(1, NKV, G, d), v[:, 0], kernel=True)
        step = len(seen)
        pr.retention_chunk(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                           v.astype(jnp.bfloat16), lg, None,
                           jnp.asarray([False]), states, zs, ids, kernel=True)
    finally:
        for fn in (pr._retention_chunk_impl, pr._retention_step_impl):
            fn.clear_cache()
    # whole passes over the columns (a traced branch may be traced again)
    cols = len(pr._columns(d))
    assert step and step % cols == 0
    assert len(seen) > step and (len(seen) - step) % cols == 0
    assert set(seen[:step]) == {jnp.dtype(jnp.float32)}
    assert set(seen[step:]) == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("d", [16, 32])
@pytest.mark.parametrize("rows", [[3, 1], [2]], ids=["two_rows", "one_row"])
def test_chunk_over_rows_of_the_array_is_in_place_by_row_id(rows, d):
    """A chunk continues rows of the state ARRAY named by id — the Pallas
    call (interpreted: ``phi(Q) S`` and the update a column of ``phi`` at a
    time) and the slice-continue-write form agree, a row that begins its
    sequence starts from zeros whatever it held, and every other row keeps
    its bits."""
    B, S, NQ, NKV = len(rows), 16, 4, 2
    q, k, v, lg = _inputs(7, B, S, NQ, NKV, d)
    states, zs = _array(3, 4, NKV, d)
    valid = np.ones((B, S), np.int32)
    valid[0, :3] = 0
    fresh = jnp.asarray([False, True][:B])
    args = (q, k, v, lg, valid, fresh, states, zs, jnp.asarray(rows))
    with jax.default_matmul_precision("highest"):
        o1, s1, z1 = pr.retention_chunk(*args, kernel=False)
        o2, s2, z2 = pr.retention_chunk(*args, kernel=True)
        ids = jnp.asarray(rows)
        start = (jnp.where(fresh[:, None, None, None], 0.0, states[ids]),
                 jnp.where(fresh[:, None, None, None], 0.0, zs[ids]))
        o3, s3, z3 = pr.retention_scan_reference(q, k, v, lg, valid, *start)
    live = valid > 0
    for o, s, z in ((o1, s1, z1), (o2, s2, z2)):
        np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o3)[live],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(s)[rows], s3, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(z)[rows], z3, rtol=2e-5,
                                   atol=2e-5)
        others = [r for r in range(4) if r not in rows]
        np.testing.assert_array_equal(np.asarray(s)[others],
                                      np.asarray(states)[others])
        np.testing.assert_array_equal(np.asarray(z)[others],
                                      np.asarray(zs)[others])


def test_the_step_of_a_call_of_many_rows_takes_the_blocks_path():
    """A call wider than one block of the chunk form is not the kernel's: it
    is sliced out and scanned (and agrees)."""
    B, S, NQ, NKV, d = 1, pr.CHUNK_ROWS + 8, 2, 1, 16
    q, k, v, lg = _inputs(2, B, S, NQ, NKV, d)
    states, zs = _array(4, 2, NKV, d)
    args = (q, k, v, lg, None, jnp.asarray([True]), states, zs,
            jnp.asarray([1]))
    o1, s1, _ = pr.retention_chunk(*args, kernel=True)
    o2, s2, _ = pr.retention_chunk(*args, kernel=False)
    np.testing.assert_array_equal(o1, o2)
    np.testing.assert_array_equal(s1, s2)


def test_retention_step_probe_prints_a_line_a_variant():
    """`tools/retention_step_probe.py --cpu --tiny`: the library's call and
    the tool's knock-outs (the step as it stood with ``phi`` in HBM, the read
    on the MXU, knocked out, on the vector unit; one stream alone) through
    the interpreter, each a line with no device number off the chip and, where
    there is a read, the XLA form's state and read."""
    import json

    from conftest import run_cli

    proc = run_cli("tools/retention_step_probe.py", "--cpu", "--tiny")
    rows = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]
    assert [r["variant"].split(":")[0].split("@")[0] for r in rows] == [
        "mxu", "none", "load", "store", "turns", "vpu", "library", "library"]
    for r in rows:
        assert "error" not in r, r
        assert r["kernel_us"] is None and "gb_per_s" not in r
        if r["variant"].startswith(("load", "store")):
            continue
        assert r["state_rel"] < 1e-6
        assert r["variant"].startswith(("none", "turns")) or r["read_rel"] < 1e-5
    assert rows[-2]["block"] == [32, 768] and rows[-1]["block"] == [32, 384]
