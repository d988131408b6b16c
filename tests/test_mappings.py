"""Conjugate-collective parity tests — the shard_map analogue of the
reference's dense-vs-sharded integration methodology
(``test/integration/parallel_layers/test_layers.py:42-84``).

Gradients are computed INSIDE the shard_map region (as a real train step
does): the custom_vjp conjugate pairs are what make per-rank cotangents exact
there.  Differentiating through the shard_map boundary instead would invoke
shard_map's own replication transpose and double-count the psums.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel import mappings as mp
from neuronx_distributed_tpu.parallel.mesh import (
    TENSOR_AXES,
    initialize_model_parallel,
)

T = TENSOR_AXES


@pytest.fixture(params=[dict(tp=8, kv=1), dict(tp=8, kv=2)], ids=["tp8", "tp8kv2"])
def mesh(request, devices8):
    return initialize_model_parallel(
        tensor_parallel_size=request.param["tp"],
        kv_size_multiplier=request.param["kv"],
        devices=devices8,
    )


def shmap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


def test_copy_and_reduce_megatron_mlp(mesh):
    """Column→Row TP matmul pair: copy fwd/bwd + reduce fwd/bwd exactly as
    the Megatron hot path uses them (reference layers.py:208-334)."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k1, (4, 16))
    w1 = jax.random.normal(k2, (16, 32)) / 4
    w2 = jax.random.normal(k3, (32, 16)) / 4
    ct = jax.random.normal(k4, (4, 16))

    def prog(x, w1, w2, ct):
        def loss(x, w1, w2):
            xc = mp.copy_to_tensor_parallel_region(x)
            y = (xc @ w1) @ w2
            return jnp.sum(mp.reduce_from_tensor_parallel_region(y) * ct)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w1, w2)

    f = shmap(
        prog,
        mesh,
        in_specs=(P(), P(None, T), P(T, None), P()),
        out_specs=(P(), (P(), P(None, T), P(T, None))),
    )
    l_s, (gx_s, gw1_s, gw2_s) = f(x, w1, w2, ct)

    def loss_dense(x, w1, w2):
        return jnp.sum((x @ w1 @ w2) * ct)

    l_d, (gx_d, gw1_d, gw2_d) = (
        loss_dense(x, w1, w2),
        jax.grad(loss_dense, argnums=(0, 1, 2))(x, w1, w2),
    )
    np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_d), rtol=1e-5)
    for a, b in [(gx_s, gx_d), (gw1_s, gw1_d), (gw2_s, gw2_d)]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_gather_and_scatter_last_dim(mesh):
    """fwd all-gather last dim ↔ bwd split, and the conjugate scatter."""
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    c = jax.random.normal(jax.random.PRNGKey(2), (4, 32))

    def prog_gather(x, c):
        def loss(x):
            return jnp.sum(mp.gather_from_tensor_parallel_region(x) * c)

        return jax.value_and_grad(loss)(x)

    f = shmap(prog_gather, mesh, in_specs=(P(None, T), P()), out_specs=(P(), P(None, T)))
    l, g = f(x, c)
    np.testing.assert_allclose(np.asarray(l), np.sum(np.asarray(x) * np.asarray(c)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(c), rtol=1e-6)


    def prog_scatter(x, c_local):
        def loss(x):
            # per-rank partial loss over this rank's shard; psum for the total
            return mp.reduce_from_tensor_parallel_region(
                jnp.sum(mp.scatter_to_tensor_parallel_region(x) * c_local)
            )

        # grad is all-gathered in bwd → replicated full-width cotangent
        return jax.value_and_grad(loss)(x)

    f = shmap(prog_scatter, mesh, in_specs=(P(), P(None, T)), out_specs=(P(), P()))
    l, g = f(x, c)
    np.testing.assert_allclose(np.asarray(l), np.sum(np.asarray(x) * np.asarray(c)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(c), rtol=1e-6)


def test_sequence_parallel_gather_to_tp(mesh):
    """SP all-gather feeding a TP block: bwd reduce-scatters the per-rank
    partial cotangents back onto the sequence shards (reference
    _GatherFromSequenceParallelRegion(to_model_parallel=True))."""
    S, H = 16, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (S, H))
    w = jax.random.normal(jax.random.PRNGKey(4), (H, 2 * H)) / 3
    ct = jax.random.normal(jax.random.PRNGKey(5), (S, 2 * H))


    def prog(x_local, w_local, ct_local):
        def loss(x_local, w_local):
            full = mp.gather_from_sequence_parallel_region(x_local, 0, True)
            y = full @ w_local  # column-parallel matmul: per-rank output shard
            return mp.reduce_from_tensor_parallel_region(jnp.sum(y * ct_local))

        return jax.value_and_grad(loss, argnums=(0, 1))(x_local, w_local)

    f = shmap(
        prog,
        mesh,
        in_specs=(P(T, None), P(None, T), P(None, T)),
        out_specs=(P(), (P(T, None), P(None, T))),
    )
    l_s, (gx_s, gw_s) = f(x, w, ct)

    def loss_dense(x, w):
        return jnp.sum((x @ w) * ct)

    gx_d, gw_d = jax.grad(loss_dense, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(l_s), np.asarray(loss_dense(x, w)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx_s), np.asarray(gx_d), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_s), np.asarray(gw_d), rtol=1e-4, atol=1e-5)


def test_sequence_parallel_scatter(mesh):
    """scatter_to_sequence fwd split ↔ bwd all-gather."""
    S, H = 16, 8
    x = jax.random.normal(jax.random.PRNGKey(6), (S, H))
    c = jax.random.normal(jax.random.PRNGKey(7), (S, H))


    def prog(x, c_local):
        def loss(x):
            return mp.reduce_from_tensor_parallel_region(
                jnp.sum(mp.scatter_to_sequence_parallel_region(x, 0) * c_local)
            )

        return jax.value_and_grad(loss)(x)

    f = shmap(prog, mesh, in_specs=(P(), P(T, None)), out_specs=(P(), P()))
    l, g = f(x, c)
    np.testing.assert_allclose(np.asarray(l), np.sum(np.asarray(x) * np.asarray(c)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(c), rtol=1e-6)


def test_reduce_scatter_to_sequence(mesh):
    """Row-parallel output with SP: fwd reduce-scatter of per-rank partial
    sums ↔ bwd all-gather (reference mappings.py:235-250)."""
    S, H = 16, 8

    # 8 per-rank partial outputs y_i; the true row-parallel output is their sum
    y_parts = jax.random.normal(jax.random.PRNGKey(8), (8, S, H))
    y_full = jnp.sum(y_parts, axis=0)
    c = jax.random.normal(jax.random.PRNGKey(9), (S, H))

    def prog(y_part, c_seq):
        y_part = y_part[0]  # [S, H] — this rank's partial sum
        def loss(y_part):
            out = mp.reduce_scatter_to_sequence_parallel_region(y_part, 0)
            return mp.reduce_from_tensor_parallel_region(jnp.sum(out * c_seq))

        return jax.value_and_grad(loss)(y_part)

    f = shmap(
        prog,
        mesh,
        in_specs=(P(T, None, None), P(T, None)),
        out_specs=(P(), P()),
    )
    l, g = f(y_parts, c)
    np.testing.assert_allclose(np.asarray(l), np.asarray(jnp.sum(y_full * c)), rtol=1e-4)
    # bwd: every rank's partial receives the all-gathered cotangent (full c)
    np.testing.assert_allclose(np.asarray(g), np.asarray(c), rtol=1e-6)
