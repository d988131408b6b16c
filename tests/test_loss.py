"""Vocab-parallel cross-entropy parity tests (reference methodology:
``test/integration/parallel_layers/`` loss tests — dense vs sharded)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.parallel.loss import (
    parallel_cross_entropy,
    vocab_parallel_cross_entropy,
)
from neuronx_distributed_tpu.parallel.mesh import (
    TENSOR_AXES,
    initialize_model_parallel,
    named_sharding,
)

T = TENSOR_AXES


def dense_ce(logits, targets, label_smoothing=0.0):
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if label_smoothing > 0.0:
        smooth = -jnp.mean(logp, axis=-1)
        return (1.0 - label_smoothing) * nll + label_smoothing * smooth
    return nll


@pytest.fixture(params=[dict(tp=8, kv=1), dict(tp=8, kv=2)], ids=["tp8", "tp8kv2"])
def mesh(request, devices8):
    return initialize_model_parallel(
        tensor_parallel_size=request.param["tp"],
        kv_size_multiplier=request.param["kv"],
        devices=devices8,
    )


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_shard_map_path_matches_dense(mesh, smoothing):
    B, S, V = 2, 4, 64
    logits = jax.random.normal(jax.random.PRNGKey(0), (B, S, V)) * 3
    targets = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V)
    ct = jax.random.normal(jax.random.PRNGKey(2), (B, S))

    def prog(logits, targets, ct):
        def loss_fn(logits):
            per_tok = vocab_parallel_cross_entropy(logits, targets, smoothing)
            return jnp.sum(per_tok * ct)

        return jax.value_and_grad(loss_fn)(logits)

    f = jax.shard_map(
        prog,
        mesh=mesh,
        in_specs=(P(None, None, T), P(), P()),
        out_specs=(P(), P(None, None, T)),
        check_vma=False,
    )
    l_s, g_s = f(logits, targets, ct)

    def loss_dense(logits):
        return jnp.sum(dense_ce(logits, targets, smoothing) * ct)

    l_d = loss_dense(logits)
    g_d = jax.grad(loss_dense)(logits)
    np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_d), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_d), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_gspmd_path_matches_dense(mesh, smoothing):
    B, S, V = 2, 4, 64
    logits = jax.random.normal(jax.random.PRNGKey(3), (B, S, V)) * 3
    targets = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, V)
    logits_sharded = jax.device_put(logits, named_sharding(None, None, T))

    @jax.jit
    def f(logits, targets):
        return parallel_cross_entropy(logits, targets, smoothing)

    out = f(logits_sharded, targets)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_ce(logits, targets, smoothing)), rtol=1e-5, atol=1e-6
    )

    @jax.jit
    def loss(logits, targets):
        return jnp.sum(parallel_cross_entropy(logits, targets, smoothing))

    g = jax.grad(loss)(logits_sharded, targets)
    g_d = jax.grad(lambda l: jnp.sum(dense_ce(l, targets, smoothing)))(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_d), rtol=1e-4, atol=1e-5)


def test_extreme_logits_stable(mesh):
    """The psum-MAX shift must keep huge logits finite (reference :17-22)."""
    B, V = 2, 64
    logits = jnp.full((B, V), 1e4, dtype=jnp.float32)
    targets = jnp.array([3, 9])

    def prog(logits, targets):
        return vocab_parallel_cross_entropy(logits, targets)

    f = jax.shard_map(
        prog, mesh=mesh, in_specs=(P(None, T), P()), out_specs=P(), check_vma=False
    )
    out = np.asarray(f(logits, targets))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, np.log(V), rtol=1e-4)
