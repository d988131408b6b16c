"""A sequence-parallel projection cut into pieces (``parallel/collective_matmul.py``;
the q/k/v projection is its one caller) against the same projection left
whole and against the dense product.

On the 8-device CPU mesh the collectives are real, so forward, ``dx`` and
``dW`` here are what every rank computes; nothing here says anything about
time or about what the chip's scheduler makes of the pieces
(``tests/test_tpu_aot_compile.py`` reads its schedule, ``tools/tp_ring_probe.py``
its times).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from conftest import sharded_params
from neuronx_distributed_tpu.parallel import collective_matmul as cm
from neuronx_distributed_tpu.parallel.layers import (
    ColumnParallelLinear,
    shard_activation,
    trailing_spec,
)
from neuronx_distributed_tpu.parallel.mesh import (
    SEQUENCE_AXES,
    TENSOR_AXES,
    destroy_model_parallel,
    initialize_model_parallel,
)
from neuronx_distributed_tpu.parallel.qkv import GQAQKVColumnParallelLinear

NEVER = 1 << 30
LAYOUTS = {
    "tp2dp4": dict(tensor_parallel_size=2),
    "tp4dp2": dict(tensor_parallel_size=4),
    "tp8": dict(tensor_parallel_size=8),
    "tp4cp2": dict(tensor_parallel_size=4, context_parallel_size=2),
    "tp8kvr2": dict(tensor_parallel_size=8, kv_size_multiplier=2),
}


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


def _qkv(dtype, heads=16, kv_heads=8, head_dim=4, **kw):
    return GQAQKVColumnParallelLinear(**{**dict(
        num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
        sequence_parallel=True, dtype=dtype), **kw})


def _dots(fn, *args):
    # a fresh function a call: jit keeps a traced function's jaxpr, whatever
    # the rule's constant says by now
    text = jax.jit(lambda *a: fn(*a)).lower(*args).as_text()
    return text.count("stablehlo.dot_general")


def _value_and_grads(apply, params, x):
    def loss(p, a):
        outs = jax.tree.leaves(apply(p, a))
        return sum(jnp.sum(jnp.sin(o.astype(jnp.float32))) for o in outs)
    out = jax.jit(lambda p, a: apply(p, a))(params, x)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, x)
    return jax.tree.leaves((out, grads))


def _column_in_pieces(n_fused, dtype):
    """A fused column projection through :func:`cm.in_pieces` directly (the
    layers of ``parallel/layers.py`` are not cut: ``PERF.md`` §6, PR 49)."""
    layer = ColumnParallelLinear(
        features=32 * n_fused, n_fused=n_fused, use_bias=False,
        sequence_parallel=True, dtype=dtype)

    def project(x, kernel):
        y = (x @ kernel if n_fused == 1
             else jnp.einsum("...h,hfp->...fp", x, kernel))
        return shard_activation(y, trailing_spec(y.ndim, last=TENSOR_AXES))

    def apply(params, x):
        kernel = params["params"]["kernel"].astype(dtype)
        x = shard_activation(
            x, trailing_spec(x.ndim, seq=SEQUENCE_AXES, last=None))
        pieces = cm.gather_pieces(x, 1 << 20)
        if pieces == 1:
            return layer.apply(params, x)
        return cm.in_pieces(project, pieces, x, kernel)
    return layer, apply


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("site", ["n_fused1", "n_fused2", "n_fused3", "qkv"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_cut_projection_equals_whole(devices8, monkeypatch, layout, site,
                                     dtype):
    """Outputs, ``dx`` and every ``dW`` of the projection cut in pieces equal
    the whole projection's on the same mesh: a row's product does not know
    its piece, and the backward is the whole projection's.  The q/k/v layer
    as the model calls it, and plain and fused kernels through ``in_pieces``
    itself.  float32 also against the dense product."""
    initialize_model_parallel(devices=devices8, **LAYOUTS[layout])
    if site == "qkv":
        layer = _qkv(dtype)
        apply = layer.apply
    else:
        layer, apply = _column_in_pieces(int(site[-1]), dtype)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 16), dtype)
    boxed = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
    params = sharded_params(boxed)

    monkeypatch.setattr(cm, "GATHER_MIN_WIDTH", 1)
    whole_dots = 3 if site == "qkv" else 1
    assert _dots(apply, params, x) == cm.GATHER_PIECES * whole_dots
    cut = _value_and_grads(apply, params, x)
    monkeypatch.setattr(cm, "GATHER_MIN_WIDTH", NEVER)
    assert _dots(apply, params, x) == whole_dots
    whole = _value_and_grads(apply, params, x)
    for got, ref in zip(cut, whole):
        # bfloat16: within one rounding of the whole layer's own result (an
        # ulp is 2^-8 to 2^-7 of a value; the data-parallel sum of a dW may
        # be taken in another order)
        assert _rel(got, ref) < (1e-6 if dtype == jnp.float32 else 2.0 ** -7)

    if dtype == jnp.float32 and site != "qkv":
        w = np.asarray(nn.unbox(boxed)["params"]["kernel"])
        dense = (x @ w if w.ndim == 2 else jnp.einsum("bsh,hfp->bsfp", x, w))
        assert _rel(cut[0], dense) < 1e-5


def test_backward_is_the_whole_projections(devices8, monkeypatch):
    """The gradient program holds the pieces' forward matmuls and ONE ``dx``
    and ONE ``dW`` matmul a kernel: the backward is not cut, and it gathers
    the input once for the three ``dW``."""
    initialize_model_parallel(tensor_parallel_size=4, devices=devices8)
    layer = _qkv(jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16), jnp.float32)
    params = sharded_params(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    monkeypatch.setattr(cm, "GATHER_MIN_WIDTH", 1)

    def loss(p, a):
        return sum(jnp.sum(o ** 2) for o in layer.apply(p, a))
    grad = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x)
    assert grad.as_text().count("stablehlo.dot_general") == 3 * (
        cm.GATHER_PIECES + 2)
    assert grad.as_text().count("stablehlo.all_gather") == cm.GATHER_PIECES + 1


def test_cut_by_the_rule_equals_one_chip(devices8):
    """A shape the RULE admits as it stands (few rows, local columns at the
    rule's constant): q/k/v on tp = 4 is cut and gives what a mesh of one
    gives."""
    tp, pieces = 4, cm.GATHER_PIECES
    head_dim = cm.GATHER_MIN_WIDTH * tp // (8 + 2 * 4)   # columns / tp at the rule
    layer = _qkv(jnp.float32, heads=8, kv_heads=4, head_dim=head_dim)
    x = jax.random.normal(jax.random.PRNGKey(0), (pieces, 64, 64), jnp.float32)
    boxed = jax.jit(layer.init)(jax.random.PRNGKey(1), x)
    outs = {}
    for name, devs in (("tp4", devices8[:4]), ("one", devices8[:1])):
        destroy_model_parallel()
        initialize_model_parallel(tensor_parallel_size=len(devs), devices=devs)
        params = sharded_params(boxed)
        assert _dots(layer.apply, params, x) == 3 * (
            pieces if name == "tp4" else 1)
        outs[name] = jax.tree.leaves(jax.jit(layer.apply)(params, x))
    for got, ref in zip(outs["tp4"], outs["one"]):
        assert _rel(got, ref) < 1e-5


DECLINES = {
    # name: (mesh, layer kwargs, batch, inside a manual pp region)
    "tp1": (dict(tensor_parallel_size=1), {}, 8, False),
    "no_sequence_parallel": (dict(tensor_parallel_size=4),
                             dict(sequence_parallel=False), 8, False),
    "lora": (dict(tensor_parallel_size=4), dict(lora_rank=4), 8, False),
    "manual_pp_region": (dict(tensor_parallel_size=4,
                              pipeline_parallel_size=2), {}, 8, True),
    "batch_gives_a_rank_no_whole_pieces": (dict(tensor_parallel_size=4), {},
                                           2, False),   # dp 2 x 2 pieces
    "no_batch_dim": (dict(tensor_parallel_size=8), {}, 0, False),
}


def test_an_eager_call_is_left_whole(devices8, monkeypatch):
    """The pieces' gather is a ``shard_map`` that leaves mesh axes to GSPMD,
    which exists only under ``jit``: the eager layer multiplies whole and
    gives what the jitted, cut one gives."""
    monkeypatch.setattr(cm, "GATHER_MIN_WIDTH", 1)
    initialize_model_parallel(tensor_parallel_size=4, devices=devices8)
    layer = _column(2, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16), jnp.float32)
    params = sharded_params(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    assert _rel(layer.apply(params, x), jax.jit(layer.apply)(params, x)) < 1e-6


@pytest.mark.parametrize("case", sorted(DECLINES))
def test_projection_is_left_whole(devices8, monkeypatch, case):
    """Where a cut has nothing to hide a gather under, or no even pieces to
    make, the lowered text holds the three matmuls it always held, however
    low the rule's constant."""
    mesh_kw, layer_kw, batch, in_pp = DECLINES[case]
    monkeypatch.setattr(cm, "GATHER_MIN_WIDTH", 1)
    mesh = initialize_model_parallel(devices=devices8, **mesh_kw)
    layer = _qkv(jnp.float32, **layer_kw)
    shape = (batch, 16, 16) if batch else (16, 16)
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    params = sharded_params(jax.jit(layer.init)(jax.random.PRNGKey(1), x))

    def apply(p, a):
        if not in_pp:
            return layer.apply(p, a)
        return jax.shard_map(
            layer.apply, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            axis_names=frozenset({"pp"}), check_vma=False)(p, a)
    # lora's own two matmuls a target (q, v) aside
    assert _dots(apply, params, x) == (7 if case == "lora" else 3)
    # and no explicit gather: a whole projection's gathers are the partitioner's
    assert "stablehlo.all_gather" not in jax.jit(apply).lower(params, x).as_text()


def test_an_eager_call_is_left_whole(devices8, monkeypatch):
    """The pieces' gather is a ``shard_map`` that leaves mesh axes to GSPMD,
    which exists only under ``jit``: the eager layer multiplies whole and
    gives what the jitted, cut one gives."""
    monkeypatch.setattr(cm, "GATHER_MIN_WIDTH", 1)
    initialize_model_parallel(tensor_parallel_size=4, devices=devices8)
    layer = _qkv(jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16), jnp.float32)
    params = sharded_params(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    for got, ref in zip(layer.apply(params, x),
                        jax.jit(layer.apply)(params, x)):
        assert _rel(got, ref) < 1e-6


def test_narrow_projection_is_left_whole_by_the_rule(devices8):
    """The toy widths of every other test in the suite stay under the rule:
    their programs are GSPMD's own text."""
    initialize_model_parallel(tensor_parallel_size=4, devices=devices8)
    layer = _qkv(jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 16), jnp.float32)
    params = sharded_params(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    assert _dots(layer.apply, params, x) == 3
