"""A sequence-parallel gather rides under the matmul it feeds.

Where a column-parallel projection takes a sequence-sharded input, GSPMD
all-gathers the sequence and then multiplies, and on the 2x2 host the gather
stands alone in the step: the compiler emits it synchronous because its only
consumer is the matmul — "nothing independent to put beside it" (``PERF.md``
§6, PR 49: 0.97 ms before every q/k/v and gate-up matmul, 20 a step).  Here
the projection is DECOMPOSED along an independent dimension ("Overlap
communication with dependent computation via decomposition", Wang et al.,
ASPLOS '23): the batch is cut into pieces, each piece is gathered and
multiplied on its own, and the products are laid end to end.  The pieces'
places are static, and the compiler's own latency-hiding scheduler now has
what it lacked: piece ``i + 1``'s all-gather is started before piece ``i``'s
matmuls and waited for after them (``async-collective-start`` / ``-done``
around the matmul fusions of the compiled step).  Only the first piece's
gather still stands alone.

The backward is the UNDIVIDED projection's, written out
(``jax.custom_vjp``): one gather of the input for every kernel's ``dW`` (three
projections of one input re-gathered it three times under autodiff), one
``dW`` matmul a kernel over the whole sequence, and GSPMD's own reduce-scatter
of ``dx`` — what the parent's text holds, under the matmuls where the
compiler already put it.  Every gradient term, dtype and rounding is the
parent's.

Both gathers are EXPLICIT collectives (:func:`gathered`): left to insert a
piece's gather itself, the partitioner may choose a windowed einsum of its
own, whose products it then has to put in place.

What was measured and is NOT here (``PERF.md`` §6, PR 49, on the four chips):
a hand-written ring of ``ppermute`` hops under ``shard_map`` (the reference's
``layers.py:270-305`` generalised) loses at every site — a rank multiplies
block ``(r + i) % tp`` at hop ``i``, so each product's place depends on the
rank, and putting them in place (a zeroed buffer and ``tp``
``dynamic_update_slice``) costs more than the gather it hides: 7.53 ms
against GSPMD's 7.24 for gate-up's forward, 6.05 were the products left where
they fall.  And this cut applied to gate-up loses 0.2% of the tp4 step: its
products come out fused-axis-major, so joining two pieces is a copy.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from neuronx_distributed_tpu.parallel.mesh import (
    CONTEXT_AXIS,
    SEQUENCE_AXES,
    TENSOR_AXES,
    ambient_manual_axes,
    get_data_parallel_size,
    get_mesh,
    get_tensor_parallel_size,
    model_parallel_is_initialized,
)

# THE RULE.  Piece i + 1's gather brings ``rows * H * itemsize * (tp-1)/tp``
# bytes to a chip while piece i's matmuls do ``2 * rows * H * width`` FLOPs
# there (``width``: the projection's local columns, all its kernels'), so the
# FLOPs a gathered byte buys are ``width * tp / (tp - 1)`` (bf16) whatever the
# rows, and the gather hides when
#   width >= peak_flops / gather_bytes_per_s * (tp - 1) / tp.
# Step 0 on the 2x2 host (PERF.md §6, PR 49): the step's all-gather of a
# ``[2, 8192, 4096]`` bf16 activation takes 0.97 ms = 96 MiB in, 104 GB/s a
# chip; the chip's peak is 197 TFLOP/s: 197e12 / 104e9 * 3/4 = 1,420 columns.
# Mistral-7B's q/k/v on tp = 4 (1,536) pass; a toy model's do not.
GATHER_MIN_WIDTH = 1408     # 11 x 128, the lane multiple under 1,420
GATHER_PIECES = 2


def gather_pieces(x: jax.Array, columns: int) -> int:
    """How many pieces a sequence-parallel projection of ``columns`` output
    columns (over all ranks and all its kernels) is cut into over
    ``x [B, ..., S, H]``; 1: not cut (decided at trace time from the mesh and
    the shapes, nothing else).  Not cut: one chip on the tensor axes; a
    region where a mesh axis is already manual (the pipeline engine's); no
    batch dim, or one that does not give every data-parallel rank whole
    pieces; matmuls too narrow to hide a gather under; an eager call (the
    pieces' gather is a ``shard_map`` that leaves mesh axes to GSPMD, which
    exists only under ``jit``)."""
    if (x.ndim < 3 or not isinstance(x, jax.core.Tracer)
            or not model_parallel_is_initialized()):
        return 1
    mesh = get_mesh()
    tp = get_tensor_parallel_size(mesh)
    if (tp == 1 or ambient_manual_axes()
            or columns // tp < GATHER_MIN_WIDTH
            or x.shape[0] % (GATHER_PIECES * get_data_parallel_size(mesh))):
        return 1
    return GATHER_PIECES


def _sequence_spec(ndim: int, axes) -> P:
    return P(*(None,) * (ndim - 2), axes, None)


def gathered(x: jax.Array) -> jax.Array:
    """``x [..., S, H]`` sharded over the sequence axes, all-gathered over the
    tensor axes by an explicit collective."""
    return jax.shard_map(
        lambda a: lax.all_gather(a, TENSOR_AXES, axis=a.ndim - 2, tiled=True),
        mesh=get_mesh(), in_specs=_sequence_spec(x.ndim, SEQUENCE_AXES),
        out_specs=_sequence_spec(x.ndim, CONTEXT_AXIS),
        axis_names=frozenset(SEQUENCE_AXES), check_vma=False)(x)


def _cut(x, pieces, groups):
    """``[B, ...]`` -> ``pieces`` arrays ``[B / pieces, ...]``, each holding as
    many rows of every data-parallel rank's share (``groups`` of them) as
    every other: no piece asks the batch to be re-sharded."""
    xs = x.reshape(groups, pieces, -1, *x.shape[1:])
    return [xs[:, i].reshape(-1, *x.shape[1:]) for i in range(pieces)]


def _join(ys, groups):
    """:func:`_cut`'s inverse, on the projection's outputs."""
    y = jnp.stack([y.reshape(groups, -1, *y.shape[1:]) for y in ys], axis=1)
    return y.reshape(-1, *y.shape[3:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def in_pieces(project: Callable, pieces: int, x: jax.Array, *kernels):
    """``project(x_gathered, *kernels)`` — the matmuls of a sequence-parallel
    column projection, outputs ``[B, ...]`` with their features over the
    tensor axes — over ``x [B, ..., S, H]`` sharded over the sequence axes,
    cut into ``pieces`` (:func:`gather_pieces`) along ``B`` so that a piece's
    gather rides under its neighbour's matmuls.  ``project`` is traced once a
    piece and once, whole, for the backward: it may use nothing but its
    arguments."""
    groups = get_data_parallel_size()
    outs = [project(gathered(piece), *kernels)
            for piece in _cut(x, pieces, groups)]
    return jax.tree.map(lambda *ys: _join(ys, groups), *outs)


def _in_pieces_fwd(project, pieces, x, *kernels):
    return in_pieces(project, pieces, x, *kernels), (x, kernels)


def _in_pieces_bwd(project, pieces, res, dy):
    x, kernels = res
    dx, *dws = jax.vjp(project, gathered(x), *kernels)[1](dy)
    # partial sums over the tensor axes: the constraint is GSPMD's
    # reduce-scatter back onto the sequence axes, as the parent's
    dx = lax.with_sharding_constraint(dx, NamedSharding(
        get_mesh(), _sequence_spec(dx.ndim, SEQUENCE_AXES)))
    return (dx, *dws)


in_pieces.defvjp(_in_pieces_fwd, _in_pieces_bwd)
