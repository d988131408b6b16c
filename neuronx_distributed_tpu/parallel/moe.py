"""Expert-parallel Mixture-of-Experts layer over the ``ep`` mesh axis.

The reference has NO MoE/expert parallelism anywhere (SURVEY §2.10: "EP —
Absent"); the mesh here carries a first-class ``ep`` axis (a sub-axis of data
parallelism, ``parallel/mesh.py``), and this module makes it real — beyond-
parity capability, like ring-attention CP.

TPU-native formulation: the GShard/Switch dense-dispatch pattern —
routing becomes two einsums against a one-hot dispatch tensor, so the
all-to-alls are GSPMD-inserted reshards between the token-sharded and
expert-sharded layouts instead of hand-written ``all_to_all`` calls, and
everything stays static-shaped (capacity-bounded) for jit:

1. router probs ``[N, E]`` (fp32 softmax);
2. top-k choice per token, position-in-expert by cumulative sum, tokens
   beyond ``capacity`` dropped (their combine weight is zero — standard
   capacity-factor semantics);
3. ``dispatch [N, E, C]`` one-hot and ``combine = dispatch * gate``;
4. ``xe = einsum('nh,nec->ech', x, dispatch)`` — result sharded ``e→ep``
   (the "all-to-all" to expert-major layout);
5. per-expert fused gate-up/down FFN, vmapped over local experts, inner
   dims TP-sharded exactly like the dense MLP;
6. ``y = einsum('ech,nec->nh', ye, combine)`` — back to token-major.

The load-balancing auxiliary loss is the Switch-Transformer form
``E * sum_e(frac_tokens_e * mean_prob_e)`` (=1 at perfect balance).

Serving takes none of that: a capacity drop makes a token's output depend on
who shares its step.  ``dispatch="dropless"`` is the token-choice path with
no capacity — the ``N * K`` assignments sorted by expert (stable), gate, up
and down each one grouped matmul over the ragged groups
(:func:`grouped_matmul`), un-sorted and summed under the gates — so a row's
result is a function of that row alone.  Rows marked invalid (a chunk's
padding, a parked slot) are routed nowhere.  It is the path every
``LlamaConfig`` MoE model is served on, and the path OLMoE (dropless by its
published definition) takes everywhere.

The dropless path also carries the sigmoid-routed family (DeepSeek-V3,
Nemotron-H): ``router_scores="sigmoid"`` scores each expert on its own, a
learned correction bias joins the scores for the CHOICE only, the chosen
UNBIASED scores are renormalised and multiplied by ``route_scale``;
``activation="relu2"`` makes an expert ``down(relu(up x)^2)``, two matmuls
and no gate; ``shared_intermediate_size`` adds one shared expert that every
row passes (scope ``moe_shared``), its output under ``shared_gate`` times
``sigmoid(x w_s)``, a scalar a row.  And it can HOLD a share of the experts:
with ``num_experts_global`` routed experts of which this program holds
``num_experts``, from ``first_expert`` on, the router, its top-k and its
normalisation run over all of them and the sum over the chosen ones that
are held — one rank's part of an expert-parallel layer, computed without
the exchange (an assignment to an absent expert goes where an invalid row's
goes: group ``E``, a zero gate, no count).  The stable sort puts those rows
LAST, so a held share of a long array (:func:`held_rows_slab`, a rule over
shapes: a training step's or a long prefill's tens of thousands of rows,
never a serving program's few thousand) passes over the rows it HOLDS: the
grouped matmuls once over the whole sorted array, as ever (their kernel
visits its groups' row tiles alone), and everything between them — the
gather, the activation, the masks, in the forward and in a written-out
backward — in two spans of static shape, the first sized for a balanced
router's share and the second, the rest, skipped when it holds no held row
(:func:`_walk_held_rows`) — exact for every count of held rows, with no
other path beside it and no second copy of a kernel; ``moe_stats`` then also
holds ``computed``, the rows the spans that ran passed over.

A third family is DeepSeek-V2's GROUP-LIMITED choice (``n_group`` > 1): the
experts lie in ``n_group`` equal groups of consecutive ones, a group scores
the MAX of its experts' scores (under a correction bias the sum of its two
best biased scores, as DeepSeek-V3 has it), a row keeps its ``topk_group`` best groups
and takes its top-k among their experts alone (scope ``moe_group_select``
inside ``moe_router``) — the published model's device-limited routing, a
group a device.  It composes with the rest: softmax or sigmoid scores, the
gates' scale, renormalised or not, the shared expert, a held share (which,
where it is whole groups, is what one device of that layout holds: a row
whose groups are all elsewhere gives it no routed work, and ``moe_stats``
then counts the rows that reach it).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from neuronx_distributed_tpu.parallel.layers import shard_activation
from neuronx_distributed_tpu.parallel.mesh import (
    BATCH_AXES,
    EXPERT_AXIS,
    TENSOR_AXES,
    ambient_manual_axes,
    get_tensor_parallel_size,
    model_parallel_is_initialized,
    strip_axes_from_spec,
)
from jax.sharding import PartitionSpec as P


def _auto_spec(*entries) -> P:
    """PartitionSpec with any ambient-*manual* mesh axes removed.

    Inside the 1F1B engine's partial-manual shard_map (manual ``dp/ep/pp``)
    GSPMD sharding constraints may only reference the remaining auto axes;
    a manual axis in a constraint is an error.  Dropping it is also the
    semantically right thing: under the engine the batch is already split
    per (dp, ep) rank, so ``ep`` degenerates to pure data parallelism and
    expert weights are simply replicated within the stage."""
    return strip_axes_from_spec(P(*entries), ambient_manual_axes())

Dtype = Any
Initializer = Callable[..., jax.Array]


def load_balancing_loss(probs: jax.Array, expert_mask: jax.Array) -> jax.Array:
    """Switch aux loss: ``E * sum_e(fraction_routed_e * mean_router_prob_e)``.
    ``probs [N, E]`` fp32 router probabilities, ``expert_mask [N, E]`` 0/1
    top-k selections (pre-capacity)."""
    E = probs.shape[-1]
    frac = jnp.mean(expert_mask.astype(jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac * mean_p)


# megablox's (m, k, n) tile is chosen from the operand's shape
# (:func:`gmm_tile`).  GMM_TILING is the most its row and lane tiles grow to
# and what it falls back to: the fastest of those tried at OLMoE-1B-7B's
# widths on the v5e (benchmarks/tools/moe_gmm_probe.py; PERF.md, PR 25), which
# both of OLMoE's matmuls tile exactly.  GMM_TILE_BYTES bounds a call's
# double-buffered inputs — a row tile ``[tm, tk]`` and a weight tile
# ``[tk, tn]``: 12.5 MiB ran beside the output and the accumulator under the
# 16 MiB a Mosaic call may hold, 15.75 MiB was refused
# (tools/gmm_tile_probe.py; PERF.md, PR 41).
GMM_TILING = (128, 2048, 1024)
GMM_TILE_BYTES = 25 * 2 ** 19


def gmm_tile(m: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """The ``(tm, tk, tn)`` tile :func:`grouped_matmul` hands megablox for
    ``[m, k] x [G, k, n]`` operands of ``itemsize`` bytes: of the tiles that
    DIVIDE the weight matrix — ``tk`` K whole or a multiple of 128 that
    divides it, ``tn`` a multiple of 128 from 512 to GMM_TILING's that
    divides N — and fit GMM_TILE_BYTES, the one that walks an expert in the
    fewest grid steps; of those, the widest in lanes.

    Why: upstream's kernel masks the LAST k-tile whenever ``k % tk != 0`` —
    the whole loaded weight tile through float32 on the vector unit
    (``mask_k_rem``) — and with more than one k-tile it fetches the row tile
    again every step and adds into its accumulator; a lane tile that hangs
    over N multiplies columns nobody reads.  At a decode's few rows an
    expert the MXU's weight push and the HBM read take about the same time,
    so all of that is exposed: Nemotron's up-projection (K 2688) took 1.30
    ms under ``(128, 2048, 1024)`` and 0.97 under ``(128, 2688, 1024)``,
    DeepSeek-V2's gate (K 5120) 0.51 and 0.43 under ``(128, 5120, 512)``
    (v5e; tools/gmm_tile_probe.py).  OLMoE's two shapes get the tile they
    were measured with.  Where no multiple of 128 divides N the lane tile is
    GMM_TILING's; where nothing that divides K fits, GMM_TILING whole — and
    upstream's mask is then right and needed."""
    tm, tk, tn = GMM_TILING
    room = GMM_TILE_BYTES // (2 * itemsize)     # elements, both input tiles
    lanes = [n] if n <= tn else [
        t for t in range(512, tn + 1, 128) if n % t == 0] or [tn]
    depths = [k] + [d for d in range(512, k, 128) if k % d == 0]
    fits = [(d, t) for d in depths for t in lanes if d * (tm + t) <= room]
    if not fits:
        return tm, min(tk, k), min(tn, n)
    tk, tn = min(fits, key=lambda f: (-(-k // f[0]) * -(-n // f[1]), -f[1]))
    return tm, tk, tn


# the backward's tiles (:func:`gmm_backward_tiles`): the rows a step of the
# weight gradient contracts, and what its blocks may hold of the 16 MiB a
# Mosaic call is given (the compiler asked for a described v5e, PR 43: at
# LFM2-8B-A1B's widths every tile the rule below would pass compiled, and the
# nearest larger ones ran out of scoped memory)
GMM_BACKWARD_ROWS = 512
GMM_BACKWARD_BYTES = 15 * 2 ** 20


def gmm_backward_tiles(m: int, k: int, n: int, itemsize: int):
    """``(data-gradient tile, weight-gradient tile)`` for the backward of
    ``[m, k] x [G, k, n]``, each chosen from the shape of ITS operands — the
    forward's tile is the wrong shape for both (upstream's ``custom_vjp``
    hands it on: at ``[8, 2048, 1792]`` the flipped ``gmm`` then asks for a
    block wider than the array and ``tgmm`` for 28 MiB of scoped memory).

    The data gradient is ``gmm`` of ``[m, n] x [G, k, n]`` with the
    right-hand side flipped — contraction ``n``, ``k`` columns out — so its
    tile is :func:`gmm_tile`'s for those.  The weight gradient ``tgmm`` holds
    a ``[tk, tn]`` float32 accumulator beside the double-buffered output
    block and the two row blocks ``[tm, tk]`` and ``[tm, tn]`` (which it
    masks through float32): of the ``tk`` and ``tn`` that are multiples of
    128 and DIVIDE ``k`` and ``n`` (or the dimension whole) and fit
    GMM_BACKWARD_BYTES at ``tm`` = GMM_BACKWARD_ROWS, the pair that reads
    the rows the fewest times over (``tiles_k x tiles_n``), then the widest
    in lanes."""
    dlhs = gmm_tile(m, n, k, itemsize)
    # the kernels take whole row tiles: the forward pads to its own (128)
    tm = next((t for t in (GMM_BACKWARD_ROWS, 256) if m % t == 0), 128)

    def dividing(x):
        return [x] + [t for t in range(128, x, 128) if x % t == 0]

    def held(tk, tn):
        return tk * tn * (4 + 2 * itemsize) \
            + tm * (tk + tn) * (2 * itemsize + 4)

    fits = [(tk, tn) for tk in dividing(k) for tn in dividing(n)
            if held(tk, tn) <= GMM_BACKWARD_BYTES]
    if not fits:
        return dlhs, (tm, min(128, k), min(128, n))
    tk, tn = min(fits, key=lambda f: (-(-k // f[0]) * -(-n // f[1]), -f[1]))
    return dlhs, (tm, tk, tn)


# grouped matmuls whose kernel arm was traced since the last take, by whether
# the k-tile divides the contraction (``masked_k``: upstream masks the last)
_GMM_LOWERED = {"whole_k": 0, "masked_k": 0}


def take_gmm_lowered() -> dict:
    """``{"whole_k": n, "masked_k": n}``: the calls of :func:`grouped_matmul`
    whose megablox arm was traced since the last call of this — a count
    made at trace time, once a lowered call and never again for a program
    that is cached (a program for another platform traces the arm too and
    drops it when it lowers).  The serving engine books it as
    ``moe/gmm_lowered_total/*`` with a program's first expert loads."""
    out = dict(_GMM_LOWERED)
    _GMM_LOWERED.update(dict.fromkeys(_GMM_LOWERED, 0))
    return out


def book_expert_loads(reg, program: str, stats: dict, running):
    """Book one program's fetched expert loads into the registry ``reg``
    under the family name ``program`` (a serve program's, or ``train_step``)
    and return ``running``, the loads summed since the caller began (``None``
    at first), with this program's added (:func:`set_expert_load_gauge`
    reads it).  From ``stats["load"] [L, E]``: ``moe/assignments_total``
    (valid rows x experts a token x layers), ``moe/rows_computed_total``
    (the assignment rows the blocks passed over: every one made, or
    ``"computed" [L]``, those of the spans that ran where a held share of a
    long array computes over the rows it holds), ``moe/layer_calls_total``
    (expert blocks that ran with a token) and ``moe/experts_hit_total``
    (experts with a row, summed over those calls: a decode's few rows leave
    experts unread, a chunk's hundreds do not) — the last three also by
    program family.  With ``"assigned" [L]``, where the layers hold a share
    of their experts, ``moe/assignments_held_total`` (those that went to an
    expert this program holds; it and ``moe/assignments_total`` then also by
    family).  With ``"reached" [L, 2]``, where a group limit lets a row
    reach none of them, ``moe/rows_routed_total`` (valid rows x layers) and
    ``moe/rows_reaching_held_total`` (those with at least one held
    assignment), both also by family.  A missing key and ``None`` are
    alike."""
    load = np.asarray(stats["load"], np.int64)
    assigned = stats.get("assigned")
    calls, hit = int((load.sum(axis=1) > 0).sum()), int((load > 0).sum())
    made = int(load.sum() if assigned is None else np.sum(assigned))
    reg.counter("moe/assignments_total").inc(made)
    # the assignment rows the blocks passed over: those of the spans that
    # ran, or every one made where a block runs over the whole array
    computed = stats.get("computed")
    for suffix in ("", "/" + program):
        reg.counter("moe/rows_computed_total" + suffix).inc(
            made if computed is None else int(np.sum(computed)))
    if assigned is not None:
        # a held share: what fell to it, also by program family
        for suffix in ("", "/" + program):
            reg.counter("moe/assignments_held_total" + suffix).inc(
                int(load.sum()))
        reg.counter("moe/assignments_total/" + program).inc(made)
    if "reached" in stats:
        # a group-limited router over a held share: the rows that were
        # routed (a layer each), and those with an assignment this program
        # holds
        rows, reached = np.asarray(stats["reached"]).sum(axis=0)
        for suffix in ("", "/" + program):
            reg.counter("moe/rows_routed_total" + suffix).inc(int(rows))
            reg.counter("moe/rows_reaching_held_total" + suffix).inc(
                int(reached))
    for suffix in ("", "/" + program):
        reg.counter("moe/layer_calls_total" + suffix).inc(calls)
        reg.counter("moe/experts_hit_total" + suffix).inc(hit)
    return load + (0 if running is None else running)


def set_expert_load_gauge(reg, running) -> None:
    """The gauge ``moe/expert_load_max_over_mean`` from ``running``, the
    ``[L, E]`` loads :func:`book_expert_loads` has summed: per layer the
    busiest expert's assignments over the mean expert's, the mean over the
    layers that took any."""
    mean = running.mean(axis=1)
    if (mean > 0).any():
        reg.gauge("moe/expert_load_max_over_mean").set(float(np.mean(
            running.max(axis=1)[mean > 0] / mean[mean > 0])))


class ExpertLoadBook:
    """What ONE serving engine has booked of a routed model's expert loads:
    the loads summed since the engine began (``[L, E]``) and the program
    families whose first loads it has seen.  What the model ran before
    (another engine, a check) is taken here and dropped."""

    def __init__(self, model, reg):
        self._take = model.take_moe_stats
        self._reg = reg
        self._load = None
        self._programs: set = set()
        self.take()

    def take(self, upto=None):
        """``(program families, device loads)`` of the paged programs the
        model ran since the last call (``take_moe_stats``: ``"load"``,
        ``"assigned"`` and ``"reached"`` where the program has them), those
        launched after ``upto`` (a ``moe_seq``) left for the next call."""
        stats = self._take(upto)
        return [s["program"] for s in stats], [
            {k: s[k] for k in ("load", "assigned", "reached") if k in s}
            for s in stats]

    def book(self, programs, loads) -> None:
        """Book the fetched ``loads`` of ``programs``
        (:func:`book_expert_loads`) and set the gauge
        ``moe/expert_load_max_over_mean`` (per layer, the busiest expert's
        assignments over the mean expert's since the engine began; the mean
        over layers).  With a family's FIRST loads — its program has been
        traced by then — also ``moe/gmm_lowered_total/{whole_k,masked_k}``:
        the grouped matmuls lowered in this process since the last booking,
        by whether the k-tile divides the contraction
        (:func:`take_gmm_lowered`)."""
        reg = self._reg
        for program, stats in zip(programs, loads):
            if program not in self._programs:
                self._programs.add(program)
                for how, n in take_gmm_lowered().items():
                    reg.counter("moe/gmm_lowered_total/" + how).inc(n)
            self._load = book_expert_loads(reg, program, stats, self._load)
        set_expert_load_gauge(reg, self._load)


def per_expert_lecun(key, shape, dtype=jnp.float32):
    """LeCun normal at ONE expert's fan-in for a stacked ``[E, in, out]``
    kernel (``lecun_normal`` on the stack counts ``E`` into the fan-in and
    draws every expert ``sqrt(E)`` times smaller); plain LeCun normal for
    a ``[in, out]`` kernel.  Drawn in float32 and rounded: ``jax.random``'s
    bfloat16 normal has a mean of -1.8% of its standard deviation, which a
    fan-in of thousands of POSITIVE inputs (relu2 hidden units) turns into
    one vector added to every token — 45% of a down-projection's output at
    3,712 inputs, and with it every token to the same experts (the busiest
    took 21 x the mean on the v5e: PERF.md, PR 32)."""
    return nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
        batch_axis=tuple(range(len(shape) - 2)))(
        key, shape, jnp.float32).astype(dtype)


def _megablox():
    import importlib

    # the package's ``gmm`` attribute is its custom_vjp function; the
    # module of that name holds the two kernels
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tgmm(x, grad, sizes, w, tile, transpose_rhs, interpret=False):
    """megablox ``tgmm``: the gradient of ``w`` where :func:`_gmm` took
    ``x`` to the rows whose cotangent is ``grad`` — float32 accumulation
    over ALL of a group's rows, rounded once to the weight's dtype; an
    expert no row chose gets zeros."""
    dw = _megablox().tgmm(x.swapaxes(0, 1), grad, sizes, w.dtype, tile,
                          num_actual_groups=w.shape[0], interpret=interpret)
    return dw.swapaxes(1, 2) if transpose_rhs else dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm(x, w, sizes, dtype, tile, transpose_rhs, interpret=False):
    """megablox ``gmm`` under ``tile``, its backward under
    :func:`gmm_backward_tiles` (upstream's ``custom_vjp`` hands the backward
    the forward's tile).  ``interpret``: the tests', on the CPU."""
    return _megablox().gmm(x, w, sizes, dtype, tile,
                           transpose_rhs=transpose_rhs, interpret=interpret)


def _gmm_fwd(x, w, sizes, dtype, tile, transpose_rhs, interpret):
    return _gmm(x, w, sizes, dtype, tile, transpose_rhs, interpret), (
        x, w, sizes)


def _gmm_bwd(dtype, tile, transpose_rhs, interpret, res, grad):
    x, w, sizes = res
    k, n = (w.shape[2], w.shape[1]) if transpose_rhs else w.shape[1:]
    dlhs, drhs = gmm_backward_tiles(x.shape[0], k, n, w.dtype.itemsize)
    mb = _megablox()
    with jax.named_scope("moe_gmm"):
        dx = mb.gmm(grad, w, sizes, x.dtype, dlhs,
                    transpose_rhs=not transpose_rhs, interpret=interpret)
        # the kernel writes the rows of its groups only, and a row in no
        # group is still SOME token's row (an assignment to an expert held
        # elsewhere): what it leaves there must not reach that token
        dx = jnp.where((jnp.arange(x.shape[0]) < jnp.sum(sizes))[:, None],
                       dx, 0)
        dw = _tgmm(x, grad, sizes, w, drhs, transpose_rhs, interpret)
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@jax.custom_vjp
def _dispatch_rows(x, order):
    """``x[order // K]``: the ``N * K`` assignment rows of ``x [N, H]`` in
    ``order``, a PERMUTATION of the token-major assignments ``n * K + k``.
    JAX's transpose of a gather is a scatter-add, row after row on a TPU;
    of a permutation it is the gather by the inverse and a sum over the
    ``K`` copies, which is what the backward here does — gathered ``k``
    major, so that the copies are ``K`` slabs ``[N, H]`` to add and no
    ``[N, K, H]`` array (``K`` rows to a tile of 16) is ever laid out.  The
    forward is the gather it always was."""
    return x[order // (order.shape[0] // x.shape[0])]


def _dispatch_rows_fwd(x, order):
    return _dispatch_rows(x, order), (order, x.shape[0])


def _rows_to_tokens(g, back, n):
    """The transpose of the dispatch: the sorted rows' cotangents ``g [N *
    K, H]`` gathered by ``back`` (the inverse of the sort) ``k`` major and
    summed in float32 over a token's ``K`` copies -> ``[N, H]``."""
    k = back.shape[0] // n
    inv = back.reshape(n, k).T.reshape(-1)
    return jnp.sum(g[inv].reshape(k, n, g.shape[-1]).astype(jnp.float32),
                   axis=0).astype(g.dtype)


def _dispatch_rows_bwd(res, g):
    order, n = res
    return _rows_to_tokens(g, jnp.argsort(order), n), None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(ys, back, order, gates):
    """``y[n] = sum_k gates[n, k] * ys[back[n * K + k]]`` in float32 — the
    experts' rows ``ys [N * K, H]`` un-sorted (``back`` the inverse of the
    permutation ``order``) and summed under the gates ``[N, K]``.  The
    backward never builds ``[N, K, H]``: a sorted row's cotangent is its
    token's, gathered by ``order // K`` like the dispatch's rows, times its
    own gate, and a gate's is the dot of the two rows, un-sorted as a
    scalar."""
    n, k = gates.shape
    return jnp.sum(ys[back].reshape(n, k, ys.shape[-1]).astype(jnp.float32)
                   * gates[:, :, None], axis=1)


def _combine_rows_fwd(ys, back, order, gates):
    return _combine_rows(ys, back, order, gates), (ys, back, order, gates)


def _combine_rows_bwd(res, dy):
    ys, back, order, gates = res
    n, k = gates.shape
    rows = dy[order // k]                                     # [N * K, H]
    d_ys = (rows * gates.reshape(-1)[order][:, None]).astype(ys.dtype)
    dots = jnp.sum(ys.astype(jnp.float32) * rows, axis=-1)
    return d_ys, None, None, dots[back].reshape(n, k).astype(gates.dtype)


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   dtype: Dtype, transpose_rhs: bool = False) -> jax.Array:
    """``x [M, K]`` rows sorted by group, ``w [G, K, N]`` (``[G, N, K]``
    with ``transpose_rhs``), ``group_sizes [G]`` -> ``[M, N]``: row ``r`` of
    group ``g`` is ``x[r] @ w[g]``, fp32 accumulation, stored in ``dtype``.  Rows past ``sum(group_sizes)``
    belong to no group; what they hold is unspecified (callers mask them).
    A row's result does not depend on the other rows or on the sizes.

    A program lowered for a TPU with the expert width whole on each chip
    carries the megablox Pallas kernel (scope ``moe_gmm``); any other,
    ``lax.ragged_dot``.  Both differentiate, and both backwards are tested
    against each other: ``ragged_dot`` by JAX's own rule, the kernel by the
    flipped ``gmm`` (data gradient; rows in no group get what the kernel
    leaves, their cotangent being the caller's zero) and ``tgmm`` (weight
    gradient) under tiles chosen from THEIR operands
    (:func:`gmm_backward_tiles`)."""
    sizes = group_sizes.astype(jnp.int32)

    def ragged(x, w, sizes):
        if transpose_rhs:
            w = jnp.swapaxes(w, 1, 2)
        return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=dtype)

    def kernel(x, w, sizes):
        k, n = (w.shape[2], w.shape[1]) if transpose_rhs else w.shape[1:]
        tile = gmm_tile(x.shape[0], k, n, w.dtype.itemsize)
        _GMM_LOWERED["masked_k" if k % tile[1] else "whole_k"] += 1
        pad = -x.shape[0] % tile[0]     # whole row tiles; the pad is in no group
        xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
        with jax.named_scope("moe_gmm"):
            out = _gmm(xp, w, sizes, jnp.dtype(dtype), tile, transpose_rhs)
        return out[:x.shape[0]]

    if model_parallel_is_initialized() and get_tensor_parallel_size() > 1:
        # GSPMD cannot split a Pallas call
        return ragged(x, w, sizes)
    return jax.lax.platform_dependent(x, w, sizes, tpu=kernel, default=ragged)


def grouped_matmul_dw(x: jax.Array, g: jax.Array, w: jax.Array,
                      group_sizes: jax.Array,
                      transpose_rhs: bool = False) -> jax.Array:
    """The gradient of ``w`` under :func:`grouped_matmul` ``(x, w,
    group_sizes)`` for the cotangent ``g [M, N]`` of its rows — the weight's
    half of that function's backward ALONE, for a caller that writes its own
    backward (:func:`_walk_held_rows`) and would otherwise lower the forward
    kernel and the data gradient's for the compiler to drop: the same
    ``tgmm`` under the same tile (:func:`gmm_backward_tiles`), one float32
    accumulation over all of a group's rows and one rounding;
    ``lax.ragged_dot``'s transpose where :func:`grouped_matmul` takes that
    arm."""
    sizes = group_sizes.astype(jnp.int32)

    def ragged(x, g, w, sizes):
        return jax.linear_transpose(lambda w: jax.lax.ragged_dot(
            x, jnp.swapaxes(w, 1, 2) if transpose_rhs else w, sizes,
            preferred_element_type=g.dtype), w)(g)[0]

    def kernel(x, g, w, sizes):
        k, n = (w.shape[2], w.shape[1]) if transpose_rhs else w.shape[1:]
        pad = -x.shape[0] % GMM_TILING[0]   # whole row tiles, in no group
        if pad:
            x, g = (jnp.pad(a, ((0, pad), (0, 0))) for a in (x, g))
        tile = gmm_backward_tiles(x.shape[0], k, n, w.dtype.itemsize)[1]
        with jax.named_scope("moe_gmm"):
            return _tgmm(x, g, sizes, w, tile, transpose_rhs)

    if model_parallel_is_initialized() and get_tensor_parallel_size() > 1:
        return ragged(x, g, w, sizes)
    return jax.lax.platform_dependent(x, g, w, sizes, tpu=kernel,
                                      default=ragged)


# A held share of a long array passes over the sorted rows it holds
# (:func:`held_rows_slab`, :func:`_walk_held_rows`).  Not under
# HELD_WALK_FLOOR assignment rows: a span is a predicate to wait for and a
# fusion barrier, and what it saves goes with the rows (a serving program
# lays out a few thousand, bound by launches: at DeepSeek-V2's 3,264 a span of
# 512 or 1,024 took 6.61 / 6.66 ms a forward where the whole array took 6.53).
# The first span is HELD_SLAB_SLACK times the rows a uniform router sends the
# share: a balanced router's rows fit it with their few hundred rows of noise
# to spare.  Module constants like GMM_TILING, measured on the v5e at
# LFM2-8B-A1B's widths (tools/held_rows_probe.py; PERF.md, PR 45).
HELD_WALK_FLOOR = 16384
HELD_SLAB_SLACK = 1.125


def held_rows_slab(rows: int, held: int, routed: int) -> int:
    """``S``: the rows of the first span where a dropless layer that HOLDS
    ``held`` of its ``routed`` experts passes over the sorted rows it holds
    (:func:`_walk_held_rows`) and not over all ``rows`` (= ``N * K``); 0
    where the block runs once over the whole array, as a layer that holds
    every expert does.  A function of shapes only.

    Why: the stable sort puts every held assignment before every assignment
    of group ``E`` (held elsewhere, or an invalid row), so the held rows ARE
    the prefix ``order[:sum(load)]`` — megablox visits the row tiles of its
    groups and no others, but everything XLA emits around the grouped
    matmuls (the gather, the activation, the masks, the transposes of each)
    passes over the array it is given whatever the loads are.  ``S`` is a
    multiple of GMM_BACKWARD_ROWS, the widest row tile of the kernels."""
    if held >= routed or rows < HELD_WALK_FLOOR:
        return 0
    tile = GMM_BACKWARD_ROWS
    share = HELD_SLAB_SLACK * rows * held / routed
    slab = -(-int(share + 0.999) // tile) * tile
    return slab if slab < rows else 0


# a gated expert is ``down(f(gate x) * up x)``: SwiGLU's and ReGLU's ``f``
GATE_FN = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _expert_activation(activation: str):
    """``pre -> h``: the experts' activation over their pre-activations, the
    pair ``(gate, up)`` or, of relu2 experts, ``(up,)``."""
    def act(pre):
        # in float32 whatever the compiler fuses, rounded once: a span is
        # compiled apart from its program, and a hidden row must not depend
        # on where a fusion's edge fell
        wide = [p.astype(jnp.float32) for p in pre]
        h = (jnp.square(jax.nn.relu(wide[0])) if activation == "relu2"
             else GATE_FN[activation](wide[0]) * wide[1])
        return shard_activation(h.astype(pre[0].dtype),
                                _auto_spec(None, TENSOR_AXES))
    return act


def _spans(rows: int, slab: int):
    """The two spans ``(first row, rows)`` of the ``rows`` sorted
    assignments: the first ``slab``, where a balanced router's held rows
    lie, and all the rest."""
    slab = min(slab, rows)
    return ((0, slab),) + (((slab, rows - slab),) if slab < rows else ())


def _over_held_spans(spans, held, turn, carry):
    """``carry`` (arrays of one row an assignment) through ``turn(cut, put,
    live, carry)`` for the first span and for every later span that holds
    one of the ``held`` rows, in order: ``cut(a)`` is the span's rows of
    ``a``, ``put(a, part)`` ``a`` with them replaced, ``live [size, 1]``
    which of them are held at all.  A later span past every held row is
    skipped and costs a predicate; what ``carry`` held there stays.  The
    first span runs whatever it holds (with nothing held its rows are all
    masked, and a branch there would be one more computation for the
    compiler to lay out and a fence between fusions, for a case no router
    produces).  Only what XLA emits row by row goes through here — the
    grouped matmuls stand OUTSIDE, once, over the whole array (their kernel
    visits the held rows' tiles alone) — so a span's body is a few fusions
    and no second copy of a kernel.

    The spans are few and stand in the program one after the other, a later
    one under its own ``lax.cond`` — NOT a loop: with a ``while`` loop around
    these kernels anywhere in the step, forward or backward, the compiled
    TRAIN step's forward came out of the compiler rounding differently from
    the same forward compiled without the optimizer, from the first routed
    layer on, and that is what the benchmark's check reads as a gradient
    5-10% off (PERF.md, PR 45: the finding and the diagnostic)."""
    for lo, size in spans:
        def cut(a, lo=lo, size=size):
            return jax.lax.slice_in_dim(a, lo, lo + size)

        def put(a, part, lo=lo):
            return jax.lax.dynamic_update_slice_in_dim(a, part, lo, axis=0)

        def body(carry, lo=lo, size=size, cut=cut, put=put):
            return turn(cut, put, (lo + jnp.arange(size) < held)[:, None],
                        carry)

        carry = jax.lax.cond(held > lo, body, lambda c: c,
                             carry) if lo else body(carry)
    return carry


def held_rows_computed(load, rows: int, slab: int):
    """The rows the spans that ran passed over, of ``rows``."""
    first = min(slab, rows)
    return jnp.where(jnp.sum(load) > first, rows, first)


def _unsorted(order):
    """``back``: where each token-major assignment lies once sorted — the
    inverse of the permutation ``order``."""
    nk = order.shape[0]
    return jnp.zeros((nk,), jnp.int32).at[order].set(
        jnp.arange(nk, dtype=jnp.int32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _walk_held_rows(xt, order, gates, load, wi, wo, slab, activation, dtype):
    """The dropless block after the sort, of a layer that holds a share of
    its experts: ``xt [N, H]``, ``order [N * K]`` the stable sort of the
    assignments by group, ``gates [N, K]`` (zero for an assignment in no
    held group), ``load [E]``, ``wi`` the pair ``(gate, up)`` (``(up,)`` of
    relu2 experts), ``wo`` the down projection -> ``y [N, H]`` float32.

    The grouped matmuls are the whole-array block's own calls — the same
    operand shapes, the same ``load``, the same tiles, ONE of each in the
    program: megablox walks the row tiles of its groups, so the rows of
    experts held elsewhere cost it nothing.  What passed over all ``N * K``
    rows was everything between the kernels: the gather of the rows, the
    activation, the mask.  Those go through :func:`_over_held_spans`: the
    sorted rows in two spans of static shape (:func:`_spans`), the first
    ``slab`` rows and the rest, the second skipped when it holds no held
    row.  So a balanced router (or one that sends nothing here): the first
    span alone; every row sent here: both, which is the whole array — every
    count of held rows from 0 to ``N * K`` through the same code, with no
    other path beside it, and the rows no span reached hold what the kernels
    never read (or zeros, where the un-sort reads them).  The un-sort and
    the gated sum over a token's ``K`` rows are the whole-array block's own
    (:func:`_combine_rows`): step 0 chose that over adding the held rows
    into ``y`` at their tokens (a row scatter-add costs 258 ns a row on the
    v5e where the un-sort's gather costs ~60: PERF.md, PR 45), and it adds a
    token's rows in one fixed order.

    The backward is written out (``defvjp`` below) for the same reason: JAX's
    transpose would pass over all the rows between the kernels.  Its kernels
    are :func:`grouped_matmul`'s own backward's, once each over the whole
    array — an expert's weight gradient is ONE ``tgmm`` over all its rows
    wherever the spans' edge falls (:func:`grouped_matmul_dw`), float32
    accumulation and one rounding, exactly the whole-array block's.  It
    keeps nothing but its inputs: the held rows are gathered again (with
    their tokens' cotangent rows, in one gather), the pre-activations
    computed again (the two matmuls a remat block would repeat anyway), and
    a gate's cotangent ``ys . dy`` is read as ``h . (dy wo^T)``, so the down
    projection is not run again."""
    rows, width = order.shape[0], xt.shape[1]
    k = rows // xt.shape[0]
    spans, held = _spans(rows, slab), jnp.sum(load)
    act = _expert_activation(activation)
    with jax.named_scope("moe_dispatch"):
        xs = _over_held_spans(
            spans, held, lambda cut, put, live, xs: put(
                xs, xt[cut(order) // k]), jnp.zeros((rows, width), dtype))
    with jax.named_scope("moe_experts"):
        pre = tuple(grouped_matmul(xs, w, load, dtype,
                                   transpose_rhs=activation == "relu2")
                    for w in wi)
        # the hidden rows take the place of the first pre-activation's
        h = _over_held_spans(
            spans, held, lambda cut, put, live, pre: (put(
                pre[0], act(tuple(cut(p) for p in pre))),) + pre[1:], pre)[0]
        out = grouped_matmul(h, wo, load, dtype)
    with jax.named_scope("moe_combine"):
        # a row in no group holds what the kernel left there
        ys = _over_held_spans(
            spans, held, lambda cut, put, live, ys: put(
                ys, jnp.where(live, cut(out), 0)),
            jnp.zeros((rows, width), dtype))
        return _combine_rows(ys, _unsorted(order), order, gates)


def _walk_held_rows_fwd(xt, order, gates, load, wi, wo, slab, activation,
                        dtype):
    return (_walk_held_rows(xt, order, gates, load, wi, wo, slab, activation,
                            dtype), (xt, order, gates, load, wi, wo))


def _walk_held_rows_bwd(slab, activation, dtype, res, dy):
    xt, order, gates, load, wi, wo = res
    n, width = xt.shape
    rows = order.shape[0]
    k = rows // n
    relu2 = activation == "relu2"
    spans, held = _spans(rows, slab), jnp.sum(load)
    act = _expert_activation(activation)
    flat_gates = gates.reshape(-1)

    def matmul(x, w, transpose_rhs):
        return grouped_matmul(x, w, load, dtype, transpose_rhs=transpose_rhs)

    with jax.named_scope("moe_dispatch"):
        # the held rows and their tokens' cotangent rows come in one gather:
        # a row gather costs by the row, not by its width
        both = jnp.concatenate([xt, dy.astype(xt.dtype)], axis=1)

        def gather(cut, put, live, carry):
            got = both[cut(order) // k]
            return tuple(put(a, part) for a, part in zip(
                carry, jnp.split(got, [width], axis=1)))

        xs, d_out = _over_held_spans(
            spans, held, gather, (jnp.zeros((rows, width), dtype),) * 2)
    with jax.named_scope("moe_experts"):
        pre = tuple(matmul(xs, w, relu2) for w in wi)
        # ``dy wo^T`` ungated: the hidden rows' cotangent is the gate times
        # it, the gate's its dot with the hidden row
        u = matmul(d_out, wo, True)

        def hidden(cut, put, live, carry):
            # each result takes the place of an operand that is read no more
            pre, u, d_out, dots = carry
            gate = flat_gates[cut(order)][:, None]
            h, pull = jax.vjp(act, tuple(cut(p) for p in pre))
            wide = jnp.where(live, cut(u).astype(jnp.float32), 0)
            dot = jnp.sum(jnp.where(live, h.astype(jnp.float32), 0) * wide,
                          axis=-1)
            d_pre = pull((wide * gate).astype(dtype))[0]
            d_ys = jnp.where(live, cut(d_out).astype(jnp.float32) * gate,
                             0).astype(dtype)
            return (tuple(put(p, d) for p, d in zip(pre, d_pre)), put(u, h),
                    put(d_out, d_ys), put(dots, dot))

        d_pre, h, d_ys, dots = _over_held_spans(
            spans, held, hidden,
            (pre, u, d_out, jnp.zeros((rows,), jnp.float32)))
        d_wo = grouped_matmul_dw(h, d_ys, wo, load)
        d_wi = tuple(grouped_matmul_dw(xs, d, w, load, transpose_rhs=relu2)
                     for w, d in zip(wi, d_pre))
        d_rows = tuple(matmul(d, w, not relu2) for d, w in zip(d_pre, wi))
    back = _unsorted(order)
    with jax.named_scope("moe_dispatch"):
        d_xs = _over_held_spans(
            spans, held, lambda cut, put, live, d_xs: put(d_xs, jnp.where(
                live, sum(cut(d).astype(jnp.float32) for d in d_rows),
                0).astype(dtype)), jnp.zeros((rows, width), dtype))
        # the transpose of the dispatch as the whole-array block has it
        gx = _rows_to_tokens(d_xs, back, n).astype(xt.dtype)
    with jax.named_scope("moe_combine"):
        d_gates = dots[back].reshape(gates.shape).astype(gates.dtype)
    return gx, None, d_gates, None, d_wi, d_wo


_walk_held_rows.defvjp(_walk_held_rows_fwd, _walk_held_rows_bwd)


class ExpertParallelMLP(nn.Module):
    """Top-k routed MoE FFN; experts sharded over ``ep``, each expert's
    hidden dim over the TP axes (the dense MLP's sharding, per expert).

    Input/output ``[..., hidden]``; returns ``(y, aux_loss)``.
    """

    num_experts: int
    intermediate_size: int
    top_k: int = 2
    capacity_factor: float = 1.25
    # "einsum": GShard dense one-hot dispatch/combine [N, E, C] tensors —
    #   collective-friendly and the parity oracle, but O(N·E·C) memory
    #   (multi-GB at Mixtral scale: N≈32k, E=8, C≈6k — VERDICT r3 weak #3).
    # "scatter": capacity-bucketed segment-sum dispatch + gather combine —
    #   O(N·K·H + E·C·H) memory, the trainable path at preset scale.
    # "dropless": no capacity — sort by expert + grouped matmuls; a row's
    #   output depends on that row alone (the served path; see the module
    #   docstring).  Experts are not split over ``ep`` on this path.
    dispatch: str = "einsum"
    # top-k gates renormalised to sum 1 (Mixtral; HF ``norm_topk_prob``) or
    # left as the softmax gave them (OLMoE)
    norm_topk_prob: bool = True
    # ``gate_up [E, H, 2, I]`` (the capacity paths' einsum reads it as it
    # lies) or, False, ``gate [E, H, I]`` and ``up [E, H, I]``: a grouped
    # matmul kernel takes ``[E, K, N]`` operands, and on the v5e cutting them
    # out of the fused array relaid 512 MB out a layer a program — 36% of
    # the device in OLMoE's serving cell (PERF.md, Findings, PR 25).  A model
    # whose dispatch IS dropless stores them apart; a model trained fused
    # and served dropless pays that copy.
    fused_gate_up: bool = True
    # manual expert parallelism (inside the PP engine's shard_map, where
    # ``ep`` is a manual axis): ``num_experts`` is then the LOCAL expert
    # count held by this ep rank and ``num_experts_global`` the routing
    # space.  Tokens are all-gathered over ep, each rank computes its
    # experts' contributions, and a psum_scatter returns each rank its
    # token shard — the explicit form of the a2a GSPMD inserts on the
    # pp==1 path.  0 = single-program GSPMD mode (num_experts is global).
    num_experts_global: int = 0
    # "topk": tokens choose experts (GShard/Switch/Mixtral; needs the aux
    #   loss + capacity drops).  "expert_choice": experts choose their top-C
    #   tokens (Zhou et al. 2022, C = ceil(factor*k*N/E)) — every expert is
    #   exactly full (no aux pressure; aux returns 0), though a token picked
    #   by NO expert passes through residual-only, and ``top_k`` only sets
    #   the AVERAGE experts per token.  CAUTION for causal LMs: each
    #   expert's top-C compares a token's score against LATER tokens of the
    #   same batch, so routing leaks future information during training and
    #   differs between teacher-forced training and incremental decoding —
    #   expert choice is principally an encoder/non-autoregressive router.
    router_type: str = "topk"
    # the dropless path's sigmoid-routed family (module docstring):
    # "softmax" | "sigmoid" scores; a correction bias ``router_bias [Eg]``
    # added for the choice only; the gates' scale; "silu" (SwiGLU experts,
    # gate/up/down) | "relu" (ReGLU: the same three matmuls, relu for silu)
    # | "relu2" (up/down); a shared expert's width (0: none)
    router_scores: str = "softmax"
    router_bias: bool = False
    route_scale: float = 1.0
    activation: str = "silu"
    shared_intermediate_size: int = 0
    # the shared expert's output times ``sigmoid(x w_s)``, a scalar a row
    # (Qwen2-MoE's and Qwen3-Next's ``shared_expert_gate``)
    shared_gate: bool = False
    # dropless with ``num_experts_global != num_experts``: the first of
    # the ``num_experts`` routed experts this program holds
    first_expert: int = 0
    # group-limited choice (module docstring): ``n_group`` equal groups of
    # consecutive experts, of which a row keeps ``topk_group``; 1: no limit
    n_group: int = 1
    topk_group: int = 1
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    kernel_init: Initializer = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x: jax.Array, valid=None,
                 router_input=None) -> Tuple[jax.Array, jax.Array]:
        """``valid [...]`` (the lead dims of ``x``; dropless path only)
        marks the rows that are tokens: the others are routed nowhere and
        come out zero.  ``router_input`` (shaped as ``x``; dropless path
        only): the rows the ROUTER scores, where they are not the rows the
        experts compute on — a router placed before the layer's attention
        reads that attention's input."""
        from jax import lax

        dropless = self.dispatch == "dropless"
        # fewer experts here than are routed: inside the PP engine's
        # shard_map this rank's share WITH its exchange (manual ep); on the
        # dropless path a held share without one
        share = bool(self.num_experts_global) and \
            self.num_experts_global != self.num_experts
        manual_ep = share and not dropless
        Eg = self.num_experts_global or self.num_experts
        if manual_ep and EXPERT_AXIS not in ambient_manual_axes():
            raise ValueError(
                "num_experts_global != num_experts requires a manual ep axis "
                "(the PP engine's shard_map); under plain GSPMD pass the "
                "global count as num_experts"
            )
        if self.top_k > Eg:
            raise ValueError(f"top_k={self.top_k} > num_experts={Eg}")
        if self.dispatch not in ("einsum", "scatter", "dropless"):
            raise ValueError(
                f"unknown dispatch {self.dispatch!r} "
                "(einsum | scatter | dropless)")
        if dropless and self.router_type != "topk":
            raise ValueError(
                "dispatch='dropless' is token-choice routing (no "
                "expert_choice)")
        if share and dropless and not (
                0 <= self.first_expert
                and self.first_expert + self.num_experts <= Eg):
            raise ValueError(
                f"experts {self.first_expert}..+{self.num_experts} are no "
                f"range of the {Eg} routed ones")
        family = (self.router_scores != "softmax" or self.router_bias
                  or self.route_scale != 1.0 or self.activation != "silu"
                  or self.shared_intermediate_size or self.n_group > 1)
        if self.shared_gate and not self.shared_intermediate_size:
            raise ValueError("shared_gate gates a shared expert: "
                             "shared_intermediate_size > 0")
        if family and not dropless:
            raise ValueError(
                "sigmoid scores, a router bias, a route scale, relu or "
                "relu2 experts, a shared expert and a group-limited choice "
                "are the dropless path's")
        if self.n_group > 1 and not (
                Eg % self.n_group == 0
                and 1 <= self.topk_group <= self.n_group
                and self.top_k <= self.topk_group * (Eg // self.n_group)):
            raise ValueError(
                f"n_group={self.n_group} must divide the {Eg} experts into "
                f"groups of which topk_group={self.topk_group} hold at "
                f"least top_k={self.top_k}")
        if self.router_scores not in ("softmax", "sigmoid") \
                or self.activation not in ("silu", "relu", "relu2"):
            raise ValueError(
                f"unknown router_scores {self.router_scores!r} (softmax | "
                f"sigmoid) or activation {self.activation!r} (silu | relu | "
                "relu2)")
        if (valid is not None or router_input is not None) and not dropless:
            raise ValueError("row validity and a router input of its own are "
                             "the dropless path's arguments")
        if self.router_type not in ("topk", "expert_choice"):
            raise ValueError(
                f"unknown router_type {self.router_type!r} "
                "(topk | expert_choice)")
        *lead, H = x.shape
        E, I, K = self.num_experts, self.intermediate_size, self.top_k
        xt = x.reshape(-1, H)
        if manual_ep:
            # gather every ep rank's token shard; conjugate psum_scatter
            # below returns this rank's shard of the combined output
            xt = lax.all_gather(xt, EXPERT_AXIS, axis=0, tiled=True)
        N = xt.shape[0]
        # static capacity: ceil(K * N / Eg * factor), at least K, multiple of 4
        cap = max(int(self.capacity_factor * K * N / Eg + 0.999), K)
        cap = min(-(-cap // 4) * 4, N)

        router = self.param(
            "router", nn.with_partitioning(self.kernel_init, (None, None)),
            (H, Eg), self.param_dtype,
        )
        gated = self.activation in GATE_FN
        if not gated:
            # ``up [E, I, H]``, each expert's matrix out-major (as a Linear
            # stores it): the kernel takes it transposed.  Stored ``[E, H,
            # I]`` at I = 1856, not a multiple of the 128 lanes, the device
            # kept H minor and every program copied 638 MB a layer into the
            # kernel's layout — 60% of the device's time (PERF.md, PR 32)
            wi = (jnp.asarray(self.param(
                "up", nn.with_partitioning(
                    lambda key, shape, dtype: jnp.swapaxes(self.kernel_init(
                        key, (shape[0], shape[2], shape[1]), dtype), 1, 2),
                    (EXPERT_AXIS, TENSOR_AXES, None)),
                (E, I, H), self.param_dtype)),)
        elif self.fused_gate_up:
            wi = self.param(
                "gate_up",
                nn.with_partitioning(self.kernel_init, (EXPERT_AXIS, None, None, TENSOR_AXES)),
                (E, H, 2, I), self.param_dtype,
            )
        elif not dropless:
            raise ValueError("fused_gate_up=False is the dropless path's layout")
        else:
            wi = tuple(jnp.asarray(self.param(
                name, nn.with_partitioning(
                    self.kernel_init, (EXPERT_AXIS, None, TENSOR_AXES)),
                (E, H, I), self.param_dtype)) for name in ("gate", "up"))
        wo = self.param(
            "down",
            nn.with_partitioning(self.kernel_init, (EXPERT_AXIS, TENSOR_AXES, None)),
            (E, I, H), self.param_dtype,
        )

        if dropless:
            if gated and self.fused_gate_up:
                wi = (jnp.asarray(wi)[:, :, 0, :], jnp.asarray(wi)[:, :, 1, :])
            bias = None
            if self.router_bias:
                # a SEEDED bias is drawn non-zero, so that the choice
                # differs from the unbiased scores' own, and SMALL: a
                # trained one is what load balancing left, and at 0.05 a
                # seeded one unbalances instead (busiest expert 2.6-4.5 x
                # the mean against 1.3-1.6 x at 0.005; what a share of the
                # experts is handed then swings with the seed: PERF.md,
                # PR 32)
                # It joins the CHOICE only: no gradient reaches it, and the
                # optimizer leaves it out (trainer.NON_TRAINABLE_LEAVES)
                bias = jax.lax.stop_gradient(jnp.asarray(self.param(
                    "router_bias", nn.with_partitioning(
                        nn.initializers.normal(0.005), (None,)),
                    (Eg,), jnp.float32)))
            y, aux = self._dropless(
                xt, None if valid is None else valid.reshape(-1),
                jnp.asarray(router), wi, jnp.asarray(wo), bias,
                **({} if router_input is None
                   else {"scored": router_input.reshape(-1, H)}))
            if self.shared_intermediate_size:
                # the shared expert: every row, the experts' activation at
                # its own width
                from neuronx_distributed_tpu.parallel.layers import (
                    ColumnParallelLinear,
                    RowParallelLinear,
                )

                F = self.shared_intermediate_size
                lin = dict(use_bias=False, dtype=self.dtype,
                           param_dtype=self.param_dtype,
                           kernel_init=per_expert_lecun)
                with jax.named_scope("moe_shared"):
                    xs = xt.astype(self.dtype)
                    up = ColumnParallelLinear(features=F, name="shared_up",
                                              **lin)(xs)
                    h = (GATE_FN[self.activation](ColumnParallelLinear(
                        features=F, name="shared_gate", **lin)(xs)) * up
                         if gated else jnp.square(jax.nn.relu(up)))
                    shared = RowParallelLinear(
                        features=H, name="shared_down", **lin)(h)
                    if self.shared_gate:
                        # float32 from its matmul to the product, rounded
                        # once
                        w_s = jnp.asarray(self.param(
                            "shared_expert_gate", nn.with_partitioning(
                                per_expert_lecun, (None, None)), (H, 1),
                            self.param_dtype))
                        shared = (shared.astype(jnp.float32) * jax.nn.sigmoid(
                            jnp.dot(xs, w_s.astype(self.dtype),
                                    preferred_element_type=jnp.float32))
                                  ).astype(shared.dtype)
                    y = y + shared
            return y.reshape(*lead, H), aux

        # -- routing (fp32), over the GLOBAL expert space ---------------------
        logits = jnp.einsum(
            "nh,he->ne", xt.astype(jnp.float32), router.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)  # [N, Eg]

        def ffn(x_e, wi_e, wo_e):
            gu = jnp.einsum("ch,hfi->cfi", x_e, wi_e.astype(self.dtype),
                            preferred_element_type=self.dtype)
            h = jax.nn.silu(gu[:, 0, :]) * gu[:, 1, :]
            h = shard_activation(h, _auto_spec(None, TENSOR_AXES))
            return jnp.einsum("ci,ih->ch", h, wo_e.astype(self.dtype),
                              preferred_element_type=self.dtype)

        if self.router_type == "expert_choice":
            # experts choose their top-C tokens (Zhou et al. 2022): every
            # expert processes exactly C = cap tokens — perfect balance, no
            # aux pressure (a token chosen by no expert is residual-only;
            # see the router_type docstring for the causal-LM caveat).
            # Gather/scatter dispatch is inherent (``dispatch`` is moot).
            e0 = lax.axis_index(EXPERT_AXIS) * E if manual_ep else 0
            w_all = probs.T.astype(jnp.float32)  # [Eg, N]
            w_loc = lax.dynamic_slice_in_dim(w_all, e0, E, axis=0) \
                if manual_ep else w_all
            g_ec, tok_idx = jax.lax.top_k(w_loc, cap)  # [E, C]
            xe = xt.astype(self.dtype)[tok_idx.reshape(-1)].reshape(E, cap, H)
            xe = shard_activation(xe, _auto_spec(EXPERT_AXIS, None, None))
            ye = jax.vmap(ffn)(xe, jnp.asarray(wi), jnp.asarray(wo))  # [E, C, H]
            ye = shard_activation(ye, _auto_spec(EXPERT_AXIS, None, None))
            contrib = (g_ec.astype(ye.dtype)[..., None] * ye).reshape(E * cap, H)
            y = jax.ops.segment_sum(contrib, tok_idx.reshape(-1), num_segments=N)
            if manual_ep:
                y = lax.psum_scatter(y, EXPERT_AXIS, scatter_dimension=0,
                                     tiled=True)
            y = shard_activation(y, _auto_spec(BATCH_AXES, None))
            return (y.reshape(*lead, H).astype(self.dtype),
                    jnp.zeros((), jnp.float32))

        gate_vals, expert_idx = jax.lax.top_k(probs, K)  # [N, K]
        onehot = jax.nn.one_hot(expert_idx, Eg, dtype=jnp.float32)  # [N, K, Eg]
        expert_mask = jnp.max(onehot, axis=1)  # [N, Eg] (for the aux loss)
        aux = load_balancing_loss(probs, expert_mask)

        # position of each (token, choice) within its expert's buffer:
        # cumulative count over tokens, k-th choices ranked after (k-1)-th
        # (the GShard priority convention)
        flat = onehot.transpose(1, 0, 2).reshape(K * N, Eg)  # k-major
        pos_flat = jnp.cumsum(flat, axis=0) - flat  # [K*N, Eg]
        pos = pos_flat.reshape(K, N, Eg).transpose(1, 0, 2)  # [N, K, Eg]
        pos_in_expert = jnp.sum(pos * onehot, axis=-1)  # [N, K]
        keep = pos_in_expert < cap  # capacity drop
        gate_vals = gate_vals * keep

        if self.norm_topk_prob:
            # normalize kept gates per token (Mixtral convention); fp32
            denom = jnp.maximum(jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
            gate_vals = gate_vals / denom

        # under manual ep this rank computes experts [e0, e0+E) of the
        # global space; elsewhere e0 = 0 and E == Eg
        e0 = lax.axis_index(EXPERT_AXIS) * E if manual_ep else 0

        if self.dispatch == "scatter":
            # flat capacity slot per (token, choice) among THIS rank's
            # experts; dropped or remote tokens target the sentinel row
            # E*cap, which never feeds an expert
            local_idx = expert_idx - e0
            mine = keep & (local_idx >= 0) & (local_idx < E)
            slot = jnp.where(
                mine, local_idx * cap + pos_in_expert.astype(jnp.int32), E * cap
            )  # [N, K] int
            src = jnp.broadcast_to(
                xt.astype(self.dtype)[:, None, :], (N, K, H)).reshape(N * K, H)
            xe_flat = jax.ops.segment_sum(
                src, slot.reshape(-1), num_segments=E * cap + 1
            )  # a slot holds at most one token, so "sum" is a placement
            xe = xe_flat[: E * cap].reshape(E, cap, H).astype(self.dtype)
            xe = shard_activation(xe, _auto_spec(EXPERT_AXIS, None, None))

            ye = jax.vmap(ffn)(xe, jnp.asarray(wi), jnp.asarray(wo))  # [E, C, H]
            ye = shard_activation(ye, _auto_spec(EXPERT_AXIS, None, None))
            ye_flat = jnp.concatenate(
                [ye.reshape(E * cap, H), jnp.zeros((1, H), ye.dtype)])
            y_nk = ye_flat[slot.reshape(-1)].reshape(N, K, H)  # sentinel -> zeros
            y = jnp.einsum(
                "nkh,nk->nh", y_nk, gate_vals.astype(ye.dtype),
                preferred_element_type=self.dtype,
            )
        else:
            # dispatch [N, Eg, C] / combine [N, Eg, C]
            pos_oh = jax.nn.one_hot(
                jnp.where(keep, pos_in_expert, cap).astype(jnp.int32), cap,
                dtype=jnp.float32,
            )  # [N, K, C] (dropped -> all-zero row)
            dispatch = jnp.einsum("nke,nkc->nec", onehot, pos_oh)
            combine = jnp.einsum("nke,nkc,nk->nec", onehot, pos_oh, gate_vals)
            if manual_ep:  # this rank's expert columns only
                dispatch = lax.dynamic_slice_in_dim(dispatch, e0, E, axis=1)
                combine = lax.dynamic_slice_in_dim(combine, e0, E, axis=1)

            xe = jnp.einsum(
                "nh,nec->ech", xt.astype(self.dtype), dispatch.astype(self.dtype),
                preferred_element_type=self.dtype,
            )
            # expert-major layout: experts over ep, tokens replicated within
            xe = shard_activation(xe, _auto_spec(EXPERT_AXIS, None, None))

            ye = jax.vmap(ffn)(xe, jnp.asarray(wi), jnp.asarray(wo))  # [E, C, H]
            ye = shard_activation(ye, _auto_spec(EXPERT_AXIS, None, None))

            y = jnp.einsum(
                "ech,nec->nh", ye, combine.astype(self.dtype),
                preferred_element_type=self.dtype,
            )
        if manual_ep:
            # every rank holds partial sums for ALL tokens (its experts'
            # contributions); the conjugate of the entry all_gather returns
            # each rank its token shard, fully combined
            y = lax.psum_scatter(y, EXPERT_AXIS, scatter_dimension=0, tiled=True)
        y = shard_activation(y, _auto_spec(BATCH_AXES, None))
        return y.reshape(*lead, H).astype(self.dtype), aux.astype(jnp.float32)

    def _dropless(self, xt, valid, router, wi, wo, bias=None, scored=None):
        """``xt [N, H]`` -> ``(y [N, H], aux)`` with no capacity, routed on
        ``scored [N, H]`` where given (else on ``xt`` itself); ``wi`` is
        the pair ``(gate, up)``, each ``[E, H, I]`` (``(up,)`` for relu2
        experts, ``[E, I, H]``).  Sown into ``moe_stats`` (kept only by an apply that
        makes it mutable): ``load [E]``, the valid assignments each expert
        HELD took; ``choice [N, K]``, each row's experts of all ``Eg`` in
        gate order (``Eg`` for an invalid row); and, where a share is held,
        ``assigned``, the valid assignments held or not, under a group
        limit ``reached [2]``, the valid rows and those of them with an
        assignment that is held, and, where the share computes over the rows
        it holds (:func:`held_rows_slab`), ``computed``, the rows the spans
        that ran passed over."""
        N, H = xt.shape
        E, I, K = self.num_experts, self.intermediate_size, self.top_k
        Eg = self.num_experts_global or E
        with jax.named_scope("moe_router"):
            logits = jnp.einsum(
                "nh,he->ne",
                (xt if scored is None else scored).astype(jnp.float32),
                router.astype(jnp.float32))
            if self.router_scores == "sigmoid":
                probs = jax.nn.sigmoid(logits)
            else:
                probs = jax.nn.softmax(logits, axis=-1)
            # chosen by the biased score, weighted by the unbiased
            ranked = probs if bias is None else probs + bias[None, :]
            if self.n_group > 1:
                with jax.named_scope("moe_group_select"):
                    # a group scores its best expert (DeepSeek-V2's
                    # greedy form) or, under a correction bias, the sum of
                    # its two best biased scores (DeepSeek-V3's); the
                    # experts of the groups not kept leave the choice
                    # (scores are >= 0, a bias may not be: -inf, not 0)
                    G = self.n_group
                    by_group = ranked.reshape(N, G, Eg // G)
                    score = (jnp.max(by_group, axis=-1) if bias is None
                             else jnp.sum(jax.lax.top_k(by_group, 2)[0],
                                          axis=-1))
                    _, keep = jax.lax.top_k(score, self.topk_group)  # [N, tg]
                    kept = jnp.any(
                        keep[:, :, None] == jnp.arange(G)[None, None, :],
                        axis=1)                                # [N, G]
                    ranked = jnp.where(jnp.repeat(kept, Eg // G, axis=1),
                                       ranked, -jnp.inf)
            if bias is None and self.n_group == 1:
                gates, choice = jax.lax.top_k(probs, K)        # [N, K]
            else:
                _, choice = jax.lax.top_k(ranked, K)
                gates = jnp.take_along_axis(probs, choice, axis=1)
            if self.norm_topk_prob:
                gates = gates / jnp.maximum(
                    jnp.sum(gates, axis=-1, keepdims=True),
                    1e-9 if self.router_scores == "softmax" else 1e-20)
            if self.route_scale != 1.0:
                gates = gates * self.route_scale
            live = (jnp.ones((N, 1), bool) if valid is None
                    else valid.astype(bool)[:, None])
            # the Switch loss over the live rows (:func:`load_balancing_loss`)
            took = jnp.where(live, jnp.sum(
                jax.nn.one_hot(choice, Eg, dtype=jnp.float32), axis=1), 0.0)
            n_live = jnp.maximum(jnp.sum(live), 1)
            aux = Eg * jnp.sum(jnp.sum(took, 0) / n_live
                               * jnp.sum(jnp.where(live, probs, 0.0), 0)
                               / n_live)
            # an invalid row's assignments go to group E: past every
            # expert's rows once sorted, in no count, under a zero gate
            choice = jnp.where(live, choice, Eg)
            gates = jnp.where(live, gates, 0.0)
            group = choice
            if Eg != E:
                # and so does an assignment to an expert held elsewhere
                held = (choice >= self.first_expert) \
                    & (choice < self.first_expert + E)
                group = jnp.where(held, choice - self.first_expert, E)
                gates = jnp.where(held, gates, 0.0)
        with jax.named_scope("moe_dispatch"):
            flat = group.reshape(-1)                           # token-major
            # stable: within an expert the rows keep their token order, so
            # nothing about a row's place depends on its neighbours' values
            order = jnp.argsort(flat, stable=True)
            load = jnp.sum(flat[:, None] == jnp.arange(E)[None, :], axis=0,
                           dtype=jnp.int32)                    # [E]
        slab = held_rows_slab(N * K, E, Eg)
        if slab:
            # a held share of a long array: over the rows it holds
            y = _walk_held_rows(
                xt.astype(self.dtype), order, gates, load,
                tuple(w.astype(self.dtype) for w in wi),
                wo.astype(self.dtype), slab, self.activation, self.dtype)
            with jax.named_scope("moe_combine"):
                y = shard_activation(y.astype(self.dtype),
                                     _auto_spec(BATCH_AXES, None))
        else:
            with jax.named_scope("moe_dispatch"):
                xs = _dispatch_rows(xt.astype(self.dtype), order)  # [N*K, H]
            with jax.named_scope("moe_experts"):
                if self.activation == "relu2":
                    h = jnp.square(jax.nn.relu(grouped_matmul(
                        xs, wi[0].astype(self.dtype), load, self.dtype,
                        transpose_rhs=True)))
                else:
                    gate, up = (grouped_matmul(xs, w.astype(self.dtype),
                                               load, self.dtype) for w in wi)
                    h = GATE_FN[self.activation](gate) * up
                h = shard_activation(h, _auto_spec(None, TENSOR_AXES))
                ys = grouped_matmul(h, wo.astype(self.dtype), load,
                                    self.dtype)
            with jax.named_scope("moe_combine"):
                in_group = jnp.arange(N * K) < jnp.sum(load)
                ys = jnp.where(in_group[:, None], ys, 0)
                y = _combine_rows(ys, _unsorted(order), order,
                                  gates).astype(self.dtype)
                y = shard_activation(y, _auto_spec(BATCH_AXES, None))
        if not self.is_initializing():  # never part of a parameter tree
            self.sow("moe_stats", "load", load)
            self.sow("moe_stats", "choice", choice)
            if Eg != E:
                self.sow("moe_stats", "assigned",
                         jnp.sum(live, dtype=jnp.int32) * K)
                if self.n_group > 1:
                    # under a group limit a row may reach no held expert:
                    # [the valid rows, those with a held assignment]
                    self.sow("moe_stats", "reached", jnp.stack([
                        jnp.sum(live, dtype=jnp.int32),
                        jnp.sum(jnp.any(group < E, axis=1),
                                dtype=jnp.int32)]))
            if slab:
                # the rows the spans that ran passed over
                self.sow("moe_stats", "computed",
                         held_rows_computed(load, N * K, slab))
        return y, aux.astype(jnp.float32)
