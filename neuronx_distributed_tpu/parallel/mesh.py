"""Parallel-state: the single global source of truth for the device mesh.

TPU-native replacement for the reference's process-group bookkeeping
(``neuronx-distributed/src/neuronx_distributed/parallel_layers/parallel_state.py:41-163``).
Where the reference builds c10d process groups with attached SPMD replica-group
lists (DP groups with stride tp, contiguous TP groups, strided PP groups), we
build one :class:`jax.sharding.Mesh` whose named axes *are* the replica groups:

======  =====================================================================
axis    meaning
======  =====================================================================
``dp``  data parallelism (gradient psum / ZeRO-1 state sharding)
``ep``  expert parallelism — a sub-axis of data parallelism along which MoE
        experts are sharded; dense models keep it at size 1
``pp``  pipeline parallelism (stage-sharded weights, ppermute transfers)
``cp``  context parallelism (ring-attention KV rotation; long-context)
``kvr`` KV-replication sub-axis of tensor parallelism — the mesh-native form
        of the reference's dedicated KV process groups
        (``modules/qkv_linear.py:26-62``): KV projections are *replicated*
        along ``kvr`` and sharded along ``tp``, so the KV gradient psum over
        the reference's KV-shared group becomes a GSPMD-inserted psum over
        ``kvr``
``tp``  tensor parallelism proper
======  =====================================================================

Megatron-style tensor parallel sharding always uses the *combined*
``TENSOR_AXES = ('kvr', 'tp')`` tuple so that when ``kv_size_multiplier == 1``
(the common case, axis size 1) nothing changes, and when it is > 1 the Q/gate
projections still shard over the full TP degree while KV shards only over
``tp``.  Axis order puts ``tp`` innermost so TP collectives ride the
fastest-varying (ICI-adjacent) devices, mirroring the reference's contiguous
TP groups (``parallel_state.py:109-122``).
"""

from __future__ import annotations

import dataclasses
import math
from contextlib import contextmanager
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from neuronx_distributed_tpu.obs import startup
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# Canonical axis names, outermost (slowest-varying / DCN-friendly) first.
DATA_AXIS = "dp"
EXPERT_AXIS = "ep"
PIPELINE_AXIS = "pp"
CONTEXT_AXIS = "cp"
KV_REPLICA_AXIS = "kvr"
TENSOR_AXIS = "tp"

MESH_AXES = (DATA_AXIS, EXPERT_AXIS, PIPELINE_AXIS, CONTEXT_AXIS, KV_REPLICA_AXIS, TENSOR_AXIS)

# Combined axis tuples used by layers/specs.
TENSOR_AXES = (KV_REPLICA_AXIS, TENSOR_AXIS)  # full TP degree = kvr * tp
BATCH_AXES = (DATA_AXIS, EXPERT_AXIS)  # full data-parallel degree = dp * ep
# Sequence-parallel regions shard the sequence axis over the full TP degree
# (the reference's Megatron-SP, mappings.py:198-250); with context parallelism
# the sequence is additionally sharded over cp.
SEQUENCE_AXES = (CONTEXT_AXIS, KV_REPLICA_AXIS, TENSOR_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes of every parallel dimension.

    ``data_parallel_size`` may be left as ``None`` to infer it from the device
    count, mirroring the reference's behaviour where DP size is always
    ``world // (tp * pp)`` (``parallel_state.py:74-88``).
    """

    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    context_parallel_size: int = 1
    expert_parallel_size: int = 1
    kv_size_multiplier: int = 1
    data_parallel_size: Optional[int] = None

    def __post_init__(self):
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is not None and v < 1:
                raise ValueError(f"{f.name} must be >= 1, got {v}")
        if self.tensor_parallel_size % self.kv_size_multiplier != 0:
            raise ValueError(
                f"tensor_parallel_size ({self.tensor_parallel_size}) must be divisible by "
                f"kv_size_multiplier ({self.kv_size_multiplier})"
            )

    @property
    def model_parallel_size(self) -> int:
        return (
            self.tensor_parallel_size
            * self.pipeline_parallel_size
            * self.context_parallel_size
            * self.expert_parallel_size
        )


class _MeshState:
    """Module-level singleton holding the live mesh, like the reference's
    module globals (``parallel_state.py:22-38``)."""

    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.config: Optional[MeshConfig] = None

    def clear(self):
        self.mesh = None
        self.config = None


_STATE = _MeshState()


def _build_device_array(devices: Sequence[jax.Device], shape: Sequence[int]) -> np.ndarray:
    """Arrange devices into the mesh shape.

    On real TPU slices, delegate to ``mesh_utils`` so the mesh respects
    physical topology: a single slice uses ``create_device_mesh`` (ICI-aware
    axis assignment), and a MULTI-slice job uses ``create_hybrid_device_mesh``
    with the data-parallel axis split across slices — so only dp traffic
    (gradient psum, once per step) rides the slow DCN links while tp/cp/pp
    collectives stay on intra-slice ICI.  This is the mesh-layout form of the
    reference's "EFA across nodes, NeuronLink within" topology
    (``run_llama_70b_tp_pp.sh:7-15``); here the transport choice falls out of
    device order instead of env flags.  For CPU/virtual devices a plain
    reshape preserves rank-contiguity (TP innermost), matching the
    reference's contiguous-TP / strided-DP group construction.
    """
    devices = list(devices)
    if math.prod(shape) != len(devices):
        raise ValueError(f"mesh shape {tuple(shape)} does not match device count {len(devices)}")
    if devices and devices[0].platform == "tpu" and len(devices) > 1:
        # No fallback to a plain reshape here: a mesh that ignores the
        # physical topology still runs, only with its collectives on the
        # wrong links, and nothing downstream would notice.  What
        # mesh_utils cannot lay out, it raises.
        from jax.experimental import mesh_utils

        n_slices = len({getattr(d, "slice_index", 0) for d in devices})
        if n_slices > 1 and shape[0] % n_slices == 0:
            dcn_shape = (n_slices,) + (1,) * (len(shape) - 1)
            local_shape = (shape[0] // n_slices, *shape[1:])
            return mesh_utils.create_hybrid_device_mesh(
                local_shape, dcn_shape, devices=devices
            )
        if n_slices > 1:
            # dp cannot absorb the slice boundary (e.g. dp=1, pp across
            # slices — the reference's 70B topology): a legitimate
            # layout, just with model-parallel traffic on DCN
            logger.warning(
                "dp=%d not divisible by %d slices; letting "
                "create_device_mesh choose the layout (some model-"
                "parallel collectives will cross DCN)", shape[0], n_slices,
            )
        return mesh_utils.create_device_mesh(tuple(shape), devices=devices)
    return np.asarray(devices).reshape(tuple(shape))


@startup.phased("mesh")
def initialize_model_parallel(
    tensor_parallel_size: int = 1,
    pipeline_parallel_size: int = 1,
    context_parallel_size: int = 1,
    expert_parallel_size: int = 1,
    kv_size_multiplier: int = 1,
    data_parallel_size: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build and install the global mesh.

    TPU-native analogue of ``parallel_state.initialize_model_parallel``
    (``parallel_state.py:41-163``): instead of constructing DP/TP/PP process
    groups with replica-group lists, one named mesh encodes the full topology
    and XLA derives every collective's replica groups from axis names.
    """
    if _STATE.mesh is not None:
        raise RuntimeError("model parallel is already initialized; call destroy_model_parallel() first")

    # RNG discipline (the framework's stance on the reference's TP-aware
    # RNG tracker, ``parallel_layers/random.py:100-127``): partitionable
    # threefry makes every jax.random draw sharding-invariant AND cheap
    # under GSPMD — each shard generates only its slice of the global
    # stream, yet the values equal the single-device run.  The reference
    # forks per-TP-rank seeds so each rank drops its own shard elements
    # independently; here the one-key global-array semantics gives each
    # shard its own mask slice for free, with no rank-seed bookkeeping.
    # Pinned centrally so dropout/noise is reproducible across tp/dp/mesh
    # choices (tests/test_rng_dropout.py).
    jax.config.update("jax_threefry_partitionable", True)

    if devices is None:
        # the program itself brings the client up (a caller that hands the
        # devices in has done it, on its own time)
        with startup.account().phase("backend"):
            devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    cfg = MeshConfig(
        tensor_parallel_size=tensor_parallel_size,
        pipeline_parallel_size=pipeline_parallel_size,
        context_parallel_size=context_parallel_size,
        expert_parallel_size=expert_parallel_size,
        kv_size_multiplier=kv_size_multiplier,
        data_parallel_size=data_parallel_size,
    )
    mp = cfg.model_parallel_size
    if n % mp != 0:
        raise ValueError(f"device count {n} not divisible by model parallel size {mp}")
    dp = n // mp
    # ``data_parallel_size`` means the FULL data-parallel degree (dp * ep),
    # matching what get_data_parallel_size() reports.
    if data_parallel_size is not None and data_parallel_size != dp * expert_parallel_size:
        raise ValueError(
            f"explicit data_parallel_size {data_parallel_size} inconsistent with "
            f"device count {n}: expected {dp * expert_parallel_size} "
            f"(= {n} / (tp*pp*cp) with ep={expert_parallel_size})"
        )
    cfg = dataclasses.replace(cfg, data_parallel_size=dp * expert_parallel_size)

    shape = (
        dp,
        expert_parallel_size,
        pipeline_parallel_size,
        context_parallel_size,
        kv_size_multiplier,
        tensor_parallel_size // kv_size_multiplier,
    )
    mesh = Mesh(_build_device_array(devices, shape), MESH_AXES)
    _STATE.mesh = mesh
    _STATE.config = cfg
    logger.info(
        "initialized mesh: dp=%d ep=%d pp=%d cp=%d kvr=%d tp=%d over %d devices",
        *shape,
        n,
    )
    return mesh


def destroy_model_parallel() -> None:
    """Tear down the global mesh (reference: ``parallel_state.py:destroy_model_parallel``)."""
    _STATE.clear()


def model_parallel_is_initialized() -> bool:
    return _STATE.mesh is not None


def get_mesh() -> Mesh:
    if _STATE.mesh is None:
        raise RuntimeError("model parallel is not initialized; call initialize_model_parallel() first")
    return _STATE.mesh


def get_mesh_config() -> MeshConfig:
    if _STATE.config is None:
        raise RuntimeError("model parallel is not initialized")
    return _STATE.config


def config_from_mesh(mesh: Mesh) -> MeshConfig:
    """Derive a MeshConfig from a mesh's axis sizes."""
    return MeshConfig(
        tensor_parallel_size=mesh.shape[KV_REPLICA_AXIS] * mesh.shape[TENSOR_AXIS],
        pipeline_parallel_size=mesh.shape[PIPELINE_AXIS],
        context_parallel_size=mesh.shape[CONTEXT_AXIS],
        expert_parallel_size=mesh.shape[EXPERT_AXIS],
        kv_size_multiplier=mesh.shape[KV_REPLICA_AXIS],
        data_parallel_size=mesh.shape[DATA_AXIS] * mesh.shape[EXPERT_AXIS],
    )


@contextmanager
def mesh_context(mesh: Mesh, config: Optional[MeshConfig] = None):
    """Temporarily install ``mesh`` as the global mesh (used by tests and the
    inference tracer, which the reference handles with set/unset override
    hooks, ``parallel_state.py:193-210``)."""
    prev_mesh, prev_cfg = _STATE.mesh, _STATE.config
    _STATE.mesh = mesh
    _STATE.config = config if config is not None else config_from_mesh(mesh)
    try:
        yield mesh
    finally:
        _STATE.mesh, _STATE.config = prev_mesh, prev_cfg


# ---------------------------------------------------------------------------
# Size / rank helpers (reference: get_*_parallel_{size,rank}).
# Sizes are host-side ints from the mesh; ranks only exist inside shard_map,
# via jax.lax.axis_index.
# ---------------------------------------------------------------------------


def _axis_size(mesh: Optional[Mesh], *axes: str) -> int:
    mesh = mesh if mesh is not None else get_mesh()
    return int(math.prod(mesh.shape[a] for a in axes))


def manual_axis_size(axis_name: str) -> int:
    """Trace-time size of a manual (shard_map) axis."""
    return jax.lax.axis_size(axis_name)


def get_tensor_parallel_size(mesh: Optional[Mesh] = None) -> int:
    """Full TP degree, kvr * tp (reference: ``get_tensor_model_parallel_size``)."""
    return _axis_size(mesh, *TENSOR_AXES)


def get_pipeline_parallel_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, PIPELINE_AXIS)


def get_data_parallel_size(mesh: Optional[Mesh] = None) -> int:
    """Full data-parallel degree, dp * ep."""
    return _axis_size(mesh, *BATCH_AXES)


def get_context_parallel_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, CONTEXT_AXIS)


def get_expert_parallel_size(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, EXPERT_AXIS)


def get_kv_size_multiplier(mesh: Optional[Mesh] = None) -> int:
    return _axis_size(mesh, KV_REPLICA_AXIS)


def tensor_parallel_rank() -> jax.Array:
    """Traced TP rank; valid only inside shard_map over the global mesh."""
    kvr = jax.lax.axis_index(KV_REPLICA_AXIS)
    tp = jax.lax.axis_index(TENSOR_AXIS)
    return kvr * manual_axis_size(TENSOR_AXIS) + tp


def named_sharding(*spec) -> NamedSharding:
    """Shorthand: NamedSharding over the global mesh."""
    return NamedSharding(get_mesh(), P(*spec))


def strip_axes_from_spec(spec: P, drop: frozenset) -> P:
    """Remove the given mesh axes from a PartitionSpec (tuple entries keep
    their remaining axes; emptied entries become None)."""

    def strip(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a not in drop)
            return kept or None
        return None if e in drop else e

    return P(*(strip(e) for e in spec))


_AXIS_ENV_WARNED = False


def ambient_manual_axes() -> frozenset:
    """Mesh axes already *manual* in the enclosing trace context.

    Inside a ``shard_map`` body the manual axes are bound in JAX's axis
    environment (that's what makes ``lax.psum(x, 'dp')`` legal there), so the
    environment reveals which axes an enclosing shard_map — e.g. the 1F1B
    engine's manual ``(dp, ep, pp)`` — already owns.  Two consumers need
    this: a nested shard_map must go manual over exactly the *rest* (Mosaic
    kernels refuse Auto axes; re-declaring an already-manual axis is an
    error — ring/flash attention), and GSPMD sharding constraints inside the
    body may only reference the remaining *auto* axes (MoE expert specs).
    """
    try:
        from jax._src.core import get_axis_env

        return frozenset(get_axis_env().axis_sizes) & frozenset(MESH_AXES)
    except Exception as e:  # pragma: no cover - internals moved in a JAX bump
        # Loud, not fatal: top-level callers still work with the empty set,
        # but nested use (inside the 1F1B engine) would re-declare or
        # re-constrain the outer manual axes and fail — log the real cause.
        global _AXIS_ENV_WARNED
        if not _AXIS_ENV_WARNED:
            _AXIS_ENV_WARNED = True
            logger.warning(
                "jax._src.core.get_axis_env unavailable (%s): cannot detect "
                "enclosing shard_map manual axes; flash/ring attention or MoE "
                "inside the pipeline engine may fail to trace on this JAX "
                "version", e,
            )
        return frozenset()


def rmsg(msg: str) -> str:
    """Rank-annotated log message (reference: ``parallel_state.py:394-406``).

    Under SPMD-jit there is no per-device python rank, so we annotate with the
    host process index instead.
    """
    return f"[proc_{jax.process_index()}] {msg}"
