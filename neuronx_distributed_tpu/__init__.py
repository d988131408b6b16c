"""neuronx_distributed_tpu — a TPU-native (JAX/XLA/pjit/pallas) distributed
training & inference framework with the capability surface of
``neuronx-distributed`` (AWS's Megatron-style model-parallelism library),
re-designed around ``jax.sharding.Mesh`` / GSPMD rather than ported.

Public API mirrors the reference's top-level exports
(``src/neuronx_distributed/__init__.py:1-7``).
"""

import time as _time

# the top of the start-up account's ``import`` phase (obs.startup): before
# anything heavy — jax and flax come in below unless the caller had them
_IMPORT_T0 = _time.perf_counter()

from neuronx_distributed_tpu.version import __version__  # noqa: E402
from neuronx_distributed_tpu.config import (
    ActivationCheckpointConfig,
    OptimizerConfig,
    PipelineConfig,
    TrainingConfig,
    training_config,
)
from neuronx_distributed_tpu.parallel.mesh import (
    MeshConfig,
    destroy_model_parallel,
    get_data_parallel_size,
    get_mesh,
    get_pipeline_parallel_size,
    get_tensor_parallel_size,
    initialize_model_parallel,
    model_parallel_is_initialized,
)
from neuronx_distributed_tpu.obs import startup as _startup

_startup.account().imported(_IMPORT_T0, _time.perf_counter())

__all__ = [
    "__version__",
    "ActivationCheckpointConfig",
    "OptimizerConfig",
    "PipelineConfig",
    "TrainingConfig",
    "training_config",
    "MeshConfig",
    "initialize_model_parallel",
    "destroy_model_parallel",
    "model_parallel_is_initialized",
    "get_mesh",
    "get_tensor_parallel_size",
    "get_pipeline_parallel_size",
    "get_data_parallel_size",
]
