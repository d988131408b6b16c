"""Multi-host coordination.

The reference drives four host channels — c10d xla groups, gloo groups,
TCPStore, and `xm.rendezvous` barriers (SURVEY §5.8) — because its runtime
is multi-process-per-host with dynamic shapes.  Under single-controller JAX
the device-side channels are GSPMD collectives; what remains host-side is
job bring-up (the coordination service) and occasional barriers/broadcasts,
wrapped here:

- :func:`initialize_distributed` ↔ torchrun env-based
  ``init_process_group`` (coordinator address/rank from env or args);
- :func:`rendezvous` ↔ ``xm.rendezvous`` (``checkpointing.py:96,129``);
- :func:`broadcast_from_host0` ↔ gloo object broadcast
  (``pipeline/comm.py:88-103``).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax

from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

_INITIALIZED = False


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[list] = None,
) -> None:
    """Bring up `jax.distributed` for multi-host meshes.  Arguments default
    from the standard env (JAX_COORDINATOR_ADDRESS etc. or the TPU pod
    metadata); a single-process job is a no-op, so library code can call
    this unconditionally."""
    global _INITIALIZED
    if _INITIALIZED:
        return
    coordinator_address = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None:
        env = os.environ.get("JAX_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("JAX_PROCESS_ID")
        process_id = int(env) if env else None
    if coordinator_address is None and num_processes in (None, 1):
        # no-op, but do NOT latch: a later call with explicit coordinator
        # args must still be able to bring the job up
        logger.info("single-process run; skipping jax.distributed.initialize")
        return
    # here and not at the top: obs reaches this package for its logger
    from neuronx_distributed_tpu.obs import startup

    with startup.account().phase("backend"):
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
        )
    _INITIALIZED = True
    logger.info(
        "jax.distributed up: process %d/%d", jax.process_index(), jax.process_count()
    )


def rendezvous(tag: str) -> None:
    """Global host barrier (the ``xm.rendezvous`` analogue; reference brackets
    checkpoint IO with these, ``parallel_layers/checkpointing.py:96,121,129``)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(tag)


def broadcast_from_host0(tree: Any) -> Any:
    """Broadcast a host-side pytree of arrays from process 0 to all
    (the gloo object-channel analogue)."""
    if jax.process_count() == 1:
        return tree
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(tree)


def is_primary() -> bool:
    """True on the process that should do singleton IO (rank-0 pattern)."""
    return jax.process_index() == 0
