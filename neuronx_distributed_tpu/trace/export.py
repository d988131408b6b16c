"""Serialize / load traced inference models.

TPU-native replacement for the reference's ``parallel_model_save`` /
``parallel_model_load`` (``trace/trace.py:189-200``), which ``torch.jit``-save
one compiled shard per TP rank.  Here the context and decode phase programs
are serialized with ``jax.export`` (portable StableHLO carrying the mesh
shardings), parameters with the orbax-backed checkpointer, and the serving
shapes as JSON — one artifact directory instead of per-rank files.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax import export as jax_export

from neuronx_distributed_tpu.obs import startup
from neuronx_distributed_tpu.trace.engine import (
    InferenceConfig,
    ParallelInferenceModel,
    _ServingBase,
)
from neuronx_distributed_tpu.utils.checkpoint_library import checkpoint_library
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

_CONTEXT = "context.stablehlo"
_DECODE = "decode.stablehlo"
_PARAMS = "params"
_META = "meta.json"


def parallel_model_save(path: str, model: ParallelInferenceModel) -> str:
    """Save a traced :class:`ParallelInferenceModel` (reference
    ``parallel_model_save``, ``trace/trace.py:189-192``)."""
    os.makedirs(path, exist_ok=True)
    (params_spec, ids_spec, vctx_spec, tok_spec, off_spec, cache_spec,
     valid_spec) = model._arg_specs

    # export from the model's own jitted phase fns (shares their trace cache)
    ctx_exp = jax_export.export(model._context_jit)(params_spec, ids_spec, vctx_spec)
    dec_exp = jax_export.export(model._decode_jit)(
        params_spec, tok_spec, off_spec, cache_spec, valid_spec
    )
    with open(os.path.join(path, _CONTEXT), "wb") as f:
        f.write(ctx_exp.serialize())
    with open(os.path.join(path, _DECODE), "wb") as f:
        f.write(dec_exp.serialize())

    ocp = checkpoint_library()
    ocp.Checkpointer(ocp.StandardCheckpointHandler()).save(
        os.path.join(path, _PARAMS), args=ocp.args.StandardSave(model.params),
        force=True,
    )
    with open(os.path.join(path, _META), "w") as f:
        json.dump(
            {
                **{
                    k: v
                    for k, v in dataclasses.asdict(model.config).items()
                    if k != "kv_cache_dtype"
                },
                "kv_cache_dtype": jnp.dtype(model.config.kv_cache_dtype).name,
            },
            f,
        )
    logger.info("saved traced model to %s", path)
    return path


class LoadedInferenceModel(_ServingBase):
    """Serving wrapper over deserialized phase programs; same ``generate`` /
    ``benchmark`` surface as :class:`ParallelInferenceModel`."""

    def __init__(self, context_exp, decode_exp, params: Any, config: InferenceConfig):
        self.config = config
        self.params = params
        # jit the exported calls so results stay on device between steps;
        # donation of the caches is re-applied at this layer.
        self.context = jax.jit(context_exp.call)
        self.decode = jax.jit(decode_exp.call, donate_argnums=(3,))
        self._decode_exp = decode_exp

    def _decode_step_traceable(self, params, tok, offset, caches, valid):
        # exported programs are traceable, so the fused scan loop composes
        return self._decode_exp.call(params, tok, offset, caches, valid)


@startup.phased("weights")
def parallel_model_load(path: str) -> LoadedInferenceModel:
    """Load a traced model saved by :func:`parallel_model_save` (reference
    ``parallel_model_load``, ``trace/trace.py:195-200``)."""
    with open(os.path.join(path, _CONTEXT), "rb") as f:
        ctx_exp = jax_export.deserialize(f.read())
    with open(os.path.join(path, _DECODE), "rb") as f:
        dec_exp = jax_export.deserialize(f.read())
    ocp = checkpoint_library()
    params = ocp.Checkpointer(ocp.StandardCheckpointHandler()).restore(
        os.path.join(path, _PARAMS)
    )
    with open(os.path.join(path, _META)) as f:
        meta = json.load(f)
    config = InferenceConfig(
        batch_size=meta["batch_size"],
        context_len=meta["context_len"],
        max_total_len=meta["max_total_len"],
        kv_cache_dtype=jnp.dtype(meta["kv_cache_dtype"]),
    )
    logger.info("loaded traced model from %s", path)
    return LoadedInferenceModel(ctx_exp, dec_exp, params, config)
