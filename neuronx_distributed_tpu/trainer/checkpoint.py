"""Sharded checkpoint save/load with tag rotation, resume, async save —
multi-host safe.

TPU-native replacement for the reference's three checkpoint generations
(SURVEY §5.4): the per-rank ``dp_rank_xx_tp_rank_xx_pp_rank_xx.pt`` file
layout, xser streaming, staggered IO waves and rendezvous barriers
(``trainer/checkpoint.py:28-284``, ``parallel_layers/checkpointing.py``) all
collapse into one TensorStore-backed (orbax) sharded format: every host
writes exactly its owned shards, restore re-shards to the live mesh, and no
host ever materializes the full state.

Multi-host discipline (reference: rank-0-guarded rotation + ``xm.rendezvous``
around IO, ``trainer/checkpoint.py:39-82,146-162``):

- every *destructive* filesystem op — clearing a stale tag dir, writing
  ``newest``/``meta.json``/``.done``, rotation — runs on **process 0 only**;
- a ``sync_global_devices`` barrier separates process-0 directory prep from
  the all-host shard writes, and the all-host writes from process-0
  finalization, so no host can read a half-written tag and no two hosts race
  a ``rmtree`` (the round-1/2 flaw: every process rotated and wrote
  ``newest``);
- the tensor payloads themselves go through ``ocp.AsyncCheckpointer``
  (StandardCheckpointHandler — the supported API; the deprecated
  ``PyTreeCheckpointer`` emitted restore warnings), which coordinates its own
  per-host shard commit.

Async save: ``save_checkpoint(..., async_save=True)`` returns immediately
after dispatching device→host copies; finalization (``.done`` marker,
``newest`` pointer, rotation) happens in ``wait_for_checkpoint()`` — called
automatically at the start of the next save, mirroring orbax's own
wait-before-next-save contract.

Kept reference semantics: tagged checkpoint directories, a ``newest`` pointer
file, ``num_kept_ckpts`` rotation, and separate model / optimizer /
scheduler / user_content payloads (``:175-199``).

Crash consistency (resilience PR): the visibility markers — ``meta.json``,
``.done``, ``newest``, written in that order after the shard payloads are
durable — go through :func:`_atomic_write` (tmp + ``fsync`` +
``os.replace``), so a hard kill at ANY point mid-save leaves
:func:`newest_tag` resolving to a complete checkpoint (the in-flight tag
never becomes visible; the next save of the same tag clears the debris).
The ``ckpt/*`` fault points interleaved below let subprocess tests kill the
process at each such point and prove it
(``tests/test_resilience.py::test_checkpoint_kill_point_matrix``).
"""

from __future__ import annotations

import glob
import json
import os
import shutil
from typing import Any, Callable, List, Optional, Tuple

import jax
from jax.experimental import multihost_utils
from jax.sharding import NamedSharding

from neuronx_distributed_tpu.obs import startup
from neuronx_distributed_tpu.resilience.faults import fault_point
from neuronx_distributed_tpu.utils.checkpoint_library import checkpoint_library
from neuronx_distributed_tpu.utils.distributed import is_primary as _is_primary
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

_NEWEST = "newest"
_DONE = ".done"


def _barrier(name: str) -> None:
    if jax.process_count() > 1:
        multihost_utils.sync_global_devices(name)


def _atomic_write(path: str, text: str) -> None:
    """Crash-consistent marker write: tmp file + ``fsync`` + ``os.replace``.
    The visibility markers (``meta.json``, ``.done``, ``newest``) are what
    :func:`newest_tag`/:func:`load_checkpoint` trust — a kill mid-``write``
    must leave either the old content or the new, never a truncated file.
    Stale tmps from previous killed saves (dead PIDs — only process 0 writes
    markers) are reaped here so crash-restart cycles can't accumulate
    orphans."""
    for stale in glob.glob(f"{path}.tmp.*"):
        try:
            os.unlink(stale)
        except OSError:
            pass
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class _PendingSave:
    """Finalization state of an in-flight async save (``checkpointers``:
    its ``ocp.AsyncCheckpointer``s)."""

    def __init__(self, checkpointers: List[Any], finalize: Callable[[], None]):
        self._checkpointers = checkpointers
        self._finalize = finalize
        self.done = False

    def wait(self) -> None:
        if self.done:
            return
        try:
            for c in self._checkpointers:
                c.wait_until_finished()
            self._finalize()
        finally:
            for c in self._checkpointers:
                c.close()  # reap the per-save background threads
            self.done = True


_PENDING: Optional[_PendingSave] = None


def wait_for_checkpoint() -> None:
    """Block until the last async ``save_checkpoint`` fully committed
    (shards durable, ``.done``/``newest`` written, rotation performed)."""
    global _PENDING
    if _PENDING is not None:
        _PENDING.wait()
        _PENDING = None


def _tag_dir(ckpt_dir: str, tag: str) -> str:
    return os.path.join(ckpt_dir, tag)


def _list_tags(ckpt_dir: str):
    if not os.path.isdir(ckpt_dir):
        return []
    tags = [
        d
        for d in sorted(os.listdir(ckpt_dir))
        if os.path.isdir(_tag_dir(ckpt_dir, d))
        and os.path.exists(os.path.join(_tag_dir(ckpt_dir, d), _DONE))
    ]
    tags.sort(key=lambda d: os.path.getmtime(os.path.join(_tag_dir(ckpt_dir, d), _DONE)))
    return tags


def save_checkpoint(
    ckpt_dir: str,
    tag: str,
    model_state: Any,
    optimizer_state: Any = None,
    scheduler_state: Any = None,
    user_content: Any = None,
    num_kept_ckpts: Optional[int] = None,
    async_save: bool = False,
    save_dtype: Any = None,
) -> str:
    """Save a tagged checkpoint (reference ``save_checkpoint``,
    ``trainer/checkpoint.py:85-199``).  With ``async_save`` the call returns
    after device arrays are snapshotted; durability is guaranteed only after
    :func:`wait_for_checkpoint` (implicitly invoked by the next save).

    ``save_dtype`` (e.g. ``jnp.bfloat16``) downcasts the MODEL state's
    floating leaves on the way to disk — half-size checkpoints, the
    reference's ``down_cast_bf16`` option
    (``parallel_layers/checkpointing.py:55,92``).  The optimizer state
    (fp32 masters/moments) is never downcast — that would defeat mixed-
    precision training; :func:`load_checkpoint` restores leaves at the
    template's dtype, so an fp32 template upcasts the stored bf16 values
    (precision truncated once at save, as with the reference)."""
    wait_for_checkpoint()  # at most one in-flight async save
    ocp = checkpoint_library()

    if save_dtype is not None:
        from neuronx_distributed_tpu.utils.dtypes import cast_floating

        model_state = cast_floating(model_state, save_dtype)

    path = _tag_dir(ckpt_dir, tag)
    if _is_primary():
        if os.path.exists(path):
            shutil.rmtree(path)
        os.makedirs(path, exist_ok=True)
    _barrier(f"ckpt_prep:{tag}")
    fault_point("ckpt/pre_shard_write", tag=tag)

    checkpointers: List[ocp.AsyncCheckpointer] = []
    payloads = [("model", model_state)]
    if optimizer_state is not None:
        payloads.append(("optimizer", optimizer_state))
    try:
        for name, state in payloads:
            c = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
            checkpointers.append(c)
            c.save(os.path.join(path, name), args=ocp.args.StandardSave(state))
            fault_point("ckpt/mid_shard_write", tag=tag, payload=name)
    except Exception:
        # never orphan an in-flight background write: a later save of the
        # same tag would rmtree the directory under its TensorStore streams
        for c in checkpointers:
            try:
                c.wait_until_finished()
            finally:
                c.close()
        raise

    def finalize() -> None:
        # all hosts reach here with their shards durable (wait_until_finished
        # ran); only process 0 commits the visibility markers and rotates
        _barrier(f"ckpt_written:{tag}")
        if _is_primary():
            meta = {"tag": tag}
            if scheduler_state is not None:
                meta["scheduler"] = scheduler_state
            if user_content is not None:
                meta["user_content"] = user_content
            fault_point("ckpt/pre_meta", tag=tag)
            _atomic_write(os.path.join(path, "meta.json"), json.dumps(meta))
            fault_point("ckpt/pre_done", tag=tag)
            _atomic_write(os.path.join(path, _DONE), "ok")
            fault_point("ckpt/pre_newest", tag=tag)
            _atomic_write(os.path.join(ckpt_dir, _NEWEST), tag)
            if num_kept_ckpts is not None and num_kept_ckpts > 0:
                for old in _list_tags(ckpt_dir)[:-num_kept_ckpts]:
                    logger.info("rotating out checkpoint %s", old)
                    shutil.rmtree(_tag_dir(ckpt_dir, old), ignore_errors=True)
                    fault_point("ckpt/mid_rotation", tag=tag, rotated=old)
        _barrier(f"ckpt_done:{tag}")
        logger.info("saved checkpoint %s", path)

    global _PENDING
    _PENDING = _PendingSave(checkpointers, finalize)
    if not async_save:
        wait_for_checkpoint()
    return path


def newest_tag(ckpt_dir: str) -> Optional[str]:
    """Resolve the ``newest`` pointer (reference ``:146-162``)."""
    p = os.path.join(ckpt_dir, _NEWEST)
    if os.path.exists(p):
        with open(p) as f:
            tag = f.read().strip()
        if os.path.exists(os.path.join(_tag_dir(ckpt_dir, tag), _DONE)):
            return tag
    tags = _list_tags(ckpt_dir)
    return tags[-1] if tags else None


def _abstract_like(template: Any):
    """Template tree → abstract arrays carrying the live-mesh shardings, the
    StandardRestore form that re-shards on read without a donated template."""

    def one(x):
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, NamedSharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree.map(one, template)


@startup.phased("weights")
def load_checkpoint(
    ckpt_dir: str,
    tag: Optional[str] = None,
    model_template: Any = None,
    optimizer_template: Any = None,
) -> Tuple[Any, Any, Any, Any]:
    """Restore ``(model_state, optimizer_state, scheduler_state,
    user_content)`` re-sharded to the live mesh via the templates' shardings
    (reference ``load_checkpoint`` + auto tag, ``trainer/checkpoint.py:203-284``)."""
    wait_for_checkpoint()
    tag = tag or newest_tag(ckpt_dir)
    if tag is None:
        raise FileNotFoundError(f"no completed checkpoints under {ckpt_dir}")
    path = _tag_dir(ckpt_dir, tag)
    ocp = checkpoint_library()
    ckptr = ocp.Checkpointer(ocp.StandardCheckpointHandler())

    model_state = None
    if model_template is not None:
        model_state = ckptr.restore(
            os.path.join(path, "model"),
            args=ocp.args.StandardRestore(_abstract_like(model_template)),
        )
    optimizer_state = None
    if optimizer_template is not None and os.path.isdir(os.path.join(path, "optimizer")):
        optimizer_state = ckptr.restore(
            os.path.join(path, "optimizer"),
            args=ocp.args.StandardRestore(_abstract_like(optimizer_template)),
        )
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    logger.info("loaded checkpoint %s", path)
    return model_state, optimizer_state, meta.get("scheduler"), meta.get("user_content")
