"""The program's own account of this process's start-up, for the readers
``benchmarks/layer_metrics/startup_*.py``.

A run is one process (``run.py``), and ``neuronx_distributed_tpu.obs.startup``
keeps ONE account a process: where the seconds from the process's start to
``ready`` (a serve cell: ``declare_warmup_done``; a train cell: the first
step's loss on the host) went, by phase, and inside them JAX's compile path
by stage.  The readers take it from here and not from ``Reading.counters``:
a train cell's runner hands the readers no registry, and the account is the
same object in every cell.

``None`` where the program has no such module (a commit older than it), or
has not declared ``ready``: a reader then reports nothing.
"""

from __future__ import annotations

from typing import Optional

READY = "startup/ready_s"
PHASE_MS = "startup/ms_total/"
STAGE_MS = "startup/compile_ms_total/"


def snapshot() -> Optional[dict]:
    """The account under the program's registry names (``startup/ready_s``
    in s, ``startup/ms_total/<phase>`` and ``startup/compile_ms_total/<stage>``
    in ms, the cache's counts), with ``label`` and ``programs``."""
    try:
        from neuronx_distributed_tpu.obs import startup
    except ImportError:
        return None
    snap = startup.account().snapshot()
    return snap if READY in snap else None


def by(snap: dict, head: str) -> dict:
    """``{phase or stage: seconds}`` of one family of the snapshot."""
    return {k[len(head):]: v / 1e3 for k, v in snap.items()
            if k.startswith(head)}
