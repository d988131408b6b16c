"""``orbax.checkpoint``, loaded when a run reads, writes or exports a
checkpoint — never by importing the package.

The library's import is 12-28 s on a chip machine's host (its
``orbax.checkpoint.logging`` brings ``google.cloud.logging``, ``grpc`` and
``aiohttp`` along: PERF.md §5 "Where set-up goes"), and a server or trainer
that names no checkpoint never calls it.  :func:`checkpoint_library` is the
one place that imports it: ``trainer/checkpoint.py`` and ``trace/export.py``
call it where they use the library, and ``fit(ckpt_dir=...)`` before its
first step, so the seconds are paid at set-up and not inside step
``ckpt_every``.  Until ``ready`` the load is the start-up account's
``import`` phase (``obs.startup``), whatever phase it happens in.
"""

from neuronx_distributed_tpu.obs import startup

_OCP = None


def checkpoint_library():
    """The ``orbax.checkpoint`` module (``ocp``), imported on first use."""
    global _OCP
    if _OCP is None:
        with startup.account().phase("import"):
            import orbax.checkpoint as ocp
        _OCP = ocp
    return _OCP
