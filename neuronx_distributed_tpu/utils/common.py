"""Small shape/partition helpers (reference: ``parallel_layers/utils.py:17-76``)."""

from __future__ import annotations


def ensure_divisibility(numerator: int, denominator: int) -> None:
    if numerator % denominator != 0:
        raise ValueError(f"{numerator} is not divisible by {denominator}")


def divide(numerator: int, denominator: int) -> int:
    """Exact integer division, raising on remainder (reference ``utils.divide``)."""
    ensure_divisibility(numerator, denominator)
    return numerator // denominator


def pad_to_multiple(n: int, multiple: int) -> int:
    """Smallest value >= n that is divisible by ``multiple``."""
    return ((n + multiple - 1) // multiple) * multiple


def ensure_virtual_devices(n: int) -> None:
    """Force an ``n``-device virtual CPU mesh for dev/test parity runs.

    When the configured platform is already CPU with >= ``n`` devices this
    is a no-op; otherwise the backend is reset onto CPU with ``n`` virtual
    devices.  An attached accelerator is never initialized here (a warning
    says it is being passed over): a chip belongs to one process at a time,
    and a dev run that touched it would hold it from the process that
    measures on it.  Do not call this on a run that should use the attached
    accelerators."""
    import jax

    from neuronx_distributed_tpu.utils.logger import get_logger

    platform = jax.config.jax_platforms
    if platform == "cpu":
        if len(jax.devices()) >= n:
            return
    else:
        get_logger(__name__).warning(
            "ensure_virtual_devices: forcing a %d-device virtual CPU mesh "
            "(configured platform %r is NOT initialized or used)", n, platform,
        )
    import jax.extend.backend as jeb

    jeb.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
    if len(jax.devices()) < n:
        raise RuntimeError(f"could not provision {n} devices (have {len(jax.devices())})")
