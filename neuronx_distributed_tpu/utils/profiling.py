"""Compiled-program cost reporting (profile → iterate support, SURVEY §5.1).

The reference leans on external Neuron tools for device-level profiling;
on TPU the XLA compiler itself reports per-executable FLOPs, HBM traffic and
memory footprints.  ``cost_report`` turns that into one dict, and
``roofline`` into a lower-bound step time against the package's ONE table
of published peaks (:data:`DEVICE_SPECS`, keyed by jax's ``device_kind``; a
device that is not in it is an error, never a default)."""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Peak compute + HBM bandwidth for one device kind — the two numbers
    a roofline needs.  ``kind`` is jax's ``device.device_kind``."""

    kind: str
    peak_flops: float
    hbm_bytes_per_s: float


def _specs(peak_flops: float, hbm_bytes_per_s: float, *kinds: str):
    return {k: DeviceSpec(k, peak_flops, hbm_bytes_per_s) for k in kinds}


# THE peak table: published bf16 peak FLOP/s and HBM bytes/s per chip, keyed
# by ``jax.devices()[0].device_kind`` exactly as jax reports it (both
# spellings a kind is known under).  Source: Google Cloud TPU documentation,
# the "System architecture" page of each version ("TPU v5e": 197 TFLOP/s
# bf16, 819 GB/s; "TPU v4": 275, 1228; "TPU v5p": 459, 2765; "TPU v6e": 918,
# 1640).  No entry, no number: :func:`device_spec` raises.
DEVICE_SPECS: Dict[str, DeviceSpec] = {
    **_specs(197e12, 819e9, "TPU v5 lite", "TPU v5e"),
    **_specs(275e12, 1228e9, "TPU v4"),
    **_specs(459e12, 2765e9, "TPU v5", "TPU v5p"),
    **_specs(918e12, 1640e9, "TPU v6 lite", "TPU v6e"),
}


class UnknownDeviceError(LookupError):
    """The device's kind is not in :data:`DEVICE_SPECS`."""


def device_spec(device: Any = None) -> DeviceSpec:
    """The :class:`DeviceSpec` of ``device`` (default: the first jax
    device) from :data:`DEVICE_SPECS`.  Raises :class:`UnknownDeviceError`
    for a kind the table does not hold — a CPU included: utilization and
    roofline figures exist for known accelerators only."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(device.device_kind)
    try:
        return DEVICE_SPECS[kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {kind!r} (known: "
            f"{sorted(DEVICE_SPECS)}); add it to "
            "utils.profiling.DEVICE_SPECS with its source") from None


_CPU_SPEC: Optional[DeviceSpec] = None


def calibrate_cpu_spec() -> DeviceSpec:
    """An explicit cost MODEL for CPU tests that need peaks to hand to
    :func:`roofline` without a chip: one fixed matmul + one fixed copy,
    measured once per process and cached, so every classification in a
    run sees the same numbers.  Never chosen for a caller —
    :func:`device_spec` raises on a CPU — and its ``kind`` is ``"cpu"``,
    so it cannot pass for a device's published peaks."""
    global _CPU_SPEC
    if _CPU_SPEC is not None:
        return _CPU_SPEC
    import numpy as np

    n = 256
    a = np.ones((n, n), np.float32)
    b = np.ones((n, n), np.float32)
    a @ b  # warm BLAS dispatch
    peak = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        a @ b
        peak = max(peak, 2.0 * n ** 3 / max(time.perf_counter() - t0, 1e-9))
    src = np.ones(4 << 20, np.uint8)
    dst = np.empty_like(src)
    np.copyto(dst, src)  # warm
    bw = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        # read + write of the buffer per copy
        bw = max(bw, 2.0 * src.nbytes / max(time.perf_counter() - t0, 1e-9))
    _CPU_SPEC = DeviceSpec("cpu", max(peak, 1e9), max(bw, 1e9))
    return _CPU_SPEC


def memory_analysis(compiled: Any) -> Optional[Dict[str, float]]:
    """Executable memory breakdown (argument / output / temp / generated-
    code bytes) from ``compiled.memory_analysis()`` as a plain dict, or
    None where the backend reports none (some raise Unimplemented).  Feeds
    the memory ledger's per-program temp/output accounting
    (``obs.memory_ledger.MemoryLedger.note_program``): the temp bytes are
    the transient workspace a step needs on top of the resident pools."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # pragma: no cover - backend-dependent
        return None
    if ma is None:
        return None
    out: Dict[str, float] = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = float(v)
    return out or None


def cost_report(compiled: Any, collectives: bool = False) -> Dict[str, Any]:
    """Summarize an executable from ``jax.jit(f).lower(...).compile()``:
    FLOPs, bytes accessed, and (when the backend reports it) the memory
    breakdown in bytes.

    ``collectives=True`` additionally walks the program's HLO for collective
    ops (counts + result-byte volumes per op kind, via
    :mod:`~..obs.hlo_audit`) — the compile-time communication view the cost
    analysis alone doesn't give."""
    out: Dict[str, Any] = {}
    ca = compiled.cost_analysis() or {}
    # newer jax backends omit keys entirely instead of reporting 0 — a
    # missing key silently dropped here used to surface downstream as NaN
    # arithmetic intensities.  Default to 0.0 and COUNT the degradation so
    # consumers can tell "program moves no bytes" from "the cost model went
    # blind".
    missing = 0
    for key in ("flops", "bytes accessed", "transcendentals"):
        if key in ca:
            out[key.replace(" ", "_")] = float(ca[key])
        else:
            out[key.replace(" ", "_")] = 0.0
            missing += 1
    if missing:
        out["cost_keys_missing"] = missing
    ma = memory_analysis(compiled)
    if ma is not None:
        out.update(ma)
    if collectives:
        # late import: obs builds on this module's cost_report
        from neuronx_distributed_tpu.obs.hlo_audit import (
            collective_bytes,
            collective_counts,
        )

        txt = compiled.as_text()
        out["collective_counts"] = collective_counts(txt)
        out["collective_bytes"] = collective_bytes(txt)
    return out


def roofline(
    report: Dict[str, float],
    peak_flops: float,
    hbm_bytes_per_s: float,
) -> Dict[str, float]:
    """Roofline lower bound for one execution of the reported program:
    ``max(flops/peak, bytes/bandwidth)`` — a measured step time below it
    was not synchronized with the device, one far above it indicates
    overhead or serialization to chase.  The peaks are the caller's
    device's, from the one table (:func:`device_spec`)."""
    flops = report.get("flops", 0.0)
    bytes_ = report.get("bytes_accessed", 0.0)
    t_compute = flops / peak_flops if peak_flops else 0.0
    t_memory = bytes_ / hbm_bytes_per_s if hbm_bytes_per_s else 0.0
    bound = max(t_compute, t_memory)
    return {
        "compute_s": t_compute,
        "memory_s": t_memory,
        "lower_bound_s": bound,
        "bound": "compute" if t_compute >= t_memory else "memory",
        "arithmetic_intensity": (flops / bytes_) if bytes_ else float("inf"),
    }


def jit_cost_report(fn, *example_args, peak_flops: Optional[float] = None,
                    hbm_bytes_per_s: Optional[float] = None) -> Dict[str, Any]:
    """One-call convenience: lower+compile ``fn`` on the example args and
    return ``{"cost": ..., "roofline": ...}``.  The roofline runs against
    the given peaks, else the published peaks of the device the process
    runs on (an unknown device is an error)."""
    import jax

    compiled = jax.jit(fn).lower(*example_args).compile()
    rep = cost_report(compiled)
    if peak_flops is None or hbm_bytes_per_s is None:
        spec = device_spec()
        peak_flops = peak_flops or spec.peak_flops
        hbm_bytes_per_s = hbm_bytes_per_s or spec.hbm_bytes_per_s
    return {"cost": rep, "roofline": roofline(rep, peak_flops, hbm_bytes_per_s)}
