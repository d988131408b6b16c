"""Compiled-program cost reporting (profile → iterate support, SURVEY §5.1).

The reference leans on external Neuron tools for device-level profiling;
on TPU the XLA compiler itself reports per-executable FLOPs, HBM traffic and
memory footprints.  ``cost_report`` turns that into one dict, and
``roofline`` into a lower-bound step time — the quick sanity check that
caught the round-2 super-peak bench number would have been one call."""

from __future__ import annotations

from typing import Any, Dict, Optional


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
    # arithmetic intensities in the perf-attribution join.  Default to 0.0
    # and COUNT the degradation so consumers can tell "program moves no
    # bytes" from "the cost model went blind".
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
    device's, from the one table (``obs.perf.device_spec``)."""
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
        from neuronx_distributed_tpu.obs.perf import device_spec

        spec = device_spec()
        peak_flops = peak_flops or spec.peak_flops
        hbm_bytes_per_s = hbm_bytes_per_s or spec.hbm_bytes_per_s
    return {"cost": rep, "roofline": roofline(rep, peak_flops, hbm_bytes_per_s)}
