"""What both runners share: the device check, the compile counter, the
profiler window, the reading handed to the per-layer metric readers."""

from __future__ import annotations

import dataclasses
import os
import shutil
import time
from typing import Any, Dict, List, Optional

from benchmarks.harness import manifest, trace_reduce

TRACE_DIR = os.path.join(manifest.REPO_ROOT, "chiprun_out", "benchmarks")


def log(msg: str) -> None:
    print(msg, flush=True)


def check_devices(cell, rehearse: bool):
    """The devices the cell runs on and their published peaks.  No
    accelerator, an unknown kind or too few chips: an error, no result."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if len(devices) < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chip(s), "
                         f"jax.devices() has {len(devices)}")
    if rehearse:
        return devices[:cell.chips], None
    if dev.platform != "tpu":
        raise SystemExit(
            f"no accelerator: jax.devices()[0].platform is {dev.platform!r}; "
            "the benchmark measures on a TPU (--rehearse runs the control "
            "flow at tiny sizes and gives no result)")
    return devices[:cell.chips], manifest.peaks_for(str(dev.device_kind))


class CompileCounter:
    """Every request JAX makes to compile a program (served from the
    persistent cache or not), counted by the benchmark itself through
    ``jax.monitoring`` — a compile in the window is a stall whichever layer
    of the program asked for it."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_):
        if name == self.REQUEST:
            self.requests += 1
        elif name == self.HIT:
            self.hits += 1

    def mark(self) -> int:
        return self.requests

    def since(self, mark: int) -> int:
        return self.requests - mark


class ProfilerWindow:
    """A short traced sub-window of a run (``--trace 1``).  ``start`` and
    ``stop`` block the calling thread for as long as the profiler takes."""

    def __init__(self, cell_name: str):
        self.dir = os.path.join(TRACE_DIR, cell_name)
        self.active = False
        self.done = False

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no per-call Python events: the
        opts.host_tracer_level = 2     # benchmark's annotations are enough
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        self.active, self.done = False, True

    def reduce(self, chips: int) -> trace_reduce.Trace:
        path = trace_reduce.find_xplane(self.dir)
        return trace_reduce.load(path, chips=chips)


def annotate(name: str):
    """A host span in the profiler's own trace; the reduction attributes
    device idle time to the innermost one that covers it."""
    import jax

    return jax.profiler.TraceAnnotation(trace_reduce.ANNOTATION_PREFIX + name)


def memory(devices) -> Dict[str, int]:
    """``memory_stats()`` of the fullest chip, named for what the runtime
    calls them.  PR 21 saw ``peak_bytes_in_use`` equal the resident state
    after a training step whose compiled temporaries were twice that, so it
    is reported as the runtime's number and not as the step's true peak."""
    peak = in_use = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
        in_use = max(in_use, int(s.get("bytes_in_use", 0)))
    return {"peak_bytes_in_use": peak, "bytes_in_use": in_use}


@dataclasses.dataclass
class Reading:
    """What a run hands to the per-layer metric readers."""

    cell: Any
    chips: int
    peak: Optional[dict]                 # published peaks (None: rehearsal)
    window_s: float
    samples: Dict[str, List[float]]      # host-clock samples of the window
    counters: Dict[str, float]           # counts and gauges
    end_to_end: Dict[str, float]         # this run's end-to-end values
    trace: Optional[trace_reduce.Trace]  # the reduced sub-window, if traced
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Outcome:
    """What a runner returns to ``run.py``."""

    correct: bool
    attempted: int
    failed: int
    setup_s: float
    reading: Reading
    memory: Dict[str, int]
    why_not: List[str] = dataclasses.field(default_factory=list)


class Clock:
    """Seconds since the process started (``run.py`` hands the start in)."""

    def __init__(self, t0: float):
        self.t0 = t0

    def __call__(self) -> float:
        return time.perf_counter()

    def since_start(self) -> float:
        return time.perf_counter() - self.t0


def program_config(spec: dict):
    """``{"model": "pkg.mod:Class", "config": "pkg.mod:Class", "kwargs":
    {...}}`` from a configuration file -> ``(module class, config object)``.
    Values of ``dtype`` / ``param_dtype`` name ``jax.numpy`` dtypes."""
    import jax.numpy as jnp

    kwargs = dict(spec["kwargs"])
    for k in ("dtype", "param_dtype"):
        if isinstance(kwargs.get(k), str):
            kwargs[k] = getattr(jnp, kwargs[k])
    return manifest.resolve(spec["model"]), manifest.resolve(spec["config"])(
        **kwargs)
