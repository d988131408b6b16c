"""The start-up account: where the time from process start to ``ready`` went.

A restarted trainer and a new serving replica pay for the chip from the
moment their process starts, and the first useful step comes a minute or
five later.  :class:`StartupAccount` is the program's own account of that
stretch, always on, ONE a process (:func:`account`), built like the step's
(``obs.flight.StepAccount``):

- **phases** (:data:`STARTUP_PHASES`) — ``phase(name)`` opens the span
  ``nxd/startup/<name>`` in the profiler's trace (``obs.tracing.phase``: a
  flag test when no profile is taken) and books the phase's SELF wall time
  at the same boundary, nested phases taken out.  ``process`` is what no
  other phase owns: the interpreter, the entry point's imports and
  arguments, whatever the caller does between the program's calls.  Time is
  the HOST's: a call that only enqueues work (the weights' fill) books its
  trace, compile and dispatch, and the device's part is waited for by
  whichever phase next reads the result;
- **the compile path, by stage** (:data:`COMPILE_STAGES`) — a second axis,
  lying inside the phases: what JAX reports through ``jax.monitoring`` of
  tracing, lowering, the compiler (or the persistent cache's read in its
  place), with the cache's hits and misses, and the programs that cost
  most.  This module holds the process's ONE duration listener and ONE
  event listener; a ``CompileLedger`` joins :data:`LEDGERS` and is fed from
  the first;
- **ready** — ``ServingEngine.declare_warmup_done`` and ``fit()``'s first
  fetched loss call :meth:`StartupAccount.ready`, once a process: the books
  close (``sum(phases) == ready_s``, ``process`` the remainder), one line
  ``startup: ready {json}`` is logged and the totals are copied into the
  caller's metric registry (``startup/*`` of ``obs.schemas``).  From then
  on ``phase()`` hands out a shared no-op and the listeners book nothing:
  the steady state pays nothing for this module (:data:`PHASES_OPENED` is
  the test hook, like ``obs.tracing.SPANS_CREATED``).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import json
import os
import re
import sys
import time
import weakref
from typing import Any, Dict, Optional

import jax

from neuronx_distributed_tpu.obs import tracing
from neuronx_distributed_tpu.obs.flight import _PhaseSpan
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

STARTUP_PHASES = ("process", "import", "backend", "mesh", "weights",
                  "optimizer", "engine", "warmup", "step0", "audit")
COMPILE_STAGES = ("trace", "lower", "backend_compile", "cache_read")
TOP_PROGRAMS = 8

# phases handed out since the process started: the overhead tests read it
# around serve and train steps after ``ready`` and assert it never moved
PHASES_OPENED = 0

# JAX times every call into its compiler under this event, cache hit or
# miss, and names the program (jax._src.dispatch.BACKEND_COMPILE_EVENT)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    COMPILE_EVENT: "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_COUNT_OF = {
    "/jax/compilation_cache/compile_requests_use_cache": "compile_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# a trace that began this long before another one's stamp is still inside
# it (both stamps are taken a few microseconds after JAX's own)
_NEST_SLACK_S = 1e-4
_NOT_A_NAME = re.compile(r"[^\w.-]")

# the ledgers fed from the duration listener (JAX offers no public way to
# take a listener out again, so there is one for good and dead ledgers fall
# out of the weak set)
LEDGERS: "weakref.WeakSet" = weakref.WeakSet()

_DONE = contextlib.nullcontext()


def _process_age_s() -> Optional[float]:
    """Seconds since this process started, by the kernel's own stamp
    (``starttime`` of ``/proc/self/stat``, in clock ticks since boot, against
    ``CLOCK_BOOTTIME``); ``None`` where there is no such file or clock."""
    try:
        with open("/proc/self/stat", "rb") as f:
            fields = f.read().rsplit(b")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return age if age >= 0 else None


def _program(name: Any) -> str:
    """One name for a program across the stages: tracing reports the
    function's (``<lambda>``), lowering and compiling the module's
    (``jit(<lambda>)``, spelt ``jit__lambda__``)."""
    name = _NOT_A_NAME.sub("_", str(name))
    if name.startswith("jit_") and name.endswith("_"):
        return name[4:-1]
    return name


class StartupAccount:
    """The process's account of its start-up (module docstring).  ``origin``
    is the process's start on ``clock`` (monotonic seconds, an attribute a
    test may replace): the kernel's stamp, else the package's import
    stamp."""

    def __init__(self, origin: Optional[float] = None):
        self.clock = time.perf_counter
        if origin is None:
            age = _process_age_s()
            if age is not None:
                origin = self.clock() - age
            else:
                pkg = sys.modules.get("neuronx_distributed_tpu")
                origin = getattr(pkg, "_IMPORT_T0", None) or self.clock()
        self.origin = origin
        self.ready_s: Optional[float] = None
        self._closed: Optional[tuple] = None   # the phases at ``ready``
        self.label: Optional[str] = None
        self._spans = [_PhaseSpan(self, i)
                       for i in range(len(STARTUP_PHASES))]
        self._own_s = [0.0] * len(STARTUP_PHASES)
        self._cur = 0
        self._mark = origin
        self.stage_s = dict.fromkeys(COMPILE_STAGES, 0.0)
        self.saved_s = 0.0
        self.counts = dict.fromkeys(_COUNT_OF.values(), 0)
        self._programs: Dict[str, float] = collections.defaultdict(float)
        # traces heard and not yet found inside another: (start, s, program)
        self._traces: list = []
        self._read_s = 0.0   # the cache's read inside the request in flight

    # -- phases --------------------------------------------------------------

    def phase(self, name: str, **attrs):
        """The phase ``name`` of :data:`STARTUP_PHASES` as a context
        manager.  After ``ready``, and for a phase that is open already
        (``initialize_parallel_model`` calls ``init_sharded_params``: both
        are ``weights``), a shared no-op."""
        if self.ready_s is not None:
            return _DONE
        index = STARTUP_PHASES.index(name)
        i = self._cur
        while i:
            if i == index:
                return _DONE
            i = self._spans[i]._outer
        global PHASES_OPENED
        PHASES_OPENED += 1
        span = self._spans[index]
        span.annotation = tracing.phase("startup/" + name, **attrs)
        return span

    def imported(self, t0: float, t1: float) -> None:
        """The package's import ran from ``t0`` to ``t1`` (the stamps at the
        top and bottom of its ``__init__``)."""
        if self.ready_s is None:
            self._own_s[self._cur] += t0 - self._mark
            self._own_s[STARTUP_PHASES.index("import")] += t1 - t0
            self._mark = t1

    def move(self, name: str, seconds: float) -> None:
        """``seconds`` that were booked to ``process`` belong to ``name``
        (the warm-up's steps: the step account timed them)."""
        if self.ready_s is None:
            self._own_s[STARTUP_PHASES.index(name)] += seconds
            self._own_s[0] -= seconds

    def ready(self, label: str, registry: Any = None) -> bool:
        """The program can do what it was started for.  The first call of a
        process closes the books, logs the line and copies the totals into
        ``registry``; a later one does nothing and returns ``False``."""
        if self.ready_s is not None:
            return False
        t = self.clock()
        self._own_s[self._cur] += t - self._mark
        self._mark = t
        # a phase still open ends here (``fit()`` is ``step0`` as a whole
        # call): its span closes now, its later exit finds a no-op
        while self._cur:
            span = self._spans[self._cur]
            span.annotation.__exit__(None, None, None)
            span.annotation = _DONE
            self._cur = span._outer
        self.ready_s = t - self.origin
        self._own_s[0] = self.ready_s - sum(self._own_s[1:])
        # the books are closed: a span that exits later writes to a copy
        self._closed = tuple(self._own_s)
        self.label = label
        self._traces.clear()
        logger.info("startup: ready %s", json.dumps(self.document()))
        if registry is not None:
            gauge, counter = registry.gauge, registry.counter
            for key, value in self.snapshot().items():
                if key == "startup/ready_s":
                    gauge(key).value = value
                elif key.startswith("startup/"):
                    counter(key).value = value
        return True

    # -- the compile path ------------------------------------------------------

    def _heard(self, event: str, seconds: float, name: Any) -> None:
        stage = _STAGE_OF.get(event)
        if stage is None:
            if event == _SAVED_EVENT:
                self.saved_s += seconds
            return
        own = seconds
        program = None if name is None else _program(name)
        if stage == "cache_read":
            # reported inside the request whose whole time follows
            self._read_s += seconds
        elif stage == "backend_compile":
            own, self._read_s = max(seconds - self._read_s, 0.0), 0.0
        elif stage == "trace":
            # a jitted function traced inside another's trace reports
            # first, and the outer one's time holds it: book self time, and
            # the whole to the outermost program
            start = time.time() - seconds
            while self._traces and \
                    self._traces[-1][0] >= start - _NEST_SLACK_S:
                _, inner, callee = self._traces.pop()
                own -= inner
                self._programs[callee] -= inner
            self._traces.append((start, seconds, program))
        self.stage_s[stage] += max(own, 0.0)
        if program is not None:
            self._programs[program] += seconds

    # -- reading it ------------------------------------------------------------

    def phases_s(self) -> Dict[str, float]:
        """Self seconds by phase; before ``ready``, as booked so far."""
        return dict(zip(STARTUP_PHASES, self._closed or self._own_s))

    def snapshot(self) -> Dict[str, Any]:
        """The account under its registry names (``obs.schemas``), with the
        ``label`` of whoever declared ``ready`` and the ``programs`` with
        the most seconds on the compile path, ``[name, seconds]`` each.
        ``startup/ready_s`` is there once ``ready`` was declared."""
        out: Dict[str, Any] = {}
        if self.ready_s is not None:
            out["startup/ready_s"] = self.ready_s
        for phase_name, s in self.phases_s().items():
            out[f"startup/ms_total/{phase_name}"] = s * 1e3
        for stage, s in self.stage_s.items():
            out[f"startup/compile_ms_total/{stage}"] = s * 1e3
        out["startup/compile_saved_ms_total"] = self.saved_s * 1e3
        for what, n in self.counts.items():
            out[f"startup/{what}_total"] = float(n)
        out["label"] = self.label
        out["programs"] = self.programs()
        return out

    def programs(self) -> list:
        """``[name, seconds]`` of the programs with the most seconds on the
        compile path (a whole request each: its trace, its lowering, its
        compile or the cache's read), most first."""
        top = sorted(self._programs.items(), key=lambda kv: -kv[1])
        return [[name, round(s, 3)]
                for name, s in top[:TOP_PROGRAMS] if s > 0]

    def document(self) -> dict:
        """What the ready line says, in seconds."""
        return {
            "label": self.label,
            "ready_s": None if self.ready_s is None
            else round(self.ready_s, 3),
            "phases_s": {p: round(s, 3)
                         for p, s in self.phases_s().items() if s},
            "compile_s": {k: round(s, 3) for k, s in self.stage_s.items()},
            "compile_saved_s": round(self.saved_s, 3),
            **self.counts,
            "programs": self.programs(),
        }


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    if event == COMPILE_EVENT:
        for ledger in list(LEDGERS):
            ledger._compile_requested(str(kw.get("fun_name", "?")),
                                      duration_secs * 1e3)
    acct = _ACCOUNT
    if acct.ready_s is None:
        acct._heard(event, duration_secs, kw.get("fun_name"))


def _on_event(event: str, **kw) -> None:
    acct = _ACCOUNT
    if acct.ready_s is None:
        what = _COUNT_OF.get(event)
        if what is not None:
            acct.counts[what] += 1


_ACCOUNT = StartupAccount()
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def account() -> StartupAccount:
    """The process's start-up account."""
    return _ACCOUNT


def phased(name: str):
    """Decorator: the call is the start-up phase ``name`` (until ``ready``;
    one flag test a call after it)."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with _ACCOUNT.phase(name):
                return fn(*args, **kwargs)
        return call
    return wrap
