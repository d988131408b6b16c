"""Step flight recorder + anomaly detectors.

A ring buffer of the last K step records — loss, grad-norm, step-time
breakdown (host dispatch vs device wait via ``block_until_ready`` timing,
data-loader stall) — that dumps to ``flight_record.json`` when the run dies
(crash or SIGTERM, hooked into ``fit()``'s existing signal path) and at
clean exit, so a post-mortem reads the last K steps from a persisted
artifact instead of reconstructing them from scrollback.

Detectors run synchronously on every record (they are a few float
comparisons) and emit three-way: a structured warning record (persisted in
the dump), a ``logger.warning``, and — when a timeline is attached — an
``instant()`` marker so the anomaly is visible in the Perfetto trace at the
step where it fired.

A loop that steps a thousand times a second keeps its own books with
:class:`StepAccount`: host time by phase, on-CPU against off-CPU, the time
blocked on the device and a stall rule, one flat record a step into the same
ring (:meth:`FlightRecorder.append`), documents only at a dump.
"""

from __future__ import annotations

import bisect
import gc
import json
import math
import os
import statistics
import time
from collections import deque, namedtuple
from typing import Any, Deque, List, Optional, Sequence

try:                    # POSIX only; a stall line without it says less
    import resource
except ImportError:     # pragma: no cover
    resource = None

from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

FLIGHT_SCHEMA = "flight_record_v1"
MAX_WARNINGS = 256


class AnomalyDetector:
    """Base detector: ``check(record, history)`` returns a message string
    when the anomaly fires, else None.  ``history`` is the ring content
    BEFORE ``record`` (oldest first)."""

    name = "anomaly"

    def check(self, record: dict, history: "Deque[dict]") -> Optional[str]:
        raise NotImplementedError


class NanLossDetector(AnomalyDetector):
    """Fires when the watched field is NaN/Inf — the canonical
    dead-run signature (the reference's runs die silently on this;
    SURVEY §5.5)."""

    name = "nan_loss"

    def __init__(self, field: str = "loss"):
        self.field = field

    def check(self, record, history):
        v = record.get(self.field)
        if v is not None and not math.isfinite(float(v)):
            return f"{self.field} is non-finite ({v!r})"
        return None


class LossSpikeDetector(AnomalyDetector):
    """Z-score of the current loss against the trailing window; fires on
    ``z > threshold`` once enough history exists.  A spike that large with a
    healthy data pipeline usually means a bad batch or an optimizer blow-up
    — worth a marker even when the run survives."""

    name = "loss_spike"

    def __init__(self, field: str = "loss", window: int = 32,
                 z_threshold: float = 6.0, min_history: int = 8):
        self.field = field
        self.window = window
        self.z_threshold = z_threshold
        self.min_history = min_history

    def check(self, record, history):
        v = record.get(self.field)
        if v is None or not math.isfinite(float(v)):
            return None  # NanLossDetector's jurisdiction
        past = [float(r[self.field]) for r in list(history)[-self.window:]
                if r.get(self.field) is not None
                and math.isfinite(float(r[self.field]))]
        if len(past) < self.min_history:
            return None
        mean = statistics.fmean(past)
        std = statistics.pstdev(past)
        # the std floor keeps a flat-loss window (std ~ 0) from firing on
        # harmless jitter: require an absolute move too
        z = (float(v) - mean) / max(std, 1e-3 * max(abs(mean), 1e-9), 1e-12)
        if z > self.z_threshold:
            return (f"{self.field} spike: {float(v):.6g} vs window "
                    f"mean {mean:.6g} (z={z:.1f})")
        return None


class ThroughputRegressionDetector(AnomalyDetector):
    """Fires when a step takes ``factor``x the trailing-window median step
    time — the host-side signature of a data stall, a recompile, or a
    neighbor stealing the chip.  ``min_excess_s`` is an absolute floor on
    the slowdown: sub-second relative jitter on tiny (dev/CPU) steps is
    noise, while the stalls worth a marker cost whole seconds.

    The trailing window is the detector's OWN: the last ``window`` values it
    was shown, kept in arrival order and in sorted order, so a step costs
    one ``insort`` and one delete in a list of ``window`` floats — no copy
    of the ring, no sort.  One detector judges one series."""

    name = "throughput_regression"

    def __init__(self, field: str = "step_time_s", window: int = 32,
                 factor: float = 3.0, min_history: int = 8,
                 min_excess_s: float = 0.25):
        self.field = field
        self.window = window
        self.factor = factor
        self.min_history = min_history
        self.min_excess_s = min_excess_s
        self.reset()

    def reset(self) -> None:
        """Forget the trailing window (a warm-up's steps are not the
        measure of the steps after it)."""
        self._recent: Deque[float] = deque()
        self._sorted: List[float] = []

    def median(self) -> Optional[float]:
        """The trailing median, ``None`` under ``min_history`` values."""
        n = len(self._sorted)
        if n < self.min_history or not n:
            return None
        mid = n // 2
        return (self._sorted[mid] if n % 2
                else 0.5 * (self._sorted[mid - 1] + self._sorted[mid]))

    def regression(self, v: float) -> Optional[float]:
        """The trailing median where ``v`` is a regression against it (past
        ``factor`` x the median AND the median + ``min_excess_s``), else
        ``None``.  Judges only: :meth:`push` is what moves the window."""
        med = self.median()
        if med is not None and med > 0 and v > self.factor * med \
                and v - med > self.min_excess_s:
            return med
        return None

    def push(self, v: float) -> None:
        if len(self._recent) >= self.window:
            del self._sorted[bisect.bisect_left(self._sorted,
                                                self._recent.popleft())]
        self._recent.append(v)
        bisect.insort(self._sorted, v)

    def check(self, record, history):
        v = record.get(self.field)
        if v is None:
            return None
        v = float(v)
        med = self.regression(v)
        self.push(v)
        if med is not None:
            return (f"step took {v * 1e3:.1f} ms vs trailing median "
                    f"{med * 1e3:.1f} ms ({v / med:.1f}x)")
        return None


def default_detectors() -> List[AnomalyDetector]:
    return [NanLossDetector(), LossSpikeDetector(), ThroughputRegressionDetector()]


def _json_safe(obj):
    """Strict-JSON view: non-finite floats become strings ("NaN"/"Inf"/
    "-Inf") so the dumped artifact parses under every JSON implementation,
    not just Python's NaN-tolerant one."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return "NaN" if math.isnan(obj) else ("Inf" if obj > 0 else "-Inf")
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    return obj


class FlightRecorder:
    """Ring buffer of step records with synchronous anomaly detection.

    ``record(step, **fields)`` appends one record and returns the warnings
    raised for it; ``dump(reason)`` atomically writes the whole ring (plus
    every warning so far) to ``flight_record.json``."""

    def __init__(
        self,
        capacity: int = 256,
        path: Optional[str] = None,
        detectors: Optional[List[AnomalyDetector]] = None,
        timeline: Any = None,
        registry: Any = None,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.path = path
        self.detectors = list(detectors) if detectors is not None else []
        self.timeline = timeline
        self.registry = registry
        self.records: Deque[dict] = deque(maxlen=capacity)
        self.warnings: Deque[dict] = deque(maxlen=MAX_WARNINGS)
        self.steps_recorded = 0

    def record(self, step: int, **fields) -> List[dict]:
        rec = {"step": int(step), "time": time.time()}
        for k, v in fields.items():
            if v is not None:
                rec[k] = float(v) if isinstance(v, (int, float)) else v
        fired: List[dict] = []
        for det in self.detectors:
            try:
                msg = det.check(rec, self.records)
            except Exception as e:  # a broken detector must not kill training
                logger.warning("flight: detector %s raised %r", det.name, e)
                continue
            if msg:
                fired.append(self.warn(
                    step, det.name, msg,
                    rec.get(getattr(det, "field", "loss")), rec["time"]))
        if fired:
            rec["anomalies"] = [w["detector"] for w in fired]
        self.records.append(rec)
        self.steps_recorded += 1
        return fired

    def append(self, rec) -> None:
        """A FLAT record (a tuple with a ``document()``: what
        :class:`StepAccount` makes) straight into the ring.  No detector
        runs: whoever keeps flat records has judged them, and says so
        through :meth:`warn`.  It becomes a ``flight_step`` document at a
        dump, not before."""
        self.records.append(rec)
        self.steps_recorded += 1

    def warn(self, step: int, detector: str, message: str, value: Any = None,
             at: Optional[float] = None, log: bool = True) -> dict:
        """Raise one anomaly three-way: the warning record (kept for the
        dump), a log line (``log=False``: the caller writes its own), the
        ``obs/anomalies*`` counters and the timeline's marker."""
        warning = {"step": int(step), "detector": detector,
                   "message": message, "value": value,
                   "time": time.time() if at is None else at}
        self.warnings.append(warning)
        if log:
            logger.warning("flight anomaly [%s] step %d: %s",
                           detector, step, message)
        if self.registry is not None:
            self.registry.counter("obs/anomalies_total").inc()
            self.registry.counter(f"obs/anomalies/{detector}").inc()
        if self.timeline is not None:
            self.timeline.instant(
                f"anomaly/{detector}", step=int(step), message=message)
        return warning

    def documents(self) -> List[dict]:
        """The ring as ``flight_step`` documents, oldest first."""
        return [r if isinstance(r, dict) else r.document()
                for r in self.records]

    def dump(self, reason: str, path: Optional[str] = None) -> Optional[str]:
        """Write the ring (and accumulated warnings) as one JSON document;
        atomic (temp file + ``os.replace``) so a crash mid-dump can't leave
        a truncated artifact.  Returns the path written, or None when the
        recorder has no sink."""
        path = path or self.path
        if path is None:
            return None
        doc = {
            "schema": FLIGHT_SCHEMA,
            "reason": reason,
            "dumped_at": time.time(),
            "capacity": self.capacity,
            "steps_recorded": self.steps_recorded,
            "records": self.documents(),
            "warnings": list(self.warnings),
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(_json_safe(doc), f, indent=1, allow_nan=False)
        os.replace(tmp, path)
        return path


# -- a loop's own books: one flat record a step ------------------------------

STEP_FIELDS = ("step", "time", "t0", "wall_ms", "cpu_ms", "blocked_ms",
               "between_ms", "stall_ms", "rows", "granted", "chunk",
               "fetches", "queue_depth", "slots_active", "terminal")
STALL_SAMPLE_EVERY = 64     # steps between two samples of the thread's state
STALL_LOG_EVERY_S = 1.0     # at most one stall line in this many seconds
STALL_RECORDS_BEFORE = 8    # records before the stalled one on its line
BETWEEN = "between"         # where a hole between two steps is booked

# what the collector did, process-wide: [collections, seconds, start stamp]
_GC = [0, 0.0, 0.0]


def _gc_callback(phase: str, info: dict) -> None:
    if phase == "start":
        _GC[2] = time.perf_counter()
    else:
        _GC[0] += 1
        _GC[1] += time.perf_counter() - _GC[2]


def _read_first_line(path: str) -> Optional[List[str]]:
    try:
        with open(path) as f:
            return f.readline().split()
    except OSError:
        return None


def thread_state() -> dict:
    """What the kernel says of the CALLING thread and of the machine, as
    running totals (a stall line prints their difference against the sample
    before): voluntary / involuntary context switches and major faults
    (``getrusage(RUSAGE_THREAD)``), ms the thread spent runnable and
    waiting for a core (``/proc/thread-self/schedstat``), ms the hypervisor
    took from the machine's cores (``steal`` of ``/proc/stat``).  A field
    the platform does not have is left out."""
    out = {}
    if resource is not None and hasattr(resource, "RUSAGE_THREAD"):
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        out.update(vol_switches=ru.ru_nvcsw, invol_switches=ru.ru_nivcsw,
                   major_faults=ru.ru_majflt)
    sched = _read_first_line("/proc/thread-self/schedstat")
    if sched and len(sched) >= 2:
        out["runq_wait_ms"] = int(sched[1]) * 1e-6
    stat = _read_first_line("/proc/stat")
    if stat and len(stat) > 8 and stat[0] == "cpu":
        out["steal_ms"] = int(stat[8]) * 1e3 / os.sysconf("SC_CLK_TCK")
    return out


def step_record_type(phases: Sequence[str]):
    """The flat record of one step of a loop with these phases: a tuple of
    :data:`STEP_FIELDS` then ``<phase>_ms`` a phase (SELF time: a phase's
    wall time less the phases inside it, so they add up to ``wall_ms``)."""
    fields = STEP_FIELDS + tuple(f"{p}_ms" for p in phases)

    class StepRecord(namedtuple("StepRecord", fields)):
        __slots__ = ()

        @property
        def off_cpu_ms(self) -> float:
            """Time the thread neither ran nor waited for the device."""
            return max(0.0, self.wall_ms - self.cpu_ms - self.blocked_ms)

        def phase_ms(self) -> dict:
            return {p: self[len(STEP_FIELDS) + i]
                    for i, p in enumerate(phases)}

        def document(self) -> dict:
            """The ``flight_step`` document of this record."""
            doc = self._asdict()
            doc["off_cpu_ms"] = self.off_cpu_ms
            return doc

    return StepRecord


class _PhaseSpan:
    """One phase of the step in progress: the profiler's annotation and the
    account's boundary, opened and closed together.  Time between two
    boundaries belongs to the innermost phase open then.  One object a
    phase NAME, handed out again each time the phase opens (a phase never
    opens inside itself)."""

    __slots__ = ("_acct", "_index", "_outer", "annotation")

    def __init__(self, acct: "StepAccount", index: int):
        self._acct, self._index = acct, index
        self._outer = 0
        self.annotation = None

    def __enter__(self):
        a = self._acct
        t = a.clock()
        a._own_s[a._cur] += t - a._mark
        a._mark = t
        self._outer = a._cur
        a._cur = self._index
        return self.annotation.__enter__()

    def __exit__(self, exc_type, exc, tb):
        a = self._acct
        t = a.clock()
        a._own_s[a._cur] += t - a._mark
        a._mark = t
        a._cur = self._outer
        return self.annotation.__exit__(exc_type, exc, tb)


class StepAccount:
    """A stepping loop's account of its own step, always on.

    ``begin(step)`` .. ``end(...)`` bracket one step; ``span(name, ann)``
    wraps a profiler annotation so that the phase's wall time is booked at
    the same boundary (``phases[0]`` is the step itself: it holds what no
    other phase owns).  ``end`` makes ONE flat record (:func:`step_record_type`)
    into ``flight``'s ring, adds to ``<metrics>/step_ms_total``,
    ``step_cpu_ms_total``, ``step_blocked_ms_total``,
    ``host_ms_total/<phase>`` and keeps the gauge ``step_ms_max``.

    **Stall rule** — ``detector``'s (:class:`ThroughputRegressionDetector`:
    past 3x the trailing median AND the median + 250 ms), applied to the
    step's wall time; a stall adds to ``stalls_total``, ``stall_ms_total``
    (the excess over the median) and ``stall_ms_total/<phase>`` (the phase
    with the most self time), and logs one line (at most one a
    ``STALL_LOG_EVERY_S``).  The time BETWEEN two steps is the caller's; it
    is held to the same rule where the step before left work behind, and
    booked to ``stall_ms_total/between`` alone: not the loop's stall.

    The clocks are the account's own (``clock``: monotonic seconds,
    ``cpu_clock``: the calling thread's CPU seconds), attributes a test may
    replace; nothing here reads a file or makes a system call a step beyond
    them: the thread's state (:func:`thread_state`) is sampled every
    ``STALL_SAMPLE_EVERY`` steps and at a stall."""

    def __init__(self, phases: Sequence[str], flight: FlightRecorder,
                 registry: Any, metrics: str, blocked: str = "fetch"):
        self.phases = tuple(phases)
        self.flight = flight
        self.metrics = metrics
        self.clock = time.perf_counter
        self.cpu_clock = time.thread_time
        self.detector = ThroughputRegressionDetector()
        self.Record = step_record_type(self.phases)
        self._spans = {p: _PhaseSpan(self, i)
                       for i, p in enumerate(self.phases)}
        self._blocked = self.phases.index(blocked)
        counter, gauge = registry.counter, registry.gauge
        self._c_wall = counter(f"{metrics}/step_ms_total")
        self._c_cpu = counter(f"{metrics}/step_cpu_ms_total")
        self._c_blocked = counter(f"{metrics}/step_blocked_ms_total")
        self._c_phase = [counter(f"{metrics}/host_ms_total/{p}")
                         for p in self.phases]
        self._g_max = gauge(f"{metrics}/step_ms_max")
        self._c_stalls = counter(f"{metrics}/stalls_total")
        self._c_stall_ms = counter(f"{metrics}/stall_ms_total")
        self._c_stall_phase = {
            p: counter(f"{metrics}/stall_ms_total/{p}")
            for p in self.phases + (BETWEEN,)}
        if _gc_callback not in gc.callbacks:
            gc.callbacks.append(_gc_callback)
        self._wall0 = time.time() - self.clock()
        self._own_s = [0.0] * len(self.phases)
        self._cur = 0
        self._mark = self._t0 = self._cpu0 = 0.0
        self._step = 0
        self._end: Optional[float] = None    # None: no step before this one
        self._left_work = False
        self._between = 0.0
        self._gc0 = (0, 0.0)
        self._sample: Optional[dict] = None
        self._sample_step = 0
        self._logged_at: Optional[float] = None
        self.suppressed = 0
        # what the step in progress launched, set by the loop as it goes
        self.rows = self.granted = self.chunk = self.fetches = 0

    # -- the step ----------------------------------------------------------

    def begin(self, step: int) -> None:
        t = self.clock()
        self._between = 0.0 if self._end is None else t - self._end
        self._step = step
        self._t0 = self._mark = t
        self._cpu0 = self.cpu_clock()
        self._own_s = [0.0] * len(self.phases)
        self._cur = 0
        self.rows = self.granted = self.chunk = self.fetches = 0

    def span(self, name: str, annotation) -> _PhaseSpan:
        span = self._spans[name]
        span.annotation = annotation
        return span

    def end(self, queue_depth: int = 0, slots_active: int = 0,
            terminal: int = 0, left_work: bool = False):
        """Close the step: the record, the totals, the stall rule.
        ``left_work``: the loop still holds work, so whoever drives it is
        expected back at once and the time until then can be a hole."""
        t = self.clock()
        cpu_ms = (self.cpu_clock() - self._cpu0) * 1e3
        self._own_s[self._cur] += t - self._mark
        wall_s = t - self._t0
        wall_ms = wall_s * 1e3
        ms = [s * 1e3 for s in self._own_s]
        for c, v in zip(self._c_phase, ms):
            c.value += v
        blocked_ms = ms[self._blocked]
        self._c_wall.value += wall_ms
        self._c_cpu.value += cpu_ms
        self._c_blocked.value += blocked_ms
        if wall_ms > self._g_max.value:
            self._g_max.value = wall_ms
        det = self.detector
        between_s, was_expected = self._between, self._left_work
        between_med = (det.regression(between_s)
                       if was_expected and between_s > det.min_excess_s
                       else None)
        med = det.regression(wall_s)
        det.push(wall_s)
        stall_ms = 0.0 if med is None else (wall_s - med) * 1e3
        rec = self.Record(
            self._step, self._wall0 + self._t0, self._t0, wall_ms, cpu_ms,
            blocked_ms, between_s * 1e3, stall_ms, self.rows, self.granted,
            self.chunk, self.fetches, queue_depth, slots_active, terminal,
            *ms)
        before = None
        if med is not None or between_med is not None:
            before = list(self.flight.records)[-STALL_RECORDS_BEFORE:]
        self.flight.append(rec)
        self._end, self._left_work = t, left_work
        if between_med is not None:
            self._stalled(rec, BETWEEN, (between_s - between_med) * 1e3,
                          between_med * 1e3, before, t)
        if med is not None:
            phase = self.phases[max(range(len(ms)), key=ms.__getitem__)]
            self._c_stalls.value += 1
            self._c_stall_ms.value += stall_ms
            self._stalled(rec, phase, stall_ms, med * 1e3, before, t)
        elif self._step - self._sample_step >= STALL_SAMPLE_EVERY \
                or self._sample is None:
            self._sample, self._sample_step = thread_state(), self._step
        self._gc0 = (_GC[0], _GC[1])
        return rec

    def reset(self) -> None:
        """What came before does not count from here on (the warm-up is
        over: its compiles are not stalls): totals, the longest step, the
        trailing median, the time since the step before."""
        for c in (self._c_wall, self._c_cpu, self._c_blocked, self._g_max,
                  self._c_stalls, self._c_stall_ms, *self._c_phase,
                  *self._c_stall_phase.values()):
            c.value = 0.0
        self.detector.reset()
        self._end = None
        self._left_work = False
        self.suppressed = 0
        self._logged_at = None

    # -- a stall -----------------------------------------------------------

    def _stalled(self, rec, phase: str, excess_ms: float, median_ms: float,
                 before: list, now: float) -> None:
        self._c_stall_phase[phase].value += excess_ms
        if self._logged_at is not None \
                and now - self._logged_at < STALL_LOG_EVERY_S:
            self.suppressed += 1
            return
        line = self.stall_line(rec, phase, excess_ms, median_ms, before)
        self._logged_at = now
        self.suppressed = 0
        logger.warning("%s: stall %s", self.metrics, json.dumps(line))
        self.flight.warn(
            rec.step, "throughput_regression",
            f"{phase} held {excess_ms:.1f} ms over the trailing median step "
            f"of {median_ms:.1f} ms ({line['kind']})", rec.wall_ms * 1e-3,
            rec.time, log=False)

    def stall_line(self, rec, phase: str, excess_ms: float, median_ms: float,
                   before: list) -> dict:
        """Everything worth reading about a stalled step, as one document:
        the record, how it reads (``kind``), what the collector did since
        the step before it ended, the thread's and the machine's state
        since the last sample, the records before it."""
        state, was = thread_state(), self._sample or {}
        since = {k: round(v - was[k], 3) for k, v in state.items()
                 if k in was}
        since["steps"] = rec.step - self._sample_step
        self._sample, self._sample_step = state, rec.step
        if phase == BETWEEN:
            kind = "between_steps"
        else:
            parts = {"blocked": rec.blocked_ms, "off_cpu": rec.off_cpu_ms,
                     "on_cpu": rec.cpu_ms}
            kind = max(parts, key=parts.__getitem__)
        doc = rec.document()
        return {
            "phase": phase, "kind": kind,
            "excess_ms": round(excess_ms, 3),
            "median_ms": round(median_ms, 3),
            **{k: (round(v, 3) if isinstance(v, float) else v)
               for k, v in doc.items()},
            "gc_collections": _GC[0] - self._gc0[0],
            "gc_ms": round((_GC[1] - self._gc0[1]) * 1e3, 3),
            "since_sample": since,
            "suppressed_lines": self.suppressed,
            "before": [[r.step, round(r.wall_ms, 3), round(r.cpu_ms, 3),
                        round(r.blocked_ms, 3), round(r.between_ms, 3)]
                       for r in before if not isinstance(r, dict)],
        }


def read_flight(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != FLIGHT_SCHEMA:
        raise ValueError(
            f"{path}: schema {doc.get('schema')!r} != {FLIGHT_SCHEMA!r}")
    return doc
