"""Compile ledger: every XLA compile the framework triggers, accounted.

The two resources that actually kill runs here are invisible by default:
a many-minute cold compile looks exactly like a hang, and a recompile on the serving hot path is a
silent multi-hundred-ms stall that poisons every latency percentile near
it.  :class:`CompileLedger` is the one accounting surface:

- **every ``.lower()/.compile()`` site reports here** — the AOT phase-fn
  builds and the lazily-jitted ``_CompiledLRU`` families in
  ``trace/engine.py`` (first call of a cached jit is timed and recorded,
  then the timing wrapper unwraps itself so steady-state calls pay
  nothing), the trainer-step compile in ``trainer/fit.py`` (which also
  covers the pipelined engine — its schedule compiles inside the same
  train-step jit);
- **cache events join the program events**: ``_CompiledLRU`` hit / miss /
  eviction counts land next to the compiles they explain, and evictions
  carry the evicted ``(family, key)`` so thrash is attributable;
- **recompilation pathologies are detected, not grepped for**: a family
  whose distinct keys exceed its cache capacity raises a ``thrash``
  warning (near-identical programs are cycling through the LRU — the
  ROADMAP item-1 composability smell), and ANY compile recorded after
  :meth:`declare_warmup_done` is a ``compile_storm`` — counted
  (``trace/compile_storms_total``), surfaced in the flight recorder's
  warnings, and traced as a ``compile`` span so the stall shows up in
  request waterfalls;
- **a recompile inside jit dispatch is seen too**: the process's one
  ``jax.monitoring`` duration listener (``obs.startup``, installed when the
  package is imported; a ledger joins the set it feeds) hears every request
  JAX makes to its compiler (served from the persistent cache or not) and
  counts it into ``trace/compile_requests_total``.  After
  warm-up, a request that no explicit ``record_compile`` accounts for — a
  cached jit whose argument came back placed otherwise, say — becomes a row
  of family ``jit_dispatch`` at the next :meth:`reconcile`, and a storm.

Rows stream to a schema-checked ``compile_ledger.jsonl``
(``obs.schemas`` kind ``compile_ledger``); ``trace/compile_ms`` /
``trace/compiles_total`` / ``trace/compiled_cache_*_total`` ride the
metric registry.  Ledger-off is allocation-free by construction: every
interception site guards on ``compile_ledger is not None`` (the
module-level :data:`LEDGER_ROWS` counter is the test hook, like
``obs.tracing.SPANS_CREATED``).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterable, List, Optional

from neuronx_distributed_tpu.obs import startup
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

COMPILE_LEDGER_FILE = "compile_ledger.jsonl"
COMPILE_LEDGER_SCHEMA = "compile_ledger/1"

# compile wall-time histogram boundaries (ms): compiles span four orders of
# magnitude — sub-second lazy jits to many-minute cold train-step builds
COMPILE_MS_BUCKETS = (
    1.0, 5.0, 10.0, 50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
    10000.0, 30000.0, 60000.0, 300000.0, 900000.0, 1800000.0,
)

# module-level row counter: the ledger-off overhead test reads it around a
# full serving run and asserts it never moved — zero rows are ever built
# with no ledger attached (the obs.tracing.SPANS_CREATED discipline)
LEDGER_ROWS = 0

# cost_report keys copied onto a compile row when the executable is
# available (AOT sites; lazy jits record wall time only)
_COST_KEYS = ("flops", "bytes_accessed", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes")


# a request this long before an explicit row's own timed stretch began is
# not that row's (clock granularity between the two stamps)
_COVER_SLACK_S = 0.05


def jit_cache_size(fn: Any) -> Optional[int]:
    """Best-effort cache size of a jitted function (``fn._cache_size()``),
    jax-version-guarded: None when the attribute is missing or raises.
    Growth between polls is the fingerprint of a silent retrace/recompile
    inside jit dispatch — the one compile class the explicit interception
    sites can't see (shared by ``fit()``'s train-step poll and the serving
    engine's sampler-jit poll)."""
    size_fn = getattr(fn, "_cache_size", None)
    try:
        return int(size_fn()) if callable(size_fn) else None
    except Exception:  # pragma: no cover - jax-version-dependent
        return None


def _signature(compiled: Any) -> Optional[str]:
    """Short stable hash of the executable's sharding/donation signature —
    two compiles of the same family with different signatures are different
    programs even at equal shape keys (the near-duplicate-program smell)."""
    try:
        parts = []
        for attr in ("input_shardings", "output_shardings"):
            v = getattr(compiled, attr, None)
            if v is not None:
                parts.append(str(v))
        dn = getattr(compiled, "donated_argnums", None)
        if dn is not None:
            parts.append(str(dn))
        if not parts:
            return None
        return hashlib.blake2s("|".join(parts).encode(),
                               digest_size=8).hexdigest()
    except Exception:  # pragma: no cover - backend-dependent reprs
        return None


class CompileLedger:
    """The run's compile accounting: program rows + cache events + pathology
    detection.

    ``path`` streams every row to a ``compile_ledger.jsonl`` as it is
    recorded (append — the artifact survives a crash mid-run).
    ``registry`` receives the ``trace/compile*`` counters and the
    ``trace/compile_ms`` histogram; ``tracer`` receives a ``compile`` span
    per post-warmup compile (storms show up in request waterfalls);
    ``flight`` (a :class:`~.flight.FlightRecorder`) receives storm/thrash
    warnings next to the step anomalies; ``memory_ledger`` receives each
    AOT program's temp/output bytes (its ``workspace`` subsystem).  All
    optional, attachable late via :meth:`attach`."""

    def __init__(self, path: Optional[str] = None, registry: Any = None,
                 tracer: Any = None, flight: Any = None,
                 memory_ledger: Any = None, wall=time.time,
                 clock=time.monotonic):
        self.path = path
        self.registry = registry
        self.tracer = tracer
        self.flight = flight
        self.memory_ledger = memory_ledger
        self._wall = wall
        self._clock = clock
        self.rows: List[dict] = []
        self.warnings: List[dict] = []
        self.warmup_done = False
        self._lock = threading.Lock()
        # family -> {"keys": set, "capacity": int|None, "compiles": int,
        #            "evictions": int, "cold_ms": float, "thrashed": bool}
        self._fams: Dict[str, dict] = {}
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_evictions = 0
        # compiler requests heard since warm-up that no row accounts for
        # yet: (program name, wall ms, clock at the request's end)
        self._unaccounted: List[tuple] = []
        startup.LEDGERS.add(self)

    # -- wiring ------------------------------------------------------------

    def attach(self, registry: Any = None, tracer: Any = None,
               flight: Any = None, memory_ledger: Any = None) -> None:
        """Fill in sinks that were not known at construction (an engine
        attaches its registry/tracer to a caller-provided ledger).  Only
        empty slots are filled — explicit construction wins."""
        if self.registry is None:
            self.registry = registry
        if self.tracer is None:
            self.tracer = tracer
        if self.flight is None:
            self.flight = flight
        if self.memory_ledger is None:
            self.memory_ledger = memory_ledger

    def set_capacity(self, family: str, capacity: int) -> None:
        """Declare a family's compiled-cache capacity — the thrash
        threshold (distinct keys beyond it are cycling the LRU)."""
        self._fam(family)["capacity"] = int(capacity)

    def _fam(self, family: str) -> dict:
        f = self._fams.get(family)
        if f is None:
            f = {"keys": set(), "capacity": None, "compiles": 0,
                 "evictions": 0, "cold_ms": 0.0, "thrashed": False}
            self._fams[family] = f
        return f

    # -- recording ---------------------------------------------------------

    def _row(self, event: str, family: str, key: Any, kind: str,
             wall_ms: Optional[float], **extra) -> dict:
        global LEDGER_ROWS
        LEDGER_ROWS += 1
        row = {
            "schema": COMPILE_LEDGER_SCHEMA,
            "time": self._wall(),
            "mono": self._clock(),
            "event": event,
            "family": str(family),
            "key": repr(key),
            "kind": kind,
            "wall_ms": (None if wall_ms is None
                        else round(float(wall_ms), 3)),
            "after_warmup": bool(self.warmup_done),
        }
        row.update(extra)
        with self._lock:
            self.rows.append(row)
        if self.path is not None:
            try:
                parent = os.path.dirname(os.path.abspath(self.path))
                if parent:
                    os.makedirs(parent, exist_ok=True)
                with open(self.path, "a") as f:
                    f.write(json.dumps(row) + "\n")
            except OSError as e:  # telemetry IO must never kill the run
                logger.warning("compile ledger: append failed: %s", e)
        return row

    def record_compile(self, family: str, key: Any,
                       wall_ms: Optional[float], kind: str = "jit",
                       compiled: Any = None, **extra) -> dict:
        """One program compiled: ``family`` is the program family (an LRU
        name, ``context``/``decode``, ``train_step``...), ``key`` the
        shape/static key within it, ``wall_ms`` the measured compile wall
        time (None when only the event is known, e.g. a detected jit-cache
        growth), ``kind`` ``"aot"`` for ``.lower().compile()`` sites and
        ``"jit"`` for lazy first-call compiles.  ``compiled`` (the
        executable) adds cost/memory stats via
        :func:`~..utils.profiling.cost_report` and the sharding/donation
        signature hash."""
        if compiled is not None:
            from neuronx_distributed_tpu.utils.profiling import cost_report

            try:
                rep = cost_report(compiled)
            except Exception:  # pragma: no cover - backend-dependent
                rep = {}
            for k in _COST_KEYS:
                if k in rep and k not in extra:
                    extra[k] = rep[k]
            missing = rep.get("cost_keys_missing")
            if missing:
                # the cost model went blind for this program — count the
                # degradation so a reader of the row can tell "moves no
                # bytes" from "unreported"
                extra.setdefault("cost_keys_missing", int(missing))
                if self.registry is not None:
                    self.registry.counter(
                        "perf/cost_model_missing_total").inc(int(missing))
            sig = _signature(compiled)
            if sig is not None:
                extra.setdefault("signature", sig)
            if self.memory_ledger is not None:
                self.memory_ledger.note_program(str(family), extra)
        fam = self._fam(family)
        fam["compiles"] += 1
        fam["keys"].add(repr(key))
        if wall_ms is not None:
            fam["cold_ms"] += float(wall_ms)
        if self.warmup_done:
            extra["storm"] = True  # stamped BEFORE the row streams to disk
        if family != "jit_dispatch":
            self._account(wall_ms)
        row = self._row("compile", family, key, kind, wall_ms, **extra)
        reg = self.registry
        if reg is not None:
            reg.counter("trace/compiles_total").inc()
            if wall_ms is not None:
                reg.histogram("trace/compile_ms",
                              COMPILE_MS_BUCKETS).observe(float(wall_ms))
        if self.warmup_done:
            self._storm(row)
        self._check_thrash(family)
        return row

    def _storm(self, row: dict) -> None:
        """A compile after warmup was declared done: the serving latency
        pathology.  Counted, flight-warned, and traced as a ``compile``
        span covering the stall's wall-time."""
        wall = (f"{row['wall_ms']} ms"
                if row["wall_ms"] is not None
                else "an unknown wall time (detected via jit-cache growth)")
        msg = (f"compile_storm: {row['family']} key {row['key']} compiled "
               f"{wall} after warmup was declared done")
        warning = {"step": -1, "detector": "compile_storm", "message": msg,
                   "time": row["time"]}
        self.warnings.append(warning)
        logger.warning("compile ledger: %s", msg)
        if self.registry is not None:
            self.registry.counter("trace/compile_storms_total").inc()
        if self.flight is not None:
            self.flight.warnings.append(warning)
        tr = self.tracer
        if tr is not None:
            s = tr.begin("compile", family=row["family"], key=row["key"],
                         wall_ms=row["wall_ms"], storm=True)
            if row["wall_ms"]:
                # the compile just FINISHED: the span covers the stall that
                # already happened, not the instant it was noticed
                s.t_start -= row["wall_ms"] / 1e3
            tr.end(s)

    def _check_thrash(self, family: str) -> None:
        fam = self._fam(family)
        cap = fam["capacity"]
        if cap is None or fam["thrashed"] or len(fam["keys"]) <= cap:
            return
        fam["thrashed"] = True
        msg = (f"compile thrash: family {family!r} has seen "
               f"{len(fam['keys'])} distinct program keys but its compiled "
               f"cache holds {cap} — near-identical programs are cycling "
               "the LRU (every eviction is a future recompile)")
        warning = {"step": -1, "detector": "compile_thrash", "message": msg,
                   "time": self._wall()}
        self.warnings.append(warning)
        logger.warning("compile ledger: %s", msg)
        self._row("thrash", family, sorted(fam["keys"]), "event", None,
                  capacity=cap, distinct_keys=len(fam["keys"]))
        if self.registry is not None:
            self.registry.counter("trace/compile_thrash_total").inc()
        if self.flight is not None:
            self.flight.warnings.append(warning)

    # -- compiles inside jit dispatch ----------------------------------------

    def _compile_requested(self, name: str, wall_ms: float) -> None:
        """The process-wide listener's entry: JAX asked its compiler for
        program ``name``."""
        if self.registry is not None:
            self.registry.counter("trace/compile_requests_total").inc()
        if self.warmup_done:
            with self._lock:
                self._unaccounted.append((name, wall_ms, self._clock()))

    def _account(self, wall_ms: Optional[float]) -> None:
        """An explicit row accounts for the requests heard inside the
        stretch it timed; one that only noticed a jit cache grow (no wall
        time) accounts for whatever dispatch compiled before it."""
        with self._lock:
            if wall_ms is None:
                self._unaccounted.clear()
            elif self._unaccounted:
                since = self._clock() - wall_ms / 1e3 - _COVER_SLACK_S
                self._unaccounted = [u for u in self._unaccounted
                                     if u[2] < since]

    def reconcile(self) -> int:
        """Book every compiler request heard after warm-up that no explicit
        row accounted for as a ``jit_dispatch`` compile (keyed by the
        program's name, with the wall time JAX measured): each is a storm.
        The engine calls this after every step; the queries call it too.
        Returns how many rows it added."""
        with self._lock:
            pending, self._unaccounted = self._unaccounted, []
        for name, wall_ms, _ in pending:
            self.record_compile("jit_dispatch", name, wall_ms, kind="jit")
        return len(pending)

    @contextmanager
    def timed(self, family: str, key: Any, kind: str = "aot"):
        """Time a compile site: ``with ledger.timed("context", key) as rec:
        rec["compiled"] = lowered.compile()`` — the row is recorded on exit
        with the measured wall time (and the executable's stats when the
        body stored it under ``"compiled"``)."""
        holder: Dict[str, Any] = {}
        t0 = time.perf_counter()
        yield holder
        wall_ms = (time.perf_counter() - t0) * 1e3
        self.record_compile(family, key, wall_ms, kind=kind,
                            compiled=holder.get("compiled"))

    # -- cache events ------------------------------------------------------

    def cache_hit(self, family: str) -> None:
        self.cache_hits += 1
        if self.registry is not None:
            self.registry.counter("trace/compiled_cache_hits_total").inc()

    def cache_miss(self, family: str) -> None:
        self.cache_misses += 1
        if self.registry is not None:
            self.registry.counter("trace/compiled_cache_misses_total").inc()

    def record_eviction(self, family: str, evicted_key: Any,
                        capacity: Optional[int] = None) -> dict:
        """An LRU dropped a compiled program — the evicted ``(family,
        key)`` is the row, so thrash is attributable to the programs
        actually cycling (the eviction log used to drop the key)."""
        self.cache_evictions += 1
        fam = self._fam(family)
        fam["evictions"] += 1
        if capacity is not None:
            fam["capacity"] = int(capacity)
        row = self._row("eviction", family, evicted_key, "event", None,
                        capacity=fam["capacity"])
        self._check_thrash(family)
        return row

    # -- warmup / storms ---------------------------------------------------

    def declare_warmup_done(self, label: str = "warmup") -> None:
        """Everything is compiled now — any compile after this is a
        ``compile_storm``.  Idempotent."""
        if self.warmup_done:
            return
        self._row("warmup_done", label, None, "event", None)
        self.warmup_done = True

    # -- queries -----------------------------------------------------------

    def compile_count(self, after_warmup_only: bool = False) -> int:
        self.reconcile()
        with self._lock:
            return sum(1 for r in self.rows if r["event"] == "compile"
                       and (r["after_warmup"] or not after_warmup_only))

    @property
    def storms(self) -> int:
        return self.compile_count(after_warmup_only=True)

    def mark(self) -> int:
        """Row-count bookmark; pair with :meth:`compiles_since` to count
        the compiles inside a measurement window."""
        self.reconcile()
        with self._lock:
            return len(self.rows)

    def compiles_since(self, mark: int) -> int:
        self.reconcile()
        with self._lock:
            return sum(1 for r in self.rows[mark:] if r["event"] == "compile")

    def summary(self) -> dict:
        """The report-facing rollup (also what ``obs_report --compare``
        diffs between runs)."""
        self.reconcile()
        with self._lock:
            rows = list(self.rows)
        return summarize_compile_records(rows, cache={
            "hits": self.cache_hits, "misses": self.cache_misses,
            "evictions": self.cache_evictions})

    def dump(self, path: Optional[str] = None) -> Optional[str]:
        """Write every row as one self-contained JSONL snapshot (streaming
        appends already keep :attr:`path` current; this is for exporting to
        a different location)."""
        path = path or self.path
        if path is None:
            return None
        parent = os.path.dirname(os.path.abspath(path))
        if parent:
            os.makedirs(parent, exist_ok=True)
        with self._lock:
            rows = list(self.rows)
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        return path


def read_compile_ledger(path: str) -> List[dict]:
    """Parse a ``compile_ledger.jsonl`` (blank lines skipped)."""
    out: List[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def summarize_compile_records(records: Iterable[dict],
                              cache: Optional[dict] = None) -> dict:
    """Rollup of ledger rows: totals, per-family breakdown, pathology
    counts — the "compile" health section of the obs report, computable
    from the artifact alone."""
    compiles = aot = 0
    cold_ms = 0.0
    cold_max = 0.0
    storms = thrash = evictions = 0
    fams: Dict[str, dict] = {}
    for r in records:
        ev = r.get("event")
        fam = fams.setdefault(r.get("family", "?"), {
            "compiles": 0, "cold_ms": 0.0, "keys": set(), "evictions": 0})
        if ev == "compile":
            compiles += 1
            fam["compiles"] += 1
            fam["keys"].add(r.get("key"))
            if r.get("kind") == "aot":
                aot += 1
            w = r.get("wall_ms")
            if w is not None:
                cold_ms += float(w)
                cold_max = max(cold_max, float(w))
                fam["cold_ms"] += float(w)
            if r.get("after_warmup"):
                storms += 1
        elif ev == "eviction":
            evictions += 1
            fam["evictions"] += 1
        elif ev == "thrash":
            thrash += 1
    out = {
        "compiles": compiles,
        "aot": aot,
        "jit": compiles - aot,
        "cold_ms_total": round(cold_ms, 3),
        "cold_ms_max": round(cold_max, 3),
        "storms": storms,
        "thrash_warnings": thrash,
        "evictions": evictions,
        "families": {
            name: {"compiles": f["compiles"],
                   "cold_ms": round(f["cold_ms"], 3),
                   "distinct_keys": len(f["keys"]),
                   "evictions": f["evictions"]}
            for name, f in sorted(fams.items()) if f["compiles"]
            or f["evictions"]},
    }
    if cache is not None:
        hits, misses = cache.get("hits", 0), cache.get("misses", 0)
        out["cache"] = {
            **cache,
            "hit_rate": (round(hits / (hits + misses), 4)
                         if hits + misses else None),
        }
    return out
