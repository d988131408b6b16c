"""Unified observability subsystem (ISSUE 1 tentpole).

One telemetry layer that can answer "why was step N slow / why did the run
die / how many bytes did this program move" from persisted artifacts alone —
the reference delegates device profiling to external Neuron tools and
scatters metrics across example code (SURVEY §5.1/§5.5); our earlier port
reproduced that fragmentation across ``trainer/metrics.py``,
``trainer/scalar_log.py``, ``utils/timeline.py`` and ``utils/profiling.py``.
This package correlates them:

- :mod:`.registry` — low-overhead counters / gauges / fixed-bucket
  histograms, serialized to the existing ``scalars.jsonl`` schema plus a
  Prometheus text exposition;
- :mod:`.flight` — a ring buffer of the last K step records (loss,
  grad-norm, host/device/data-wait step-time breakdown) dumped to
  ``flight_record.json`` on crash/SIGTERM, with built-in anomaly detectors
  (NaN/Inf loss, loss-spike z-score, throughput regression); and
  ``StepAccount``, a stepping loop's own books (host time by phase, CPU
  against off-CPU time, the stall rule), one flat record a step into the
  same ring;
- :mod:`.startup` — the process's account of its own start-up, always on:
  wall time by phase from the process's start to ``ready`` (import, mesh,
  weights, optimizer, engine, warm-up, first step) on the profiler's clock,
  JAX's compile path by stage inside them, and the package's one pair of
  ``jax.monitoring`` listeners;
- :mod:`.hlo_audit` — compile-time collective-op counts and byte volumes
  walked out of a compiled program's HLO (the reusable form of the
  assertions in ``tests/test_hlo_collectives.py``), one audit record per
  executable;
- :mod:`.schemas` — the checked-in schema list every JSONL artifact is
  validated against (the contract downstream tooling relies on);
- :mod:`.tracing` — request-lifecycle distributed tracing for the serving
  stack (ring-bounded span tracer, ``trace_events.jsonl`` + Perfetto
  exporters) and the trainer's Chrome-trace :class:`Timeline` (moved here
  from ``utils/timeline.py``, which re-exports it);
- :mod:`.metrics_server` — stdlib HTTP ``/metrics`` (live Prometheus
  text) + ``/healthz`` endpoints over a registry (CLI:
  ``tools/metrics_server.py``; live: ``runner.py serve --metrics-port``);
- :mod:`.health` — the fleet health monitor: threshold / EWMA-trend /
  multi-window SLO burn-rate rules evaluated over live registry
  snapshots, firing/resolved edges streamed to schema-checked
  ``alerts.jsonl`` (``fit(obs=Observability(health=True))``,
  ``ServingEngine(health=...)``, ``FleetRouter(health=...)``);
- :mod:`.aggregate` — fleet-wide metric aggregation: per-replica registry
  merge (sum/max/histogram-merge per metric kind), the replica-labeled
  ``/metrics?scope=fleet`` Prometheus exposition, and the
  :class:`~.aggregate.FleetHealth` control room the router drives;
- :mod:`.report` — merges scalars + timeline traces + flight records + HLO
  audits + request traces into one run summary (CLI:
  ``tools/obs_report.py``).

:class:`Observability` glues them into the one object ``fit()`` (and any
other driver) wires in.
"""

from __future__ import annotations

import os
import time
from typing import Any, Optional

from neuronx_distributed_tpu.obs.flight import (
    AnomalyDetector,
    FlightRecorder,
    LossSpikeDetector,
    NanLossDetector,
    StepAccount,
    ThroughputRegressionDetector,
    default_detectors,
)
from neuronx_distributed_tpu.obs.compile_ledger import (
    COMPILE_LEDGER_FILE,
    CompileLedger,
    read_compile_ledger,
    summarize_compile_records,
)
from neuronx_distributed_tpu.obs.hlo_audit import (
    append_audit,
    collective_bytes,
    collective_counts,
    comm_audit,
    read_audits,
)
from neuronx_distributed_tpu.obs.memory_ledger import (
    MEMORY_BREAKDOWN_FILE,
    MemoryLedger,
    read_memory_breakdown,
)
from neuronx_distributed_tpu.obs.health import (
    ALERT_SCHEMA,
    ALERTS_FILE,
    BurnRateRule,
    HealthMonitor,
    Rule,
    ThresholdRule,
    TrendRule,
    default_rules,
    read_alerts,
)
from neuronx_distributed_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
)
from neuronx_distributed_tpu.obs.schemas import (
    REGISTRY_METRICS,
    SCHEMAS,
    validate_jsonl,
    validate_record,
    validate_registry_metrics,
)
from neuronx_distributed_tpu.obs.tracing import (
    TRACE_EVENT_SCHEMA,
    TRACE_EVENTS_FILE,
    Span,
    Tracer,
    read_trace_events,
    write_chrome_trace,
)
from neuronx_distributed_tpu.obs.transfer_audit import TransferAudit
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

# canonical artifact names inside an obs run directory — obs/report.py and
# tools/obs_report.py look these up by name
SCALARS_FILE = "scalars.jsonl"
FLIGHT_FILE = "flight_record.json"
HLO_AUDIT_FILE = "hlo_audit.jsonl"
PROMETHEUS_FILE = "metrics.prom"

# step-time-style histogram boundaries (milliseconds)
MS_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
              1000.0, 2500.0, 5000.0, 10000.0, 30000.0)


class Observability:
    """The per-run telemetry hub: one registry, one flight recorder, one
    HLO-audit stream, all persisting under ``out_dir``.

    ``fit(obs=...)`` accepts either an instance (caller keeps the registry
    to add its own metrics) or a directory path (``fit`` builds one).  Every
    artifact it writes validates against :mod:`.schemas`, so downstream
    tooling (``tools/obs_report.py``, dashboards) can rely on the formats.
    """

    def __init__(
        self,
        out_dir: str,
        flight_capacity: int = 256,
        detectors: Optional[list] = None,
        timeline: Any = None,
        registry: Optional[MetricRegistry] = None,
        ledgers: bool = False,
        health: Any = False,
    ):
        self.out_dir = out_dir
        os.makedirs(out_dir, exist_ok=True)
        self.timeline = timeline
        self.registry = registry if registry is not None else MetricRegistry()
        self.scalars_path = os.path.join(out_dir, SCALARS_FILE)
        self.flight_path = os.path.join(out_dir, FLIGHT_FILE)
        self.hlo_audit_path = os.path.join(out_dir, HLO_AUDIT_FILE)
        self.prometheus_path = os.path.join(out_dir, PROMETHEUS_FILE)
        self.flight = FlightRecorder(
            capacity=flight_capacity,
            path=self.flight_path,
            detectors=detectors if detectors is not None else default_detectors(),
            timeline=timeline,
            registry=self.registry,
        )
        # resource ledgers (ledgers=True): compile accounting streamed to
        # compile_ledger.jsonl + per-subsystem memory watermarks with OOM
        # forensics into memory_breakdown.json — fit() threads them through
        # the train-step compile and its crash handler.  Off by default:
        # every consumer guards on `is not None`, so the hot path stays
        # allocation-free.
        self.compile_ledger: Optional[CompileLedger] = None
        self.memory_ledger: Optional[MemoryLedger] = None
        if ledgers:
            self.memory_ledger = MemoryLedger(
                registry=self.registry,
                path=os.path.join(out_dir, MEMORY_BREAKDOWN_FILE))
            self.compile_ledger = CompileLedger(
                path=os.path.join(out_dir, COMPILE_LEDGER_FILE),
                registry=self.registry, flight=self.flight,
                memory_ledger=self.memory_ledger)
        # fleet health monitor (health=True or a rule list builds one with
        # the default pack; pass a HealthMonitor to keep the rules/sink):
        # evaluated on the observe_step cadence over this hub's registry,
        # alert edges streamed to alerts.jsonl under out_dir.  Off by
        # default — every consumer guards on `is not None`, so the hot
        # path stays allocation-free (the ALERTS_EVALUATED discipline).
        self.health_monitor: Optional[HealthMonitor] = None
        if isinstance(health, HealthMonitor):
            self.health_monitor = health
            health.attach_registry(self.registry)
        elif health:
            if isinstance(health, str):  # a default-pack scope name
                rules = default_rules(health)
            elif isinstance(health, (list, tuple)):
                rules = list(health)
            else:
                # health=True: the hub serves BOTH fit() and serving
                # engines, so the bare boolean gets the union pack —
                # scope-specific rules over absent metrics stay silent
                rules = default_rules("all")
            self.health_monitor = HealthMonitor(
                rules, registry=self.registry,
                path=os.path.join(out_dir, ALERTS_FILE))
        self._last_step = 0
        self._closed = False
        # pre-declare the step metrics so a zero-step run still exports them
        self.registry.counter("train/steps_total")
        self.registry.histogram("train/step_time_ms", MS_BUCKETS)
        self.registry.histogram("train/data_wait_ms", MS_BUCKETS)

    # -- step path ---------------------------------------------------------

    def observe_step(self, step: int, **fields) -> list:
        """Record one training step (flight record + registry metrics);
        returns the anomaly warnings the detectors raised (possibly [])."""
        self._last_step = step
        reg = self.registry
        reg.counter("train/steps_total").inc()
        for key in ("loss", "grad_norm", "seq_per_sec"):
            if key in fields and fields[key] is not None:
                reg.gauge(f"train/{key}").set(float(fields[key]))
        if fields.get("step_time_s") is not None:
            reg.histogram("train/step_time_ms", MS_BUCKETS).observe(
                1e3 * float(fields["step_time_s"]))
        if fields.get("data_wait_s") is not None:
            reg.histogram("train/data_wait_ms", MS_BUCKETS).observe(
                1e3 * float(fields["data_wait_s"]))
        warnings = self.flight.record(step, **fields)
        if self.health_monitor is not None:
            self.health_monitor.on_step()
        return warnings

    # -- compile path ------------------------------------------------------

    def audit_executable(self, name: str, compiled: Any) -> dict:
        """Walk one compiled executable's HLO for collectives and persist
        the audit record; also mirrors the headline numbers as gauges."""
        rec = comm_audit(compiled, name=name)
        append_audit(self.hlo_audit_path, rec)
        for op, n in rec["collective_counts"].items():
            self.registry.gauge(f"hlo/{name}/{op}_count").set(float(n))
        self.registry.gauge(f"hlo/{name}/collective_bytes").set(
            float(rec["total_collective_bytes"]))
        logger.info(
            "obs: HLO audit %r: %s collectives, %.3e bytes moved",
            name, sum(rec["collective_counts"].values()),
            rec["total_collective_bytes"],
        )
        return rec

    # -- persistence -------------------------------------------------------

    def dump_scalars(self, step: Optional[int] = None) -> None:
        """Append the registry snapshot to ``scalars.jsonl`` (same schema as
        :class:`~..trainer.scalar_log.ScalarWriter`)."""
        self.registry.dump_jsonl(
            self.scalars_path, step if step is not None else self._last_step)

    def dump_flight(self, reason: str) -> Optional[str]:
        """Dump the flight-recorder ring to ``flight_record.json``."""
        return self.flight.dump(reason)

    def close(self, reason: str = "close") -> None:
        """Final persistence: last scalars snapshot, flight dump, Prometheus
        text export.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.dump_scalars()
        self.dump_flight(reason)
        if self.memory_ledger is not None:
            try:
                self.memory_ledger.poll_device()
                self.memory_ledger.dump(reason=reason)
            except OSError as e:  # telemetry IO must never mask the exit
                logger.warning("obs: memory breakdown dump failed: %s", e)
        if self.health_monitor is not None:
            self.health_monitor.close()
        with open(self.prometheus_path, "w") as f:
            f.write(self.registry.prometheus_text())

    def __enter__(self) -> "Observability":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close("exception:%s" % exc_type.__name__ if exc_type else "close")


__all__ = [
    "Observability",
    "MetricRegistry",
    "HealthMonitor",
    "Rule",
    "ThresholdRule",
    "TrendRule",
    "BurnRateRule",
    "default_rules",
    "read_alerts",
    "ALERTS_FILE",
    "ALERT_SCHEMA",
    "CompileLedger",
    "MemoryLedger",
    "read_compile_ledger",
    "read_memory_breakdown",
    "summarize_compile_records",
    "COMPILE_LEDGER_FILE",
    "MEMORY_BREAKDOWN_FILE",
    "Counter",
    "Gauge",
    "Histogram",
    "FlightRecorder",
    "StepAccount",
    "AnomalyDetector",
    "NanLossDetector",
    "LossSpikeDetector",
    "ThroughputRegressionDetector",
    "default_detectors",
    "comm_audit",
    "collective_counts",
    "collective_bytes",
    "append_audit",
    "read_audits",
    "SCHEMAS",
    "REGISTRY_METRICS",
    "validate_record",
    "validate_jsonl",
    "validate_registry_metrics",
    "TransferAudit",
    "Tracer",
    "Span",
    "read_trace_events",
    "write_chrome_trace",
    "TRACE_EVENTS_FILE",
    "TRACE_EVENT_SCHEMA",
    "SCALARS_FILE",
    "FLIGHT_FILE",
    "HLO_AUDIT_FILE",
    "PROMETHEUS_FILE",
    "MS_BUCKETS",
]
