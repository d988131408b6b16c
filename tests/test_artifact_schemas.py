"""Schema-stability smoke test: every JSONL/JSON artifact the framework
emits parses against the checked-in schema list (``obs.schemas.SCHEMAS``),
so downstream tooling — ``tools/obs_report.py``, dashboards — can rely on
the formats.

Covers both directions: committed artifacts in the repo validate as-is, and
every live emitter's fresh output validates too.  A failure here means an
emitter changed a required field — bump the artifact's schema version and
update ``SCHEMAS`` deliberately instead."""

import json
import os

import pytest

from neuronx_distributed_tpu.obs import Observability
from neuronx_distributed_tpu.obs.hlo_audit import append_audit, comm_audit
from neuronx_distributed_tpu.obs.registry import MetricRegistry
from neuronx_distributed_tpu.obs.schemas import (
    SCHEMAS,
    validate_flight_document,
    validate_jsonl,
    validate_record,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_schema_list_is_complete():
    """The artifact kinds the framework documents all have schemas."""
    assert {"scalars", "flight_record", "flight_step", "anomaly",
            "hlo_audit", "obs_report",
            "serving_stats", "supervisor_event",
            "router_stats", "trace_event",
            "compile_ledger", "memory_breakdown", "alert",
            "autopilot_action", "weight_swap"} <= set(SCHEMAS)


def test_committed_golden_scalars_validate():
    path = os.path.join(REPO, "docs", "convergence", "golden_parity",
                        "scalars.jsonl")
    if not os.path.exists(path):
        pytest.skip("no committed golden scalars")
    assert validate_jsonl("scalars", path) > 0


def test_scalar_writer_output_validates(tmp_path):
    from neuronx_distributed_tpu.trainer.scalar_log import ScalarWriter

    with ScalarWriter(str(tmp_path), use_tensorboard=False) as w:
        w.scalars(0, loss=2.0, grad_norm=1.5)
        w.scalar("eval_loss", 1.9, step=1)
    assert validate_jsonl("scalars", str(tmp_path / "scalars.jsonl")) == 3


def test_registry_dump_validates(tmp_path):
    reg = MetricRegistry()
    reg.counter("c").inc()
    reg.histogram("h", (1.0, 2.0)).observe(1.5)
    path = str(tmp_path / "scalars.jsonl")
    reg.dump_jsonl(path, step=3)
    assert validate_jsonl("scalars", path) >= 4  # c + h/count + h/sum + edges


def test_flight_and_audit_and_report_validate(tmp_path):
    obs = Observability(str(tmp_path / "obs"), flight_capacity=8)
    for i in range(5):
        obs.observe_step(i, loss=2.0, grad_norm=1.0, seq_per_sec=8.0,
                         step_time_s=0.01, data_wait_s=0.0)
    obs.observe_step(5, loss=float("nan"))  # exercise the anomaly schema
    # a crafted-text audit exercises the jsonl writer without a compile
    append_audit(obs.hlo_audit_path,
                 comm_audit("%r = f32[8]{0} all-reduce(f32[8]{0} %x)",
                            name="crafted"))
    obs.close("schema_test")

    with open(obs.flight_path) as f:
        validate_flight_document(json.load(f))
    assert validate_jsonl("hlo_audit", obs.hlo_audit_path) == 1
    assert validate_jsonl("scalars", obs.scalars_path) > 0

    from neuronx_distributed_tpu.obs.report import build_report

    report = build_report(run_dir=obs.out_dir)
    validate_record("obs_report", report)
    assert report["health"]["anomaly_count"] == 1


def test_serving_stats_schema(tmp_path):
    """One serving_stats record per terminal request: the shape the serving
    engine emits (the live-emitter path is validated end-to-end in
    tests/test_serving.py) — including the null ttft_ms of a request that
    never produced a token."""
    from neuronx_distributed_tpu.serving.engine import SERVING_STATS_SCHEMA

    recs = [
        # a speculative engine's record: proposed/accepted + acceptance rate
        {"schema": SERVING_STATS_SCHEMA, "time": 1.0, "request_id": 0,
         "state": "finished", "finish_reason": "length", "prompt_len": 5,
         "new_tokens": 8, "queue_ms": 0.5, "ttft_ms": 12.0, "total_ms": 40.0,
         "spec_proposed": 12, "spec_accepted": 9, "acceptance_rate": 0.75,
         "adapter_id": 0, "priority": "interactive", "deadline_s": None,
         "queue_wait_ms": 0.5, "preemptions": 0, "shed_reason": None,
         "mono": 100.25, "decode_steps": 4, "prefill_chunks": 0,
         "preempted_ms": 0.0, "trace_id": None, "weights_version": 0},
        # a non-speculative, multi-tenant, batch-tier record: served under
        # LoRA adapter 3, preempted once, shed at the pre-prefill expiry
        # check, linked into trace_events.jsonl via trace_id (v5)
        {"schema": SERVING_STATS_SCHEMA, "time": 2.0, "request_id": 1,
         "state": "timed_out", "finish_reason": "timed_out", "prompt_len": 3,
         "new_tokens": 0, "queue_ms": 100.0, "ttft_ms": None,
         "total_ms": 100.0, "spec_proposed": 0, "spec_accepted": 0,
         "acceptance_rate": None, "adapter_id": 3, "priority": "batch",
         "deadline_s": 0.25, "queue_wait_ms": 100.0, "preemptions": 1,
         "shed_reason": "expired_before_prefill",
         "mono": 101.5, "decode_steps": 0, "prefill_chunks": 2,
         "preempted_ms": 40.0, "trace_id": 1, "weights_version": 2},
    ]
    path = tmp_path / "serving_stats.jsonl"
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    assert validate_jsonl("serving_stats", str(path)) == 2
    with pytest.raises(ValueError, match="missing required field"):
        validate_record("serving_stats", {"schema": SERVING_STATS_SCHEMA})
    with pytest.raises(ValueError, match="expected"):
        bad = dict(recs[0], new_tokens="8")
        validate_record("serving_stats", bad)
    with pytest.raises(ValueError, match="missing required field"):
        # a v3-shaped record (no SLO fields) no longer validates
        v3 = dict(recs[0])
        for f in ("priority", "deadline_s", "queue_wait_ms", "preemptions",
                  "shed_reason"):
            v3.pop(f)
        validate_record("serving_stats", v3)
    with pytest.raises(ValueError, match="missing required field"):
        # a v4-shaped record (no tracing fields) no longer validates against
        # the live-emitter floor — but obs.report still READS it (the
        # version-tolerant reader is covered in tests/test_tracing.py)
        v4 = dict(recs[0])
        for f in ("mono", "decode_steps", "prefill_chunks", "preempted_ms",
                  "trace_id"):
            v4.pop(f)
        validate_record("serving_stats", v4)
    with pytest.raises(ValueError, match="missing required field"):
        # a v5-shaped record (no weights_version) no longer validates
        # against the live-emitter floor; obs.report reads it as version 0
        v5 = dict(recs[0])
        v5.pop("weights_version")
        validate_record("serving_stats", v5)

    # the SLO counters/per-class histograms are declared with their kinds,
    # and a live SLO-serving registry validates + grows the report line
    from neuronx_distributed_tpu.obs.schemas import (
        REGISTRY_METRICS,
        validate_registry_metrics,
    )

    assert {"serving/preemptions_total", "serving/shed_total",
            "serving/expired_before_prefill_total",
            "serving/prefill_chunks_total",
            "serving/ttft_ms_interactive",
            "serving/intertoken_ms_batch"} <= set(REGISTRY_METRICS)
    reg = MetricRegistry()
    reg.counter("serving/preemptions_total").inc(2)
    reg.counter("serving/shed_total").inc()
    reg.counter("serving/expired_before_prefill_total").inc()
    reg.counter("serving/prefill_chunks_total").inc(5)
    from neuronx_distributed_tpu.obs import MS_BUCKETS
    reg.histogram("serving/ttft_ms_interactive", MS_BUCKETS).observe(12.0)
    reg.histogram("serving/intertoken_ms_interactive",
                  MS_BUCKETS).observe(3.0)
    validate_registry_metrics(reg)

    from neuronx_distributed_tpu.obs.registry import read_histograms
    from neuronx_distributed_tpu.obs.report import (
        _summarize_scalars,
        _summarize_slo,
        render_markdown,
    )

    scalar_recs = reg.to_scalar_records(step=1)
    hists = read_histograms(scalar_recs)
    slo = _summarize_slo(_summarize_scalars(scalar_recs, frozenset(hists)),
                         hists)
    assert slo is not None
    assert slo["preemptions"] == 2.0 and slo["shed"] == 1.0
    assert slo["expired_before_prefill"] == 1.0
    assert slo["prefill_chunks"] == 5.0
    assert "interactive" in slo["classes"]
    report_md = render_markdown({
        "schema": "obs_report_v1", "health": {
            "anomaly_count": 0, "host_blocked": {}, "slo": slo,
            "total_collective_count": 0, "total_collective_bytes": 0,
            "restarts": 0},
        "scalars": {}, "histograms": {}, "flight": None, "anomalies": [],
        "hlo_audits": [], "timeline": {"events": 0, "instants": 0,
                                       "files": 0, "total_ms_by_name": {}},
        "supervisor": None,
    })
    assert "slo:" in report_md and "preemption" in report_md


def test_router_stats_schema_and_fleet_report_line(tmp_path):
    """One router_stats record per terminal fleet request (the live-emitter
    path is validated end-to-end in tests/test_fleet.py), the ``router/*``
    registry metrics are declared with their kinds, and the obs report
    grows a fleet health section from them."""
    from neuronx_distributed_tpu.obs.schemas import (
        REGISTRY_METRICS,
        validate_registry_metrics,
    )
    from neuronx_distributed_tpu.serving.fleet import ROUTER_STATS_SCHEMA

    recs = [
        # a request that survived a failover: dispatched twice, requeued once
        {"schema": ROUTER_STATS_SCHEMA, "time": 1.0, "request_id": 1 << 32,
         "client_id": 0, "replica": 2, "state": "finished",
         "finish_reason": "length", "dispatches": 2, "requeues": 1,
         "migrations": 0, "role": "mixed",
         "affinity_pages": 3, "new_tokens": 8, "policy": "prefix_affinity"},
        # a router-held cancellation: never reached an engine (role null)
        {"schema": ROUTER_STATS_SCHEMA, "time": 2.0,
         "request_id": (1 << 32) | 1, "client_id": 1, "replica": -1,
         "state": "cancelled", "finish_reason": "cancelled", "dispatches": 0,
         "requeues": 0, "migrations": 0, "role": None,
         "affinity_pages": 0, "new_tokens": 0,
         "policy": "prefix_affinity"},
        # a disaggregated request: prefilled on a prefill-role replica,
        # migrated once, finished on decode capacity (v2 fields live)
        {"schema": ROUTER_STATS_SCHEMA, "time": 3.0,
         "request_id": (1 << 32) | 2, "client_id": 2, "replica": 1,
         "state": "finished", "finish_reason": "stop", "dispatches": 2,
         "requeues": 0, "migrations": 1, "role": "decode",
         "affinity_pages": 2, "new_tokens": 4, "policy": "role_aware"},
    ]
    path = tmp_path / "router_stats.jsonl"
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    assert validate_jsonl("router_stats", str(path)) == 3
    with pytest.raises(ValueError, match="missing required field"):
        validate_record("router_stats", {"schema": ROUTER_STATS_SCHEMA})
    with pytest.raises(ValueError, match="expected"):
        validate_record("router_stats", dict(recs[0], requeues=None))

    assert {"router/dispatched_total", "router/requeued_total",
            "router/failovers_total", "router/affinity_hits_total",
            "router/replicas_alive",
            "router/fleet_prefix_hit_rate"} <= set(REGISTRY_METRICS)

    # a live router's registry validates, and its scalars grow the report's
    # fleet health line
    reg = MetricRegistry()
    for _ in range(3):
        reg.counter("router/dispatched_total").inc()
    reg.counter("router/requeued_total").inc()
    reg.counter("router/failovers_total").inc()
    reg.counter("router/affinity_hits_total").inc(2)
    reg.counter("router/affinity_misses_total").inc()
    reg.gauge("router/replicas_alive").set(4)
    validate_registry_metrics(reg)
    reg.dump_jsonl(str(tmp_path / "scalars.jsonl"), step=1)

    from neuronx_distributed_tpu.obs.report import build_report, render_markdown

    report = build_report(run_dir=str(tmp_path))
    validate_record("obs_report", report)
    fleet = report["health"]["fleet"]
    assert fleet["dispatched"] == 3.0 and fleet["failovers"] == 1.0
    assert fleet["affinity_hit_rate"] == round(2 / 3, 4)
    assert "- fleet: 4 replica(s) in rotation" in render_markdown(report)


def test_supervisor_events_validate_and_merge_into_report(tmp_path):
    """The live supervisor emitter's events validate against the schema, and
    the obs report merges them (restarts / causes / final outcome)."""
    import sys

    from neuronx_distributed_tpu.resilience.supervisor import Supervisor

    events = str(tmp_path / "supervisor_events.jsonl")
    sup = Supervisor([sys.executable, "-c", "print('ok')"],
                     events_path=events, max_restarts=0)
    res = sup.run()
    assert res.ok
    assert validate_jsonl("supervisor_event", events) == 3  # start/exit/success
    with pytest.raises(ValueError, match="missing required field"):
        validate_record("supervisor_event", {"schema": "supervisor_events/1",
                                             "time": 1.0, "event": "start"})

    from neuronx_distributed_tpu.obs.report import build_report

    report = build_report(run_dir=str(tmp_path))
    validate_record("obs_report", report)
    assert report["supervisor"]["succeeded"] is True
    assert report["supervisor"]["restarts"] == 0
    assert report["health"]["restarts"] == 0


def test_registry_metric_contract_for_async_hot_path(tmp_path):
    """The prefetch / transfer-audit / host-blocked registry metrics are
    declared in obs.schemas.REGISTRY_METRICS with their kinds, a live
    emitter's registry validates against the declaration, its scalars.jsonl
    dump stays schema-checked, and a kind mismatch is caught."""
    import numpy as np

    from neuronx_distributed_tpu.data.prefetch import DevicePrefetcher
    from neuronx_distributed_tpu.obs import TransferAudit
    from neuronx_distributed_tpu.obs.schemas import (
        REGISTRY_METRICS,
        validate_registry_metrics,
    )

    assert {"data/prefetch_queue_depth", "data/prefetch_staged_ahead",
            "data/prefetch_rewinds_total", "data/prefetch_wait_ms",
            "train/host_blocked_ms", "serving/host_blocked_ms",
            "transfer/explicit_fetches_total",
            "transfer/fetch_wait_ms"} <= set(REGISTRY_METRICS)

    reg = MetricRegistry()
    audit = TransferAudit(reg)
    with DevicePrefetcher(lambda s: np.full((2,), s, np.int32),
                          depth=2, registry=reg) as pf:
        staged = pf.get(0)
    with audit.section("test"):
        audit.fetch(staged, label="train")
    validate_registry_metrics(reg)  # live kinds match the declaration

    path = str(tmp_path / "scalars.jsonl")
    reg.dump_jsonl(path, step=1)
    assert validate_jsonl("scalars", path) > 8  # counters + histogram edges

    bad = MetricRegistry()
    bad.counter("train/host_blocked_ms")  # declared a histogram
    with pytest.raises(ValueError, match="misfile"):
        validate_registry_metrics(bad)


def test_validate_record_rejects_bad_records():
    with pytest.raises(ValueError, match="missing required field"):
        validate_record("scalars", {"step": 1, "tag": "x", "time": 0.0})
    with pytest.raises(ValueError, match="expected"):
        validate_record("scalars",
                        {"step": "1", "tag": "x", "value": 1.0, "time": 0.0})
    with pytest.raises(ValueError, match="unknown artifact kind"):
        validate_record("nope", {})
    # bools must not pass as numeric metric values
    with pytest.raises(ValueError, match="bool"):
        validate_record("scalars",
                        {"step": 1, "tag": "x", "value": True, "time": 0.0})


def test_compile_ledger_and_memory_breakdown_schemas(tmp_path):
    """The resource-ledger emitters honor their checked-in schemas (the
    live engine/fit paths are validated end-to-end in
    tests/test_resource_ledgers.py), the trace/compile* + mem/* registry
    metrics are declared with their kinds, and the obs report grows the
    compile/memory sections from the artifacts."""
    from neuronx_distributed_tpu.obs import CompileLedger, MemoryLedger
    from neuronx_distributed_tpu.obs.schemas import (
        REGISTRY_METRICS,
        validate_registry_metrics,
    )

    led = CompileLedger(path=str(tmp_path / "compile_ledger.jsonl"))
    led.set_capacity("decode_pages", 1)
    led.record_compile("decode_pages", ("fp", True), 42.0, kind="jit")
    led.record_eviction("decode_pages", ("fp", True))
    led.declare_warmup_done()
    led.record_compile("verify_pages", 3, 10.0, kind="jit")  # storm
    n = validate_jsonl("compile_ledger", str(tmp_path / "compile_ledger.jsonl"))
    assert n == len(led.rows)
    with pytest.raises(ValueError, match="missing required field"):
        validate_record("compile_ledger", {"schema": "compile_ledger/1"})
    with pytest.raises(ValueError, match="expected"):
        validate_record("compile_ledger", dict(led.rows[0], wall_ms="slow"))

    ml = MemoryLedger(path=str(tmp_path / "memory_breakdown.json"))
    ml.set("kv_pool", 4096)
    ml.dump()
    doc = json.load(open(tmp_path / "memory_breakdown.json"))
    validate_record("memory_breakdown", doc)
    with pytest.raises(ValueError, match="missing required field"):
        validate_record("memory_breakdown", {"schema": doc["schema"]})

    assert {"trace/compiles_total", "trace/compile_ms",
            "trace/compile_storms_total", "trace/compile_thrash_total",
            "trace/compiled_cache_evictions_total",
            "mem/kv_pool_bytes", "mem/params_bytes",
            "mem/workspace_bytes"} <= set(REGISTRY_METRICS)
    reg = MetricRegistry()
    led2 = CompileLedger(registry=reg)
    led2.record_compile("context", "aot", 100.0, kind="aot")
    MemoryLedger(registry=reg).set("kv_pool", 123)
    validate_registry_metrics(reg)

    from neuronx_distributed_tpu.obs.report import build_report, render_markdown

    reg.dump_jsonl(str(tmp_path / "scalars.jsonl"), step=1)
    report = build_report(run_dir=str(tmp_path))
    validate_record("obs_report", report)
    assert report["compile"]["compiles"] == 2  # from the jsonl rollup
    assert report["compile"]["storms"] == 1
    assert report["memory"]["subsystems"]["kv_pool"]["bytes"] == 4096
    md = render_markdown(report)
    assert "- compile:" in md and "1 storm(s)" in md
    assert "## Memory ledger" in md


def test_alert_schema_and_registry_metrics(tmp_path):
    """alerts.jsonl smoke: the HealthMonitor's own sink validates against
    the checked-in alert schema (the live engine/fleet emitter paths are
    covered end-to-end in tests/test_health.py), the obs/alerts_* registry
    pair is declared with its kinds, and hand-built records missing the
    edge fields are rejected."""
    from neuronx_distributed_tpu.obs.health import (
        HealthMonitor,
        ThresholdRule,
        read_alerts,
    )
    from neuronx_distributed_tpu.obs.schemas import (
        REGISTRY_METRICS,
        validate_registry_metrics,
    )

    assert {"obs/alerts_firing", "obs/alerts_total"} <= set(REGISTRY_METRICS)
    reg = MetricRegistry()
    path = str(tmp_path / "alerts.jsonl")
    mon = HealthMonitor([ThresholdRule("queue_backlog", "g", 1.0)],
                        registry=reg, path=path)
    reg.gauge("g").set(5.0)
    mon.evaluate()
    reg.gauge("g").set(0.0)
    mon.evaluate()
    mon.set_condition("replica_down", True, key="1", severity="page")
    mon.close()
    assert validate_jsonl("alert", path) == 3
    recs = read_alerts(path)
    assert [r["state"] for r in recs] == ["firing", "resolved", "firing"]
    assert recs[1]["duration_s"] >= 0.0  # resolve edges carry duration
    assert recs[2]["key"] == "1"         # conditions carry their key
    validate_registry_metrics(reg)
    with pytest.raises(ValueError, match="missing required field"):
        bad = dict(recs[0])
        del bad["mono"]
        validate_record("alert", bad)
    with pytest.raises(ValueError, match="expected"):
        validate_record("alert", dict(recs[0], observed="high"))

    # ... and the report's alerts section builds from the artifact
    from neuronx_distributed_tpu.obs.report import build_report

    report = build_report(run_dir=str(tmp_path))
    validate_record("obs_report", report)
    assert report["alerts"]["firing"] == 1
    assert report["alerts"]["worst_severity"] == "page"


def test_trace_events_schema(tmp_path):
    """trace_events.jsonl smoke: the Tracer's own export validates against
    the checked-in trace_event schema (the live serving-engine emitter path
    is covered end-to-end in tests/test_tracing.py), and hand-built records
    missing either clock stamp are rejected."""
    from neuronx_distributed_tpu.obs import Tracer

    tr = Tracer()
    root = tr.begin("request", request_id=7, priority="interactive")
    q = tr.begin("queue", request_id=7, parent=root)
    tr.end(q, slot=0)
    tr.end(root, state="finished")
    path = tmp_path / "trace_events.jsonl"
    assert tr.export_jsonl(str(path)) == 2
    assert validate_jsonl("trace_event", str(path)) == 2
    recs = [json.loads(l) for l in open(path)]
    assert recs[0]["name"] == "queue" and recs[0]["parent_id"] is not None
    # both clocks on every span: wall ts for cross-host merges, monotonic
    # mono for skew-free ordering
    for r in recs:
        assert r["mono"] == r["t_start"] and "ts" in r
    with pytest.raises(ValueError, match="missing required field"):
        bad = dict(recs[0])
        bad.pop("mono")
        validate_record("trace_event", bad)
    with pytest.raises(ValueError, match="expected"):
        validate_record("trace_event", dict(recs[0], attrs=None))


def test_autopilot_action_schema_report_and_compare_gate(tmp_path):
    """autopilot_actions.jsonl smoke: the controller's live emitter path
    is covered in tests/test_autopilot.py; here the checked-in schema,
    the autopilot/* registry declarations, the report's autopilot
    section, and the --compare action-rate regression gate are pinned
    from hand-built artifacts."""
    from neuronx_distributed_tpu.obs.schemas import REGISTRY_METRICS

    assert "autopilot_action" in SCHEMAS
    assert {"autopilot/actions_total", "autopilot/scale_outs_total",
            "autopilot/scale_ins_total", "autopilot/drains_total",
            "autopilot/restarts_total",
            "autopilot/admission_tightenings_total",
            "autopilot/rebalances_total",
            "autopilot/mode"} <= set(REGISTRY_METRICS)

    def rec(mono, action, trigger, replica=-1):
        return {"schema": "autopilot_action/1", "time": 100.0 + mono,
                "mono": mono, "action": action, "trigger": trigger,
                "mode": "auto", "replica": replica, "detail": {},
                "edge": None, "budget_remaining": 7}

    a_dir = tmp_path / "a"
    b_dir = tmp_path / "b"
    for d in (a_dir, b_dir):
        d.mkdir()
        (d / "autopilot_actions.jsonl").write_text("")
    rows = [rec(0.0, "scale_out", "slo_burn_fast_interactive", replica=2),
            rec(5.0, "tighten", "slo_burn_fast_interactive"),
            rec(60.0, "relax", "burn_resolved")]
    path = str(b_dir / "autopilot_actions.jsonl")
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert validate_jsonl("autopilot_action", path) == 3
    with pytest.raises(ValueError, match="missing required field"):
        bad = dict(rows[0])
        del bad["budget_remaining"]
        validate_record("autopilot_action", bad)
    with pytest.raises(ValueError, match="expected"):
        validate_record("autopilot_action", dict(rows[0], detail=None))

    from neuronx_distributed_tpu.obs.report import (
        build_report,
        compare_resources,
        render_markdown,
    )

    report = build_report(run_dir=str(b_dir))
    validate_record("obs_report", report)
    ap = report["autopilot"]
    assert ap["actions"] == 3
    assert ap["by_action"] == {"scale_out": 1, "tighten": 1, "relax": 1}
    assert ap["triggers"]["slo_burn_fast_interactive"]["actions"] == 2
    assert ap["span_s"] == 60.0 and ap["rate_per_s"] == pytest.approx(0.05)
    assert report["health"]["autopilot"]["actions"] == 3
    md = render_markdown(report)
    assert "## Autopilot actions" in md and "- autopilot:" in md

    # an autopilot that never acted still reports (empty ledger != off)
    quiet = build_report(run_dir=str(a_dir))
    validate_record("obs_report", quiet)
    assert quiet["autopilot"]["actions"] == 0
    assert "never had to act" in render_markdown(quiet)

    # compare gate: actions in B when A's controller never acted is a
    # threshold-free regression; a run against itself is clean
    diff = compare_resources(str(a_dir), str(b_dir))
    assert diff["regressed"]
    assert any("autopilot" in r for r in diff["regressions"])
    assert not compare_resources(str(b_dir), str(b_dir))["regressed"]
