"""Request-lifecycle distributed tracing tests (obs/tracing.py + the
threading through scheduler/engine/fleet) and the metrics-server satellite.

Four layers:

- TRACER units — pure host-side: ring bound + drop accounting, span
  parenting and ids, per-replica scopes over one shared ring, both
  exporters (schema-checked ``trace_events.jsonl``, Perfetto-parseable
  Chrome JSON);
- ZERO-OVERHEAD-OFF — the acceptance bar's other half: a full serving run
  with ``tracer=None`` (the default) allocates NO span objects, asserted
  via the ``obs.tracing.SPANS_CREATED`` counter (no profiler needed);
- E2E stitched traces on the CPU tiny Llama — a preempted + requeued
  request and a fleet-failover clone each produce ONE trace (all spans
  share the global id) whose phase spans are schema-valid, monotonic,
  parented under their roots, and SUM to the request's reported
  ``serving_stats``/output latency (±ms — phase boundaries share single
  timestamps by construction);
- satellites: serving_stats v5 live-emitter validation + the
  version-tolerant v4 reader, the obs_report ``--trace`` waterfall
  section, wall+mono stamps on registry records, and the stdlib
  Prometheus ``/metrics`` + ``/healthz`` server.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_cli, sharded_params
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import MetricRegistry, Tracer, tracing
from neuronx_distributed_tpu.obs.metrics_server import (
    MetricsServer,
    prometheus_from_scalars,
)
from neuronx_distributed_tpu.obs.report import (
    build_report,
    read_serving_stats,
    render_markdown,
    summarize_trace,
)
from neuronx_distributed_tpu.obs.schemas import validate_jsonl, validate_record
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.resilience import clear_plan, install_plan
from neuronx_distributed_tpu.serving import (
    FleetRouter,
    Replica,
    Request,
    ServingEngine,
)
from neuronx_distributed_tpu.serving.driver import replay
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

pytestmark = pytest.mark.trace

PHASES = ("queue", "prefill", "decode", "preempted")


# -- tracer units ------------------------------------------------------------

def test_ring_bound_drops_oldest_and_counts():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.end(tr.begin(f"s{i}"))
    spans = tr.spans()
    assert len(spans) == 4
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6


def test_span_ids_parenting_and_contextmanager():
    tr = Tracer()
    with tr.span("root", request_id=3) as root:
        with tr.span("child", request_id=3, parent=root) as child:
            pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["child"].parent_id == spans["root"].span_id
    assert spans["root"].parent_id is None
    assert spans["child"].span_id != spans["root"].span_id
    assert spans["child"].t_end >= spans["child"].t_start
    inst = tr.instant("marker", request_id=3, note="x")
    assert inst.t_end == inst.t_start and inst.attrs["note"] == "x"


def test_scoped_replicas_share_ring_and_sequence():
    tr = Tracer()
    a, b = tr.scoped(0), tr.scoped(1)
    a.end(a.begin("x", request_id=1))
    b.end(b.begin("y", request_id=1))
    spans = tr.spans()  # the parent handle sees both scopes' spans
    assert [s.replica for s in spans] == [0, 1]
    assert len({s.span_id for s in spans}) == 2  # one shared id sequence


def test_explicit_timestamps_tile_phases():
    """Adjacent phases given the same boundary instant sum exactly."""
    tr = Tracer(clock=lambda: 0.0)
    q = tr.begin("queue", request_id=1, t=1.0)
    tr.end(q, t=2.0)
    p = tr.begin("prefill", request_id=1, t=2.0)
    tr.end(p, t=3.5)
    assert sum(s.duration_ms for s in tr.spans()) == pytest.approx(2500.0)


def test_exporters_jsonl_schema_and_perfetto(tmp_path):
    tr = Tracer(replica=2)
    root = tr.begin("request", request_id=9, hop=0)
    tr.end(tr.begin("queue", request_id=9, parent=root), slot=1)
    tr.end(root, state="finished")
    ev = tmp_path / "trace_events.jsonl"
    ch = tmp_path / "trace.json"
    assert tr.export_jsonl(str(ev)) == 2
    assert validate_jsonl("trace_event", str(ev)) == 2
    tr.export_chrome(str(ch))
    # the Perfetto-tolerant array format parses line-wise (obs.report's
    # timeline parser accepts exactly this shape)
    from neuronx_distributed_tpu.obs.report import _parse_timeline

    events = _parse_timeline(str(ch))
    xs = [e for e in events if e.get("ph") == "X"]
    ms = [e for e in events if e.get("ph") == "M"]
    assert len(xs) == 2 and ms, "complete events + metadata tracks"
    assert all(e["pid"] == 2 for e in xs), "pid = replica"


# -- registry wall + mono satellite ------------------------------------------

def test_registry_records_carry_wall_and_mono():
    reg = MetricRegistry()
    reg.counter("c").inc()
    recs = reg.to_scalar_records(step=1)
    assert recs and all("mono" in r and "time" in r for r in recs)
    # injectable for deterministic artifacts
    recs = reg.to_scalar_records(step=1, now=10.0, mono=5.0)
    assert recs[0]["time"] == 10.0 and recs[0]["mono"] == 5.0
    validate_record("scalars", recs[0])  # extra key rides the v1 schema


# -- metrics server satellite ------------------------------------------------

def test_metrics_server_serves_metrics_and_healthz():
    reg = MetricRegistry()
    reg.counter("serving/tokens_total").inc(7)
    state = {"ok": True}
    with MetricsServer(reg, health_fn=lambda: dict(state),
                       port=0, host="127.0.0.1") as srv:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "# TYPE serving_tokens_total counter" in body
        assert "serving_tokens_total 7" in body
        health = json.loads(
            urllib.request.urlopen(base + "/healthz").read().decode())
        assert health["ok"] is True
        state["ok"] = False  # a dead target must fail LB checks with 503
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/healthz")
        assert exc.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/nope")
        assert exc.value.code == 404


def test_prometheus_from_scalars_reassembles_histograms():
    reg = MetricRegistry()
    reg.counter("serving/tokens_total").inc(3)
    reg.gauge("serving/queue_depth").set(2)
    reg.histogram("serving/step_ms", (1.0, 10.0)).observe(0.5)
    text = prometheus_from_scalars(reg.to_scalar_records(step=4))
    assert "# TYPE serving_tokens_total counter" in text
    assert "serving_tokens_total 3" in text
    assert "# TYPE serving_queue_depth gauge" in text
    assert 'serving_step_ms_bucket{le="+Inf"} 1' in text
    assert "serving_step_ms_count 1" in text


# -- serving_stats v4/v5 reader ----------------------------------------------

def test_read_serving_stats_fills_v4_defaults(tmp_path):
    v4 = {"schema": "serving_stats/4", "time": 1.0, "request_id": 0,
          "state": "finished", "finish_reason": "length", "prompt_len": 4,
          "new_tokens": 2, "queue_ms": 1.0, "ttft_ms": 5.0, "total_ms": 9.0,
          "spec_proposed": 0, "spec_accepted": 0, "acceptance_rate": None,
          "adapter_id": 0, "priority": "interactive", "deadline_s": None,
          "queue_wait_ms": 1.0, "preemptions": 0, "shed_reason": None}
    path = tmp_path / "serving_stats.jsonl"
    path.write_text(json.dumps(v4) + "\n")
    [rec] = read_serving_stats(str(path))
    assert rec["decode_steps"] == 0 and rec["prefill_chunks"] == 0
    assert rec["preempted_ms"] == 0.0 and rec["trace_id"] is None
    assert rec["mono"] is None


# -- waterfall section -------------------------------------------------------

def test_summarize_trace_waterfall_and_markdown(tmp_path):
    tr = Tracer(replica=0, clock=lambda: 0.0)
    for rid, (q, p, d) in {1: (1.0, 2.0, 3.0), 2: (0.5, 0.5, 9.0)}.items():
        root = tr.begin("request", request_id=rid, hop=0, t=0.0)
        tr.end(tr.begin("queue", request_id=rid, parent=root, t=0.0), t=q)
        tr.end(tr.begin("prefill", request_id=rid, parent=root, t=q),
               t=q + p)
        tr.end(tr.begin("decode", request_id=rid, parent=root, t=q + p),
               t=q + p + d)
        tr.end(root, t=q + p + d, state="finished")
    ev = tmp_path / "trace_events.jsonl"
    tr.export_jsonl(str(ev))
    stats = [{"trace_id": 2, "total_ms": 10_000.0, "state": "finished"}]
    trace = summarize_trace([str(ev)], stats)
    assert trace["requests"] == 2 and trace["spans"] == 8
    slowest = trace["slowest"]
    assert slowest[0]["request_id"] == 2  # 10s beats 6s
    assert slowest[0]["total_ms"] == pytest.approx(10_000.0)
    assert slowest[0]["decode_ms"] == pytest.approx(9_000.0)
    assert slowest[0]["stats_total_ms"] == 10_000.0
    md = render_markdown({
        "schema": "obs_report_v1", "trace": trace,
        "health": {"anomaly_count": 0, "host_blocked": {},
                   "total_collective_count": 0, "total_collective_bytes": 0,
                   "restarts": 0},
        "scalars": {}, "histograms": {}, "flight": None, "anomalies": [],
        "hlo_audits": [], "timeline": {"events": 0, "instants": 0,
                                       "files": 0, "total_ms_by_name": {}},
        "supervisor": None,
    })
    assert "Request traces" in md and "| 2 | finished |" in md
    assert summarize_trace([str(tmp_path / "missing.jsonl")]) is None


# -- e2e: CPU tiny Llama -----------------------------------------------------

@pytest.fixture
def paged_pool(devices8):
    """B=3 paged pool model + B=1 solo reference (page 4 divides C=8 and
    T=16) — the same shape as the test_slo_serving serving fixture."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none",
    )
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((3, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    solo = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=1, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    return cfg, pool, solo


def _phases_by_request(spans):
    """{gid: {phase: total_ms}} over the four lifecycle phases."""
    out = {}
    for s in spans:
        rid = s["request_id"]
        if rid < 0 or s["name"] not in PHASES:
            continue
        out.setdefault(rid, {p: 0.0 for p in PHASES})
        out[rid][s["name"]] += (s["t_end"] - s["t_start"]) * 1e3
    return out


def _assert_parented_and_monotonic(spans, gid):
    """Every phase span of ``gid`` is parented under one of its root spans
    and monotonic; span ids are unique."""
    mine = [s for s in spans if s["request_id"] == gid]
    roots = {s["span_id"] for s in mine if s["name"] == "request"}
    assert roots, f"request {gid} has no root span"
    ids = [s["span_id"] for s in mine]
    assert len(ids) == len(set(ids)), "duplicate span ids"
    for s in mine:
        assert s["t_end"] >= s["t_start"], f"non-monotonic span {s['name']}"
        if s["name"] in PHASES:
            assert s["parent_id"] in roots, (
                f"phase {s['name']} of {gid} not parented under a root")


def test_tracer_off_is_zero_span_allocations(paged_pool):
    """The default engine (tracer=None) must never allocate a span — the
    'no measurable overhead vs the untraced engine' acceptance bar, made
    checkable as an exact allocation count."""
    cfg, pool, _ = paged_pool
    rs = np.random.RandomState(0)
    before = tracing.SPANS_CREATED
    engine = ServingEngine(pool, page_size=4, num_pages=16)
    for i in range(4):
        engine.submit(Request(
            request_id=i,
            prompt_ids=rs.randint(1, cfg.vocab_size, size=5).tolist(),
            max_new_tokens=4))
    outs = engine.run_until_complete(max_steps=200)
    engine.close()
    assert len(outs) == 4
    assert tracing.SPANS_CREATED == before, (
        "tracer-off serving allocated spans in the hot path")
    # and the terminal records carry a null trace_id (no tracer attached)
    assert all(o.trace_id is None for o in outs)


def test_preemption_e2e_one_stitched_trace_summing_to_latency(
        paged_pool, tmp_path):
    """The acceptance instrument: an interactive arrival preempts a
    decoding batch victim; with the tracer on, EVERY request yields one
    trace whose phase spans (queue, prefill, decode, preempted gap) are
    schema-valid, monotonic, parented, and sum to its reported latency —
    and the victim's trace shows the preempted gap that serving_stats
    v5's preempted_ms reports."""
    cfg, pool, _ = paged_pool
    rs = np.random.RandomState(5)
    prompts = {i: rs.randint(1, cfg.vocab_size, size=5).tolist()
               for i in range(4)}
    stats_path = str(tmp_path / "serving_stats.jsonl")
    tracer = Tracer(replica=0)
    engine = ServingEngine(pool, page_size=4, num_pages=13, tracer=tracer,
                           stats_path=stats_path)
    outs = {}
    for i in range(3):
        engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                              max_new_tokens=8, priority="batch"))
    for o in engine.step():
        outs[o.request_id] = o
    engine.submit(Request(request_id=3, prompt_ids=prompts[3],
                          max_new_tokens=3, priority="interactive"))
    for o in engine.run_until_complete(max_steps=400):
        outs[o.request_id] = o
    engine.close()
    assert len(outs) == 4 and all(o.state == "finished"
                                  for o in outs.values())
    preempted = [o for o in outs.values() if o.preemptions > 0]
    assert preempted, "workload produced no preemption"

    ev = tmp_path / "trace_events.jsonl"
    n = tracer.export_jsonl(str(ev))
    assert validate_jsonl("trace_event", str(ev)) == n
    spans = [json.loads(l) for l in open(ev)]
    phases = _phases_by_request(spans)
    for gid, out in outs.items():
        _assert_parented_and_monotonic(spans, gid)
        total = sum(phases[gid].values())
        assert total == pytest.approx(out.total_ms, abs=5.0), (
            f"request {gid}: phases {phases[gid]} sum {total:.3f}ms != "
            f"reported {out.total_ms:.3f}ms")
    # the victim's park shows up as BOTH the preempted span and the v5 field
    victim = preempted[0]
    assert phases[victim.request_id]["preempted"] > 0
    assert victim.preempted_ms == pytest.approx(
        phases[victim.request_id]["preempted"], abs=5.0)
    assert victim.decode_steps > 0 and victim.trace_id == victim.request_id

    # serving_stats v5 validates and links via trace_id
    assert validate_jsonl("serving_stats", stats_path) == 4
    recs = {r["trace_id"]: r for r in read_serving_stats(stats_path)}
    assert set(recs) == set(outs)

    # ... and the obs_report --trace section renders the waterfall,
    # cross-checked against the linked stats records
    report = build_report(run_dir=str(tmp_path))
    validate_record("obs_report", report)
    trace = report["trace"]
    assert trace is not None and trace["requests"] == 4
    slowest = trace["slowest"][0]
    assert slowest["stats_total_ms"] == pytest.approx(
        slowest["total_ms"], abs=5.0)
    md = render_markdown(report)
    assert "Request traces" in md


def test_spans_ride_the_injected_engine_clock(paged_pool):
    """Every engine/scheduler span is stamped from the ENGINE's injectable
    clock, never the tracer's internal one — a fake-clock harness (the
    established ServingEngine(clock=...) pattern) must yield a coherent
    trace on the fake timescale whose phases still sum to the reported
    latency."""
    cfg, pool, _ = paged_pool
    t = [1e9]  # far from any real time.monotonic() value

    def clock():
        t[0] += 0.25
        return t[0]

    tracer = Tracer(replica=0)  # default (real) clock — must never leak in
    engine = ServingEngine(pool, page_size=4, num_pages=16, tracer=tracer,
                           clock=clock)
    engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3],
                          max_new_tokens=3))
    outs = engine.run_until_complete(max_steps=100)
    engine.close()
    assert len(outs) == 1 and outs[0].state == "finished"
    spans = tracer.spans()
    assert spans
    for s in spans:
        assert 1e9 < s.t_start <= s.t_end < 1e9 + 1e3, (
            f"span {s.name} leaked the tracer's real clock")
    total = sum(s.duration_ms for s in spans
                if s.request_id == 0 and s.name in PHASES)
    assert total == pytest.approx(outs[0].total_ms, rel=1e-6)


@pytest.mark.chaos
@pytest.mark.fleet
def test_fleet_failover_clone_stitches_one_trace(paged_pool, tmp_path):
    """A replica killed mid-run: the requeued clone keeps the global id,
    so the dead replica's (aborted) spans and the sibling's fresh lifecycle
    stitch into ONE trace — with a route/requeue hop edge, hop-tagged clone
    spans, and phase spans that still sum to the request's reported
    end-to-end latency (the crash/requeue gap is sub-ms in-process)."""
    cfg, pool, _ = paged_pool
    rs = np.random.RandomState(31)
    prompts = [rs.randint(1, cfg.vocab_size, size=5).tolist()
               for _ in range(6)]
    tracer = Tracer()

    def make_factory(rid):
        def factory():
            return ServingEngine(pool, registry=MetricRegistry(),
                                 page_size=4, num_pages=13,
                                 tracer=tracer.scoped(rid))
        return factory

    install_plan({"faults": [{
        "point": "fleet/replica_step", "action": "exception",
        "match": {"replica": 0, "step": 2}, "count": 1}]})
    try:
        router = FleetRouter(
            [Replica(i, make_factory(i), backoff_base_s=0.0)
             for i in range(2)],
            policy="round_robin", tracer=tracer)
        reqs = [Request(request_id=i, prompt_ids=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        outs = replay(router, np.zeros(len(reqs)), reqs,
                      sleep=lambda s: None)
        router.assert_invariants()
    finally:
        clear_plan()
    assert len(outs) == len(prompts)
    assert all(o.state == "finished" for o in outs.values())
    snap = router.registry.snapshot()
    assert snap["router/failovers_total"] == 1.0
    assert snap["router/requeued_total"] >= 1.0
    router.close()

    ev = tmp_path / "trace_events.jsonl"
    tracer.export_jsonl(str(ev))
    assert validate_jsonl("trace_event", str(ev)) > 0
    spans = [json.loads(l) for l in open(ev)]
    hops = [s for s in spans if s["name"] == "route/requeue"]
    assert hops, "no failover hop edge recorded"
    phases = _phases_by_request(spans)
    moved = {s["request_id"] for s in hops}
    for gid in moved:
        mine = [s for s in spans if s["request_id"] == gid]
        # the stitched trace spans BOTH replicas under one global id
        assert len({s["replica"] for s in mine
                    if s["name"] in PHASES}) >= 2
        roots = [s for s in mine if s["name"] == "request"]
        assert len(roots) >= 2  # the aborted original + the clone's
        assert any(r["attrs"].get("hop", 0) >= 1 for r in roots), (
            "clone spans must carry the hop attr")
        assert any(r["attrs"].get("aborted") for r in roots), (
            "the dead replica's root must be sealed as aborted")
        _assert_parented_and_monotonic(spans, gid)
        total = sum(phases[gid].values())
        assert total == pytest.approx(outs[gid].total_ms, abs=25.0), (
            f"stitched phases sum {total:.3f}ms != reported "
            f"{outs[gid].total_ms:.3f}ms")
    # every request (moved or not) still has exactly one coherent trace
    for gid, out in outs.items():
        _assert_parented_and_monotonic(spans, gid)


# -- CLI rungs (out of tier-1) -----------------------------------------------

@pytest.mark.slow
def test_runner_serve_trace_and_metrics_cli(tmp_path):
    import os

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_dir = str(tmp_path / "traces")
    proc = run_cli(os.path.join(REPO, "examples", "inference", "runner.py"),
                   "serve", "--preset", "tiny", "--dtype", "float32", "--batch-size", "2",
                   "--num-requests", "3", "--max-new-tokens", "3",
                   "--quiet", "--trace-out", out_dir,
                   "--metrics-port", "0")
    events = [json.loads(l) for l in proc.stdout.strip().splitlines()
              if l.startswith("{")]
    msrv = [e for e in events if e.get("event") == "metrics_server"]
    assert msrv and msrv[0]["port"] > 0
    tr = [e for e in events if e.get("event") == "trace"]
    assert tr and validate_jsonl("trace_event", tr[0]["trace_events"]) > 0
    assert os.path.exists(tr[0]["trace_perfetto"])
