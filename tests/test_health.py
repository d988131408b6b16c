"""Fleet health monitor tests (obs/health.py + obs/aggregate.py + the
threading through engine/router/report/benches).

Five layers:

- RULE units — pure host-side: threshold fire/resolve with hysteresis
  (flapping metrics emit edges only on real transitions), rate-mode
  counters, EWMA trend warmup/drift/collapse edge cases, and the
  multi-window burn-rate arithmetic against hand-computed fixtures;
- EARLY-WARNING acceptance — the burn-rate alert fires while the
  cumulative p99 is still inside the deadline bound (the whole point of
  burn-rate alerting over percentile-threshold alerting), asserted from
  ``alerts.jsonl`` edges on a synthetic event stream AND from a real
  overloaded engine run;
- FLEET AGGREGATION — merge properties (the merged histogram equals the
  histogram of the concatenated samples), the replica-labeled Prometheus
  exposition with ONE ``# TYPE`` line per family, and the
  ``/metrics?scope=fleet`` + monitor-aware ``/healthz`` server;
- MONITOR-OFF — a full paged serving run with ``health=None`` performs
  ZERO rule evaluations (``obs.health.ALERTS_EVALUATED``, the
  SPANS_CREATED discipline);
- E2E + CLI — the PR-7 replica-kill chaos scenario firing→resolving
  ``replica_down`` through the router's ``FleetHealth``, the obs_report
  fleet-layout merge + alerts section, the ``--compare`` alerts
  regression, and the ``fleet_watch`` rungs.
"""

import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_cli, sharded_params
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import MetricRegistry, Observability
from neuronx_distributed_tpu.obs import health as health_mod
from neuronx_distributed_tpu.obs.aggregate import (
    FleetAggregator,
    FleetHealth,
    discover_replica_dirs,
    fleet_prometheus_text,
    merge_scalar_records,
    merge_snapshots,
)
from neuronx_distributed_tpu.obs.health import (
    ALERTS_FILE,
    BurnRateRule,
    EvalContext,
    HealthMonitor,
    ThresholdRule,
    TrendRule,
    default_rules,
    read_alerts,
)
from neuronx_distributed_tpu.obs.metrics_server import MetricsServer
from neuronx_distributed_tpu.obs.report import (
    build_report,
    compare_resources,
    render_markdown,
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

pytestmark = pytest.mark.health

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _monitor(rules, reg=None, path=None, clock=None, **kw):
    clock = clock or FakeClock()
    return HealthMonitor(rules, registry=reg, path=path, clock=clock,
                         wall=clock, **kw), clock


# -- threshold rules ---------------------------------------------------------

def test_threshold_fire_resolve_edges_and_gauges(tmp_path):
    reg = MetricRegistry()
    reg.gauge("serving/queue_depth").set(100)
    path = str(tmp_path / ALERTS_FILE)
    mon, clk = _monitor(
        [ThresholdRule("queue_backlog", "serving/queue_depth", 64, op=">=")],
        reg=reg, path=path)
    edges = mon.evaluate()
    assert len(edges) == 1 and edges[0]["state"] == "firing"
    assert edges[0]["observed"] == 100.0 and edges[0]["bound"] == 64.0
    assert mon.evaluate() == []  # steady state: no re-emission
    assert reg.snapshot()["obs/alerts_firing"] == 1.0
    assert reg.snapshot()["obs/alerts_total"] == 1.0
    clk.t = 5.0
    reg.gauge("serving/queue_depth").set(3)
    [edge] = mon.evaluate()
    assert edge["state"] == "resolved" and edge["duration_s"] == 5.0
    assert reg.snapshot()["obs/alerts_firing"] == 0.0
    mon.close()
    assert validate_jsonl("alert", path) == 2
    records = read_alerts(path)
    assert [r["state"] for r in records] == ["firing", "resolved"]


def test_threshold_hysteresis_suppresses_flapping():
    """A metric oscillating across the bound every evaluation must emit
    ZERO edges under fire_after=2/resolve_after=2 — and a sustained breach
    exactly one."""
    reg = MetricRegistry()
    mon, _ = _monitor([ThresholdRule(
        "flappy", "g", 10, op=">", fire_after=2, resolve_after=2)], reg=reg)
    g = reg.gauge("g")
    for i in range(10):  # 15, 5, 15, 5, ... — a fresh streak every round
        g.set(15 if i % 2 == 0 else 5)
        assert mon.evaluate() == []
    g.set(15)
    assert mon.evaluate() == []          # streak 1
    [edge] = mon.evaluate()              # streak 2: the one firing edge
    assert edge["state"] == "firing"
    g.set(5)
    assert mon.evaluate() == []
    [edge] = mon.evaluate()
    assert edge["state"] == "resolved"


def test_threshold_rate_mode_counter_delta():
    """rate=True observes the DELTA between evaluations (the compile-storm
    shape): firing while the counter moves, resolved when it goes quiet;
    the first sighting establishes the baseline without firing."""
    reg = MetricRegistry()
    mon, _ = _monitor([ThresholdRule(
        "compile_storm", "trace/compile_storms_total", 0, op=">",
        rate=True)], reg=reg)
    c = reg.counter("trace/compile_storms_total")
    c.inc(5)
    assert mon.evaluate() == []  # first sight: baseline only
    assert mon.evaluate() == []  # no movement
    c.inc(2)
    [edge] = mon.evaluate()
    assert edge["state"] == "firing" and edge["observed"] == 2.0
    [edge] = mon.evaluate()      # quiet again
    assert edge["state"] == "resolved" and edge["observed"] == 0.0


def test_missing_metric_holds_state_and_streaks():
    reg = MetricRegistry()
    mon, _ = _monitor([ThresholdRule("r", "absent", 1)], reg=reg)
    assert mon.evaluate() == []
    assert mon.firing() == []


# -- trend rules -------------------------------------------------------------

def test_trend_drift_up_warmup_then_fires_and_resolves():
    reg = MetricRegistry()
    rule = TrendRule("ttft_drift", "v", direction="up", ratio=2.0,
                     fast_alpha=0.6, slow_alpha=0.05, warmup=5)
    mon, _ = _monitor([rule], reg=reg)
    v = reg.gauge("v")
    for _ in range(6):  # warmup: no verdict even if the value moved
        v.set(10.0)
        assert mon.evaluate() == []
    edges = []
    v.set(100.0)  # 10x jump: fast EWMA races past 2x the slow baseline
    for _ in range(4):
        edges += mon.evaluate()
    assert [e["state"] for e in edges] == ["firing"]
    assert edges[0]["observed"] > edges[0]["bound"]
    v.set(10.0)  # back to baseline: fast decays below the bound again
    for _ in range(30):
        edges += mon.evaluate()
    assert [e["state"] for e in edges] == ["firing", "resolved"]


def test_trend_collapse_down_and_min_slow_guard():
    reg = MetricRegistry()
    rule = TrendRule("hit_collapse", "rate", direction="down", ratio=2.0,
                     fast_alpha=0.7, slow_alpha=0.02, warmup=3,
                     min_slow=0.05)
    mon, _ = _monitor([rule], reg=reg)
    r = reg.gauge("rate")
    # a near-zero baseline must never produce a "collapse" verdict
    for _ in range(10):
        r.set(0.001)
        assert mon.evaluate() == []
    rule2 = TrendRule("hit_collapse2", "rate", direction="down", ratio=2.0,
                      fast_alpha=0.7, slow_alpha=0.02, warmup=3)
    mon2, _ = _monitor([rule2], reg=reg)
    for _ in range(6):
        r.set(0.8)
        mon2.evaluate()
    r.set(0.05)  # collapse: fast drops under slow / 2
    edges = []
    for _ in range(5):
        edges += mon2.evaluate()
    assert edges and edges[0]["state"] == "firing"
    assert edges[0]["rule"] == "hit_collapse2"


# -- burn-rate rules ---------------------------------------------------------

def test_burn_rate_hand_computed_multiwindow_fixture():
    """Hand-computed fixture: objective 0.9 (budget 0.1), windows 60s/600s,
    factor 5 — the alert fires exactly when BOTH windows burn >= 5, i.e.
    both error fractions >= 0.5."""
    rule = BurnRateRule("burn", priority="interactive", objective=0.9,
                        windows=(60.0, 600.0), factor=5.0, min_events=4)
    mon, clk = _monitor([rule])
    # minute 0-10: one event per 10s at t=10..600, bad at i % 5 == 0
    for i in range(60):
        clk.t += 10.0
        mon.note_request(good=(i % 5 != 0), now=clk.t)
    ctx = EvalContext({}, clk.t, mon)
    rates = dict((w, b) for w, b, _ in rule.burn_rates(ctx))
    # 60s window at t=600 holds t in [540, 600] = events i=53..59 (7),
    # of which i=55 is bad: burn = (1/7) / 0.1
    assert rates[60.0] == pytest.approx((1 / 7) / 0.1)
    # 600s window holds all 60 events, 12 bad: burn = 0.2 / 0.1
    assert rates[600.0] == pytest.approx(2.0)
    assert mon.evaluate(now=clk.t) == []
    # now 100% bad: the 60s window saturates fast (burn 10), but the 600s
    # window still dilutes — the multiwindow AND holds the alert back
    for i in range(6):
        clk.t += 10.0
        mon.note_request(good=False, now=clk.t)
    ctx = EvalContext({}, clk.t, mon)
    rates = dict((w, b) for w, b, _ in rule.burn_rates(ctx))
    # 60s window at t=660 holds t in [600, 660]: the good i=59 event plus
    # the 6 new bad ones: burn = (6/7) / 0.1
    assert rates[60.0] == pytest.approx((6 / 7) / 0.1)
    # long window: 60 events in (t-600, t]: the first 6 aged out, so 54
    # old (11 bad: i=0,5,...,55 minus the aged i=0 → hand-count) + 6 new
    # bad.  Compute exactly instead of hand-waving:
    good, bad = mon._window_counts("interactive", 600.0, clk.t)
    assert rates[600.0] == pytest.approx((bad / (good + bad)) / 0.1)
    if rates[600.0] < 5.0:
        assert mon.evaluate(now=clk.t) == []
    # keep failing until the long window crosses 50% bad too
    edges = []
    for _ in range(60):
        clk.t += 10.0
        mon.note_request(good=False, now=clk.t)
        edges += mon.evaluate(now=clk.t)
        if edges:
            break
    assert edges and edges[0]["state"] == "firing"
    good, bad = mon._window_counts("interactive", 600.0, edges[0]["mono"])
    assert bad / (good + bad) >= 0.5, "fired before the long window burned"
    assert edges[0]["window"] == "60s+600s"
    assert edges[0]["bound"] == 5.0
    # recovery: a quiet stretch drains the short window first — resolve
    for _ in range(12):
        clk.t += 10.0
        mon.note_request(good=True, now=clk.t)
        edges += mon.evaluate(now=clk.t)
    assert edges[-1]["state"] == "resolved"


def test_burn_rate_min_events_and_empty_window():
    rule = BurnRateRule("burn", objective=0.9, windows=(60.0,), factor=2.0,
                        min_events=4)
    mon, clk = _monitor([rule])
    for _ in range(3):
        clk.t += 1.0
        mon.note_request(good=False, now=clk.t)
    # 100% bad but only 3 events < min_events: no page on noise
    assert mon.evaluate(now=clk.t) == []
    clk.t += 1.0
    mon.note_request(good=False, now=clk.t)
    [edge] = mon.evaluate(now=clk.t)
    assert edge["state"] == "firing"
    clk.t += 120.0  # window empties: burn 0 resolves (no events needed)
    [edge] = mon.evaluate(now=clk.t)
    assert edge["state"] == "resolved"


def test_burn_rate_fires_before_cumulative_p99_breaches():
    """The acceptance property: after a long healthy history, an overload
    spike trips the fast-window burn-rate alert while the CUMULATIVE p99
    latency-attainment statistic is still inside the bound — burn-rate
    alerting leads percentile alerting, asserted from alerts.jsonl
    edges."""
    rule = BurnRateRule("slo_burn_fast_interactive", objective=0.99,
                        windows=(30.0, 120.0), factor=10.0, min_events=4)
    import tempfile

    path = os.path.join(tempfile.mkdtemp(), "alerts.jsonl")
    mon, clk = _monitor([rule], path=path)
    outcomes = []  # (t, good) — the cumulative record p99 is computed on

    def note(good):
        clk.t += 1.0
        outcomes.append((clk.t, good))
        mon.note_request(good=good, now=clk.t)
        return mon.evaluate(now=clk.t)

    for _ in range(3600):  # a healthy hour at 1 req/s
        assert note(True) == []
    edges = []
    while not edges:  # the overload spike: every request misses
        edges += note(False)
        assert len(outcomes) < 3700, "burn rule never fired"
    fired_at = edges[0]["mono"]
    bad_before = sum(1 for t, ok in outcomes if not ok and t <= fired_at)
    frac_before = bad_before / sum(1 for t, _ in outcomes if t <= fired_at)
    # at the firing edge, under 1% of ALL requests have missed — the
    # cumulative p99 attainment is still within the SLO bound
    assert frac_before < 0.01, (
        f"burn rule fired late: {frac_before:.2%} already bad")
    # ... and the breach DOES come later (the alert was early, not wrong)
    for _ in range(40):
        note(False)
    frac_after = (sum(1 for _, ok in outcomes if not ok)
                  / len(outcomes))
    assert frac_after > 0.01
    mon.close()
    records = read_alerts(path)
    assert [r["rule"] for r in records] == ["slo_burn_fast_interactive"]
    assert records[0]["severity"] == "page"


# -- conditions / severity / default pack ------------------------------------

def test_set_condition_replica_down_idempotent_and_healthz(tmp_path):
    path = str(tmp_path / ALERTS_FILE)
    mon, clk = _monitor([], path=path)
    assert mon.healthz()["ok"] is True
    edge = mon.set_condition("replica_down", True, key="2", severity="page",
                             replica_id=2, cause="step_crash")
    assert edge is not None and edge["state"] == "firing"
    assert edge["key"] == "2" and edge["replica_id"] == 2
    assert mon.set_condition("replica_down", True, key="2") is None  # no-op
    hz = mon.healthz()
    assert hz["ok"] is False and hz["worst_severity"] == "page"
    assert "replica_down" in hz["firing"]
    clk.t = 3.0
    edge = mon.set_condition("replica_down", False, key="2", severity="page")
    assert edge["state"] == "resolved" and edge["duration_s"] == 3.0
    assert mon.healthz()["ok"] is True
    mon.close()
    assert validate_jsonl("alert", path) == 2


def test_default_rule_packs():
    for scope in ("serving", "fleet", "train"):
        rules = default_rules(scope)
        names = [r.name for r in rules]
        assert len(set(names)) == len(names)
    serving = {r.name for r in default_rules("serving")}
    assert {"queue_backlog", "kv_headroom", "compile_storm", "ttft_drift",
            "prefix_hit_collapse", "spec_acceptance_collapse",
            "throughput_sag", "adapter_thrash", "slo_burn_fast_interactive",
            "slo_burn_slow_interactive", "slo_burn_fast_batch",
            "slo_burn_slow_batch"} <= serving
    fleet = {r.name for r in default_rules("fleet")}
    assert {"router_backlog", "failover_storm", "kv_headroom"} <= fleet
    # the Observability(health=True) union: serving pack + the train sag
    # rule under a distinct name (no collision with the serving one)
    union = {r.name for r in default_rules("all")}
    assert serving | {"train_throughput_sag"} == union
    with pytest.raises(ValueError):
        default_rules("nope")


def test_window_fraction_spec_acceptance_scale():
    """The spec-acceptance feed is d(accepted)/d(proposed) — accepted is
    a SUBSET of proposed, so 100% acceptance must observe 1.0 (a
    hits/misses-style ratio would compress it to 0.5)."""
    from neuronx_distributed_tpu.obs.health import _WindowFraction

    fn = _WindowFraction("serving/spec_accepted_total",
                         "serving/spec_proposed_total")
    ctx = EvalContext({"serving/spec_accepted_total": 0.0,
                       "serving/spec_proposed_total": 0.0}, 0.0)
    assert fn(ctx) is None  # baseline
    ctx = EvalContext({"serving/spec_accepted_total": 8.0,
                       "serving/spec_proposed_total": 8.0}, 1.0)
    assert fn(ctx) == pytest.approx(1.0)
    ctx = EvalContext({"serving/spec_accepted_total": 10.0,
                       "serving/spec_proposed_total": 16.0}, 2.0)
    assert fn(ctx) == pytest.approx(0.25)  # 2 accepted of 8 proposed


def test_eval_every_cadence_and_quiet_file(tmp_path):
    path = str(tmp_path / ALERTS_FILE)
    mon, _ = _monitor([ThresholdRule("r", "absent", 1)], path=path,
                      eval_every=4)
    before = mon.evaluations
    for _ in range(8):
        mon.on_step()
    assert mon.evaluations - before == 2
    mon.close()
    # a quiet monitor still leaves the (empty, valid) artifact
    assert os.path.exists(path) and validate_jsonl("alert", path) == 0


# -- fleet aggregation -------------------------------------------------------

def test_histogram_merge_equals_concatenated_samples():
    """Property: merging per-replica registry snapshots equals one registry
    that observed every replica's samples."""
    rs = np.random.RandomState(7)
    bounds = (1.0, 5.0, 25.0, 100.0)
    regs = [MetricRegistry() for _ in range(3)]
    union = MetricRegistry()
    for reg in regs:
        for _ in range(rs.randint(5, 40)):
            v = float(rs.exponential(20.0))
            reg.histogram("serving/step_ms", bounds).observe(v)
            union.histogram("serving/step_ms", bounds).observe(v)
        n = float(rs.randint(0, 100))
        reg.counter("serving/tokens_total").inc(n)
        union.counter("serving/tokens_total").inc(n)
    merged = merge_snapshots([r.snapshot() for r in regs])
    want = union.snapshot()
    assert merged["serving/step_ms"] == want["serving/step_ms"]
    assert merged["serving/tokens_total"] == want["serving/tokens_total"]


def test_merge_snapshots_gauge_sum_and_max():
    snaps = [{"serving/queue_depth": 3.0, "serving/last_step_ms": 5.0},
             {"serving/queue_depth": 4.0, "serving/last_step_ms": 9.0}]
    merged = merge_snapshots(snaps)
    assert merged["serving/queue_depth"] == 7.0   # fleet queue = sum
    assert merged["serving/last_step_ms"] == 9.0  # worst replica = max


def test_fleet_prometheus_text_one_type_line_per_family():
    regs = {}
    for rid in range(3):
        reg = MetricRegistry()
        reg.counter("serving/tokens_total").inc(rid + 1)
        reg.gauge("serving/queue_depth").set(rid)
        reg.histogram("serving/step_ms", (1.0, 10.0)).observe(0.5 + rid)
        regs[rid] = reg
    text = fleet_prometheus_text({k: r.snapshot() for k, r in regs.items()})
    lines = text.splitlines()
    type_lines = [ln for ln in lines if ln.startswith("# TYPE")]
    # THE satellite bugfix: one TYPE line per family, however many
    # replica-labeled series exist under it
    assert len(type_lines) == len(set(type_lines)) == 3
    assert 'serving_tokens_total{replica="0"} 1' in lines
    assert 'serving_tokens_total{replica="2"} 3' in lines
    assert "serving_tokens_total 6" in lines  # the merged series
    assert 'serving_step_ms_bucket{replica="1",le="+Inf"} 1' in lines
    assert "serving_step_ms_count 3" in lines
    # families stay contiguous under their TYPE line (exposition rule)
    fam_of = {}
    current = None
    for ln in lines:
        if ln.startswith("# TYPE"):
            current = ln.split()[2]
            assert current not in fam_of, "family split across TYPE lines"
            fam_of[current] = True


def test_metrics_server_monitor_healthz_and_fleet_scope():
    reg = MetricRegistry()
    reg.counter("serving/tokens_total").inc(7)
    mon, _ = _monitor([])
    agg = FleetAggregator({0: reg})
    with MetricsServer(reg, monitor=mon,
                       scopes={"fleet": agg.prometheus_text},
                       port=0, host="127.0.0.1") as srv:
        base = f"http://127.0.0.1:{srv.port}"
        hz = json.loads(urllib.request.urlopen(base + "/healthz").read())
        assert hz["ok"] is True and hz["alerts_firing"] == 0
        body = urllib.request.urlopen(
            base + "/metrics?scope=fleet").read().decode()
        assert 'serving_tokens_total{replica="0"} 7' in body
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/metrics?scope=nope")
        assert exc.value.code == 400
        # a page-severity alert takes readiness to 503 while /metrics lives
        mon.set_condition("slo_burn_fast_interactive", True, severity="page")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(base + "/healthz")
        assert exc.value.code == 503
        doc = json.loads(exc.value.read().decode())
        assert doc["worst_severity"] == "page"
        assert urllib.request.urlopen(base + "/metrics").status == 200


# -- report / compare --------------------------------------------------------

def test_obs_report_fleet_layout_and_alerts_section(tmp_path):
    run = tmp_path / "run"
    for rid in range(2):
        sub = run / f"replica{rid}"
        sub.mkdir(parents=True)
        reg = MetricRegistry()
        reg.counter("serving/tokens_total").inc(10 + rid)
        reg.histogram("serving/ttft_ms", (1.0, 10.0)).observe(5.0)
        reg.dump_jsonl(str(sub / "scalars.jsonl"), step=3)
    (run / "router_stats.jsonl").write_text(json.dumps({
        "schema": "router_stats/1", "time": 1.0, "request_id": 1,
        "client_id": 0, "replica": 0, "state": "finished",
        "finish_reason": "length", "dispatches": 2, "requeues": 1,
        "affinity_pages": 0, "new_tokens": 2,
        "policy": "round_robin"}) + "\n")
    mon, clk = _monitor([ThresholdRule("queue_backlog", "g", 1)],
                        path=str(run / ALERTS_FILE))
    mon.evaluate(snapshot={"g": 5.0})
    clk.t = 2.0
    mon.evaluate(snapshot={"g": 0.0})
    mon.close()
    assert discover_replica_dirs(str(run)) == [
        ("replica0", str(run / "replica0")),
        ("replica1", str(run / "replica1"))]
    report = build_report(run_dir=str(run))
    validate_record("obs_report", report)
    # per-replica counters/histograms merged, not shadowed
    assert report["scalars"]["serving/tokens_total"]["last"] == 21.0
    assert report["histograms"]["serving/ttft_ms"]["count"] == 2.0
    alerts = report["alerts"]
    assert alerts["records"] == 2 and alerts["firing"] == 0
    assert alerts["rules"]["queue_backlog"]["fired"] == 1
    assert alerts["rules"]["queue_backlog"]["time_firing_s"] == 2.0
    assert report["health"]["alerts"]["rules_fired"] == 1
    assert report["health"]["fleet"]["router_stats"]["requeued"] == 1
    md = render_markdown(report)
    assert "## Alerts" in md and "queue_backlog" in md
    assert "router stats" in md
    # no alert files at all -> the section is null, not {}
    empty = build_report(run_dir=str(tmp_path / "nothing"))
    assert empty["alerts"] is None
    validate_record("obs_report", empty)


def test_merge_scalar_records_latest_per_replica_sums():
    reg_a, reg_b = MetricRegistry(), MetricRegistry()
    reg_a.counter("c_total").inc(2)
    reg_b.counter("c_total").inc(3)
    reg_a.histogram("h", (1.0,)).observe(0.5)
    reg_b.histogram("h", (1.0,)).observe(2.0)
    # replica A dumped twice: only its LATEST snapshot may contribute
    stream_a = (reg_a.to_scalar_records(step=1)
                + reg_a.to_scalar_records(step=5))
    stream_b = reg_b.to_scalar_records(step=3)
    merged = {r["tag"]: r["value"]
              for r in merge_scalar_records([stream_a, stream_b])}
    assert merged["c_total"] == 5.0
    assert merged["h/count"] == 2.0
    assert merged["h/sum"] == 2.5
    assert merged["h/le_inf"] == 2.0  # cumulative edges add


def test_compare_alerts_regression(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(), b.mkdir()
    (a / ALERTS_FILE).write_text("")  # A ran monitored and stayed quiet
    mon, _ = _monitor([ThresholdRule("queue_backlog", "g", 1)],
                      path=str(b / ALERTS_FILE))
    mon.evaluate(snapshot={"g": 9.0})
    mon.close()
    diff = compare_resources(str(a), str(b))
    assert diff["regressed"]
    assert any("queue_backlog" in r for r in diff["regressions"])
    assert "Alerts (firing edges)" in diff["markdown"]
    # symmetric quiet runs do not regress
    diff = compare_resources(str(a), str(a))
    assert not any("alert" in r for r in diff["regressions"])


def test_observability_health_knob(tmp_path):
    obs = Observability(str(tmp_path / "obs"),
                        health=[ThresholdRule("train_backlog", "g", 1)])
    assert obs.health_monitor is not None
    obs.registry.gauge("g").set(5.0)
    before = obs.health_monitor.evaluations
    obs.observe_step(0, loss=1.0)
    assert obs.health_monitor.evaluations == before + 1
    assert obs.health_monitor.firing()[0]["rule"] == "train_backlog"
    obs.close()
    path = os.path.join(obs.out_dir, ALERTS_FILE)
    assert validate_jsonl("alert", path) == 1
    # the scalars dump carries the obs/alerts_* pair
    text = open(obs.prometheus_path).read()
    assert "obs_alerts_firing 1" in text


# -- e2e: CPU tiny Llama -----------------------------------------------------

@pytest.fixture
def paged_pool(devices8):
    """B=3 paged pool model (page 4 divides C=8 and T=16) — the same shape
    as the tracing/SLO serving fixtures."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none",
    )
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((3, 8), jnp.int32)))
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    return cfg, pool


def test_health_off_is_zero_evaluations(paged_pool):
    """The default engine (health=None) performs ZERO rule evaluations
    over a full paged serving run — the allocation-free-when-off
    acceptance bar, checkable as an exact counter."""
    cfg, pool = paged_pool
    rs = np.random.RandomState(0)
    before = health_mod.ALERTS_EVALUATED
    engine = ServingEngine(pool, page_size=4, num_pages=16)
    for i in range(4):
        engine.submit(Request(
            request_id=i,
            prompt_ids=rs.randint(1, cfg.vocab_size, size=5).tolist(),
            max_new_tokens=4))
    outs = engine.run_until_complete(max_steps=200)
    engine.close()
    assert len(outs) == 4
    assert health_mod.ALERTS_EVALUATED == before, (
        "health-off serving evaluated rules in the hot path")


def test_engine_overload_fires_fast_burn_rule(paged_pool, tmp_path):
    """Queue overload e2e: a flood of tight-deadline requests overruns the
    3-slot engine — queued requests expire, the engine feeds each terminal
    outcome into the monitor, and the fast-window burn-rate rule fires a
    page alert in alerts.jsonl while requests are still completing (the
    control room sees the overload from the live engine, not a
    post-mortem)."""
    cfg, pool = paged_pool
    rs = np.random.RandomState(3)
    path = str(tmp_path / ALERTS_FILE)
    rule = BurnRateRule("slo_burn_fast_interactive", objective=0.9,
                        windows=(60.0, 120.0), factor=2.0, min_events=2)
    mon = HealthMonitor([rule], path=path, eval_every=1)
    stats = str(tmp_path / "serving_stats.jsonl")
    engine = ServingEngine(pool, page_size=4, num_pages=16, health=mon,
                           stats_path=stats)
    # 10 requests, 3 slots, deadlines far tighter than the backlog drains:
    # the head finishes, the tail times out in the queue
    for i in range(10):
        engine.submit(Request(
            request_id=i,
            prompt_ids=rs.randint(1, cfg.vocab_size, size=6).tolist(),
            max_new_tokens=6, deadline_s=0.05 if i >= 3 else 30.0))
    outs = engine.run_until_complete(max_steps=400)
    engine.close()
    mon.close()
    assert len(outs) == 10
    timed_out = [o for o in outs if o.state == "timed_out"]
    assert timed_out, "overload produced no deadline misses"
    records = read_alerts(path)
    fired = [r for r in records
             if r["rule"] == "slo_burn_fast_interactive"
             and r["state"] == "firing"]
    assert fired, f"no burn-rate edge in {records}"
    assert fired[0]["severity"] == "page"
    assert fired[0]["observed"] >= fired[0]["bound"]
    assert validate_jsonl("alert", path) == len(records)
    # the edge is on the ENGINE clock's timescale, inside the run window
    assert validate_jsonl("serving_stats", stats) == 10
    monos = [json.loads(l)["mono"] for l in open(stats)]
    assert min(monos) <= fired[0]["mono"] <= max(monos) + 1.0, (
        "alert edge not interleaved with the serving run")


@pytest.mark.chaos
@pytest.mark.fleet
def test_fleet_replica_kill_fires_then_resolves_replica_down(
        paged_pool, tmp_path):
    """The PR-7 chaos acceptance: a replica killed mid-run fires
    `replica_down` (page severity, keyed by replica id) at the failover
    and RESOLVES it at the warm restart — asserted from alerts.jsonl
    edge ordering — while the per-replica + fleet monitors keep
    evaluating and /healthz-style state flips accordingly."""
    cfg, pool = paged_pool
    rs = np.random.RandomState(31)
    prompts = [rs.randint(1, cfg.vocab_size, size=5).tolist()
               for _ in range(6)]
    path = str(tmp_path / ALERTS_FILE)
    health = FleetHealth(path=path, eval_every=2)

    def make_factory(rid):
        def factory():
            return ServingEngine(pool, registry=MetricRegistry(),
                                 page_size=4, num_pages=13)
        return factory

    install_plan({"faults": [{
        "point": "fleet/replica_step", "action": "exception",
        "match": {"replica": 0, "step": 2}, "count": 1}]})
    try:
        router = FleetRouter(
            [Replica(i, make_factory(i), backoff_base_s=0.0)
             for i in range(2)],
            policy="round_robin", health=health)
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
    assert snap["obs/alerts_total"] >= 1.0  # the edge hit the registry too
    router.close()
    health.close()

    records = read_alerts(path)
    assert validate_jsonl("alert", path) == len(records)
    down = [r for r in records if r["rule"] == "replica_down"]
    assert [r["state"] for r in down] == ["firing", "resolved"], (
        f"replica_down sequence wrong: {down}")
    assert down[0]["severity"] == "page"
    assert down[0]["replica_id"] == 0 and down[1]["replica_id"] == 0
    assert down[0]["mono"] <= down[1]["mono"]
    assert "InjectedFault" in down[0]["cause"]
    # fleet + replica monitors both ran (cadenced) during the run
    assert health.fleet.evaluations > 0
    assert health.replica_monitors, "no per-replica monitor was created"
    assert health.healthz()["ok"] is True  # resolved: back in the LB


# -- CLI rungs ---------------------------------------------------------------

def test_fleet_watch_once_renders_run_dir(tmp_path):
    run = tmp_path / "run"
    sub = run / "replica0"
    sub.mkdir(parents=True)
    reg = MetricRegistry()
    reg.counter("serving/tokens_total").inc(42)
    reg.gauge("serving/slots_active").set(2)
    reg.gauge("kvcache/pages_total").set(16)
    reg.gauge("kvcache/pages_in_use").set(8)
    reg.dump_jsonl(str(sub / "scalars.jsonl"), step=1)
    mon, _ = _monitor([ThresholdRule("kv_headroom", "g", 1, severity="warn")],
                      path=str(run / ALERTS_FILE))
    mon.evaluate(snapshot={"g": 9.0})  # leave it FIRING
    mon.close()
    proc = run_cli(os.path.join(REPO, "tools", "fleet_watch.py"),
                   "--run-dir", str(run), "--once")
    out = proc.stdout
    assert "== fleet ==" in out and "== alerts firing (1) ==" in out
    assert "kv_headroom" in out and "warn" in out
    assert "replica0" in out and "8/16" in out and "50%" in out
    assert "tokens" in out
