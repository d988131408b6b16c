"""The serve step accounts for itself (the ``tracing`` PR after the
run-ahead decode loop): ``obs.flight.StepAccount`` behind
``ServingEngine._phase``.

- a step's phases (SELF times) add up to its wall time to the clock's grain,
  and the totals in the registry are the records' sums;
- a hole injected into each phase in turn — the account's clock jumps inside
  it — is a stall booked to THAT phase, counted once, logged once; a hole
  between two steps is booked to ``between`` and is not the loop's stall;
- ``declare_warmup_done`` starts the account over;
- the trailing-median detector's O(1) form fires where the form it replaces
  (a ring copy and ``statistics.median`` a step) fired;
- the record costs microseconds a step;
- with and without an ``obs=`` hub the ring holds the same records.

Everything runs on the account's OWN clocks, replaced here by scripted ones;
the engine's ``clock=`` is not touched.
"""

import json
import logging
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import MetricRegistry, Observability
from neuronx_distributed_tpu.obs import flight as flight_mod
from neuronx_distributed_tpu.obs.flight import (
    BETWEEN,
    FlightRecorder,
    StepAccount,
    ThroughputRegressionDetector,
)
from neuronx_distributed_tpu.obs.schemas import validate_flight_document
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.serving.engine import SERVE_PHASES
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

TICK = 1.0 / 1024        # a scripted clock's grain: sums of it are exact


class ScriptedClock:
    """Advances one tick a read; ``jump(s)`` is a hole of ``s`` seconds."""

    def __init__(self, tick=TICK):
        self.t, self.tick, self.reads = 100.0, tick, 0

    def __call__(self):
        self.t += self.tick
        self.reads += 1
        return self.t

    def jump(self, seconds):
        self.t += seconds


class Lines(logging.Handler):
    """The stall lines the account logged, parsed."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.stalls = []

    def emit(self, record):
        msg = record.getMessage()
        if ": stall {" in msg:
            self.stalls.append(json.loads(msg[msg.index("{"):]))


@pytest.fixture
def stall_lines():
    handler = Lines()
    log = logging.getLogger(flight_mod.__name__)
    log.addHandler(handler)
    yield handler.stalls
    log.removeHandler(handler)


@pytest.fixture
def tiny_paged(devices8):
    """A B=3 paged serving model on one device (page 4, C=8, T=32)."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(sequence_parallel=False, dtype=jnp.float32,
                           param_dtype=jnp.float32, max_seq_len=32,
                           remat="none")
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((3, 8), jnp.int32)))
    return cfg, ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=32,
                        kv_cache_dtype=jnp.float32))


def _request(cfg, rid, plen, new):
    rs = np.random.RandomState(100 + rid)
    return Request(request_id=rid, max_new_tokens=new,
                   prompt_ids=rs.randint(1, cfg.vocab_size, plen).tolist())


def _engine(pool, **kw):
    engine = ServingEngine(pool, page_size=4, prefill_chunk_tokens=4, **kw)
    clock, cpu = ScriptedClock(), ScriptedClock(TICK / 4)
    engine._account.clock, engine._account.cpu_clock = clock, cpu
    return engine, clock


def _drive(engine, cfg, steps, late_at=None):
    """``steps`` steps of two long requests; a third, of two chunks, joins at
    step ``late_at`` (its admission, chunks and first token follow)."""
    engine.submit(_request(cfg, 0, 4, 12))
    engine.submit(_request(cfg, 1, 3, 12))
    for i in range(steps):
        if i == late_at:
            engine.submit(_request(cfg, 2, 7, 4))
        engine.step()


# -- the books balance ---------------------------------------------------------

def test_phase_times_add_up_to_the_step_and_totals_to_the_records(tiny_paged):
    cfg, pool = tiny_paged
    engine, clock = _engine(pool)
    _drive(engine, cfg, 9, late_at=3)
    recs = list(engine._flight.records)
    assert [r.step for r in recs] == list(range(1, 10))
    head = len(flight_mod.STEP_FIELDS)
    assert recs[0]._fields[head:] == tuple(p + "_ms" for p in SERVE_PHASES)
    for r in recs:
        by = r.phase_ms()
        assert tuple(by) == SERVE_PHASES
        # SELF times: nothing counted twice, nothing left out — exact on a
        # clock whose grain is a power of two
        assert sum(by.values()) == r.wall_ms
        assert r.blocked_ms == by["fetch"] > 0
        # the scripted CPU clock runs at a quarter of the wall clock's pace
        assert r.cpu_ms == pytest.approx(TICK / 4 * 1e3)
        assert r.off_cpu_ms == r.wall_ms - r.cpu_ms - r.blocked_ms > 0
        # the collect's fetch and a first-token tail's own: a tick each
        assert r.fetches in (1, 2) and by["fetch"] == r.fetches * TICK * 1e3
    assert recs[0].between_ms == 0.0
    assert all(r.between_ms == TICK * 1e3 for r in recs[1:])
    # the third request: granted at step 4, two chunks, a first-token tail
    assert [r.granted for r in recs] == [2, 0, 0, 1, 0, 0, 0, 0, 0]
    assert sum(1 for r in recs if r.chunk == 4) >= 2
    assert sum(1 for r in recs if r.first_token_ms > 0) == 3
    assert max(r.rows for r in recs) == 3
    snap = engine.registry.snapshot()
    assert snap["serving/step_ms_total"] == sum(r.wall_ms for r in recs)
    assert snap["serving/step_cpu_ms_total"] == sum(r.cpu_ms for r in recs)
    assert snap["serving/step_blocked_ms_total"] == sum(
        r.blocked_ms for r in recs)
    assert snap["serving/step_ms_max"] == max(r.wall_ms for r in recs)
    assert sum(snap[f"serving/host_ms_total/{p}"] for p in SERVE_PHASES) \
        == pytest.approx(snap["serving/step_ms_total"], rel=1e-12)
    for p in SERVE_PHASES:
        assert snap[f"serving/host_ms_total/{p}"] > 0
    assert snap["serving/stalls_total"] == snap["serving/stall_ms_total"] == 0
    engine.close()


# -- a hole lands where it was -------------------------------------------------

# phase -> (the object's attribute that runs inside it and nowhere else
# before it, so a jump of the clock on its way in is the phase's own time)
HOLES = {
    "step": lambda e: (e, "_launch_decode"),        # before ``dispatch``
    "admit": lambda e: (e.scheduler, "sweep"),
    "prefill_chunk": lambda e: (e, "_dispatch_chunk"),
    "first_token": lambda e: (e, "_finish_prefill"),
    "dispatch": lambda e: (e, "_dispatch_decode"),
    "collect": lambda e: (e, "_collect_decode"),    # before its ``fetch``
    "fetch": lambda e: (e._audit, "fetch"),
    "finish": lambda e: (e, "_finish_decode"),
    "tail": lambda e: (e._kv, "export_gauges"),
}
HOLE_S = 5.0


@pytest.mark.parametrize("phase", list(SERVE_PHASES) + [BETWEEN])
def test_a_hole_is_booked_to_its_phase_counted_once_logged_once(
        tiny_paged, stall_lines, phase):
    cfg, pool = tiny_paged
    engine, clock = _engine(pool)
    armed = [False]
    if phase != BETWEEN:
        owner, name = HOLES[phase](engine)
        inner = getattr(owner, name)

        def holed(*a, **kw):
            if armed[0]:
                armed[0] = False
                clock.jump(HOLE_S)
            return inner(*a, **kw)

        setattr(owner, name, holed)
    engine.submit(_request(cfg, 0, 4, 12))
    engine.submit(_request(cfg, 1, 3, 12))
    for i in range(12):
        if i == 9:
            # history enough for a median; the late request's admission,
            # chunk and first token all fall after this step
            engine.submit(_request(cfg, 2, 3, 2))
            if phase == BETWEEN:
                clock.jump(HOLE_S)
            else:
                armed[0] = True
        engine.step()
    assert not armed[0]
    snap = engine.registry.snapshot()
    median_ms = statistics.median(
        r.wall_ms for r in list(engine._flight.records)[:9])
    booked = {p: snap[f"serving/stall_ms_total/{p}"]
              for p in SERVE_PHASES + (BETWEEN,)}
    assert {p for p, v in booked.items() if v} == {phase}
    assert booked[phase] == pytest.approx(HOLE_S * 1e3, abs=3 * median_ms)
    (line,) = stall_lines
    assert line["phase"] == phase
    assert line["excess_ms"] == pytest.approx(booked[phase], abs=1e-3)
    assert line["median_ms"] == pytest.approx(median_ms, abs=1e-3)
    assert len(line["before"]) == 8 and line["suppressed_lines"] == 0
    assert {"gc_collections", "gc_ms", "since_sample", "cpu_ms",
            "blocked_ms", "off_cpu_ms", "between_ms", "rows", "granted",
            "chunk", "fetches"} <= set(line)
    assert line["since_sample"]["steps"] >= 1
    (warning,) = engine._flight.warnings
    assert warning["detector"] == "throughput_regression"
    if phase == BETWEEN:
        # the caller's time: seen, booked apart, NOT the loop's stall —
        # engine_stall_share's numerator stays 0
        assert snap["serving/stalls_total"] == 0
        assert snap["serving/stall_ms_total"] == 0
        assert line["kind"] == "between_steps"
        assert line["between_ms"] == pytest.approx(HOLE_S * 1e3, abs=1.0)
        assert all(r.stall_ms == 0 for r in engine._flight.records)
    else:
        assert snap["serving/stalls_total"] == 1
        assert snap["serving/stall_ms_total"] == booked[phase]
        assert line[f"{phase}_ms"] == pytest.approx(HOLE_S * 1e3,
                                                    abs=3 * median_ms)
        # the scripted CPU clock did not move in the hole: off-CPU, unless
        # the hole lay in the blocking fetch
        assert line["kind"] == ("blocked" if phase == "fetch" else "off_cpu")
        assert [r.step for r in engine._flight.records
                if r.stall_ms > 0] == [line["step"]]
        assert snap["serving/step_ms_max"] == pytest.approx(
            HOLE_S * 1e3, abs=3 * median_ms)
    engine.close()


def test_an_idle_engine_woken_late_is_no_hole(tiny_paged, stall_lines):
    """Time between steps is held to the rule only where the step before
    left work behind: a server that sat idle was not stalled."""
    cfg, pool = tiny_paged
    engine, clock = _engine(pool)
    engine.submit(_request(cfg, 0, 4, 11))
    engine.run_until_complete(max_steps=50)
    assert not engine.has_work and len(engine._flight.records) >= 9
    clock.jump(60.0)
    engine.submit(_request(cfg, 1, 4, 2))
    engine.run_until_complete(max_steps=50)
    assert stall_lines == []
    assert engine.registry.snapshot()[
        f"serving/stall_ms_total/{BETWEEN}"] == 0
    assert max(r.between_ms for r in engine._flight.records) > 59e3
    engine.close()


def test_stall_lines_are_rate_limited_not_the_counters():
    reg, clock = MetricRegistry(), ScriptedClock()
    acct = StepAccount(("step", "fetch"), FlightRecorder(), reg, "loop")
    acct.clock = acct.cpu_clock = clock
    seen = Lines()
    log = logging.getLogger(flight_mod.__name__)
    log.addHandler(seen)
    try:
        for step in range(1, 15):
            acct.begin(step)
            if step in (10, 11, 14):
                clock.jump(0.4 if step != 14 else 0.9)
            acct.end(left_work=True)
    finally:
        log.removeHandler(seen)
    assert reg.counter("loop/stalls_total").value == 3
    # steps 10 and 11 fall inside one second of the account's clock: one
    # line; step 14 comes after it and says what was held back
    assert [line["step"] for line in seen.stalls] == [10, 14]
    assert [line["suppressed_lines"] for line in seen.stalls] == [0, 1]
    assert reg.counter("loop/stall_ms_total/step").value == pytest.approx(
        reg.counter("loop/stall_ms_total").value)


# -- warm-up is not the measure ------------------------------------------------

def test_declare_warmup_done_starts_the_account_over(tiny_paged, stall_lines):
    cfg, pool = tiny_paged
    engine, clock = _engine(pool)
    inner = engine._dispatch_decode

    def compiling(*a, **kw):            # a warm-up step that "compiles"
        if engine._steps == 10:
            clock.jump(30.0)
        return inner(*a, **kw)

    engine._dispatch_decode = compiling
    _drive(engine, cfg, 11)
    snap = engine.registry.snapshot()
    assert snap["serving/stalls_total"] == 1 and len(stall_lines) == 1
    assert snap["serving/step_ms_max"] > 30e3
    engine.declare_warmup_done()
    snap = engine.registry.snapshot()
    zeroed = [k for k in snap if k.startswith((
        "serving/step_ms_total", "serving/step_cpu_ms_total",
        "serving/step_blocked_ms_total", "serving/step_ms_max",
        "serving/stalls_total", "serving/stall_ms_total",
        "serving/host_ms_total/"))]
    assert len(zeroed) == 6 + 2 * len(SERVE_PHASES) + 1
    assert all(snap[k] == 0 for k in zeroed)
    assert engine._account.detector.median() is None
    # the histogram and the gauge on the engine's clock are not the account's
    assert snap["serving/step_ms"]["count"] == 11
    clock.jump(7.0)                     # the caller's lead-in: not a hole
    engine.step()
    rec = engine._flight.records[-1]
    assert rec.between_ms == 0.0 and len(stall_lines) == 1
    assert engine.registry.snapshot()["serving/step_ms_total"] == rec.wall_ms
    engine.close()


# -- the detector, O(1) a step -------------------------------------------------

def _old_rule(past, v, factor, min_history, min_excess_s):
    """``ThroughputRegressionDetector.check`` as it was: the window copied
    out of the ring, ``statistics.median`` over it."""
    if len(past) < min_history:
        return None
    med = statistics.median(past)
    if med > 0 and v > factor * med and v - med > min_excess_s:
        return med
    return None


@pytest.mark.parametrize("window,min_history,seed", [
    (32, 8, 0), (32, 8, 1), (16, 8, 2), (5, 1, 3), (64, 33, 4)])
def test_detector_agrees_with_the_ring_copy_and_median_it_replaces(
        window, min_history, seed):
    """A recorded series: steps of ~17 ms that drift, steps of two kinds
    (the median sits between them), holes of every size round the rule's
    two thresholds, runs of equal values."""
    rs = np.random.RandomState(seed)
    series = []
    for i in range(3000):
        base = 0.017 * (1 + 0.5 * np.sin(i / 200.0))
        v = base * (3.0 if rs.rand() < 0.3 else 1.0) + rs.rand() * 1e-3
        if rs.rand() < 0.03:
            v += rs.choice([0.05, 0.24, 0.26, 0.3, 2.0, 13.0])
        if rs.rand() < 0.1:
            v = round(v, 2)
        series.append(float(v))
    det = ThroughputRegressionDetector(window=window,
                                       min_history=min_history)
    fired = 0
    for i, v in enumerate(series):
        want = _old_rule(series[max(i - window, 0):i], v, det.factor,
                         min_history, det.min_excess_s)
        assert det.median() == (statistics.median(series[max(i - window, 0):i])
                                if i >= min_history and i else None)
        got = det.regression(v)
        assert got == want
        msg = det.check({"step_time_s": v}, None)
        assert (msg is not None) == (want is not None)
        fired += want is not None
    assert fired >= 20 and len(det._sorted) == len(det._recent) == window
    det.reset()
    assert det.median() is None and not det._sorted


# -- what it costs -------------------------------------------------------------

class _NoAnnotation:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_the_record_costs_microseconds_a_step():
    """One step's account — begin, every phase of ``SERVE_PHASES`` once and
    a second ``fetch``, end: the record, the totals, the stall rule — on the
    real clocks.  Reckoned under 10 us; the ceiling is generous because the
    suite shares its cores."""
    acct = StepAccount(SERVE_PHASES, FlightRecorder(), MetricRegistry(),
                       "serving")
    none = _NoAnnotation()
    span = acct.span

    def step(i):
        acct.begin(i)
        with span("step", none):
            with span("admit", none):
                pass
            with span("prefill_chunk", none):
                pass
            with span("first_token", none):
                with span("fetch", none):
                    pass
            with span("dispatch", none):
                pass
            with span("collect", none):
                with span("fetch", none):
                    pass
            with span("finish", none):
                pass
            with span("tail", none):
                pass
        acct.end(1, 2, 0, True)

    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for i in range(2000):
            step(i)
        best = min(best, (time.perf_counter() - t0) / 2000)
    print(f"step account: {best * 1e6:.2f} us a step")
    assert best < 50e-6
    assert acct.flight.steps_recorded == 10000
    assert len(acct.flight.records) == acct.flight.capacity


# -- one ring, hub or no hub ---------------------------------------------------

def test_the_ring_holds_the_same_records_with_and_without_a_hub(tiny_paged,
                                                                tmp_path):
    cfg, pool = tiny_paged
    obs = Observability(str(tmp_path / "obs"))
    rings = []
    for hub in (None, obs):
        engine, clock = _engine(pool, **({"obs": hub} if hub else {}))
        _drive(engine, cfg, 9, late_at=3)
        rings.append(engine)
    bare, hubbed = rings
    assert hubbed._flight is obs.flight and bare._flight is not obs.flight
    assert len(bare._flight.records) == len(obs.flight.records) == 9
    for a, b in zip(bare._flight.records, obs.flight.records):
        assert type(a).__name__ == type(b).__name__ == "StepRecord"
        assert a._fields == b._fields
        # the same scripted clocks: the same record but for the wall stamp
        assert a._replace(time=0.0) == b._replace(time=0.0)
    # flat in the ring, documents at a dump
    assert not any(isinstance(r, dict) for r in obs.flight.records)
    path = hubbed.dump_flight("unit_test")
    assert path == obs.flight_path and bare.dump_flight("unit_test") is None
    with open(path) as f:
        doc = json.load(f)
    validate_flight_document(doc)
    assert [r["step"] for r in doc["records"]] == list(range(1, 10))
    assert {"wall_ms", "cpu_ms", "blocked_ms", "off_cpu_ms", "between_ms",
            "queue_depth", "slots_active", "tail_ms",
            "first_token_ms"} <= set(doc["records"][0])
    for engine in rings:
        engine.close()
