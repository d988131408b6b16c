"""The start-up account (``obs.startup``): every second from the process's
start to ``ready`` has a phase, the compile path has its stages, and after
``ready`` the steady state pays nothing.

- on a scripted clock the phases add up to ``ready_s`` exactly, nested
  phases book self time, a phase open inside itself is one phase;
- JAX's duration and cache events (fed through ``jax.monitoring``) land in
  their counters, made disjoint, and stop being booked after ``ready``;
- ``ready`` is taken once a process;
- a tiny ``ServingEngine`` warmed up and a tiny ``fit()`` each leave the
  declared names in the registry of whoever declared, and the spans
  ``nxd/startup/*`` in a profile taken over set-up;
- after ``ready`` no phase opens over serve steps and train steps;
- a ``fit()`` that names a checkpoint directory loads the checkpoint library
  before its first step, under ``import``; one that names none loads nothing.

Each test gets an account of its own in place of the process's.
"""

import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import CompileLedger, MetricRegistry, startup
from neuronx_distributed_tpu.obs.schemas import (
    REGISTRY_METRICS,
    validate_registry_metrics,
)
from neuronx_distributed_tpu.obs.startup import (
    COMPILE_STAGES,
    STARTUP_PHASES,
    StartupAccount,
)
from neuronx_distributed_tpu.parallel.layers import init_sharded_params
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel
from neuronx_distributed_tpu.trainer import (
    default_batch_spec,
    fit,
    initialize_parallel_model,
    initialize_parallel_optimizer,
)
from neuronx_distributed_tpu.utils import checkpoint_library as library
from test_device_names import _host_spans

TICK = 1.0 / 1024        # a scripted clock's grain: sums of it are exact
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


class ScriptedClock:
    """Stands still between ``jump``s."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def jump(self, ticks):
        self.t += ticks * TICK


class TickingClock(ScriptedClock):
    """Moves one tick each time it is read."""

    def __call__(self):
        self.t += TICK
        return self.t


@pytest.fixture
def acct(monkeypatch):
    """A fresh account in the process's place, born at 0 on a scripted
    clock."""
    a = StartupAccount(origin=0.0)
    a.clock = ScriptedClock()
    monkeypatch.setattr(startup, "_ACCOUNT", a)
    return a


@pytest.fixture
def live(monkeypatch):
    """A fresh account on the real clock, born now."""
    a = StartupAccount(origin=time.perf_counter())
    monkeypatch.setattr(startup, "_ACCOUNT", a)
    return a


def _ms(snap, family):
    head = f"startup/{family}/"
    return {k[len(head):]: v for k, v in snap.items() if k.startswith(head)}


# -- the phases ----------------------------------------------------------------

def test_phases_add_up_to_ready_exactly_and_process_is_the_remainder(acct):
    clock = acct.clock
    clock.jump(40)                          # the interpreter, the imports
    acct.imported(clock.t, clock.t + 24 * TICK)
    clock.jump(24 + 8)                      # the import, then the caller
    with acct.phase("mesh"):
        clock.jump(2)
    clock.jump(5)
    with acct.phase("weights"):
        clock.jump(100)
    with acct.phase("engine"):
        clock.jump(7)
    clock.jump(64)                          # the warm-up's steps: process
    acct.move("warmup", 60 * TICK)
    assert acct.ready("engine")
    got = acct.phases_s()
    assert got == {"process": 57 * TICK, "import": 24 * TICK,
                   "backend": 0.0, "mesh": 2 * TICK, "weights": 100 * TICK,
                   "optimizer": 0.0, "engine": 7 * TICK, "warmup": 60 * TICK,
                   "step0": 0.0, "audit": 0.0}
    assert sum(got.values()) == acct.ready_s == 250 * TICK
    snap = acct.snapshot()
    assert snap["startup/ready_s"] == 250 * TICK
    assert sum(_ms(snap, "ms_total").values()) == 250 * TICK * 1e3
    assert tuple(_ms(snap, "ms_total")) == STARTUP_PHASES
    assert snap["label"] == "engine"


def test_nested_phases_book_self_time(acct):
    clock = acct.clock
    with acct.phase("step0"):
        clock.jump(3)
        with acct.phase("weights"):         # a resume's checkpoint load
            clock.jump(10)
            with acct.phase("mesh"):
                clock.jump(1)
            clock.jump(2)
        clock.jump(4)
        with acct.phase("audit"):
            clock.jump(20)
        clock.jump(5)
    acct.ready("fit")
    got = acct.phases_s()
    assert (got["step0"], got["weights"], got["mesh"], got["audit"]) \
        == (12 * TICK, 12 * TICK, 1 * TICK, 20 * TICK)
    assert got["process"] == 0.0 and acct.ready_s == 45 * TICK


def test_a_phase_open_inside_itself_is_one_phase(acct):
    clock = acct.clock
    before = startup.PHASES_OPENED
    with acct.phase("weights"):             # initialize_parallel_model
        clock.jump(1)
        with acct.phase("mesh"):
            with acct.phase("weights"):     # not this one's: mesh's inside
                clock.jump(2)
        with acct.phase("weights"):         # init_sharded_params
            clock.jump(4)
    assert startup.PHASES_OPENED == before + 2
    acct.ready("fit")
    assert acct.phases_s()["weights"] == 5 * TICK
    assert acct.phases_s()["mesh"] == 2 * TICK


def test_a_phase_open_at_ready_ends_there(acct):
    clock = acct.clock
    with acct.phase("step0"):               # fit() as a whole call
        clock.jump(9)
        assert acct.ready("fit")
        clock.jump(1000)                    # the steps after the first
    clock.jump(5)
    assert acct.phases_s()["step0"] == 9 * TICK == acct.ready_s
    assert sum(acct.phases_s().values()) == acct.ready_s


def test_an_unknown_phase_is_an_error(acct):
    with pytest.raises(ValueError):
        acct.phase("warm-up")


def test_off_linux_the_origin_is_the_packages_import_stamp(monkeypatch):
    monkeypatch.setattr(startup, "_process_age_s", lambda: None)
    assert StartupAccount().origin == nxd._IMPORT_T0
    # and on Linux the kernel's stamp lies before it
    monkeypatch.undo()
    age = startup._process_age_s()
    if age is not None:
        assert StartupAccount().origin < nxd._IMPORT_T0
        assert 0 < age < 24 * 3600
    # the process's own account booked the package's import
    assert startup.account().phases_s()["import"] > 0


# -- the compile path ----------------------------------------------------------

@pytest.mark.parametrize("event,stage", [
    (TRACE, "trace"), (LOWER, "lower"), (COMPILE, "backend_compile"),
    (CACHE_READ, "cache_read")])
def test_a_duration_event_lands_in_its_stage_until_ready(acct, event, stage):
    # the longer one first: a trace that holds the one before it is its
    # caller's, and books only its own part
    jax.monitoring.record_event_duration_secs(event, 0.5, fun_name="f")
    jax.monitoring.record_event_duration_secs(event, 0.25, fun_name="f")
    assert acct.stage_s == {**dict.fromkeys(COMPILE_STAGES, 0.0),
                            stage: 0.75}
    acct.ready("engine")
    jax.monitoring.record_event_duration_secs(event, 8.0, fun_name="f")
    snap = acct.snapshot()
    assert snap[f"startup/compile_ms_total/{stage}"] == 750.0
    assert sum(_ms(snap, "compile_ms_total").values()) == 750.0


def test_cache_counts_and_saved_time_until_ready(acct):
    for name, n in ((REQUEST, 5), (HIT, 3), (MISS, 2)):
        for _ in range(n):
            jax.monitoring.record_event(name)
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    jax.monitoring.record_event_duration_secs(SAVED, 30.0)
    jax.monitoring.record_event_duration_secs(SAVED, 12.5)
    assert acct.counts == {"compile_requests": 5, "cache_hits": 3,
                           "cache_misses": 2}
    acct.ready("engine")
    for name in (REQUEST, HIT, MISS):
        jax.monitoring.record_event(name)
    jax.monitoring.record_event_duration_secs(SAVED, 99.0)
    snap = acct.snapshot()
    assert (snap["startup/compile_requests_total"],
            snap["startup/cache_hits_total"],
            snap["startup/cache_misses_total"],
            snap["startup/compile_saved_ms_total"]) == (5, 3, 2, 42500.0)


def test_the_stages_are_disjoint_and_programs_hold_whole_requests(acct):
    # a request the cache served: JAX reports the read, then the whole
    # request (the read inside it) under the program's name
    jax.monitoring.record_event_duration_secs(CACHE_READ, 0.75)
    jax.monitoring.record_event_duration_secs(COMPILE, 1.0,
                                              fun_name="jit__step_")
    # one it did not: the compiler's time is the request's
    jax.monitoring.record_event_duration_secs(COMPILE, 16.0,
                                              fun_name="jit(<lambda>)")
    # a jitted helper traced inside the step's trace reports first
    jax.monitoring.record_event_duration_secs(TRACE, 0.125, fun_name="where")
    jax.monitoring.record_event_duration_secs(TRACE, 0.125, fun_name="where")
    jax.monitoring.record_event_duration_secs(TRACE, 2.0, fun_name="_step")
    time.sleep(0.02)                        # a sibling, later: not inside
    jax.monitoring.record_event_duration_secs(TRACE, 0.0078125,
                                              fun_name="<lambda>")
    assert acct.stage_s == {"trace": 2.0078125, "lower": 0.0,
                            "backend_compile": 16.25, "cache_read": 0.75}
    acct.ready("fit")
    assert acct.snapshot()["programs"] == [["_lambda_", 16.008],
                                           ["_step", 3.0]]


def test_a_ledger_is_fed_from_the_accounts_listener_after_ready_too(acct):
    reg = MetricRegistry()
    led = CompileLedger(registry=reg)
    assert led in startup.LEDGERS
    acct.ready("engine")
    led.declare_warmup_done()
    jax.monitoring.record_event_duration_secs(COMPILE, 0.5, fun_name="g")
    jax.monitoring.record_event_duration_secs(TRACE, 0.5, fun_name="g")
    assert reg.counter("trace/compile_requests_total").value == 1
    assert led.storms == 1 and led.rows[-1]["family"] == "jit_dispatch"
    assert acct.stage_s == dict.fromkeys(COMPILE_STAGES, 0.0)


# -- ready ----------------------------------------------------------------------

class Lines(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.ready = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("startup: ready {"):
            self.ready.append(json.loads(msg[msg.index("{"):]))


@pytest.fixture
def ready_lines():
    handler = Lines()
    log = logging.getLogger(startup.__name__)
    log.addHandler(handler)
    yield handler.ready
    log.removeHandler(handler)


def test_ready_is_taken_once_and_logs_one_line(acct, ready_lines):
    first, second = MetricRegistry(), MetricRegistry()
    acct.clock.jump(10)
    assert acct.ready("engine", first) is True
    acct.clock.jump(10)
    assert acct.ready("fit", second) is False
    assert acct.ready_s == 10 * TICK and acct.label == "engine"
    assert first.snapshot()["startup/ready_s"] == 10 * TICK
    assert not second.snapshot()
    (line,) = ready_lines
    assert line["label"] == "engine" and line["phases_s"] == {
        "process": round(10 * TICK, 3)}
    assert set(line) == {"label", "ready_s", "phases_s", "compile_s",
                         "compile_saved_s", "compile_requests", "cache_hits",
                         "cache_misses", "programs"}
    # after it a phase is a shared no-op, whatever the name
    before = startup.PHASES_OPENED
    assert acct.phase("weights") is acct.phase("engine")
    assert startup.PHASES_OPENED == before
    validate_registry_metrics(first)
    declared = {k for k in REGISTRY_METRICS if k.startswith("startup/")}
    assert {m.name for m in first.metrics()} == declared


# -- the program's own calls ---------------------------------------------------

def _profiled(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)


def _request(cfg, rid, plen, new):
    rs = np.random.RandomState(100 + rid)
    return Request(request_id=rid, max_new_tokens=new,
                   prompt_ids=rs.randint(1, cfg.vocab_size, plen).tolist())


def _tiny_engine():
    nxd.initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(sequence_parallel=False, dtype=jnp.float32,
                           param_dtype=jnp.float32, max_seq_len=32,
                           remat="none")
    module = LlamaForCausalLM(cfg)
    params, _ = init_sharded_params(module, jax.random.PRNGKey(0),
                                    jnp.zeros((3, 8), jnp.int32))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=32,
                        kv_cache_dtype=jnp.float32))
    return cfg, pool, ServingEngine(pool, page_size=4,
                                    prefill_chunk_tokens=4)


def test_a_warmed_up_engine_is_ready_and_its_set_up_is_spans(
        devices8, live, tmp_path, ready_lines):
    _profiled(tmp_path)
    try:
        cfg, pool, engine = _tiny_engine()
        engine.submit(_request(cfg, 0, 6, 3))
        engine.run_until_complete(max_steps=100)
        stepping_ms = engine.registry.counter("serving/step_ms_total").value
        engine.declare_warmup_done()
    finally:
        jax.profiler.stop_trace()
    snap = engine.registry.snapshot()
    assert snap["startup/ready_s"] == live.ready_s > 0
    phases = _ms(snap, "ms_total")
    assert sum(phases.values()) == pytest.approx(live.ready_s * 1e3,
                                                 abs=1e-6)
    assert phases["warmup"] == pytest.approx(stepping_ms) and stepping_ms > 0
    for name in ("mesh", "weights", "engine", "process"):
        assert phases[name] > 0, name
    assert phases["step0"] == phases["optimizer"] == 0
    stages = _ms(snap, "compile_ms_total")
    assert stages["trace"] > 0 and stages["backend_compile"] > 0
    assert sum(stages.values()) < live.ready_s * 1e3
    validate_registry_metrics(engine.registry)
    (line,) = ready_lines
    assert line["label"] == "engine"
    assert "init_sharded" in [name for name, _ in line["programs"]]
    # what the set-up calls opened, and nothing else under the prefix: the
    # model's wrapper and the engine are ``engine`` twice
    spans = _host_spans(str(tmp_path), "nxd/startup/")
    assert [s[0] for s in spans] == ["mesh", "weights", "engine", "engine"]
    assert {s[0] for s in spans} <= set(STARTUP_PHASES)
    # a second engine of the process changes nothing, and no phase opens
    # over 50 steps of the first
    before = startup.PHASES_OPENED
    other = ServingEngine(pool, page_size=4, prefill_chunk_tokens=4)
    other.declare_warmup_done()
    assert "startup/ready_s" not in other.registry.snapshot()
    for rid in range(1, 4):
        engine.submit(_request(cfg, rid, 5, 24))
    for _ in range(50):
        engine.step()
    engine.close()
    other.close()
    assert startup.PHASES_OPENED == before
    assert engine.registry.snapshot()["startup/ready_s"] == live.ready_s
    assert len(ready_lines) == 1


def _tiny_fit(steps, obs=None, **kw):
    config = nxd.training_config(tensor_parallel_size=2, learning_rate=1e-3)
    cfg = LlamaConfig.tiny(max_seq_len=32)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg),
        (jnp.zeros((1, 16), jnp.int32),))
    opt = initialize_parallel_optimizer(config, model)

    def data(step):
        ids = np.random.RandomState(step).randint(
            1, cfg.vocab_size, (8, 16)).astype(np.int32)
        return {"ids": ids, "labels": ids}

    spec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
    return fit(config, model, opt, data, steps=steps,
               loss_fn=make_causal_lm_loss_sum(), batch_spec=spec,
               log_every=0, obs=obs, **kw)


@pytest.mark.parametrize("deferred", [True, False])
def test_a_fit_is_ready_at_its_first_loss(devices8, live, tmp_path,
                                          ready_lines, deferred):
    from neuronx_distributed_tpu.obs import Observability

    hub = Observability(str(tmp_path / "obs"))
    heard = []

    def on_step(step, m):
        # the step whose loss is here first is step 0, and the process is
        # ready before anybody hears of it
        heard.append((step, live.ready_s is not None))

    _profiled(tmp_path / "profile")
    try:
        _tiny_fit(3, obs=hub, defer_metrics=deferred, on_step=on_step)
    finally:
        jax.profiler.stop_trace()
    assert heard == [(0, True), (1, True), (2, True)]
    snap = hub.registry.snapshot()
    assert snap["startup/ready_s"] == live.ready_s > 0
    phases = _ms(snap, "ms_total")
    assert sum(phases.values()) == pytest.approx(live.ready_s * 1e3,
                                                 abs=1e-6)
    for name in ("mesh", "backend", "weights", "optimizer", "step0",
                 "audit"):
        assert phases[name] > 0, name
    assert phases["engine"] == phases["warmup"] == 0
    validate_registry_metrics(hub.registry)
    (line,) = ready_lines
    assert line["label"] == "fit"
    spans = _host_spans(str(tmp_path / "profile"), "nxd/startup/")
    assert [s[0] for s in spans] == ["weights", "mesh", "backend",
                                     "optimizer", "step0", "audit"]
    by = {s[0]: s for s in spans}
    # the mesh comes up inside initialize_parallel_model, the client inside
    # the mesh, the audit's second compile inside step0, and step0 ends at
    # ``ready``, not with fit()
    assert by["weights"][1] <= by["mesh"][1] <= by["backend"][1] \
        and by["backend"][2] <= by["mesh"][2] <= by["weights"][2]
    assert by["step0"][1] <= by["audit"][1] \
        and by["audit"][2] <= by["step0"][2]
    assert (by["step0"][2] - by["step0"][1]) * 1e-6 \
        == pytest.approx(phases["step0"] + phases["audit"], rel=0.05)
    # a second fit() of the process changes nothing and opens nothing
    # (the set-up calls and fit() itself take the no-op), over 20 steps
    before = startup.PHASES_OPENED
    nxd.destroy_model_parallel()
    _tiny_fit(20, defer_metrics=deferred)
    assert startup.PHASES_OPENED == before
    assert len(ready_lines) == 1
    assert hub.registry.snapshot()["startup/ready_s"] == live.ready_s


@pytest.mark.parametrize("named", [True, False])
def test_a_fit_that_names_a_checkpoint_loads_the_library_under_import(
        devices8, acct, monkeypatch, tmp_path, named):
    # every reading of the clock is one tick, so a phase's self time is the
    # boundaries read while it was the innermost: the load's is ONE
    acct.clock = TickingClock()
    monkeypatch.setattr(library, "_OCP", None)      # a fresh process
    opened = []
    phase = acct.phase

    def listed(name, **kw):
        opened.append(name)
        return phase(name, **kw)

    monkeypatch.setattr(acct, "phase", listed)
    _tiny_fit(2, ckpt_dir=str(tmp_path / "ckpt") if named else None)
    got = acct.phases_s()
    assert acct.label == "fit" and sum(got.values()) == acct.ready_s
    if named:
        # before the first step (inside fit(), which is step0), and not in
        # step0's or the weights' seconds
        assert opened.index("import") == opened.index("step0") + 1
        assert got["import"] == TICK
        assert library._OCP is library.checkpoint_library()
    else:
        assert "import" not in opened and got["import"] == 0.0
        assert library._OCP is None
