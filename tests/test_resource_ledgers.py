"""Compile & HBM resource ledgers (obs/compile_ledger.py +
obs/memory_ledger.py and their threading through trace/serving/trainer/
fleet/tools).

Five layers:

- LEDGER UNITS — pure host-side: compile rows + cache events + jsonl
  schema, thrash/storm detection with tracer/flight surfacing, memory
  subsystem accounting + peaks + the OOM breakdown dump, the jax-version-
  guarded ``profiling.memory_analysis``;
- INTERCEPTION COMPLETENESS — monkeypatched compile counters
  (``jax.stages.Lowered.compile`` for the AOT phase fns,
  ``_CompiledLRU.put`` for the lazy-jit families) must equal the ledger's
  rows: no compile site escapes the accounting;
- ZERO-RECOMPILE-AFTER-WARMUP — steady-state guard tests across serving
  configs (plain / chunked / spec / lora / paged-kernel) and steady-state
  ``fit()``: after warmup is declared done, ledger-counted compiles == 0
  and storms == 0;
- LEDGERS-OFF — the default engine allocates NO ledger rows (module
  counter ``obs.compile_ledger.LEDGER_ROWS``, the SPANS_CREATED
  discipline) and registers no ``mem/`` gauges;
- SURFACES — ``mem/*_bytes`` gauges summing to the pools'
  ``page_bytes``-derived logical sizes, fleet ``Replica.load()``/
  ``describe()`` headroom views, obs_report "compile"/"memory" sections +
  markdown tables, and the ``obs_report --compare`` regression diff
  (nonzero rc on regression).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FakeCompiled, sharded_params
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import (
    CompileLedger,
    MemoryLedger,
    MetricRegistry,
    Tracer,
    read_compile_ledger,
    read_memory_breakdown,
)
from neuronx_distributed_tpu.obs import compile_ledger as compile_ledger_mod
from neuronx_distributed_tpu.obs.flight import FlightRecorder
from neuronx_distributed_tpu.obs.report import (
    build_report,
    compare_resources,
    render_markdown,
)
from neuronx_distributed_tpu.obs.schemas import validate_jsonl, validate_record
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.serving import Replica, Request, ServingEngine
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel
from neuronx_distributed_tpu.trace.engine import _CompiledLRU

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- ledger units ------------------------------------------------------------

def test_compile_ledger_rows_schema_and_summary(tmp_path):
    path = str(tmp_path / "compile_ledger.jsonl")
    reg = MetricRegistry()
    led = CompileLedger(path=path, registry=reg)
    led.set_capacity("decode_pages", 4)
    led.record_compile("decode_pages", ("fp", True), 120.0, kind="jit")
    led.record_compile("context", (2, 8, 16), 350.0, kind="aot")
    led.cache_hit("decode_pages")
    led.cache_miss("decode_pages")
    led.record_eviction("decode_pages", ("int8", False))
    led.declare_warmup_done("test")
    assert led.warmup_done and led.storms == 0
    led.record_compile("verify_pages", 3, 80.0, kind="jit")  # a storm
    assert led.storms == 1 and led.compile_count() == 3

    n = validate_jsonl("compile_ledger", path)
    rows = read_compile_ledger(path)
    assert n == len(rows) == 5  # 3 compiles + eviction + warmup_done
    events = [r["event"] for r in rows]
    assert events.count("compile") == 3
    assert "eviction" in events and "warmup_done" in events
    evic = next(r for r in rows if r["event"] == "eviction")
    # the EVICTED key is the row's key — thrash is attributable
    assert "int8" in evic["key"] and evic["family"] == "decode_pages"
    storm_row = next(r for r in rows if r.get("storm"))
    assert storm_row["after_warmup"] is True

    s = led.summary()
    assert s["compiles"] == 3 and s["aot"] == 1 and s["jit"] == 2
    assert s["storms"] == 1 and s["evictions"] == 1
    assert s["cold_ms_total"] == pytest.approx(550.0)
    assert s["families"]["decode_pages"]["evictions"] == 1
    assert s["cache"]["hits"] == 1 and s["cache"]["misses"] == 1

    snap = reg.snapshot()
    assert snap["trace/compiles_total"] == 3.0
    assert snap["trace/compile_storms_total"] == 1.0
    assert snap["trace/compile_ms"]["count"] == 3


def test_compile_ledger_thrash_detection():
    reg = MetricRegistry()
    led = CompileLedger(registry=reg)
    led.set_capacity("decode_loop", 2)
    led.record_compile("decode_loop", 4, 10.0)
    led.record_compile("decode_loop", 8, 10.0)
    assert not led.warnings
    led.record_compile("decode_loop", 16, 10.0)  # 3 distinct keys > cap 2
    assert any(w["detector"] == "compile_thrash" for w in led.warnings)
    assert reg.snapshot()["trace/compile_thrash_total"] == 1.0
    # fires once per family, not per further key
    led.record_compile("decode_loop", 32, 10.0)
    assert sum(1 for w in led.warnings
               if w["detector"] == "compile_thrash") == 1
    assert any(r["event"] == "thrash" for r in led.rows)


def test_compile_storm_surfaces_in_tracer_and_flight():
    tr = Tracer()
    flight = FlightRecorder(capacity=8)
    led = CompileLedger(tracer=tr, flight=flight)
    led.declare_warmup_done()
    led.record_compile("decode_pages", "k", 250.0, kind="jit")
    spans = tr.spans()
    assert [s.name for s in spans] == ["compile"]
    assert spans[0].attrs["storm"] is True
    # the span back-dates its start by the compile wall time (plus the
    # few microseconds between begin and end)
    assert spans[0].duration_ms == pytest.approx(250.0, rel=0.05)
    # the flight warning validates against the anomaly schema (it rides
    # flight_record.json["warnings"] next to the step anomalies)
    assert len(flight.warnings) == 1
    validate_record("anomaly", dict(flight.warnings[0]))
    assert flight.warnings[0]["detector"] == "compile_storm"


def test_compile_ledger_timed_context_and_cost_stats():
    led = CompileLedger()
    with led.timed("probe", (3,), kind="aot") as rec:
        rec["compiled"] = jax.jit(lambda x: x * 2).lower(
            jnp.ones(3)).compile()
    [row] = [r for r in led.rows if r["event"] == "compile"]
    assert row["wall_ms"] > 0 and row["kind"] == "aot"
    # cost/memory stats off the executable (CPU backend reports them)
    assert "flops" in row and "output_size_in_bytes" in row


def test_ledger_counts_cost_model_degradation():
    reg = MetricRegistry()
    led = CompileLedger(registry=reg)
    led.record_compile("train_step", "k", 1.0, kind="jit",
                       compiled=FakeCompiled({"flops": 7.0}))
    row = led.rows[-1]
    assert row["flops"] == 7.0 and row["bytes_accessed"] == 0.0
    assert row["cost_keys_missing"] == 2
    assert reg.counter("perf/cost_model_missing_total").value == 2


def test_memory_ledger_accounting_peaks_and_breakdown(tmp_path):
    reg = MetricRegistry()
    ml = MemoryLedger(registry=reg, path=str(tmp_path / "mb.json"))
    ml.set("kv_pool", 1000)
    ml.set("kv_pool", 400)  # peak stays at the watermark
    ml.account_tree("params", {"w": np.zeros((4, 4), np.float32)})
    ml.note_program("decode", {"temp_size_in_bytes": 512.0,
                               "output_size_in_bytes": 64.0})
    assert ml.total_bytes == 400 + 64 + 512
    snap = reg.snapshot()
    assert snap["mem/kv_pool_bytes"] == 400.0
    assert snap["mem/kv_pool_peak_bytes"] == 1000.0
    assert snap["mem/params_bytes"] == 64.0
    assert snap["mem/workspace_bytes"] == 512.0
    doc = ml.breakdown("test")
    validate_record("memory_breakdown", doc)
    assert doc["top"][0][0] == "workspace"
    path = ml.dump()
    assert read_memory_breakdown(path)["subsystems"]["kv_pool"][
        "peak_bytes"] == 1000


def test_memory_ledger_oom_dump(tmp_path):
    ml = MemoryLedger(path=str(tmp_path / "mb.json"))
    ml.set("kv_pool", 123456)
    assert ml.oom_dump(ValueError("just a bug")) is None
    assert not os.path.exists(ml.path)
    path = ml.oom_dump(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 bytes"))
    doc = read_memory_breakdown(path)
    assert doc["reason"] == "oom:RuntimeError"
    assert doc["top"][0] == ["kv_pool", 123456]


def test_profiling_memory_analysis_guarded():
    from neuronx_distributed_tpu.utils.profiling import (
        cost_report,
        memory_analysis,
    )

    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    ma = memory_analysis(compiled)
    assert ma is None or "argument_size_in_bytes" in ma
    rep = cost_report(compiled)
    assert rep.get("flops", 0) > 0
    # a backend that raises normalizes to None, never an exception
    class Broken:
        def memory_analysis(self):
            raise NotImplementedError("backend")

    assert memory_analysis(Broken()) is None


def test_lru_first_call_timing_hits_misses_and_unwrap():
    class Owner:
        pass

    owner = Owner()
    owner.compile_ledger = CompileLedger()
    lru = _CompiledLRU("cache", capacity=2, owner=owner)
    assert lru.get(("decode_pages", "fp")) is None  # miss
    lru.put(("decode_pages", "fp"), lambda x: x + 1)
    wrapped = lru.get(("decode_pages", "fp"))  # hit (the timing wrapper)
    assert wrapped(41) == 42
    # the first call recorded the compile — attributed to the PROGRAM
    # family (the key's leading name), not the cache — and UNWRAPPED
    assert owner.compile_ledger.compile_count() == 1
    row = owner.compile_ledger.rows[-1]
    assert row["family"] == "decode_pages" and row["wall_ms"] is not None
    raw = lru.get(("decode_pages", "fp"))
    assert raw is not wrapped and raw(1) == 2
    assert owner.compile_ledger.compile_count() == 1  # no double count
    # overflow evicts oldest WITH its key on the ledger
    lru.put(("verify_pages", 3), lambda x: x)
    lru.put(("verify_pages", 5), lambda x: x)
    evic = [r for r in owner.compile_ledger.rows if r["event"] == "eviction"]
    assert len(evic) == 1
    assert evic[0]["family"] == "decode_pages"
    assert "fp" in evic[0]["key"]
    assert owner.compile_ledger.cache_hits == 2
    assert owner.compile_ledger.cache_misses == 1


# -- e2e: CPU tiny Llama -----------------------------------------------------

def _tiny_model(batch_size=3, C=8, T=16, ledger=None):
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none",
    )
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((batch_size, C), jnp.int32)))
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=batch_size, context_len=C,
                        max_total_len=T, kv_cache_dtype=jnp.float32),
        compile_ledger=ledger)
    return cfg, model


@pytest.fixture
def tiny_serving(devices8):
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    return _tiny_model()


def test_interception_completeness_monkeypatched_counter(devices8,
                                                         monkeypatch):
    """Every compile site is accounted: the AOT ``.lower().compile()``
    calls (counted by patching ``jax.stages.Lowered.compile``) equal the
    ledger's "aot" rows, and every ``_CompiledLRU.put`` (each put is a new
    program whose first call compiles) equals the ledger's lazy-jit rows."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    import jax.stages as jax_stages
    from neuronx_distributed_tpu.trace import engine as trace_engine

    led = CompileLedger()
    aot_count = [0]
    orig_compile = jax_stages.Lowered.compile

    def counting_compile(self, *a, **k):
        aot_count[0] += 1
        return orig_compile(self, *a, **k)

    monkeypatch.setattr(jax_stages.Lowered, "compile", counting_compile)
    put_count = [0]
    orig_put = trace_engine._CompiledLRU.put

    def counting_put(self, key, fn):
        put_count[0] += 1
        return orig_put(self, key, fn)

    monkeypatch.setattr(trace_engine._CompiledLRU, "put", counting_put)

    cfg, model = _tiny_model(ledger=led)
    # the contiguous context/decode pair compiles AOT on first use (a paged
    # engine never calls it): a stepped solo generate is what asks for both
    prompt = jnp.zeros((model.config.batch_size, model.config.context_len),
                       jnp.int32)
    jax.block_until_ready(model.generate(prompt, 2, fused=False))
    engine = ServingEngine(model, page_size=4, num_pages=16,
                           compile_ledger=led)
    rs = np.random.RandomState(0)
    for i in range(3):
        engine.submit(Request(
            request_id=i,
            prompt_ids=rs.randint(1, cfg.vocab_size, size=5).tolist(),
            max_new_tokens=4))
    outs = engine.run_until_complete(max_steps=200)
    engine.close()
    assert len(outs) == 3

    rows = [r for r in led.rows if r["event"] == "compile"]
    aot_rows = [r for r in rows if r["kind"] == "aot"]
    # lazy-jit rows from the LRU families (module-level sampler jits are
    # polled separately under "jit:*" families and have no put)
    lru_rows = [r for r in rows
                if r["kind"] == "jit" and not r["family"].startswith("jit:")]
    assert len(aot_rows) == aot_count[0] > 0
    assert len(lru_rows) == put_count[0] > 0
    families = {r["family"] for r in rows}
    assert {"context", "decode", "decode_pages", "prefill_chunk_pages",
            "insert_valid"} <= families


def _serve(engine, cfg, rids, prompt_len=5, seed=0, adapter_id=0,
           max_new=4):
    rs = np.random.RandomState(seed)
    for i in rids:
        engine.submit(Request(
            request_id=i,
            prompt_ids=rs.randint(1, cfg.vocab_size,
                                  size=prompt_len).tolist(),
            max_new_tokens=max_new, adapter_id=adapter_id))
    return engine.run_until_complete(max_steps=400)


def _zero_recompile_engine(config, devices8):
    """Build (cfg, engine, warm_fn, measure_fn) for one serving config."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    led = CompileLedger()
    cfg, model = _tiny_model(ledger=led)
    kw = dict(page_size=4, num_pages=24, compile_ledger=led,
              memory_ledger=MemoryLedger())
    if config == "chunked":
        kw["prefill_chunk_tokens"] = 4
    elif config == "spec":
        _, draft = _tiny_model(ledger=led)
        kw.update(draft=draft, spec_k=2)
    elif config == "lora":
        from neuronx_distributed_tpu.tenancy import make_adapter_store

        store = make_adapter_store(model, rank=2, num_pages=8,
                                   page_elems=512)
        r2 = np.random.RandomState(7)
        H, NQ, NKV, D = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                         cfg.head_dim_)
        store.register(1, [{
            "a_q": (r2.randn(H, 2) * 0.05).astype(np.float32),
            "b_q": (r2.randn(2, NQ * D) * 0.05).astype(np.float32),
            "a_v": (r2.randn(H, 2) * 0.05).astype(np.float32),
            "b_v": (r2.randn(2, NKV * D) * 0.05).astype(np.float32),
        } for _ in range(cfg.num_layers)], alpha=4.0)
        kw["adapter_store"] = store
    elif config == "paged_kernel":
        kw["paged_kernel"] = True
    engine = ServingEngine(model, rng=jax.random.PRNGKey(0), **kw)
    return cfg, engine, led


@pytest.mark.parametrize("config", [
    "plain", "chunked", "spec", "lora",
    pytest.param("paged_kernel", marks=pytest.mark.slow),
])
def test_zero_recompiles_after_warmup(config, devices8):
    """The steady-state guard: once the warm pass has exercised every
    program the workload needs, declare_warmup_done() — and the measured
    pass must compile NOTHING (compiles == storms == 0)."""
    cfg, engine, led = _zero_recompile_engine(config, devices8)
    adapter = 1 if config == "lora" else 0
    # warm: full-width AND short prompts so every chunk width / prefix
    # shape the measured pass hits is compiled
    outs = _serve(engine, cfg, [100, 101], prompt_len=8, seed=1,
                  adapter_id=adapter)
    outs += _serve(engine, cfg, [102], prompt_len=5, seed=2,
                   adapter_id=adapter)
    assert len(outs) == 3 and led.compile_count() > 0
    engine.declare_warmup_done()
    outs = _serve(engine, cfg, [0, 1, 2], prompt_len=8, seed=3,
                  adapter_id=adapter)
    outs += _serve(engine, cfg, [3, 4], prompt_len=5, seed=4,
                   adapter_id=adapter)
    engine.close()
    assert len(outs) == 5
    assert all(o.state == "finished" for o in outs)
    assert led.compile_count(after_warmup_only=True) == 0, (
        f"{config}: compiles after warmup: "
        f"{[r for r in led.rows if r['event'] == 'compile' and r['after_warmup']]}")
    assert led.storms == 0 and not led.warnings


def test_zero_recompiles_steady_fit(devices8, tmp_path):
    """Steady-state fit(): the ledger books the audit AOT compile and the
    first step's cold dispatch, declares warmup, and sees NOTHING after —
    and the memory ledger accounts params + opt state and dumps the
    breakdown at close."""
    import neuronx_distributed_tpu as nxd
    from test_resilience import _build, _fit_kwargs, _step_data
    from neuronx_distributed_tpu.obs import Observability
    from neuronx_distributed_tpu.trainer import fit

    config = nxd.training_config(tensor_parallel_size=2, learning_rate=5e-3)
    m, o = _build(config)
    obs = Observability(str(tmp_path / "obs"), ledgers=True)
    res = fit(config, m, o, _step_data(), steps=5, **_fit_kwargs(), obs=obs)
    assert res.steps_run == 5
    led = obs.compile_ledger
    fams = {r["family"] for r in led.rows if r["event"] == "compile"}
    assert fams == {"train_step"}
    assert led.warmup_done
    assert led.compile_count(after_warmup_only=True) == 0
    assert led.storms == 0
    # the streamed jsonl + close-time breakdown validate
    assert validate_jsonl("compile_ledger",
                          str(tmp_path / "obs" / "compile_ledger.jsonl")) > 0
    doc = read_memory_breakdown(
        str(tmp_path / "obs" / "memory_breakdown.json"))
    assert {"params", "opt_state"} <= set(doc["subsystems"])
    assert doc["subsystems"]["params"]["bytes"] > 0
    # and the report grows populated compile/memory sections
    report = build_report(run_dir=str(tmp_path / "obs"))
    validate_record("obs_report", report)
    assert report["compile"]["compiles"] >= 2  # aot audit + step0
    assert report["memory"]["subsystems"]["params"]["bytes"] > 0
    md = render_markdown(report)
    assert "- compile:" in md and "- memory:" in md
    assert "## Compile ledger" in md and "## Memory ledger" in md


def test_ledgers_off_is_allocation_free(tiny_serving):
    """The default engine (no ledgers) must never build a ledger row or
    register a mem/ gauge — the zero-overhead-off contract, checkable as
    an exact module-counter delta."""
    cfg, model = tiny_serving
    before = compile_ledger_mod.LEDGER_ROWS
    engine = ServingEngine(model, page_size=4, num_pages=16)
    outs = _serve(engine, cfg, range(4))
    engine.close()
    assert len(outs) == 4
    assert compile_ledger_mod.LEDGER_ROWS == before, (
        "ledger-off serving built compile-ledger rows")
    names = {m.name for m in engine.registry.metrics()}
    assert not any(n.startswith("mem/") for n in names)
    assert not any(n.startswith("trace/compile") for n in names)


def test_memory_gauges_match_pool_logical_sizes(devices8):
    """Acceptance bar: the mem/*_bytes gauges' sum matches the pools'
    page_bytes-derived logical sizes (the same arithmetic admission
    uses), and the fleet views expose the headroom."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg, model = _tiny_model()
    pool = model.make_page_pool(16, 4)
    expected_pool_bytes = 16 * pool.page_bytes
    del pool

    def factory():
        return ServingEngine(model, page_size=4, num_pages=16,
                             memory_ledger=MemoryLedger())

    rep = Replica(0, factory)
    engine = rep.engine
    snap = engine.registry.snapshot()
    assert snap["mem/kv_pool_bytes"] == float(expected_pool_bytes)
    assert engine.memory_ledger.subsystems()["kv_pool"]["bytes"] == \
        expected_pool_bytes
    from neuronx_distributed_tpu.obs.memory_ledger import tree_bytes

    assert snap["mem/params_bytes"] == float(tree_bytes(model.params))
    assert engine.memory_ledger.total_bytes == sum(
        v for k, v in snap.items()
        if k.startswith("mem/") and k.endswith("_bytes")
        and not k.endswith("_peak_bytes") and not k.startswith("mem/device")
        and k != "mem/live_array_bytes")
    # fleet views: byte-denominated headroom for the router
    view = rep.load()
    assert view["mem_bytes"] == engine.memory_ledger.total_bytes
    assert view["kv_headroom_bytes"] == \
        view["pages_free"] * engine._page_bytes
    desc = rep.describe()
    assert desc["kv_page_bytes"] == engine._page_bytes
    rep.close()


def test_engine_oom_dump_on_resource_exhausted(tiny_serving, tmp_path,
                                               monkeypatch):
    """A RESOURCE_EXHAUSTED escaping step() dumps memory_breakdown.json
    naming the biggest holders before re-raising."""
    cfg, model = tiny_serving
    ml = MemoryLedger(path=str(tmp_path / "mb.json"))
    engine = ServingEngine(model, page_size=4, num_pages=16,
                           memory_ledger=ml)
    engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3],
                          max_new_tokens=2))
    monkeypatch.setattr(
        engine, "_step_impl",
        lambda: (_ for _ in ()).throw(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory")))
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        engine.step()
    doc = read_memory_breakdown(str(tmp_path / "mb.json"))
    assert doc["reason"] == "oom:RuntimeError"
    assert doc["subsystems"]["kv_pool"]["bytes"] > 0


# -- obs_report --compare ----------------------------------------------------

def _write_run(run_dir, compiles, peak_kv):
    os.makedirs(run_dir, exist_ok=True)
    led = CompileLedger(path=os.path.join(run_dir, "compile_ledger.jsonl"))
    for i in range(compiles):
        led.record_compile("decode_pages", i, 100.0, kind="jit")
    ml = MemoryLedger(path=os.path.join(run_dir, "memory_breakdown.json"))
    ml.set("kv_pool", peak_kv)
    ml.set("params", 1000)
    ml.dump()


def test_compare_resources_flags_regressions(tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _write_run(a, compiles=2, peak_kv=1000)
    _write_run(b, compiles=5, peak_kv=2000)
    diff = compare_resources(a, b)
    assert diff["regressed"]
    kinds = " ".join(diff["regressions"])
    assert "compiles regressed" in kinds and "kv_pool" in kinds
    assert "| compiles | 2 | 5 |" in diff["markdown"]
    same = compare_resources(a, a)
    assert not same["regressed"] and same["regressions"] == []


def test_obs_report_compare_cli_rc(tmp_path):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _write_run(a, compiles=2, peak_kv=1000)
    _write_run(b, compiles=5, peak_kv=2000)
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    tool = os.path.join(REPO, "tools", "obs_report.py")
    ok = subprocess.run([sys.executable, tool, "--compare", a, a],
                        capture_output=True, text=True, env=env, timeout=120)
    assert ok.returncode == 0, ok.stderr[-2000:]
    assert "Resource regression diff" in ok.stdout
    bad = subprocess.run(
        [sys.executable, tool, "--compare", a, b,
         "--out", str(tmp_path / "diff.json")],
        capture_output=True, text=True, env=env, timeout=120)
    assert bad.returncode == 1
    assert "REGRESSION" in bad.stderr
    doc = json.loads((tmp_path / "diff.json").read_text())
    assert doc["regressed"] is True

