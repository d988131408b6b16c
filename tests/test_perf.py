"""Per-phase roofline profiler, device-time accounting and MFU telemetry
(obs/perf.py + the serving-engine/fit device-time hooks + the report,
health and compare surfaces).

Layers:

- ROOFLINE MATH — hand-computed fixtures against ``roofline_attribution``
  / ``attribute``: lower-bound times, compute-/memory-bound
  classification, MFU/MBU, pct_roofline, intensity-null-when-no-bytes,
  and the ``_total`` record whose floor is the SUM of per-family floors;
- DEVICE SPECS — the one peak table keyed by ``device_kind``; an unknown
  kind (the CPU included) raises, a CPU cost model is passed explicitly;
- COST MODEL — ``utils.profiling.cost_report`` defaults missing cost
  keys to 0.0 and the ledger counts the degradation
  (``perf/cost_model_missing_total``);
- LIVE ENGINE — ``perf=None`` allocates ZERO perf records over a full
  paged serving run (module counter ``obs.perf.PERF_RECORDS``, the
  SPANS_CREATED discipline); with a tracer AND perf attached, each
  family's attributed device time sums to its traced span wall-time
  within 1 ms, every family classifies compute- or memory-bound, and the
  ledger join supplies nonzero flops (program families -> phase
  families, weighted by LRU-counted executions);
- TRAINER — ``fit()`` under ``Observability(perf=<spec>)`` drops a
  schema-valid artifact and the obs report grows a perf section with an
  MFU rollup;
- SURFACES — fleet merge (``merge_perf_records``), the default health
  pack's ``mfu_sag``/``roofline_drift`` trend rules, and the
  ``obs_report --compare`` MFU-regression gate (nonzero rc).
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import CompileLedger, Tracer
from neuronx_distributed_tpu.obs import perf as perf_mod
from neuronx_distributed_tpu.obs.perf import (
    DeviceSpec,
    PERF_FAMILIES,
    PerfAttribution,
    attribute,
    device_spec,
    merge_perf_records,
    read_perf_attribution,
    roofline_attribution,
    summarize_perf,
)
from neuronx_distributed_tpu.obs.schemas import validate_jsonl
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a synthetic device: 1 TFLOP/s, 100 GB/s — round numbers so every
# expected value below is hand-computable
SPEC = DeviceSpec("test", 1e12, 1e11)


# -- roofline math ------------------------------------------------------------

def test_roofline_compute_bound_hand_computed():
    # 5e9 flops -> 5 ms at peak; 2e8 bytes -> 2 ms at peak BW; the
    # compute wall dominates, and 10 ms achieved is 2x off the roofline
    r = roofline_attribution("x", 2, 10.0, 5e9, 2e8, SPEC)
    assert r["bound"] == "compute"
    assert r["lower_bound_ms"] == pytest.approx(5.0)
    assert r["pct_roofline"] == pytest.approx(0.5)
    assert r["mfu"] == pytest.approx(0.5)       # 5e9 / 1e-2 / 1e12
    assert r["mbu"] == pytest.approx(0.2)       # 2e8 / 1e-2 / 1e11
    assert r["arithmetic_intensity"] == pytest.approx(25.0)
    assert r["flops_per_s"] == pytest.approx(5e11)


def test_roofline_memory_bound_hand_computed():
    # 1e8 flops -> 0.1 ms; 1e9 bytes -> 10 ms; the memory wall dominates
    # and the family runs AT the roofline
    r = roofline_attribution("x", 1, 10.0, 1e8, 1e9, SPEC)
    assert r["bound"] == "memory"
    assert r["lower_bound_ms"] == pytest.approx(10.0)
    assert r["pct_roofline"] == pytest.approx(1.0)
    assert r["mbu"] == pytest.approx(1.0)


def test_roofline_zero_bytes_and_zero_wall():
    r = roofline_attribution("x", 1, 5.0, 1e9, 0.0, SPEC)
    assert r["arithmetic_intensity"] is None
    assert r["bound"] == "compute"    # t_mem == 0 <= t_compute
    z = roofline_attribution("x", 0, 0.0, 0.0, 0.0, SPEC)
    assert z["pct_roofline"] == 0.0 and z["mfu"] == 0.0


def test_attribute_is_per_call_wrapper():
    per = attribute("x", 4, 8.0, 1e9, 1e7, SPEC)
    tot = roofline_attribution("x", 4, 8.0, 4e9, 4e7, SPEC)
    for k in ("flops", "bytes", "lower_bound_ms", "pct_roofline", "mfu"):
        assert per[k] == tot[k]


def test_total_record_sums_lower_bounds_and_tokens_ceiling(tmp_path):
    path = str(tmp_path / "perf_attribution.jsonl")
    perf = PerfAttribution(path=path, spec=SPEC)
    # compute-bound family: 2 calls x 1e9 flops -> 2 ms floor
    perf.note_cost("prefill", 1e9, 1e6)
    perf.note_phase("prefill", 10.0, calls=2.0)
    # memory-bound family: 8 calls x 1e8 bytes -> 8 ms floor
    perf.note_cost("decode_step", 1e6, 1e8)
    perf.note_phase("decode_step", 20.0, calls=8.0)
    perf.note_tokens(100.0)
    recs = perf.attribution()
    total = recs[-1]
    assert total["family"] == "_total"
    # sequential phases: the total's floor is the SUM of per-family floors
    assert total["lower_bound_ms"] == pytest.approx(2.0 + 8.0)
    assert total["device_ms"] == pytest.approx(30.0)
    assert total["pct_roofline"] == pytest.approx(10.0 / 30.0)
    assert total["toks_per_s_ceiling"] == pytest.approx(100.0 / 10e-3)
    assert perf.dump() == path
    assert validate_jsonl("perf_attribution", path) == 3


# -- device specs -------------------------------------------------------------

def test_device_spec_is_keyed_by_device_kind():
    from types import SimpleNamespace as NS

    # exact device_kind strings, both spellings jax knows a chip under
    assert device_spec(NS(device_kind="TPU v5 lite")).peak_flops == 197e12
    assert device_spec(NS(device_kind="TPU v5e")).hbm_bytes_per_s == 819e9
    assert device_spec(NS(device_kind="TPU v5p")).peak_flops == 459e12
    assert device_spec(NS(device_kind="TPU v6 lite")).kind == "TPU v6 lite"


def test_unknown_device_kind_raises():
    """One peak table, no default: an unknown accelerator — and the CPU
    the tests run on — has no roofline; a caller that wants a cost model
    there passes one explicitly."""
    from types import SimpleNamespace as NS

    from neuronx_distributed_tpu.obs.perf import (
        UnknownDeviceError,
        calibrate_cpu_spec,
    )

    with pytest.raises(UnknownDeviceError, match="mystery accelerator"):
        device_spec(NS(device_kind="mystery accelerator"))
    with pytest.raises(UnknownDeviceError):
        device_spec()  # jax.devices()[0] is the CPU here
    with pytest.raises(UnknownDeviceError):
        PerfAttribution()
    a = calibrate_cpu_spec()
    assert a is calibrate_cpu_spec()    # calibrated once, cached
    assert a.kind == "cpu" and a.peak_flops >= 1e9 and a.hbm_bytes_per_s >= 1e9


# -- cost model ---------------------------------------------------------------

class _FakeCompiled:
    """cost_analysis() that omits keys, the way newer CPU/TPU backends do."""

    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        return self._ca

    def memory_analysis(self):
        return None


def test_cost_report_defaults_missing_keys_to_zero():
    from neuronx_distributed_tpu.utils.profiling import cost_report

    rep = cost_report(_FakeCompiled({"flops": 5.0}))
    assert rep["flops"] == 5.0
    assert rep["bytes_accessed"] == 0.0         # defaulted, not absent
    assert rep["transcendentals"] == 0.0
    assert rep["cost_keys_missing"] == 2
    full = cost_report(_FakeCompiled(
        {"flops": 1.0, "bytes accessed": 2.0, "transcendentals": 3.0}))
    assert "cost_keys_missing" not in full


def test_ledger_counts_cost_model_degradation():
    from neuronx_distributed_tpu.obs import MetricRegistry

    reg = MetricRegistry()
    led = CompileLedger(registry=reg)
    led.record_compile("train_step", "k", 1.0, kind="jit",
                       compiled=_FakeCompiled({"flops": 7.0}))
    row = led.rows[-1]
    assert row["flops"] == 7.0 and row["bytes_accessed"] == 0.0
    assert row["cost_keys_missing"] == 2
    assert reg.counter("perf/cost_model_missing_total").value == 2


# -- live engine --------------------------------------------------------------

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


def _serve(engine, cfg, n=3, max_new=4):
    rs = np.random.RandomState(0)
    for i in range(n):
        engine.submit(Request(
            request_id=i,
            prompt_ids=rs.randint(1, cfg.vocab_size, size=5).tolist(),
            max_new_tokens=max_new))
    return engine.run_until_complete(max_steps=400)


def test_perf_off_allocates_zero_perf_records(devices8):
    """The default engine (perf=None) must not create a single perf
    accounting record over a full paged run — the PERF_RECORDS module
    counter is the same discipline SPANS_CREATED enforces for tracing."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg, model = _tiny_model()
    engine = ServingEngine(model, page_size=4, num_pages=16)
    before = perf_mod.PERF_RECORDS
    outs = _serve(engine, cfg)
    engine.close()
    assert len(outs) == 3
    assert perf_mod.PERF_RECORDS == before


@pytest.mark.parametrize("config", ["plain", "chunked"])
def test_attribution_sums_to_traced_wall_time(config, devices8, tmp_path):
    """The acceptance property: with a tracer AND perf attached to the
    same engine, each phase family's attributed device time equals the
    summed wall-time of its tracer spans within 1 ms (they are stamped
    with the SAME clock reads), every family classifies compute- or
    memory-bound, and the ledger join supplies nonzero flops so the
    rollup MFU and tokens/s ceiling are real numbers."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    led = CompileLedger()
    cfg, model = _tiny_model(ledger=led)
    tr = Tracer()
    perf = PerfAttribution(path=str(tmp_path / "perf_attribution.jsonl"),
                           spec=SPEC)
    kw = dict(page_size=4, num_pages=24, compile_ledger=led,
              tracer=tr, perf=perf)
    if config == "chunked":
        kw["prefill_chunk_tokens"] = 4
    engine = ServingEngine(model, **kw)
    outs = _serve(engine, cfg)
    engine.close()
    assert len(outs) == 3

    recs = perf.attribution()
    fams = {r["family"]: r for r in recs if r["family"] != "_total"}
    assert fams, "no phase families accounted"

    span_path = str(tmp_path / "trace_events.jsonl")
    tr.export_jsonl(span_path)
    span_ms = {}
    for line in open(span_path):
        s = json.loads(line)
        if s["name"] in PERF_FAMILIES:
            span_ms[s["name"]] = (span_ms.get(s["name"], 0.0)
                                  + (s["t_end"] - s["t_start"]) * 1e3)

    for fam, rec in fams.items():
        assert rec["bound"] in ("compute", "memory")
        assert fam in span_ms, f"{fam} accounted but never traced"
        assert rec["device_ms"] == pytest.approx(span_ms[fam], abs=1.0), (
            f"{fam}: attributed {rec['device_ms']} ms != traced "
            f"{span_ms[fam]} ms")
    # the ledger join resolved program costs onto the phases actually run
    assert sum(r["flops"] for r in fams.values()) > 0.0
    roll = perf.rollup()
    assert roll["mfu"] > 0.0
    assert roll["toks_per_s_ceiling"] and roll["toks_per_s_ceiling"] > 0.0
    assert roll["tokens"] == sum(len(o.token_ids) for o in outs)
    # and the artifact round-trips
    assert perf.dump() is not None
    assert validate_jsonl("perf_attribution",
                          str(tmp_path / "perf_attribution.jsonl")) >= 2


# -- trainer ------------------------------------------------------------------

def test_fit_perf_artifact_and_report_section(devices8, tmp_path):
    """fit() under Observability(perf=<cost model>): the run drops a schema-valid
    perf_attribution.jsonl whose train_step family carries ledger-joined
    flops, and the obs report grows the perf section + MFU rollup."""
    import neuronx_distributed_tpu as nxd
    from test_resilience import _build, _fit_kwargs, _step_data

    from neuronx_distributed_tpu.obs import Observability
    from neuronx_distributed_tpu.obs.report import build_report
    from neuronx_distributed_tpu.trainer import fit

    config = nxd.training_config(tensor_parallel_size=2, learning_rate=5e-3)
    m, o = _build(config)
    obs = Observability(str(tmp_path / "obs"), ledgers=True, perf=SPEC)
    res = fit(config, m, o, _step_data(), steps=5, **_fit_kwargs(), obs=obs)
    assert res.steps_run == 5
    obs.close()

    path = str(tmp_path / "obs" / "perf_attribution.jsonl")
    assert validate_jsonl("perf_attribution", path) == 2  # train_step + _total
    recs = read_perf_attribution(path)
    train = recs[0]
    assert train["family"] == "train_step"
    assert train["calls"] == 5.0
    assert train["flops"] > 0.0          # joined from the ledger cost row

    report = build_report(run_dir=str(tmp_path / "obs"))
    assert report["perf"] is not None
    assert report["perf"]["rollup"]["mfu"] > 0.0
    assert set(report["perf"]["families"]) == {"train_step"}
    assert report["health"]["perf"]["bound"] in ("compute", "memory")


# -- surfaces -----------------------------------------------------------------

def _dump_run(run_dir, flops_per_call):
    os.makedirs(run_dir, exist_ok=True)
    perf = PerfAttribution(
        path=os.path.join(run_dir, "perf_attribution.jsonl"), spec=SPEC)
    perf.note_cost("train_step", flops_per_call, 1e6)
    perf.note_phase("train_step", 10.0, calls=1.0)
    perf.dump()


def test_merge_perf_records_sums_across_replicas(tmp_path):
    streams = []
    for i in range(2):
        perf = PerfAttribution(spec=SPEC)
        perf.note_cost("decode_step", 1e9, 1e8)
        perf.note_phase("decode_step", 10.0, calls=4.0)
        perf.note_tokens(50.0)
        streams.append(perf.attribution())
    merged = merge_perf_records(streams)
    fams = {r["family"]: r for r in merged}
    assert fams["decode_step"]["calls"] == 8.0
    assert fams["decode_step"]["flops"] == pytest.approx(8e9)
    assert fams["decode_step"]["device_ms"] == pytest.approx(20.0)
    assert fams["_total"]["tokens"] == 100.0
    summary = summarize_perf(merged)
    assert summary["rollup"]["device_ms"] == pytest.approx(20.0)
    # fleet MFU is computed over the merged totals, not averaged
    assert summary["rollup"]["mfu"] == pytest.approx(8e9 / 20e-3 / 1e12)


def test_default_health_pack_watches_mfu_and_roofline():
    from neuronx_distributed_tpu.obs.health import default_rules

    for scope in ("train", "serving", "fleet", "all"):
        names = [r.name for r in default_rules(scope)]
        assert "mfu_sag" in names and "roofline_drift" in names


def test_compare_gates_on_mfu_regression(tmp_path):
    """obs_report --compare: run B's rollup MFU sagging >5% below A's is
    a regression — surfaced in the markdown, the regressions list, and
    the CLI's nonzero rc."""
    from neuronx_distributed_tpu.obs.report import compare_resources

    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _dump_run(a, 5e9)   # MFU 0.5
    _dump_run(b, 1e9)   # MFU 0.1 — an 80% sag
    diff = compare_resources(a, b)
    assert diff["regressed"]
    assert any("mfu regressed" in r for r in diff["regressions"])
    assert "## Perf (roofline rollup)" in diff["markdown"]
    # a generous threshold waves the same pair through
    ok = compare_resources(a, b, mfu_threshold=0.9)
    assert not any("mfu" in r for r in ok["regressions"])

    spec = importlib.util.spec_from_file_location(
        "obs_report_cli", os.path.join(REPO, "tools", "obs_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["--compare", a, b]) == 1
    assert mod.main(["--compare", a, b,
                     "--mfu-regress-threshold", "0.9"]) == 0
