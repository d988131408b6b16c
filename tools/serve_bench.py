"""Serving benchmark on the local chip — one JSON line per measurement.

Three modes:

- default: static-batch decode latency through the serving engine's
  neuronperf-equivalent harness (`trace.engine.benchmark`: context-encode
  ms, per-token p50/p99 ms, tokens/s — reference
  `examples/inference/benchmark.py:53-77`).  `--tiny` smoke-tests the
  harness on CPU.
- `--continuous`: replays a Poisson arrival trace through the
  continuous-batching `serving.ServingEngine` and reports TTFT p50/p99,
  inter-token p50/p99, and goodput against the static lockstep `generate`
  baseline over the same prompts — the utilization gap iteration-level
  scheduling closes.  Writes a schema-checked `serving_stats.jsonl`.
- `--paged`: paged vs contiguous KV at a FIXED HBM budget.  The contiguous
  engine's `[B, T]` reservation defines the budget; the paged engine gets
  the same bytes as a page pool but twice the slots, and both replay the
  same shared-system-prompt Poisson workload.  One JSON line each
  (`"mode": "contiguous"` / `"mode": "paged"`): max concurrent requests,
  TTFT / inter-token p50/p99, goodput, and (paged) the prefix-page hit
  rate + prefills skipped — the kvcache/ subsystem's acceptance numbers.
- `--spec`: batched speculative decoding over the paged engine vs the
  PR-5 paged baseline, `draft == target` (the measured control: every
  proposal must be accepted, so tokens/step ≈ k+1 by construction and any
  shortfall is engine overhead, not draft quality).  One JSON line for the
  baseline plus one per k in `--spec-ks` (default 2,4,8): tokens/step
  (committed/rounds), acceptance rate, TTFT / inter-token p50/p99, goodput.
  rc 1 when a k >= 2 rung commits <= 1 token/step or its greedy outputs
  diverge from the baseline's.
- `--slo`: stall-free SLO serving.  A bimodal trace — a Poisson stream of
  short interactive prompts with full-context-width batch prompts landing
  inside it — served three ways: interactive-only baseline, unchunked
  FCFS control (the long prefills stall co-batched decodes), and the
  chunked + priority engine (`prefill_chunk_tokens` + batch-tier long
  prompts).  One JSON line per rung with per-tier inter-token/TTFT
  percentiles, chunk and preemption counts.  rc 1 unless the SLO engine
  holds interactive inter-token p99 within 2x the baseline WHILE the
  control spikes past that bound.

- `--compose`: every serving feature through ONE engine on a tp=2 mesh —
  speculative decoding (draft == target), int8 KV pages, co-batched LoRA
  adapters, chunked + priority prefill and the paged-attention kernel
  substrate — over a mixed interactive/batch workload.  The warm engine
  replays the identical workload first, so the measured window must
  compile NOTHING.  One JSON line; rc 1 on any refused admission, any
  unfinished request, any post-warmup compile (a compile storm), or
  nonzero `kvcache/gather_bytes_total` (a phase fell off the kernel
  substrate).

``--trace-out DIR`` (engine rungs: `--continuous`, `--slo`) attaches a
request-lifecycle tracer to every measured engine and drops one
schema-checked `<rung>.trace_events.jsonl` + one Perfetto-loadable
`<rung>.trace.json` per rung — the per-request waterfall evidence
`tools/obs_report.py --trace` renders.

Every measured engine carries a compile ledger with warmup declared done
at construction, so each rung reports ``compiles_during_measurement`` —
the proof that its percentiles exclude compile time (any nonzero count is
a compile storm inside the measured window).  ``--ledger-out DIR``
additionally drops the full artifacts per rung: a schema-checked
``<rung>.compile_ledger.jsonl`` and a ``<rung>.memory_breakdown.json``
(the per-subsystem HBM accounting `tools/obs_report.py --compare` diffs
between runs).

``--alerts-out DIR`` (engine rungs: `--continuous`, `--slo`) runs every
measured engine under the DEFAULT health-monitor rule pack
(``obs.health.default_rules``) and drops one schema-checked
``<rung>.alerts.jsonl`` per rung; the ``--slo`` rc additionally fails when
a page-severity alert fires during the compliant rung — a passing bench
must be QUIET under the production rule pack.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _percentiles(values, ps=(50, 99)):
    from neuronx_distributed_tpu.serving.driver import percentiles

    return percentiles(values, ps)


def _make_tracer(args):
    """A fresh request-lifecycle tracer when ``--trace-out`` is set (one
    per rung, so each dropped file is self-contained), else None — the
    zero-overhead default."""
    if not getattr(args, "trace_out", None):
        return None
    from neuronx_distributed_tpu.obs import Tracer

    return Tracer()


def _export_trace(tracer, args, label: str) -> dict:
    """Drop the rung's trace pair under ``--trace-out`` — a schema-checked
    ``<label>.trace_events.jsonl`` and a Perfetto-loadable
    ``<label>.trace.json`` — and return their paths for the JSON line."""
    if tracer is None:
        return {}
    from neuronx_distributed_tpu.obs.schemas import validate_jsonl

    os.makedirs(args.trace_out, exist_ok=True)
    ev = os.path.join(args.trace_out, f"{label}.trace_events.jsonl")
    ch = os.path.join(args.trace_out, f"{label}.trace.json")
    tracer.export_jsonl(ev)
    tracer.export_chrome(ch)
    validate_jsonl("trace_event", ev)  # the emitter honors its own schema
    return {"trace_events": os.path.abspath(ev),
            "trace_perfetto": os.path.abspath(ch)}


def _make_health(args, label: str):
    """A fresh health monitor under the DEFAULT rule pack when
    ``--alerts-out`` is set (one per rung, its ``<label>.alerts.jsonl``
    self-contained), else None — the zero-overhead default.  The bench's
    contract is that a PASSING rung is QUIET: the default pack's bounds
    are production-shaped, so a page-severity alert during a compliant
    rung is itself a failure."""
    if not getattr(args, "alerts_out", None):
        return None
    from neuronx_distributed_tpu.obs.health import (
        HealthMonitor,
        default_rules,
    )

    os.makedirs(args.alerts_out, exist_ok=True)
    path = os.path.join(args.alerts_out, f"{label}.alerts.jsonl")
    if os.path.exists(path):
        os.remove(path)  # the sink appends: a rerun must not accumulate
    return HealthMonitor(default_rules("serving"), path=path, eval_every=4)


def _health_fields(monitor, args, label: str) -> dict:
    """Close the rung's monitor, schema-validate its dropped
    ``<label>.alerts.jsonl``, and report the firing evidence (total edges
    + page-severity firing edges) for the rung's JSON line."""
    if monitor is None:
        return {}
    from neuronx_distributed_tpu.obs.schemas import validate_jsonl

    monitor.close()
    path = os.path.join(args.alerts_out, f"{label}.alerts.jsonl")
    n = validate_jsonl("alert", path)  # the emitter honors its schema
    return {"alerts": os.path.abspath(path),
            "alert_edges": n,
            "page_alerts": monitor.page_edges()}


def _make_ledgers(args):
    """One compile ledger per rung, attached to the WARM engine too (the
    warm pass's cold compiles are then the rung's warmup rows, and a later
    rung's warm engine can never book into a previous rung's warm-declared
    ledger), plus a memory ledger for the measured engine when
    ``--ledger-out`` asks for the full artifacts."""
    from neuronx_distributed_tpu.obs import CompileLedger, MemoryLedger

    mem = MemoryLedger() if getattr(args, "ledger_out", None) else None
    return CompileLedger(memory_ledger=mem), mem


def _ledger_fields(led, mem, args, label: str) -> dict:
    """The rung's ledger evidence: ``compiles_during_measurement`` (the
    measured engine declared warmup done at construction, so every compile
    past that point happened inside the measured window — percentiles
    provably exclude compiles only when this is 0) plus, under
    ``--ledger-out``, a schema-checked ``<label>.compile_ledger.jsonl`` +
    ``<label>.memory_breakdown.json`` pair."""
    out = {"compiles_during_measurement":
           led.compile_count(after_warmup_only=True)}
    if not getattr(args, "ledger_out", None):
        return out
    from neuronx_distributed_tpu.obs.memory_ledger import (
        read_memory_breakdown,
    )
    from neuronx_distributed_tpu.obs.schemas import (
        validate_jsonl,
        validate_record,
    )

    os.makedirs(args.ledger_out, exist_ok=True)
    cl = os.path.join(args.ledger_out, f"{label}.compile_ledger.jsonl")
    led.dump(cl)
    validate_jsonl("compile_ledger", cl)  # the emitter honors its schema
    out["compile_ledger"] = os.path.abspath(cl)
    if mem is not None:
        mb = os.path.join(args.ledger_out, f"{label}.memory_breakdown.json")
        mem.dump(mb, reason=f"serve_bench:{label}")
        validate_record("memory_breakdown", read_memory_breakdown(mb))
        out["memory_breakdown"] = os.path.abspath(mb)
    return out


def _make_perf(args, label: str):
    """A fresh roofline perf-attribution layer when ``--profile-out`` is
    set (one ``<label>.perf_attribution.jsonl`` per rung; the measured
    engine attaches its registry + compile ledger and stamps per-phase
    device time), else None — the zero-allocation default."""
    if not getattr(args, "profile_out", None):
        return None
    from neuronx_distributed_tpu.obs.perf import (
        PerfAttribution,
        calibrate_cpu_spec,
    )

    os.makedirs(args.profile_out, exist_ok=True)
    # --tiny is the explicit CPU harness smoke: its attribution records run
    # against a cost model labelled "cpu"; every other run reads the
    # device's published peaks (and fails on a device without any)
    return PerfAttribution(
        path=os.path.join(args.profile_out,
                          f"{label}.perf_attribution.jsonl"),
        spec=calibrate_cpu_spec() if args.tiny else None)


def _perf_fields(perf, args, label: str) -> dict:
    """The rung's roofline evidence: dump + schema-check the
    ``<label>.perf_attribution.jsonl`` artifact and surface the rollup —
    ``mfu_model`` / ``pct_roofline`` per rung, plus the tokens/s ceiling
    when the rung committed tokens."""
    if perf is None:
        return {}
    from neuronx_distributed_tpu.obs.schemas import validate_jsonl

    path = perf.dump()
    out = {}
    roll = perf.rollup()
    if roll is not None:
        out["mfu_model"] = round(roll["mfu"], 6)
        out["pct_roofline"] = round(roll["pct_roofline"], 6)
        out["perf_bound"] = roll["bound"]
        if roll.get("toks_per_s_ceiling"):
            out["toks_per_s_ceiling"] = round(roll["toks_per_s_ceiling"], 2)
    if path:
        validate_jsonl("perf_attribution", path)  # emitter honors schema
        out["perf_attribution"] = os.path.abspath(path)
    return out


def _profile_ctx(args, label: str):
    """An XLA device-profile capture (``jax.profiler`` via
    ``obs.tracing.device_trace``) over the measured window when
    ``--profile-out`` is set — one ``<DIR>/<label>`` trace dir per rung —
    else a no-op context."""
    from contextlib import nullcontext

    if not getattr(args, "profile_out", None):
        return nullcontext()
    from neuronx_distributed_tpu.obs.tracing import device_trace

    return device_trace(os.path.join(args.profile_out, label))


def run_continuous(args, model, vocab_size: int) -> dict:
    """Replay a Poisson arrival trace through ServingEngine; compare against
    lockstep static batches of the same prompts."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.obs.schemas import validate_jsonl
    from neuronx_distributed_tpu.serving import (
        Request, ServingEngine, poisson_arrivals, replay_trace)

    B, C = model.config.batch_size, model.config.context_len
    rs = np.random.RandomState(args.seed)
    n = args.num_requests
    if n < 1:
        raise SystemExit(f"--continuous needs --num-requests >= 1, got {n}")
    prompts = [
        rs.randint(1, vocab_size, size=rs.randint(max(2, C // 4), C + 1)).tolist()
        for _ in range(n)
    ]
    arrivals = poisson_arrivals(n, args.arrival_rate, rs)

    # warm every compiled phase (prefill_one/insert_slot/decode_slots + the
    # static baseline's fused loop) so compile time never pollutes TTFT;
    # one registry across warm + measured engines so model-level compiled-
    # cache metrics land in the snapshot we report
    registry = MetricRegistry()
    led, mem = _make_ledgers(args)
    perf = _make_perf(args, "continuous")
    if perf is not None:
        # the warm pass owns the first (compiling) calls: with model.perf
        # set, the compiled-fn cache books flops/bytes cost extras into
        # the shared ledger rows the perf layer joins against.  The warm
        # engine itself carries NO perf= — warmup device time must not
        # pollute the measured attribution.
        model.perf = perf
    warm = ServingEngine(model, registry=registry, stats_path=None,
                         compile_ledger=led)
    warm.submit(Request(request_id=-1, prompt_ids=prompts[0],
                        max_new_tokens=min(2, args.max_new_tokens)))
    warm.run_until_complete(max_steps=1000)
    warm.close()
    del warm  # drop its device caches before the measured engine allocates
    pad = np.zeros((B, C), np.int32)
    jax.block_until_ready(model.generate(
        jnp.asarray(pad), args.max_new_tokens,
        prompt_lens=jnp.full((B,), C, jnp.int32)))

    stats_path = args.stats_out or os.path.join(
        tempfile.mkdtemp(prefix="serve_bench_"), "serving_stats.jsonl")
    if os.path.exists(stats_path):
        os.remove(stats_path)
    tracer = _make_tracer(args)
    health = _make_health(args, "continuous")
    engine = ServingEngine(model, registry=registry, stats_path=stats_path,
                           tracer=tracer, compile_ledger=led,
                           memory_ledger=mem, health=health, perf=perf)
    engine.declare_warmup_done()  # the warm engine compiled everything
    with _profile_ctx(args, "continuous"):
        t0 = time.monotonic()
        outputs = replay_trace(
            engine, arrivals,
            [Request(request_id=i, prompt_ids=prompts[i],
                     max_new_tokens=args.max_new_tokens) for i in range(n)])
        t_cont = time.monotonic() - t0
    engine.close()
    trace_paths = _export_trace(tracer, args, "continuous")
    ledger_fields = _ledger_fields(led, mem, args, "continuous")
    health_fields = _health_fields(health, args, "continuous")
    perf_fields = _perf_fields(perf, args, "continuous")

    n_stats = validate_jsonl("serving_stats", stats_path)
    assert n_stats == n, f"expected {n} serving_stats records, got {n_stats}"

    total_tokens = sum(len(o.token_ids) for o in outputs.values())
    ttfts = [o.ttft_ms for o in outputs.values() if o.ttft_ms is not None]
    inter = [ms for o in outputs.values() for ms in o.intertoken_ms]

    # static lockstep baseline: the same prompts in full batches of B; every
    # batch decodes max_new_tokens in lockstep (what generate offers today)
    t0 = time.monotonic()
    static_tokens = 0
    for i in range(0, n, B):
        chunk = prompts[i:i + B]
        ids = np.zeros((B, C), np.int32)
        lens = np.zeros((B,), np.int32)
        for j, p in enumerate(chunk):
            ids[j, C - len(p):] = p
            lens[j] = len(p)
        jax.block_until_ready(model.generate(
            jnp.asarray(ids), args.max_new_tokens, prompt_lens=jnp.asarray(lens)))
        static_tokens += len(chunk) * args.max_new_tokens
    t_static = max(time.monotonic() - t0, 1e-9)

    return {
        "num_requests": n,
        "arrival_rate_hz": args.arrival_rate,
        "ttft_ms": _percentiles(ttfts),
        "intertoken_ms": _percentiles(inter),
        "goodput_tok_s": total_tokens / max(t_cont, 1e-9),
        "static_tok_s": static_tokens / t_static,
        "continuous_s": round(t_cont, 4),
        "static_s": round(t_static, 4),
        "finished": sum(1 for o in outputs.values() if o.state == "finished"),
        "stats_records": n_stats,
        "stats_path": os.path.abspath(stats_path),
        **trace_paths,
        **ledger_fields,
        **health_fields,
        **perf_fields,
    }


def _drive_workload(engine, arrivals, requests):
    """Replay the workload tracking peak slot concurrency; returns
    ``(outputs, wall_s, peak_concurrent)``."""
    import time as _time

    from neuronx_distributed_tpu.serving import replay_trace

    peak = [0]
    orig_step = engine.step

    def step():
        out = orig_step()
        peak[0] = max(peak[0], engine.scheduler.active_count)
        return out

    engine.step = step
    t0 = _time.monotonic()
    outputs = replay_trace(engine, arrivals, requests)
    wall = _time.monotonic() - t0
    return outputs, wall, peak[0]


def run_paged(args, module, params, cfg, icfg) -> int:
    """Paged vs contiguous at a fixed HBM budget over one shared-system-
    prompt workload; prints one JSON line per mode."""
    import dataclasses

    import numpy as np

    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    B, C, T = args.batch_size, args.context_len, args.max_total_len
    page = args.page_size
    if C % page or T % page:
        raise SystemExit(f"--page-size {page} must divide --context-len {C} "
                         f"and --max-total-len {T}")
    # the fixed budget: exactly the contiguous engine's [B, T] reservation,
    # expressed in pages (the paged pool spends one of them on the shared
    # NULL page — honest accounting, the paged scheme pays its overhead)
    budget_pages = B * (T // page)
    paged_slots = args.paged_slots or 2 * B
    model_c = ParallelInferenceModel(module, params, icfg)
    model_p = ParallelInferenceModel(
        module, params, dataclasses.replace(icfg, batch_size=paged_slots))

    # shared-system-prompt workload: fixed-length prompts (equal padding is
    # what makes page-aligned prefixes shareable) opening with a common
    # system preamble.  Half-width prompts are the case paged serving is
    # FOR: the contiguous engine reserves [T] per slot regardless, the
    # paged engine holds only the real prompt + decode pages (padding pages
    # ride the NULL page, the shared preamble's pages exist once).
    rs = np.random.RandomState(args.seed)
    n = args.num_requests
    L = max(C // 2, 1)
    sys_len = max(L // 2, 1)
    sys_ids = rs.randint(1, cfg.vocab_size, size=sys_len).tolist()
    prompts = [
        sys_ids + rs.randint(1, cfg.vocab_size, size=L - sys_len).tolist()
        for _ in range(n)
    ]
    # burst arrival (everything at t=0): the measurement is how many
    # requests the KV budget can hold IN FLIGHT at once, so the backlog —
    # not the arrival tempo — must be the limiter
    arrivals = np.zeros(n)

    def requests():
        return [Request(request_id=i, prompt_ids=prompts[i],
                        max_new_tokens=args.max_new_tokens)
                for i in range(n)]

    def measure(model, paged):
        label = "paged" if paged else "contiguous"
        kw = dict(page_size=page, num_pages=budget_pages) if paged else {}
        # warm every compiled phase on a throwaway engine (same model ⇒
        # shared compiled-fn caches) so compile time never pollutes TTFT
        led, mem = _make_ledgers(args)
        perf = _make_perf(args, label)
        if perf is not None:
            # warm-pass first calls book flops/bytes cost extras into the
            # shared ledger; the warm engine carries no perf= so warmup
            # device time stays out of the measured attribution
            model.perf = perf
        warm = ServingEngine(model, registry=MetricRegistry(),
                             compile_ledger=led, **kw)
        warm.submit(Request(request_id=-1,
                            prompt_ids=rs.randint(1, cfg.vocab_size,
                                                  size=L).tolist(),
                            max_new_tokens=min(2, args.max_new_tokens)))
        warm.run_until_complete(max_steps=1000)
        warm.close()
        del warm  # its device KV must not double the measured HBM footprint
        engine = ServingEngine(model, registry=MetricRegistry(),
                               compile_ledger=led, memory_ledger=mem,
                               perf=perf, **kw)
        engine.declare_warmup_done()
        with _profile_ctx(args, label):
            outputs, wall, peak = _drive_workload(engine, arrivals,
                                                  requests())
        snap = engine.registry.snapshot()
        total_tokens = sum(len(o.token_ids) for o in outputs.values())
        ttfts = [o.ttft_ms for o in outputs.values() if o.ttft_ms is not None]
        inter = [ms for o in outputs.values() for ms in o.intertoken_ms]
        rec = {
            "metric": "serving_paged",
            "mode": "paged" if paged else "contiguous",
            "hbm_budget_pages": budget_pages,
            "page_size": page,
            "slots": model.config.batch_size,
            "num_requests": n,
            "max_concurrent": peak,
            "finished": sum(1 for o in outputs.values()
                            if o.state == "finished"),
            "ttft_ms": _percentiles(ttfts),
            "intertoken_ms": _percentiles(inter),
            "goodput_tok_s": total_tokens / max(wall, 1e-9),
            "wall_s": round(wall, 4),
        }
        if paged:
            hits = snap.get("kvcache/prefix_hits_total", 0.0)
            misses = snap.get("kvcache/prefix_misses_total", 0.0)
            rec["prefix_hit_rate"] = (
                round(hits / (hits + misses), 4) if hits + misses else None)
            rec["prefills_skipped"] = snap.get(
                "kvcache/prefill_skipped_total", 0.0)
            rec["evictions"] = snap.get("kvcache/evictions_total", 0.0)
        rec.update(_ledger_fields(led, mem, args, label))
        rec.update(_perf_fields(perf, args, label))
        return rec

    base = {"config": {"batch": B, "context": C, "max_total": T,
                       "max_new": args.max_new_tokens}}
    rec_c = measure(model_c, paged=False)
    print(json.dumps({**rec_c, **base}))
    rec_p = measure(model_p, paged=True)
    print(json.dumps({**rec_p, **base}))
    if rec_p["max_concurrent"] <= rec_c["max_concurrent"]:
        print(f"serve_bench: paged sustained {rec_p['max_concurrent']} "
              f"concurrent <= contiguous {rec_c['max_concurrent']} at the "
              "same HBM budget", file=sys.stderr)
        return 1
    return 0


def run_lora(args, module, params, cfg, icfg) -> int:
    """Batched multi-adapter serving (tenancy/): >= --lora-adapters LoRA
    adapters co-batched through one compiled envelope vs the no-adapter
    paged baseline; prints one JSON line per rung.  rc 1 when fewer than
    min(adapters, slots) distinct adapters ever decode in the same batch,
    when any request fails, or when the multi-adapter inter-token p99
    blows past the (generous, CI-noise-tolerant) near-baseline bound."""
    import numpy as np

    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.tenancy import make_adapter_store
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    B, C, T = args.batch_size, args.context_len, args.max_total_len
    page = args.page_size
    if C % page or T % page:
        raise SystemExit(f"--page-size {page} must divide --context-len {C} "
                         f"and --max-total-len {T}")
    A = args.lora_adapters
    model = ParallelInferenceModel(module, params, icfg)
    num_pages = B * (T // page) + 1

    rs = np.random.RandomState(args.seed)
    n = max(args.num_requests, 2 * A)
    prompts = [
        rs.randint(1, cfg.vocab_size,
                   size=rs.randint(max(2, C // 4), C + 1)).tolist()
        for _ in range(n)
    ]
    arrivals = np.zeros(n)  # burst: the batch must actually fill

    rank = 4
    adapter_layers = []
    H, NQ, NKV, D = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim_)

    def random_adapter(seed):
        r2 = np.random.RandomState(seed)
        return [{
            "a_q": (r2.randn(H, rank) * 0.05).astype(np.float32),
            "b_q": (r2.randn(rank, NQ * D) * 0.05).astype(np.float32),
            "a_v": (r2.randn(H, rank) * 0.05).astype(np.float32),
            "b_v": (r2.randn(rank, NKV * D) * 0.05).astype(np.float32),
        } for _ in range(cfg.num_layers)]

    def make_store():
        store = make_adapter_store(
            model, rank=rank,
            num_pages=A * _store_pages(model, rank) + 1,
            page_elems=2048)
        for aid in range(1, A + 1):
            store.register(aid, random_adapter(args.seed + aid), alpha=8.0)
        return store

    def _store_pages(model, rank):
        from neuronx_distributed_tpu.tenancy import AdapterLayout

        return AdapterLayout.for_model(model, rank, 2048).pages_per_adapter

    def requests(with_adapters):
        return [Request(request_id=i, prompt_ids=prompts[i],
                        max_new_tokens=args.max_new_tokens,
                        adapter_id=(i % A) + 1 if with_adapters else 0)
                for i in range(n)]

    def measure(with_adapters):
        kw = dict(page_size=page, num_pages=num_pages)
        if with_adapters:
            kw["adapter_store"] = make_store()
        led, mem = _make_ledgers(args)
        warm = ServingEngine(model, registry=MetricRegistry(),
                             compile_ledger=led, **kw)
        warm.submit(Request(request_id=-1, prompt_ids=prompts[0],
                            max_new_tokens=min(2, args.max_new_tokens),
                            adapter_id=1 if with_adapters else 0))
        warm.run_until_complete(max_steps=1000)
        warm.close()
        del warm
        if with_adapters:
            kw["adapter_store"] = make_store()  # fresh pins for the run
        engine = ServingEngine(model, registry=MetricRegistry(),
                               compile_ledger=led, memory_ledger=mem, **kw)
        engine.declare_warmup_done()
        peak_adapters = [0]
        orig_step = engine.step

        def step():
            out = orig_step()
            if with_adapters:
                live = {engine._slot_adapter[s]
                        for s, _ in engine.scheduler.active()
                        if engine._slot_adapter[s]}
                peak_adapters[0] = max(peak_adapters[0], len(live))
            return out

        engine.step = step
        outputs, wall, peak = _drive_workload(engine, arrivals,
                                              requests(with_adapters))
        engine.close()
        snap = engine.registry.snapshot()
        total_tokens = sum(len(o.token_ids) for o in outputs.values())
        inter = [ms for o in outputs.values() for ms in o.intertoken_ms]
        rec = {
            "metric": "serving_lora",
            "mode": "lora" if with_adapters else "baseline",
            "adapters": A if with_adapters else 0,
            "slots": B,
            "num_requests": n,
            "finished": sum(1 for o in outputs.values()
                            if o.state == "finished"),
            "max_concurrent": peak,
            "max_adapters_cobatched": peak_adapters[0],
            "intertoken_ms": _percentiles(inter),
            "goodput_tok_s": total_tokens / max(wall, 1e-9),
            "wall_s": round(wall, 4),
        }
        if with_adapters:
            rec["adapter_loads"] = snap.get("tenancy/adapter_loads_total", 0.0)
            rec["adapter_hits"] = snap.get("tenancy/adapter_hits_total", 0.0)
            rec["adapter_evictions"] = snap.get(
                "tenancy/adapter_evictions_total", 0.0)
        rec.update(_ledger_fields(led, mem, args,
                                  "lora" if with_adapters else "lora_baseline"))
        return rec

    base = {"config": {"batch": B, "context": C, "max_total": T,
                       "max_new": args.max_new_tokens, "page_size": page,
                       "rank": rank}}
    rec_b = measure(False)
    print(json.dumps({**rec_b, **base}))
    rec_l = measure(True)
    print(json.dumps({**rec_l, **base}))
    rc = 0
    want_cobatch = min(A, B)
    if rec_l["max_adapters_cobatched"] < want_cobatch:
        print(f"serve_bench: only {rec_l['max_adapters_cobatched']} distinct "
              f"adapters ever co-batched (< {want_cobatch})", file=sys.stderr)
        rc = 1
    if rec_l["finished"] != n:
        print(f"serve_bench: {n - rec_l['finished']} multi-adapter requests "
              "did not finish", file=sys.stderr)
        rc = 1
    p99_b = rec_b["intertoken_ms"].get("p99") or 0.0
    p99_l = rec_l["intertoken_ms"].get("p99") or 0.0
    # near-baseline bound: the low-rank gather+einsum must not dominate a
    # decode step.  3x absorbs CI timing noise at tiny-model scale; on
    # silicon the observed ratio is what to read, not the gate.
    if p99_b > 0 and p99_l > 3.0 * p99_b:
        print(f"serve_bench: multi-adapter inter-token p99 {p99_l:.2f}ms "
              f"> 3x baseline {p99_b:.2f}ms", file=sys.stderr)
        rc = 1
    return rc


def run_kv_quant(args, module, params, cfg, icfg) -> int:
    """Int8 vs fp KV pages at a FIXED HBM budget: the fp pool's bytes buy
    ~2x the int8 pages, so the int8 engine must sustain >= 2x the max
    concurrency on a page-bound burst workload; prints one JSON line per
    mode, rc 1 otherwise."""
    import dataclasses

    import numpy as np

    from neuronx_distributed_tpu.kvcache.pool import PagePool
    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    B, C, T = args.batch_size, args.context_len, args.max_total_len
    page = args.page_size
    if C % page or T % page:
        raise SystemExit(f"--page-size {page} must divide --context-len {C} "
                         f"and --max-total-len {T}")
    # the fixed budget: a fp pool exactly covering the contiguous [B, T]
    # reservation; the int8 pool gets the SAME bytes (pure arithmetic —
    # constructing a PagePool here would eagerly allocate throwaway HBM)
    from neuronx_distributed_tpu.kvcache.quant import page_layer_bytes

    fp_pages = B * (T // page)
    mcfg = module.config
    budget_bytes = fp_pages * mcfg.num_layers * page_layer_bytes(
        page, mcfg.num_kv_heads, mcfg.head_dim_, None, icfg.kv_cache_dtype)
    int8_pages = PagePool.pages_for_budget(
        budget_bytes, mcfg.num_layers, page, mcfg.num_kv_heads,
        mcfg.head_dim_, icfg.kv_cache_dtype, quant="int8")
    slots = args.paged_slots or 4 * B
    model = ParallelInferenceModel(
        module, params, dataclasses.replace(icfg, batch_size=slots))

    # page-bound workload: unique full-width prompts (no padding pages, no
    # shared prefix) arriving in one burst — concurrency is then exactly
    # what the pool can hold in flight
    rs = np.random.RandomState(args.seed)
    n = args.num_requests
    prompts = [rs.randint(1, cfg.vocab_size, size=C).tolist()
               for _ in range(n)]
    arrivals = np.zeros(n)

    def requests():
        return [Request(request_id=i, prompt_ids=prompts[i],
                        max_new_tokens=args.max_new_tokens)
                for i in range(n)]

    def measure(quant, num_pages):
        kw = dict(page_size=page, num_pages=num_pages + 1,  # + NULL page
                  kv_quant=quant)
        led, mem = _make_ledgers(args)
        warm = ServingEngine(model, registry=MetricRegistry(),
                             compile_ledger=led, **kw)
        warm.submit(Request(request_id=-1, prompt_ids=prompts[0],
                            max_new_tokens=min(2, args.max_new_tokens)))
        warm.run_until_complete(max_steps=1000)
        warm.close()
        del warm
        engine = ServingEngine(model, registry=MetricRegistry(),
                               compile_ledger=led, memory_ledger=mem, **kw)
        engine.declare_warmup_done()
        outputs, wall, peak = _drive_workload(engine, arrivals, requests())
        engine.close()
        snap = engine.registry.snapshot()
        total_tokens = sum(len(o.token_ids) for o in outputs.values())
        ttfts = [o.ttft_ms for o in outputs.values() if o.ttft_ms is not None]
        inter = [ms for o in outputs.values() for ms in o.intertoken_ms]
        return {
            "metric": "serving_kv_quant",
            "mode": quant or "fp",
            **_ledger_fields(led, mem, args, quant or "fp"),
            "hbm_budget_bytes": budget_bytes,
            "pool_pages": num_pages,
            "page_size": page,
            "slots": slots,
            "num_requests": n,
            "max_concurrent": peak,
            "finished": sum(1 for o in outputs.values()
                            if o.state == "finished"),
            "ttft_ms": _percentiles(ttfts),
            "intertoken_ms": _percentiles(inter),
            "goodput_tok_s": total_tokens / max(wall, 1e-9),
            "quant_page_writes": snap.get("kvcache/quant_pages_total", 0.0),
            "wall_s": round(wall, 4),
        }

    base = {"config": {"batch": B, "context": C, "max_total": T,
                       "max_new": args.max_new_tokens}}
    rec_fp = measure(None, fp_pages)
    print(json.dumps({**rec_fp, **base}))
    rec_q = measure("int8", int8_pages)
    print(json.dumps({**rec_q, **base}))
    if rec_q["max_concurrent"] < 2 * rec_fp["max_concurrent"]:
        print(f"serve_bench: int8 pages sustained {rec_q['max_concurrent']} "
              f"concurrent < 2x fp {rec_fp['max_concurrent']} at the same "
              "HBM budget", file=sys.stderr)
        return 1
    return 0


def run_slo(args, module, params, cfg, icfg) -> int:
    """Stall-free SLO rung: a bimodal short/long-prompt Poisson trace
    (interactive short prompts decoding while full-width batch prompts
    arrive) served three ways — the chunked + priority engine WITHOUT the
    long prompts (baseline: latency absent adversarial load), unchunked
    FCFS on the mixed trace (control: every whole prefill is a full-width
    forward that stalls co-batched decodes), and the chunked + priority
    engine on the mixed trace (slo).  One JSON line per rung.  rc 1 unless
    the SLO engine holds the interactive inter-token p99 within 2x the
    no-long-prompt baseline WHILE the unchunked control spikes past that
    bound (the stall the subsystem exists to remove)."""
    import numpy as np

    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.serving import (
        Request, ServingEngine, poisson_arrivals)
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    B, C, T = args.batch_size, args.context_len, args.max_total_len
    page = args.page_size
    if C % page or T % page:
        raise SystemExit(f"--page-size {page} must divide --context-len {C} "
                         f"and --max-total-len {T}")
    chunk = args.slo_chunk or max(page, (C // 8) // page * page)
    if chunk % page:
        raise SystemExit(f"--slo-chunk {chunk} must be a multiple of "
                         f"--page-size {page}")
    num_pages = B * (T // page) + 1
    model = ParallelInferenceModel(module, params, icfg)

    LONG_BASE = 1 << 16  # long-prompt ids live in their own range
    rs = np.random.RandomState(args.seed)
    n_i = args.num_requests
    n_l = args.slo_long
    # interactive prompts are genuinely SHORT (their own prefills must not
    # stall each other, or the baseline inherits the very spike the rung
    # measures); the batch tier is full compiled width
    short_prompts = [
        rs.randint(1, cfg.vocab_size,
                   size=rs.randint(max(2, C // 32), max(3, C // 16))).tolist()
        for _ in range(n_i)
    ]
    long_prompts = [rs.randint(1, cfg.vocab_size, size=C).tolist()
                    for _ in range(n_l)]
    arr_i = poisson_arrivals(n_i, args.arrival_rate, rs)
    span = float(arr_i[-1]) if n_i > 1 else 1.0
    # long prompts land inside the interactive window, so their prefills
    # contend with live decodes — the stall under test
    arr_l = np.linspace(0.0, max(span * 0.7, 1e-3), n_l)

    def trace(with_long, batch_tier):
        items = [(float(arr_i[i]),
                  Request(request_id=i, prompt_ids=short_prompts[i],
                          max_new_tokens=args.max_new_tokens,
                          priority="interactive"))
                 for i in range(n_i)]
        if with_long:
            items += [(float(arr_l[j]),
                       Request(request_id=LONG_BASE + j,
                               prompt_ids=long_prompts[j],
                               max_new_tokens=args.max_new_tokens,
                               priority="batch" if batch_tier
                               else "interactive"))
                      for j in range(n_l)]
        items.sort(key=lambda it: it[0])
        return [t for t, _ in items], [r for _, r in items]

    def measure(mode):
        """``baseline`` = chunked + priority engine, interactive-only trace
        (what latency looks like without adversarial load); ``control`` =
        unchunked FCFS on the mixed trace (whole full-width prefills stall
        co-batched decodes); ``slo`` = chunked + priority on the mixed
        trace."""
        with_long = mode != "baseline"
        kw = dict(page_size=page, num_pages=num_pages)
        if mode != "control":
            kw["prefill_chunk_tokens"] = chunk
        # warm EVERY prefill shape the trace will hit: the long prompt,
        # the whole path (full prefix hits ride it), and — in chunked
        # modes — one prompt per possible chunk width (1..budget pages),
        # so compile time never pollutes the measured percentiles
        led, mem = _make_ledgers(args)
        warm = ServingEngine(model, registry=MetricRegistry(),
                             compile_ledger=led, **kw)
        warm_prompts = [long_prompts[0], short_prompts[0], [1, 2]]
        if mode != "control":
            warm_prompts += [
                list(range(1, k * page + 1))
                for k in range(1, chunk // page + 1)]
        for i, p in enumerate(warm_prompts):
            warm.submit(Request(request_id=-1 - i, prompt_ids=p,
                                max_new_tokens=min(2, args.max_new_tokens)))
        warm.run_until_complete(max_steps=2000)
        warm.close()
        del warm
        tracer = _make_tracer(args)
        health = _make_health(args, f"slo_{mode}")
        engine = ServingEngine(model, registry=MetricRegistry(),
                               tracer=tracer, compile_ledger=led,
                               memory_ledger=mem, health=health, **kw)
        engine.declare_warmup_done()
        arrivals, requests = trace(with_long, batch_tier=mode == "slo")
        outputs, wall, peak = _drive_workload(engine, arrivals, requests)
        engine.close()
        trace_paths = _export_trace(tracer, args, f"slo_{mode}")
        ledger_fields = _ledger_fields(led, mem, args, f"slo_{mode}")
        health_fields = _health_fields(health, args, f"slo_{mode}")
        snap = engine.registry.snapshot()
        inter_i = [ms for o in outputs.values() if o.request_id < LONG_BASE
                   for ms in o.intertoken_ms]
        inter_l = [ms for o in outputs.values() if o.request_id >= LONG_BASE
                   for ms in o.intertoken_ms]
        total_tokens = sum(len(o.token_ids) for o in outputs.values())
        ttfts = [o.ttft_ms for o in outputs.values()
                 if o.ttft_ms is not None and o.request_id < LONG_BASE]
        return {
            "metric": "serving_slo",
            "mode": mode,
            # baseline AND slo run chunked; only the control is whole-prefill
            "chunk_tokens": chunk if mode != "control" else None,
            "interactive": n_i,
            "long_prompts": n_l if with_long else 0,
            "finished": sum(1 for o in outputs.values()
                            if o.state == "finished"),
            "interactive_ttft_ms": _percentiles(ttfts),
            "interactive_intertoken_ms": _percentiles(inter_i),
            "batch_intertoken_ms": _percentiles(inter_l),
            "prefill_chunks": snap.get("serving/prefill_chunks_total", 0.0),
            "preemptions": snap.get("serving/preemptions_total", 0.0),
            "goodput_tok_s": total_tokens / max(wall, 1e-9),
            "wall_s": round(wall, 4),
            "max_concurrent": peak,
            **trace_paths,
            **ledger_fields,
            **health_fields,
        }

    base_cfg = {"config": {"batch": B, "context": C, "max_total": T,
                           "max_new": args.max_new_tokens,
                           "page_size": page}}
    rec_base = measure("baseline")
    print(json.dumps({**rec_base, **base_cfg}))
    rec_ctrl = measure("control")
    print(json.dumps({**rec_ctrl, **base_cfg}))
    rec_slo = measure("slo")
    print(json.dumps({**rec_slo, **base_cfg}))

    rc = 0
    p99_base = rec_base["interactive_intertoken_ms"].get("p99") or 0.0
    p99_ctrl = rec_ctrl["interactive_intertoken_ms"].get("p99") or 0.0
    p99_slo = rec_slo["interactive_intertoken_ms"].get("p99") or 0.0
    bound = 2.0 * p99_base
    if p99_base <= 0:
        print("serve_bench: no baseline interactive inter-token samples",
              file=sys.stderr)
        rc = 1
    else:
        if p99_slo > bound:
            print(f"serve_bench: SLO engine interactive inter-token p99 "
                  f"{p99_slo:.2f}ms > 2x no-long-prompt baseline "
                  f"{p99_base:.2f}ms", file=sys.stderr)
            rc = 1
        if p99_ctrl <= bound:
            print(f"serve_bench: unchunked control p99 {p99_ctrl:.2f}ms did "
                  f"not spike past 2x baseline {p99_base:.2f}ms — the "
                  "workload exhibits no stall to remove", file=sys.stderr)
            rc = 1
    n_total = n_i + n_l
    for rec in (rec_ctrl, rec_slo):
        if rec["finished"] != n_total:
            print(f"serve_bench: {rec['mode']} finished {rec['finished']} "
                  f"of {n_total} requests", file=sys.stderr)
            rc = 1
    if rec_slo["prefill_chunks"] <= 0:
        print("serve_bench: SLO engine dispatched no prefill chunks",
              file=sys.stderr)
        rc = 1
    if args.alerts_out and rec_slo.get("page_alerts", 0) > 0:
        # the compliant rung's contract: alerts must be QUIET when the
        # bench passes — a page-severity alert during the SLO-holding run
        # means the default rule pack and the gate disagree about health
        print(f"serve_bench: {rec_slo['page_alerts']} page-severity "
              "alert(s) fired during the compliant SLO rung (see "
              f"{rec_slo['alerts']})", file=sys.stderr)
        rc = 1
    return rc


def run_spec(args, module, params, cfg, icfg) -> int:
    """Speculative draft-k-verify vs the plain paged engine over one Poisson
    workload, draft == target; prints one JSON line per rung."""
    import numpy as np

    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.serving import (
        Request, ServingEngine, poisson_arrivals)
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    B, C, T = args.batch_size, args.context_len, args.max_total_len
    page = args.page_size
    if C % page or T % page:
        raise SystemExit(f"--page-size {page} must divide --context-len {C} "
                         f"and --max-total-len {T}")
    ks = sorted({int(x) for x in args.spec_ks.split(",")})
    if any(k < 1 for k in ks):
        raise SystemExit(f"--spec-ks must be >= 1, got {args.spec_ks}")
    if C + args.max_new_tokens + max(ks) > T:
        raise SystemExit(
            f"--context-len {C} + --max-new-tokens {args.max_new_tokens} + "
            f"k {max(ks)} exceeds --max-total-len {T}: the verification "
            "step writes up to k tokens past the budget before rolling back")
    # the spec engine reserves ceil((max_new + k)/page) decode pages per
    # slot; the drop-in pool (contiguous footprint + NULL page) covers it
    num_pages = B * (T // page) + 1
    model = ParallelInferenceModel(module, params, icfg)

    rs = np.random.RandomState(args.seed)
    n = args.num_requests
    prompts = [
        rs.randint(1, cfg.vocab_size,
                   size=rs.randint(max(2, C // 4), C + 1)).tolist()
        for _ in range(n)
    ]
    arrivals = poisson_arrivals(n, args.arrival_rate, rs)

    def requests():
        return [Request(request_id=i, prompt_ids=prompts[i],
                        max_new_tokens=args.max_new_tokens)
                for i in range(n)]

    def measure(spec_k):
        kw = dict(page_size=page, num_pages=num_pages)
        if spec_k:
            # draft == target: the SAME compiled model proposes and
            # verifies, so acceptance is 1.0 up to numerics
            kw.update(draft=model, spec_k=spec_k)
        # warm every compiled phase on a throwaway engine (same model ⇒
        # shared compiled-fn caches) so compile time never pollutes TTFT
        led, mem = _make_ledgers(args)
        warm = ServingEngine(model, registry=MetricRegistry(),
                             compile_ledger=led, **kw)
        warm.submit(Request(request_id=-1, prompt_ids=prompts[0],
                            max_new_tokens=min(2, args.max_new_tokens)))
        warm.run_until_complete(max_steps=1000)
        warm.close()
        del warm
        engine = ServingEngine(model, registry=MetricRegistry(),
                               compile_ledger=led, memory_ledger=mem, **kw)
        engine.declare_warmup_done()
        outputs, wall, peak = _drive_workload(engine, arrivals, requests())
        engine.close()
        snap = engine.registry.snapshot()
        total_tokens = sum(len(o.token_ids) for o in outputs.values())
        ttfts = [o.ttft_ms for o in outputs.values() if o.ttft_ms is not None]
        inter = [ms for o in outputs.values() for ms in o.intertoken_ms]
        proposed = snap.get("serving/spec_proposed_total", 0.0)
        accepted = snap.get("serving/spec_accepted_total", 0.0)
        rounds = snap.get("serving/spec_rounds_total", 0.0)
        committed = snap.get("serving/spec_committed_total", 0.0)
        rec = {
            "metric": "serving_spec",
            "mode": "spec" if spec_k else "baseline",
            "spec_k": spec_k,
            "num_requests": n,
            "finished": sum(1 for o in outputs.values()
                            if o.state == "finished"),
            "tokens_per_step": (round(committed / rounds, 4) if rounds
                                else (1.0 if not spec_k else None)),
            "acceptance_rate": (round(accepted / proposed, 4) if proposed
                                else None),
            "ttft_ms": _percentiles(ttfts),
            "intertoken_ms": _percentiles(inter),
            "goodput_tok_s": total_tokens / max(wall, 1e-9),
            "wall_s": round(wall, 4),
            "max_concurrent": peak,
            **_ledger_fields(led, mem, args,
                             f"spec_k{spec_k}" if spec_k else "spec_baseline"),
        }
        return rec, {i: list(o.token_ids) for i, o in outputs.items()}

    base = {"config": {"batch": B, "context": C, "max_total": T,
                       "max_new": args.max_new_tokens, "page_size": page}}
    rec0, base_tokens = measure(0)
    print(json.dumps({**rec0, **base}))
    rc = 0
    for k in ks:
        rec, tokens = measure(k)
        identical = tokens == base_tokens
        rec["identical_to_baseline"] = identical
        print(json.dumps({**rec, **base}))
        if k >= 2 and (rec["tokens_per_step"] is None
                       or rec["tokens_per_step"] <= 1.0):
            print(f"serve_bench: spec k={k} committed "
                  f"{rec['tokens_per_step']} tokens/step <= 1 with "
                  "draft == target", file=sys.stderr)
            rc = 1
        if not identical:
            print(f"serve_bench: spec k={k} greedy outputs diverged from "
                  "the paged baseline", file=sys.stderr)
            rc = 1
    return rc


def run_compose(args, module, params, cfg, icfg) -> int:
    """Every serving feature through ONE engine on a tp=2 mesh —
    speculative decoding (draft == target), int8 KV pages, co-batched
    LoRA adapters, chunked + priority prefill, and the paged-attention
    kernel substrate — the zero-refused-pairs contract made executable.

    The warm engine replays the IDENTICAL workload first (same prompts,
    same adapters, same chunk widths), so every phase-fn parameterization
    the measured window hits is compiled up front; the measured engine
    then declares warmup done.  One JSON line; rc 1 on any refused
    admission (``serving/rejected_total`` nonzero), any unfinished
    request, any compile past the declared warmup (a compile storm
    inside the measured window), or a nonzero
    ``kvcache/gather_bytes_total`` (some phase fell off the kernel
    substrate back onto the gather path)."""
    import numpy as np

    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.parallel.mesh import get_tensor_parallel_size
    from neuronx_distributed_tpu.serving import (
        Request, ServingEngine, poisson_arrivals, replay_trace)
    from neuronx_distributed_tpu.tenancy import AdapterLayout, make_adapter_store
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    B, C, T = args.batch_size, args.context_len, args.max_total_len
    page = args.page_size
    if C % page or T % page:
        raise SystemExit(f"--page-size {page} must divide --context-len {C} "
                         f"and --max-total-len {T}")
    chunk = args.slo_chunk or max(page, (C // 8) // page * page)
    if chunk % page:
        raise SystemExit(f"--slo-chunk {chunk} must be a multiple of "
                         f"--page-size {page}")
    spec_k = 2
    if C + args.max_new_tokens + spec_k > T:
        raise SystemExit(
            f"--context-len {C} + --max-new-tokens {args.max_new_tokens} + "
            f"k {spec_k} exceeds --max-total-len {T}")
    num_pages = B * (T // page) + 1
    model = ParallelInferenceModel(module, params, icfg)

    A = 2  # distinct co-batched adapters (plus the base-model id 0)
    rank = 2
    H, NQ, NKV, D = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim_)

    def random_adapter(seed):
        r2 = np.random.RandomState(seed)
        return [{
            "a_q": (r2.randn(H, rank) * 0.05).astype(np.float32),
            "b_q": (r2.randn(rank, NQ * D) * 0.05).astype(np.float32),
            "a_v": (r2.randn(H, rank) * 0.05).astype(np.float32),
            "b_v": (r2.randn(rank, NKV * D) * 0.05).astype(np.float32),
        } for _ in range(cfg.num_layers)]

    def make_store():
        per = AdapterLayout.for_model(model, rank, 2048).pages_per_adapter
        store = make_adapter_store(model, rank=rank,
                                   num_pages=A * per + 1, page_elems=2048)
        for aid in range(1, A + 1):
            store.register(aid, random_adapter(args.seed + aid), alpha=8.0)
        return store

    # mixed workload: short interactive prompts (whole or single-chunk
    # prefill) interleaved with full-context batch-tier prompts (multi-
    # chunk prefill), adapters round-robined over {base, 1..A}
    rs = np.random.RandomState(args.seed)
    n = args.num_requests
    prompts, prios = [], []
    for i in range(n):
        if i % 4 == 3:
            prompts.append(rs.randint(1, cfg.vocab_size, size=C).tolist())
            prios.append("batch")
        else:
            prompts.append(rs.randint(
                1, cfg.vocab_size,
                size=rs.randint(max(2, C // 8), max(3, C // 2))).tolist())
            prios.append("interactive")
    arrivals = poisson_arrivals(n, args.arrival_rate, rs)

    def requests(base_id):
        return [Request(request_id=base_id + i, prompt_ids=prompts[i],
                        max_new_tokens=args.max_new_tokens,
                        adapter_id=i % (A + 1), priority=prios[i])
                for i in range(n)]

    led, mem = _make_ledgers(args)
    kw = dict(page_size=page, num_pages=num_pages, draft=model,
              spec_k=spec_k, kv_quant="int8", prefill_chunk_tokens=chunk,
              paged_kernel=True)
    # the warm pass replays the identical workload, so every phase-fn
    # parameterization (chunk widths, spec rounds, adapter tables, the
    # masked quantized page writer) compiles before measurement begins
    warm = ServingEngine(model, registry=MetricRegistry(),
                         compile_ledger=led, adapter_store=make_store(), **kw)
    replay_trace(warm, np.zeros(n), requests(1 << 20))
    warm.close()
    del warm

    engine = ServingEngine(model, registry=MetricRegistry(),
                           compile_ledger=led, memory_ledger=mem,
                           adapter_store=make_store(), **kw)
    engine.declare_warmup_done()
    peak_adapters = [0]
    orig_step = engine.step

    def step():
        out = orig_step()
        live = {engine._slot_adapter[s]
                for s, _ in engine.scheduler.active()
                if engine._slot_adapter[s]}
        peak_adapters[0] = max(peak_adapters[0], len(live))
        return out

    engine.step = step
    outputs, wall, peak = _drive_workload(engine, arrivals, requests(0))
    engine.close()
    snap = engine.registry.snapshot()

    total_tokens = sum(len(o.token_ids) for o in outputs.values())
    ttfts = [o.ttft_ms for o in outputs.values() if o.ttft_ms is not None]
    inter = [ms for o in outputs.values() for ms in o.intertoken_ms]
    rounds = snap.get("serving/spec_rounds_total", 0.0)
    committed = snap.get("serving/spec_committed_total", 0.0)
    rec = {
        "metric": "serving_compose",
        "tp": get_tensor_parallel_size(),
        "features": ["spec", "kv_quant", "lora", "chunked_prefill",
                     "paged_kernel"],
        "spec_k": spec_k,
        "adapters": A,
        "chunk_tokens": chunk,
        "num_requests": n,
        "finished": sum(1 for o in outputs.values()
                        if o.state == "finished"),
        "rejected": snap.get("serving/rejected_total", 0.0),
        "gather_bytes": snap.get("kvcache/gather_bytes_total", 0.0),
        "quant_page_writes": snap.get("kvcache/quant_pages_total", 0.0),
        "prefill_chunks": snap.get("serving/prefill_chunks_total", 0.0),
        "tokens_per_step": round(committed / rounds, 4) if rounds else None,
        "max_adapters_cobatched": peak_adapters[0],
        "max_concurrent": peak,
        "ttft_ms": _percentiles(ttfts),
        "intertoken_ms": _percentiles(inter),
        "goodput_tok_s": total_tokens / max(wall, 1e-9),
        "wall_s": round(wall, 4),
        **_ledger_fields(led, mem, args, "compose"),
    }
    print(json.dumps({**rec, "config": {
        "batch": B, "context": C, "max_total": T,
        "max_new": args.max_new_tokens, "page_size": page}}))

    rc = 0
    if rec["finished"] != n:
        print(f"serve_bench: compose finished {rec['finished']} of {n} "
              "requests", file=sys.stderr)
        rc = 1
    if rec["rejected"] > 0:
        print(f"serve_bench: compose refused {rec['rejected']} "
              "admission(s) — the zero-refused-pairs contract is broken",
              file=sys.stderr)
        rc = 1
    if rec["compiles_during_measurement"] > 0:
        print(f"serve_bench: {rec['compiles_during_measurement']} "
              "compile(s) inside the measured window — a compile storm "
              "(some feature pair missed the warm replay)", file=sys.stderr)
        rc = 1
    if rec["gather_bytes"] > 0:
        print(f"serve_bench: compose moved {rec['gather_bytes']} gather "
              "bytes — some phase fell off the kernel substrate",
              file=sys.stderr)
        rc = 1
    if rec["prefill_chunks"] <= 0:
        print("serve_bench: compose dispatched no prefill chunks",
              file=sys.stderr)
        rc = 1
    return rc


def run_paged_kernel(args, module, params, cfg, icfg) -> int:
    """Block-table-native decode kernel vs the [B, T] gather path: decode
    step cost at a FIXED real context across growing ``max_total_len``.

    The claim under test is the ISSUE-11/ROADMAP-2 contract: the gather
    path rematerializes the whole padded ``[B, T]`` view every step, so its
    step cost grows with T even when the actual context is constant; the
    kernel walks only the pages the slot's chain actually holds, so its
    step cost is FLAT in T.  One JSON line per (T, mode); rc 1 unless the
    kernel's metric stays within ``1.3x`` smallest→largest T while the
    gather path's grows past it, or if per-step logits diverge.

    On a real TPU the metric is measured step wall-time; on the CPU
    interpreter wall time measures the pallas interpreter, not HBM, so the
    rung gates on the bytes-moved model instead (gather: the full clone;
    kernel: the pages actually read); the wall-clock gate needs the chip."""
    import dataclasses
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.kvcache.quant import page_layer_bytes
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    B, C = args.batch_size, args.context_len
    page = args.page_size
    lens = sorted({int(x) for x in args.paged_kernel_lens.split(",")})
    if any(t % page for t in lens) or C % page:
        raise SystemExit(f"--page-size {page} must divide --context-len {C} "
                         f"and every --paged-kernel-lens entry {lens}")
    if any(t <= C for t in lens):
        raise SystemExit(f"--paged-kernel-lens {lens} must all exceed "
                         f"--context-len {C} (the fixed real context)")
    on_tpu = jax.devices()[0].platform != "cpu"
    steps = args.kernel_steps if on_tpu else min(args.kernel_steps, 3)
    rs = np.random.RandomState(args.seed)
    need = math.ceil((C + steps + 1) / page)  # pages one slot really uses
    kv_dtype = icfg.kv_cache_dtype
    itemsize = jnp.dtype(kv_dtype).itemsize
    L, NKV, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_

    def step_bytes(mode, T):
        """The bytes-moved model: K+V across layers, per decode step."""
        if mode == "gather":
            return L * 2 * B * T * NKV * D * itemsize
        return B * need * L * page_layer_bytes(page, NKV, D, None, kv_dtype)

    records, rc = [], 0
    for T in lens:
        PP = T // page
        num_pages = B * need + 1
        model = ParallelInferenceModel(
            module, params,
            dataclasses.replace(icfg, max_total_len=T), paged_kernel=False)
        # each slot owns `need` distinct physical pages; the table's tail
        # rides the NULL page like any unwritten decode tail
        tables = np.zeros((B, PP), np.int32)
        for b in range(B):
            tables[b, :need] = 1 + b * need + np.arange(need)
        host_caches = [
            tuple(rs.standard_normal((num_pages, NKV, page, D)).astype(
                np.float32) for _ in range(2))
            for _ in range(L)
        ]
        valid = np.zeros((B, T), np.int32)
        valid[:, :C] = 1
        tok = rs.randint(1, cfg.vocab_size, size=(B, 1)).astype(np.int32)
        offs = np.full((B,), C, np.int32)

        logits_by_mode = {}
        for mode in ("gather", "kernel"):
            pk = mode == "kernel"
            caches = [tuple(jnp.asarray(x, kv_dtype) for x in lyr)
                      for lyr in host_caches]
            v = jnp.asarray(valid)
            # warm (compile) once, then time `steps` donated decode steps
            logits, caches, v = model.decode_pages(
                jnp.asarray(tok), offs, tables, caches, v, paged_kernel=pk)
            jax.block_until_ready(logits)
            logits_by_mode[mode] = np.asarray(logits)
            o = offs + 1
            t0 = time.monotonic()
            for s in range(steps):
                logits, caches, v = model.decode_pages(
                    jnp.asarray(tok), o + s, tables, caches, v,
                    paged_kernel=pk)
            jax.block_until_ready(logits)
            ms = (time.monotonic() - t0) * 1e3 / steps
            rec = {"metric": "serving_paged_kernel", "mode": mode,
                   "max_total_len": T, "context_len": C, "page_size": page,
                   "pages_used_per_slot": need, "batch": B,
                   "step_ms": round(ms, 3), "step_bytes": step_bytes(mode, T),
                   "gate_on": "step_ms" if on_tpu else "step_bytes"}
            records.append(rec)
            print(json.dumps(rec))
        # tolerance keys on the COMPUTE dtype: the two paths accumulate in
        # different orders, so bf16 models differ at bf16 rounding scale
        tol = (2e-4 if jnp.dtype(cfg.dtype).itemsize >= 4
               and jnp.dtype(kv_dtype).itemsize >= 4 else 5e-2)
        if not np.allclose(logits_by_mode["gather"], logits_by_mode["kernel"],
                           rtol=0.0, atol=tol):
            print(f"serve_bench: paged-kernel logits diverged from the "
                  f"gather path at T={T}", file=sys.stderr)
            rc = 1

    gate = "step_ms" if on_tpu else "step_bytes"
    kern = [r[gate] for r in records if r["mode"] == "kernel"]
    gath = [r[gate] for r in records if r["mode"] == "gather"]
    flat = max(kern) / max(min(kern), 1e-9)
    growth = max(gath) / max(min(gath), 1e-9)
    if flat > 1.3:
        print(f"serve_bench: kernel {gate} NOT flat in T "
              f"({min(kern)} -> {max(kern)}, x{flat:.2f} > 1.3)",
              file=sys.stderr)
        rc = 1
    if growth <= 1.3:
        print(f"serve_bench: gather {gate} did not grow with T "
              f"({min(gath)} -> {max(gath)}, x{growth:.2f}) — the "
              "comparison is vacuous", file=sys.stderr)
        rc = 1
    print(json.dumps({"metric": "serving_paged_kernel_gate", "gate_on": gate,
                      "kernel_ratio": round(flat, 3),
                      "gather_ratio": round(growth, 3), "rc": rc}))
    return rc


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true", help="CPU smoke config")
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--context-len", type=int, default=128)
    p.add_argument("--max-total-len", type=int, default=256)
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--continuous", action="store_true",
                   help="continuous-batching mode: Poisson arrivals through "
                        "serving.ServingEngine vs the static generate baseline")
    p.add_argument("--paged", action="store_true",
                   help="paged-KV mode: paged vs contiguous engines at the "
                        "same HBM budget on a shared-system-prompt workload "
                        "(one JSON line each)")
    p.add_argument("--page-size", type=int, default=8,
                   help="KV page size in tokens (paged mode; must divide "
                        "context/total lengths)")
    p.add_argument("--paged-slots", type=int, default=None,
                   help="paged engine slot count (default: 2x --batch-size)")
    p.add_argument("--slo", action="store_true",
                   help="stall-free SLO mode: bimodal short/long-prompt "
                        "Poisson trace through the chunked + priority "
                        "engine vs an unchunked FCFS control and an "
                        "interactive-only baseline (one JSON line each; "
                        "rc 1 unless the SLO engine holds interactive "
                        "inter-token p99 within 2x baseline while the "
                        "control spikes)")
    p.add_argument("--slo-long", type=int, default=4,
                   help="full-context-width batch-tier prompts the --slo "
                        "trace mixes into the interactive stream")
    p.add_argument("--slo-chunk", type=int, default=None,
                   help="prefill chunk budget in tokens for the --slo rung "
                        "(default: ~context/8, page-aligned)")
    p.add_argument("--spec", action="store_true",
                   help="speculative-decoding mode: draft-k-verify over the "
                        "paged engine vs the plain paged baseline, "
                        "draft == target (one JSON line per rung; rc 1 if "
                        "tokens/step <= 1 at k >= 2 or outputs diverge)")
    p.add_argument("--spec-ks", default="2,4,8",
                   help="comma-separated draft depths for the --spec sweep")
    p.add_argument("--lora", action="store_true",
                   help="multi-adapter mode (tenancy/): >= --lora-adapters "
                        "LoRA adapters co-batched through one paged engine "
                        "vs the no-adapter baseline (one JSON line each; "
                        "rc 1 if co-batching or the near-baseline "
                        "inter-token bound fails)")
    p.add_argument("--lora-adapters", type=int, default=8,
                   help="distinct adapters the --lora rung registers and "
                        "round-robins requests across")
    p.add_argument("--compose", action="store_true",
                   help="composition mode: speculative decoding + int8 KV "
                        "+ LoRA adapters + chunked/priority prefill + the "
                        "paged kernel through ONE engine on a tp=2 mesh "
                        "(one JSON line; rc 1 on any refused admission, "
                        "any compile past warmup, or nonzero gather bytes)")
    p.add_argument("--paged-kernel", action="store_true",
                   help="paged decode kernel mode: block-table-native "
                        "kernel vs the [B, T] gather path at a fixed real "
                        "context across growing max_total_len (one JSON "
                        "line per (T, mode); rc 1 unless the kernel's step "
                        "cost is flat in T while the gather path's grows)")
    p.add_argument("--paged-kernel-lens", default="512,2048,8192",
                   help="comma-separated max_total_len sweep for "
                        "--paged-kernel")
    p.add_argument("--kernel-steps", type=int, default=20,
                   help="timed decode steps per --paged-kernel rung "
                        "(capped at 3 on the CPU interpreter)")
    p.add_argument("--kv-quant", action="store_true",
                   help="int8-KV mode: int8 vs fp pages at a fixed HBM "
                        "budget (one JSON line each; rc 1 unless int8 "
                        "sustains >= 2x max concurrency)")
    p.add_argument("--num-requests", type=int, default=16)
    p.add_argument("--arrival-rate", type=float, default=20.0,
                   help="Poisson arrival rate, requests/s")
    p.add_argument("--stats-out", default=None,
                   help="serving_stats.jsonl path (continuous mode)")
    p.add_argument("--trace-out", default=None,
                   help="directory to drop request-lifecycle trace "
                        "artifacts into (engine rungs: --continuous and "
                        "--slo): one schema-checked "
                        "<rung>.trace_events.jsonl + one Perfetto "
                        "<rung>.trace.json per measured engine")
    p.add_argument("--alerts-out", default=None,
                   help="directory to drop health-monitor artifacts into "
                        "(engine rungs: --continuous and --slo): every "
                        "measured engine runs under the default rule pack "
                        "and drops one schema-checked <rung>.alerts.jsonl; "
                        "the --slo rc additionally fails if a page-severity "
                        "alert fires during the compliant rung")
    p.add_argument("--ledger-out", default=None,
                   help="directory to drop resource-ledger artifacts into "
                        "(engine rungs): one schema-checked "
                        "<rung>.compile_ledger.jsonl + one "
                        "<rung>.memory_breakdown.json per measured engine; "
                        "every rung also reports "
                        "compiles_during_measurement regardless")
    p.add_argument("--profile-out", default=None,
                   help="directory to drop roofline perf-attribution "
                        "artifacts into (engine rungs: --continuous and "
                        "--paged): one schema-checked "
                        "<rung>.perf_attribution.jsonl per measured "
                        "engine (per-phase device time joined with "
                        "compiled flops/bytes -> mfu_model/pct_roofline "
                        "on the rung's JSON line) plus an XLA device "
                        "profile of the measured window under "
                        "<DIR>/<rung>")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    if args.compose:
        # the compose rung runs tp=2 even on the CPU mesh — force a second
        # host device before jax initializes (no-op when already set)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2").strip()

    import jax

    if args.tiny:
        jax.config.update("jax_platforms", "cpu")
    from neuronx_distributed_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()

    import jax.numpy as jnp

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    if not on_tpu and not args.tiny:
        print("refusing to record a non-TPU serving number; use --tiny for "
              "a CPU harness smoke", file=sys.stderr)
        return 1
    tp = 2 if args.compose and len(devices) >= 2 else 1
    nxd.initialize_model_parallel(tensor_parallel_size=tp, devices=devices[:tp])

    if args.continuous and args.batch_size == 1:
        # a 1-slot pool degenerates to serial serving — not a continuous-
        # batching measurement
        args.batch_size = 3
        print("serve_bench: --continuous with --batch-size 1 is a serial "
              "run; using batch size 3", file=sys.stderr)
    if args.paged and args.batch_size == 1:
        # a 1-slot contiguous baseline is degenerate for a concurrency
        # comparison (and its 1-row budget leaves the pool no headroom)
        args.batch_size = 2
        print("serve_bench: --paged with --batch-size 1 is a serial "
              "baseline; using batch size 2", file=sys.stderr)
    if args.spec and args.batch_size == 1:
        # tokens/step must be measured with speculation co-batched across
        # slots, not in a degenerate serial engine
        args.batch_size = 2
        print("serve_bench: --spec with --batch-size 1 is a serial run; "
              "using batch size 2", file=sys.stderr)
    if args.lora and args.batch_size < args.lora_adapters:
        # co-batching A distinct adapters needs at least A slots
        args.batch_size = args.lora_adapters
        print(f"serve_bench: --lora needs >= {args.lora_adapters} slots to "
              f"co-batch {args.lora_adapters} adapters; using batch size "
              f"{args.batch_size}", file=sys.stderr)
    if args.kv_quant and args.batch_size == 1:
        args.batch_size = 2
        print("serve_bench: --kv-quant with --batch-size 1 is a degenerate "
              "concurrency comparison; using batch size 2", file=sys.stderr)
    if args.compose and args.batch_size < 3:
        # composition needs co-batched slots: spec rounds, adapter
        # co-residency and chunked prefills all landing in one batch
        args.batch_size = 3
        print("serve_bench: --compose needs co-batched requests; using "
              "batch size 3", file=sys.stderr)
    if args.slo and args.batch_size < 3:
        # the stall under test needs interactive decodes CO-BATCHED with a
        # long prompt's prefill
        args.batch_size = 3
        print("serve_bench: --slo needs co-batched interactive + long "
              "requests; using batch size 3", file=sys.stderr)

    if args.tiny:
        cfg = LlamaConfig.tiny(max_seq_len=args.max_total_len,
                               sequence_parallel=False, remat="none")
        args.max_new_tokens = min(args.max_new_tokens, 8)
        if args.paged_kernel and args.paged_kernel_lens == "512,2048,8192":
            # interpreter-scale sweep (still >1.3x T growth end to end);
            # the gate runs on the bytes-moved model off-TPU anyway
            args.paged_kernel_lens = "192,320,576"
        # the --slo rung gates on an interactive p99 — it needs more
        # samples than the other tiny modes to keep the percentile stable
        args.num_requests = min(args.num_requests, 16 if args.slo else 8)
    else:
        # the bench.py 438M model (7B hidden layout / 4)
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_layers=12, num_heads=12, num_kv_heads=12, head_dim=128,
            max_seq_len=args.max_total_len, sequence_parallel=False,
            remat="none",
        )
    from flax import linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neuronx_distributed_tpu.parallel.mesh import get_mesh

    module = LlamaForCausalLM(cfg)
    ids0 = jnp.zeros((args.batch_size, args.context_len), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), ids0)
    specs = nn.get_partition_spec(params)
    mesh = get_mesh()
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        nn.unbox(params), specs,
        is_leaf=lambda x: isinstance(x, P) or not isinstance(x, dict))
    icfg = InferenceConfig(
        batch_size=args.batch_size, context_len=args.context_len,
        max_total_len=args.max_total_len,
        kv_cache_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
    )
    if args.compose:
        return run_compose(args, module, params, cfg, icfg)
    if args.paged_kernel:
        return run_paged_kernel(args, module, params, cfg, icfg)
    if args.paged:
        return run_paged(args, module, params, cfg, icfg)
    if args.slo:
        return run_slo(args, module, params, cfg, icfg)
    if args.spec:
        return run_spec(args, module, params, cfg, icfg)
    if args.lora:
        return run_lora(args, module, params, cfg, icfg)
    if args.kv_quant:
        return run_kv_quant(args, module, params, cfg, icfg)
    model = ParallelInferenceModel(module, params, icfg)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    base = {
        "device": getattr(devices[0], "device_kind", devices[0].platform),
        "model_params_m": round(n_params / 1e6),
        "config": {"batch": args.batch_size, "context": args.context_len,
                   "max_new": args.max_new_tokens},
    }
    if args.continuous:
        stats = run_continuous(args, model, cfg.vocab_size)
        print(json.dumps({"metric": "serving_continuous", **base, **stats}))
    else:
        stats = model.benchmark(max_new_tokens=args.max_new_tokens)
        print(json.dumps({"metric": "serving_decode_latency", **base, **stats}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
