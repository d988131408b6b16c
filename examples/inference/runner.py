#!/usr/bin/env python
"""Inference runner CLI — trace / infer / benchmark / check-accuracy, the
framework-native analogue of the reference's
``examples/inference/runner.py:232-260`` command surface.

  # trace and save a compiled serving artifact
  python examples/inference/runner.py trace --preset tiny --tp 2 \
      --batch-size 2 --context-len 32 --max-total-len 64 \
      --out /tmp/traced --virtual-devices 8

  # generate from the saved artifact
  python examples/inference/runner.py infer --model /tmp/traced \
      --max-new-tokens 16

  # per-token latency stats
  python examples/inference/runner.py benchmark --model /tmp/traced \
      --max-new-tokens 64

  # cached decode vs teacher-forced full forward
  python examples/inference/runner.py check-accuracy --preset tiny --tp 2 \
      --batch-size 2 --context-len 32 --max-total-len 64 --virtual-devices 8

  # continuous-batching serving demo (Poisson arrivals, streamed tokens)
  python examples/inference/runner.py serve --preset tiny --batch-size 3 \
      --context-len 16 --max-total-len 32 --num-requests 6 --rate 50

  # batched speculative serving (--draft equal to --preset
  # is the draft == target control: acceptance 1.0, tokens/step ~ k+1)
  python examples/inference/runner.py serve --preset tiny --batch-size 3 \
      --context-len 16 --max-total-len 64 --page-size 8 \
      --draft tiny --spec-k 4 --num-requests 6
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def build_model(args, preset=None, seed=None):
    import jax
    import jax.numpy as jnp

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models import (
        Gemma2Config,
        Gemma2ForCausalLM,
        GemmaConfig,
        GemmaForCausalLM,
    )
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel.layers import init_sharded_params
    from neuronx_distributed_tpu.parallel.mesh import (
        model_parallel_is_initialized,
    )
    from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

    if not model_parallel_is_initialized():
        nxd.initialize_model_parallel(tensor_parallel_size=args.tp)
    else:
        from neuronx_distributed_tpu.parallel.mesh import get_tensor_parallel_size

        if get_tensor_parallel_size() != args.tp:
            raise SystemExit(
                f"model parallel already initialized with tp="
                f"{get_tensor_parallel_size()}, but --tp {args.tp} requested")
    # weights, compute and KV cache in the dtype the ARGUMENTS name — never
    # switched on the backend the run happens to find
    dtype = jnp.dtype(args.dtype)
    cfg_cls, model_cls = {
        "llama": (LlamaConfig, LlamaForCausalLM),
        "gemma": (GemmaConfig, GemmaForCausalLM),
        "gemma2": (Gemma2Config, Gemma2ForCausalLM),
    }[getattr(args, "family", "llama")]
    cfg = getattr(cfg_cls, preset or args.preset)(
        max_seq_len=args.max_total_len,
        sequence_parallel=False,
        remat="none",
        dtype=dtype,
        param_dtype=dtype,
    )
    module = model_cls(cfg)
    ids0 = jnp.zeros((args.batch_size, args.context_len), jnp.int32)
    # born sharded over the mesh (as initialize_parallel_model does): the
    # whole model never sits unsharded on the default device
    params, _ = init_sharded_params(
        module, jax.random.PRNGKey(args.seed if seed is None else seed), ids0)
    icfg = InferenceConfig(
        batch_size=args.batch_size, context_len=args.context_len,
        max_total_len=args.max_total_len,
        kv_cache_dtype=dtype,
        chunked_prefill=getattr(args, "chunked_prefill", False))
    return cfg, module, params, ParallelInferenceModel(module, params, icfg)


def cmd_trace(args):
    from neuronx_distributed_tpu.trace import parallel_model_save

    _, _, _, model = build_model(args)
    path = parallel_model_save(args.out, model)
    print(f"saved traced model to {path}")


def _prompt_ids(seed, batch_size, context_len, vocab):
    import jax

    return jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch_size, context_len), 0, vocab)


def cmd_infer(args):
    import jax

    from neuronx_distributed_tpu.trace import parallel_model_load

    model = parallel_model_load(args.model)
    cfg = model.config
    prompt = _prompt_ids(args.seed, cfg.batch_size, cfg.context_len, 256)
    lens = None
    if args.prompt_lens:
        lens = [int(x) for x in args.prompt_lens.split(",")]
        if len(lens) != cfg.batch_size:
            raise SystemExit(f"--prompt-lens needs {cfg.batch_size} comma-separated ints")
    out = model.generate(prompt, args.max_new_tokens,
                         temperature=args.temperature,
                         rng=jax.random.PRNGKey(args.seed) if args.temperature else None,
                         prompt_lens=lens)
    print(json.dumps({"generated": out[:, cfg.context_len:].tolist()}))


def cmd_spec_decode(args):
    import time

    from neuronx_distributed_tpu.trace import speculative_generate

    tcfg, _, _, target = build_model(args)
    _, _, _, draft = build_model(args, preset=args.draft_preset, seed=args.seed + 1)
    prompt = _prompt_ids(args.seed, args.batch_size, args.context_len, tcfg.vocab_size)

    # warm both paths, then time
    import jax

    jax.block_until_ready(target.generate(prompt, args.max_new_tokens))
    jax.block_until_ready(
        speculative_generate(target, draft, prompt, args.max_new_tokens, k=args.spec_k))
    t0 = time.perf_counter()
    want = target.generate(prompt, args.max_new_tokens)
    jax.block_until_ready(want)
    t_plain = time.perf_counter() - t0
    t0 = time.perf_counter()
    got, stats = speculative_generate(
        target, draft, prompt, args.max_new_tokens, k=args.spec_k, return_stats=True)
    jax.block_until_ready(got)
    t_spec = time.perf_counter() - t0
    import numpy as np

    identical = bool((np.asarray(got) == np.asarray(want)).all())
    print(json.dumps({
        "identical_to_target_greedy": identical,
        "plain_s": round(t_plain, 4), "spec_s": round(t_spec, 4),
        "speedup": round(t_plain / max(t_spec, 1e-9), 3), **stats,
    }))
    sys.exit(0 if identical else 1)


def cmd_serve(args):
    """Continuous-batching serving demo: drive ``ServingEngine`` (or, with
    ``--replicas N``, a ``FleetRouter`` over N in-process replicas) from a
    JSONL prompt file (``{"prompt_ids": [...], "max_new_tokens"?,
    "temperature"?}`` per line; random prompts when no file) with Poisson
    arrivals, streaming each token as a JSONL event and ending with one
    stats line."""
    import time

    import jax
    import numpy as np

    from neuronx_distributed_tpu.obs import MetricRegistry
    from neuronx_distributed_tpu.serving import (
        FleetRouter, Replica, Request, SamplingParams, ServingEngine,
        poisson_arrivals, replay, summarize_outputs)

    cfg, _, _, model = build_model(args)
    rs = np.random.RandomState(args.seed)
    specs = []
    if args.prompts:
        with open(args.prompts) as f:
            for line in f:
                line = line.strip()
                if line:
                    specs.append(json.loads(line))
        # the whole file unless --num-requests explicitly caps it
        if args.num_requests is not None:
            specs = specs[: args.num_requests]
    else:
        n = args.num_requests if args.num_requests is not None else 8
        specs = [
            {"prompt_ids": rs.randint(
                1, cfg.vocab_size,
                size=rs.randint(2, args.context_len + 1)).tolist()}
            for _ in range(n)
        ]
    if not specs:
        raise SystemExit("serve: no prompts (empty --prompts file or "
                         "--num-requests 0)")
    arrivals = poisson_arrivals(len(specs), args.rate, rs)

    def stream(req, tok):
        if not args.quiet:
            print(json.dumps({"event": "token", "request_id": req.request_id,
                              "token": int(tok)}), flush=True)

    # the KV cache is a page pool: its HBM is num_pages * page_bytes, not
    # B * T.  Left unset, --num-pages is the engine's own default — the pool
    # in which every slot can hold --max-total-len — and a smaller pool
    # trades HBM for admission backpressure.
    paged_kw = dict(page_size=args.page_size, num_pages=args.num_pages,
                    paged_kernel={"auto": "auto", "on": True,
                                  "off": False}[args.paged_kernel])
    if args.kv_dtype == "int8":
        # int8 KV pages: same page count by default, half the HBM — or
        # shrink --num-pages less aggressively for ~2x the in-flight
        # requests at the fp pool's byte budget
        paged_kw["kv_quant"] = "int8"
    n_adapters = args.adapters or 0
    if n_adapters:
        # multi-tenant demo: N random rank-4 LoRA adapters registered on
        # every engine, requests round-robined across them (JSONL prompt
        # specs may instead pin one explicitly via "adapter_id")
        def make_store():
            import numpy as np

            from neuronx_distributed_tpu.tenancy import (
                AdapterLayout, AdapterStore)

            H, NQ, NKV, D = (cfg.hidden_size, cfg.num_heads,
                             cfg.num_kv_heads, cfg.head_dim_)
            rank = 4
            layout = AdapterLayout.for_model(model, rank, 2048)
            # every adapter resident at once, plus the NULL page
            store = AdapterStore(
                layout, n_adapters * layout.pages_per_adapter + 1)
            for aid in range(1, n_adapters + 1):
                r2 = np.random.RandomState(args.seed + aid)
                store.register(aid, [{
                    "a_q": (r2.randn(H, rank) * 0.05).astype(np.float32),
                    "b_q": (r2.randn(rank, NQ * D) * 0.05).astype(np.float32),
                    "a_v": (r2.randn(H, rank) * 0.05).astype(np.float32),
                    "b_v": (r2.randn(rank, NKV * D) * 0.05).astype(np.float32),
                } for _ in range(cfg.num_layers)], alpha=8.0)
            return store
    if args.draft:
        # speculative serving: a co-batched draft proposes --spec-k tokens
        # per slot per step, the target verifies them in one batched chunk.
        # The draft preset shares the target's seed, so `--draft` equal to
        # `--preset` is the draft == target control (acceptance 1.0).
        _, _, _, draft = build_model(args, preset=args.draft)
        paged_kw.update(draft=draft, spec_k=args.spec_k)
    tracer = None
    if args.trace_out:
        from neuronx_distributed_tpu.obs import Tracer

        tracer = Tracer()
    fleet = args.replicas > 1
    health = None
    if args.alerts_out:
        # the control room: default rule pack over the live registries,
        # alert edges streamed to alerts.jsonl; a fleet gets per-replica
        # monitors + one fleet monitor through the router, a bare engine
        # one serving-scope monitor
        os.makedirs(args.alerts_out, exist_ok=True)
        alerts_path = os.path.join(args.alerts_out, "alerts.jsonl")
        if os.path.exists(alerts_path):
            os.remove(alerts_path)  # the sink appends: a rerun starts fresh
        if fleet:
            from neuronx_distributed_tpu.obs.aggregate import FleetHealth

            health = FleetHealth(path=alerts_path, tracer=tracer)
        else:
            from neuronx_distributed_tpu.obs.health import (
                HealthMonitor,
                default_rules,
            )

            health = HealthMonitor(default_rules("serving"),
                                   path=alerts_path, tracer=tracer,
                                   eval_every=4)
    if fleet:
        # in-process fleet: N engines share the one compiled model (one
        # set of device params) but each owns its KV state — and, with
        # --adapters, its own adapter store (every adapter registered on
        # every replica, so a requeued clone is admissible anywhere);
        # --stats-out becomes the router's router_stats.jsonl instead of a
        # single engine's serving_stats.jsonl
        def make_factory(rid):
            def factory():
                kw = dict(paged_kw)
                if n_adapters:
                    kw["adapter_store"] = make_store()
                if tracer is not None:
                    # one shared ring, per-replica span tags: a request's
                    # trace stitches across replicas by its global id
                    kw["tracer"] = tracer.scoped(rid)
                return ServingEngine(
                    model, rng=jax.random.PRNGKey(args.seed),
                    registry=MetricRegistry(), **kw)
            return factory

        target = FleetRouter(
            [Replica(i, make_factory(i)) for i in range(args.replicas)],
            policy=args.routing, seed=args.seed, stats_path=args.stats_out,
            tracer=tracer, health=health)
    else:
        if n_adapters:
            paged_kw["adapter_store"] = make_store()
        target = engine = ServingEngine(
            model, rng=jax.random.PRNGKey(args.seed),
            stats_path=args.stats_out, tracer=tracer, health=health,
            **paged_kw)
    requests = [
        Request(
            request_id=i,
            prompt_ids=s["prompt_ids"],
            max_new_tokens=int(s.get("max_new_tokens", args.max_new_tokens)),
            sampling=SamplingParams(
                temperature=float(s.get("temperature", args.temperature))),
            stream_cb=stream,
            adapter_id=int(s.get(
                "adapter_id", (i % n_adapters) + 1 if n_adapters else 0)),
        )
        for i, s in enumerate(specs)
    ]

    def done(out):
        ev = {"event": "done", "request_id": out.request_id,
              "state": out.state, "tokens": list(out.token_ids)}
        if fleet:  # the id the caller submitted, pre-re-keying
            ev["client_id"] = target.client_id(out.request_id)
        print(json.dumps(ev), flush=True)

    msrv = None
    if args.metrics_port is not None:
        # live scrape endpoint for the run's duration: /metrics serves the
        # front door's registry (router metrics for a fleet, engine
        # metrics solo); /healthz answers 503 once liveness is gone
        from neuronx_distributed_tpu.obs.metrics_server import MetricsServer

        if fleet:
            def liveness():
                alive = sum(1 for r in target.replicas.values() if r.alive)
                return {"ok": alive > 0, "replicas": args.replicas,
                        "alive_replicas": alive,
                        "inflight": target.inflight}
        else:
            def liveness():
                return {"ok": True, "steps": engine._steps,
                        "active": engine.scheduler.active_count,
                        "queued": engine.scheduler.queue_depth}

        scopes = None
        if fleet:
            from neuronx_distributed_tpu.obs.aggregate import (
                FleetAggregator,
            )

            scopes = {"fleet":
                      FleetAggregator.for_router(target).prometheus_text}
        msrv = MetricsServer(registry=target.registry, health_fn=liveness,
                             monitor=health, scopes=scopes,
                             port=args.metrics_port)
        endpoints = ["/metrics", "/healthz"]
        if scopes:
            endpoints.append("/metrics?scope=fleet")
        print(json.dumps({"event": "metrics_server", "port": msrv.port,
                          "endpoints": endpoints}),
              flush=True)

    t0 = time.monotonic()
    try:
        outputs = replay(target, arrivals, requests, on_output=done,
                         tracer=tracer)
    finally:
        if msrv is not None:
            msrv.close()
    wall = time.monotonic() - t0
    if tracer is not None:
        from neuronx_distributed_tpu.obs.schemas import validate_jsonl

        os.makedirs(args.trace_out, exist_ok=True)
        ev = os.path.join(args.trace_out, "trace_events.jsonl")
        ch = os.path.join(args.trace_out, "trace.json")
        tracer.export_jsonl(ev)
        tracer.export_chrome(ch)
        validate_jsonl("trace_event", ev)
        print(json.dumps({"event": "trace", "trace_events": ev,
                          "trace_perfetto": ch}), flush=True)
    if health is not None:
        from neuronx_distributed_tpu.obs.schemas import validate_jsonl

        health.close()
        ap = os.path.join(args.alerts_out, "alerts.jsonl")
        print(json.dumps({"event": "alerts", "alerts": ap,
                          "edges": validate_jsonl("alert", ap)}),
              flush=True)
    if fleet:
        snap = target.registry.snapshot()
        prefix = target.fleet_prefix_stats()
        target.close()
        hits = snap.get("router/affinity_hits_total", 0.0)
        misses = snap.get("router/affinity_misses_total", 0.0)
        summary = summarize_outputs(outputs, wall)
        summary.update({
            "replicas": args.replicas,
            "routing": target.policy.name,
            "dispatched": int(snap.get("router/dispatched_total", 0)),
            "requeued": int(snap.get("router/requeued_total", 0)),
            "failovers": int(snap.get("router/failovers_total", 0)),
            "affinity_hit_rate": (round(hits / (hits + misses), 4)
                                  if hits + misses else None),
            "fleet_prefix_hit_rate": prefix["prefix_hit_rate"],
            "prefills_skipped": prefix["prefills_skipped"],
        })
        if n_adapters:
            summary["adapters"] = n_adapters
        print(json.dumps(summary))
        return
    engine.close()
    snap = engine.registry.snapshot()
    ttfts = [o.ttft_ms for o in outputs.values() if o.ttft_ms is not None]
    summary = {
        "requests": len(outputs),
        "finished": int(snap.get("serving/finished_total", 0)),
        "tokens": int(snap.get("serving/tokens_total", 0)),
        "ttft_p50_ms": float(np.percentile(ttfts, 50)) if ttfts else None,
        "wall_s": round(wall, 4),
        "tokens_per_s": (int(snap.get("serving/tokens_total", 0)) /
                         max(wall, 1e-9)),
        "kv_pages_in_use": int(snap.get("kvcache/pages_in_use", 0)),
        "prefix_hits": int(snap.get("kvcache/prefix_hits_total", 0)),
        "prefills_skipped": int(
            snap.get("kvcache/prefill_skipped_total", 0)),
    }
    if args.kv_dtype == "int8":
        summary["quant_page_writes"] = int(
            snap.get("kvcache/quant_pages_total", 0))
    if n_adapters:
        summary["adapters_resident"] = int(
            snap.get("tenancy/adapters_resident", 0))
        summary["adapter_loads"] = int(
            snap.get("tenancy/adapter_loads_total", 0))
        summary["adapter_hits"] = int(
            snap.get("tenancy/adapter_hits_total", 0))
    if args.draft:
        proposed = snap.get("serving/spec_proposed_total", 0.0)
        rounds = snap.get("serving/spec_rounds_total", 0.0)
        summary["tokens_per_step"] = (
            round(snap.get("serving/spec_committed_total", 0.0) / rounds, 4)
            if rounds else None)
        summary["acceptance_rate"] = (
            round(snap.get("serving/spec_accepted_total", 0.0) / proposed, 4)
            if proposed else None)
    print(json.dumps(summary))


def cmd_benchmark(args):
    from neuronx_distributed_tpu.trace import parallel_model_load

    model = parallel_model_load(args.model)
    stats = model.benchmark(max_new_tokens=args.max_new_tokens)
    print(json.dumps(stats, indent=2))


def cmd_check_accuracy(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, module, params, model = build_model(args)
    prompt = _prompt_ids(args.seed, args.batch_size, args.context_len, cfg.vocab_size)
    out = model.generate(prompt, args.max_new_tokens)
    full = jax.jit(module.apply)(params, out)
    ok = True
    for t in range(args.context_len, args.context_len + args.max_new_tokens):
        pred = np.asarray(jnp.argmax(full[:, t - 1, :], axis=-1))
        if not (pred == np.asarray(out[:, t])).all():
            ok = False
            print(f"mismatch at position {t}")
    print(json.dumps({"inference_success": int(ok)}))
    sys.exit(0 if ok else 1)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp, traced=False):
        sp.add_argument("--virtual-devices", type=int, default=None)
        sp.add_argument("--seed", type=int, default=0)
        sp.add_argument("--max-new-tokens", type=int, default=16)
        if traced:
            sp.add_argument("--model", required=True, help="saved artifact dir")
        else:
            sp.add_argument("--preset", default="tiny",
                            help="config preset on the family's Config class")
            sp.add_argument("--family", default="llama",
                            choices=["llama", "gemma", "gemma2"])
            sp.add_argument("--tp", type=int, default=1)
            sp.add_argument("--dtype", default="bfloat16",
                            choices=["bfloat16", "float32"],
                            help="weights, compute and KV-cache dtype")
            sp.add_argument("--batch-size", type=int, default=1)
            sp.add_argument("--context-len", type=int, default=128)
            sp.add_argument("--max-total-len", type=int, default=256)
            sp.add_argument("--chunked-prefill", action="store_true",
                            help="also compile a chunk-prefill executable so "
                                 "prompts of any multiple of --context-len serve "
                                 "without re-tracing")

    sp = sub.add_parser("trace", help="compile + save a serving artifact")
    common(sp)
    sp.add_argument("--out", required=True)
    sp.set_defaults(fn=cmd_trace)

    sp = sub.add_parser("infer", help="generate from a saved artifact")
    sp.add_argument("--prompt-lens", default=None,
                    help="comma-separated per-example prompt lengths "
                         "(ragged batch, left-padded)")
    common(sp, traced=True)
    sp.add_argument("--temperature", type=float, default=0.0)
    sp.set_defaults(fn=cmd_infer)

    sp = sub.add_parser("benchmark", help="p50/p99 per-token latency")
    common(sp, traced=True)
    sp.set_defaults(fn=cmd_benchmark)

    sp = sub.add_parser("serve", help="continuous-batching serving demo: "
                                      "JSONL prompts, Poisson arrivals, "
                                      "streamed tokens + stats line")
    common(sp)
    sp.add_argument("--prompts", default=None,
                    help="JSONL prompt file ({'prompt_ids': [...]} per line; "
                         "random prompts when omitted)")
    sp.add_argument("--num-requests", type=int, default=None,
                    help="request count (default: whole --prompts file, or "
                         "8 random prompts)")
    sp.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate, requests/s")
    sp.add_argument("--temperature", type=float, default=0.0)
    sp.add_argument("--stats-out", default=None,
                    help="serving_stats.jsonl output path")
    sp.add_argument("--quiet", action="store_true",
                    help="suppress per-token stream events")
    sp.add_argument("--page-size", type=int, default=8,
                    help="tokens a page of the KV pool holds (must divide "
                         "--context-len and --max-total-len); repeated "
                         "prompts share prefix pages and skip prefill")
    sp.add_argument("--num-pages", type=int, default=None,
                    help="KV pool size in pages (default: every slot can "
                         "hold --max-total-len, batch*total/page + the "
                         "reserved NULL page; smaller pools trade HBM for "
                         "admission backpressure)")
    sp.add_argument("--adapters", type=int, default=0,
                    help="multi-tenant demo: register this many random "
                         "rank-4 LoRA adapters and round-robin requests "
                         "across them (JSONL specs may pin 'adapter_id')")
    sp.add_argument("--kv-dtype", default="fp", choices=["fp", "int8"],
                    help="KV page dtype: int8 stores pages quantized with "
                         "per-page scale/zero (~2x pages per HBM byte at a "
                         "bounded logit drift)")
    sp.add_argument("--paged-kernel", default="auto",
                    choices=["auto", "on", "off"],
                    help="block-table-native decode kernel "
                         "(ops.paged_attention): auto = kernel on TPU, "
                         "gather path elsewhere")
    sp.add_argument("--draft", default=None,
                    help="enable speculative serving with this draft-model "
                         "preset (same family/seed as the target, so a "
                         "preset equal to --preset is the draft == target "
                         "control)")
    sp.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per slot per round "
                         "(speculative serving; requires --draft)")
    sp.add_argument("--replicas", type=int, default=1,
                    help="serve through a FleetRouter over this many "
                         "in-process engine replicas (1 = a bare engine); "
                         "--stats-out then writes router_stats.jsonl")
    sp.add_argument("--metrics-port", type=int, default=None,
                    help="expose /metrics (Prometheus text over the live "
                         "registry) and /healthz (engine/fleet liveness) "
                         "on this port for the duration of the serve run "
                         "(0 = ephemeral; the chosen port is printed as a "
                         "metrics_server event)")
    sp.add_argument("--trace-out", default=None,
                    help="directory to drop request-lifecycle trace "
                         "artifacts into after the run: trace_events.jsonl "
                         "(schema-checked spans, stitched across replicas) "
                         "+ trace.json (Perfetto)")
    sp.add_argument("--alerts-out", default=None,
                    help="run under the default health-monitor rule pack "
                         "(fleet: per-replica + fleet monitors) and stream "
                         "schema-checked alert edges to "
                         "DIR/alerts.jsonl; with --metrics-port, /healthz "
                         "readiness then reflects firing-alert state (503 "
                         "on page severity) and a fleet exposes "
                         "/metrics?scope=fleet (replica-labeled merge)")
    sp.add_argument("--routing", default="prefix_affinity",
                    choices=["round_robin", "random", "least_loaded",
                             "prefix_affinity"],
                    help="fleet dispatch policy (with --replicas > 1)")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("spec-decode", help="speculative decoding: verify + time vs plain greedy")
    common(sp)
    sp.add_argument("--draft-preset", default="tiny",
                    help="draft model preset on the same family "
                         "(should be much smaller than the target)")
    sp.add_argument("--spec-k", type=int, default=4, help="draft tokens per round")
    sp.set_defaults(fn=cmd_spec_decode)

    sp = sub.add_parser("check-accuracy", help="cached decode vs teacher forcing")
    common(sp)
    sp.set_defaults(fn=cmd_check_accuracy)

    args = p.parse_args()
    if args.virtual_devices:
        from neuronx_distributed_tpu.utils.common import ensure_virtual_devices

        ensure_virtual_devices(args.virtual_devices)
    from neuronx_distributed_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    import jax

    dev = jax.devices()[0]
    # stderr: stdout carries the commands' JSON lines
    print(f"devices: {len(jax.devices())} x {dev.device_kind} (platform "
          f"{dev.platform}); dtype {getattr(args, 'dtype', 'as traced')}; "
          f"compile cache {cache_dir}", file=sys.stderr, flush=True)
    args.fn(args)


if __name__ == "__main__":
    main()
