"""The runner of ``"runner": "serve"`` configurations.

The normal path: ``initialize_model_parallel`` -> ``init_sharded_params``
(weights born on the device, in the served dtype, from ``--seed``) ->
``ParallelInferenceModel`` -> paged ``ServingEngine.submit`` / ``step``.

One thread does everything — submits what is due, then calls
``engine.step()`` — so the load is the same on every run and the generator
cannot race the server for the interpreter.  Every time is the BENCHMARK's:
a token's time is when ``Request.stream_cb`` fired, on this module's clock,
and an open-loop request is timed from when it was DUE by the schedule, not
from when the loop got round to submitting it (how late that was is the
metric ``generator_lateness_p50_ms``).

Timeline of a run: set-up (weights, the reference check, one warm-up
request) | lead-in of ``lead_in_s`` under the mix's load, unmeasured, so the
window opens on a full engine and not on a ramp | the window of
``--seconds`` | a short drain until every request due in the window has its
first token | everything outstanding is cancelled and the pool must come
back clean.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from benchmarks.harness import check, common, stats, traffic
from benchmarks.harness.common import Outcome, Reading, annotate, log

DRAIN_CAP_S = 10.0


@dataclasses.dataclass
class Rec:
    """What the benchmark saw of one request, on its own clock."""

    rid: int
    prompt_len: int
    max_new: int
    due: float
    seq: int = 0          # position in the run's submission order
    submitted: Optional[float] = None
    first: Optional[float] = None
    last: Optional[float] = None
    tokens: int = 0
    done_at: Optional[float] = None
    state: Optional[str] = None
    queue_ms: Optional[float] = None


def build(cell, args, devices, ledger):
    import jax
    import jax.numpy as jnp

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.parallel.layers import init_sharded_params
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    cfg = cell.config
    s = cfg["serving"]
    nxd.initialize_model_parallel(
        tensor_parallel_size=cfg["layout"]["tensor_parallel_size"],
        devices=devices)
    module_cls, model_cfg = common.program_config(
        {**cfg["program"], "kwargs": {**cfg["program"]["kwargs"],
                                      "max_seq_len": s["max_total_len"]}})
    module = module_cls(model_cfg)
    params, _ = init_sharded_params(
        module, jax.random.PRNGKey(args.seed),
        jnp.zeros((1, s["page_size"]), jnp.int32))
    model = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=s["slots"], context_len=s["context_len"],
                        max_total_len=s["max_total_len"],
                        kv_cache_dtype=getattr(jnp, s["kv_cache_dtype"])),
        compile_ledger=ledger)
    return params, model


def reference_check(cell, params, model, seed) -> List[str]:
    """Prefill-then-decode through the page cache, by the paged programs the
    engine dispatches (same shapes, so this also warms them), against the
    plain float32 reference's full forward of the same tokens: logits of
    the last prompt position and of each decoded position."""
    import jax.numpy as jnp

    cfg = cell.config
    s, probe = cfg["serving"], cfg["probe"]
    page, C, T, B = (s["page_size"], s["context_len"], s["max_total_len"],
                     s["slots"])
    W, PP, nd = s["prefill_chunk_tokens"], T // page, probe["decodes"]
    lens = probe["prompt_lens"]
    rs = np.random.RandomState(seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    # left-padded rows, as the engine lays them out: the prompt sits in
    # cache positions [C - L, C), decoded tokens from C on
    tables = np.zeros((B, PP), np.int32)
    valid = np.zeros((B, T), np.int32)
    nxt = 1
    for b, L in enumerate(lens):
        for lp in range((C - L) // page, (C + nd - 1) // page + 1):
            tables[b, lp] = nxt
            nxt += 1
        valid[b, C - L:C] = 1
    caches = model.make_page_pool(s["num_pages"], page).caches
    got: Dict[tuple, np.ndarray] = {}
    for b, L in enumerate(lens):
        row = np.zeros((C,), np.int32)
        row[C - L:] = seqs[b][:L]
        off = (C - L) // page * page
        logits = None
        while off < C:
            width = min(W, C - off)
            ids = np.zeros((1, W), np.int32)
            ids[0, :width] = row[off:off + width]
            logits, caches = model.prefill_chunk_pages(
                jnp.asarray(ids), off, tables[b][None, :], caches,
                valid[b][None, :], last_row=width - 1)
            off += width
        got[(b, 0)] = np.asarray(logits[0], np.float32)
    dvalid = jnp.asarray(valid)
    for j in range(nd):
        tok = np.zeros((B, 1), np.int32)
        offs = np.full((B,), T, np.int32)  # parked
        for b, L in enumerate(lens):
            tok[b, 0] = seqs[b][L + j]
            offs[b] = C + j
        logits, caches, dvalid = model.decode_pages(
            jnp.asarray(tok), offs, tables, caches, dvalid)
        lg = np.asarray(logits, np.float32)
        for b in range(len(lens)):
            got[(b, j + 1)] = lg[b]
    del caches, dvalid, logits
    gc.collect()

    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = cell.reference_weights(params)
    tol = cfg["tolerances"]["logits_rel"]
    why_not = []
    for b, L in enumerate(lens):
        ref = np.asarray(ref_mod.logits_at(
            ref_w, shape, seqs[b], list(range(L - 1, L + nd))), np.float32)
        errs = [check.rel_err(got[(b, j)], ref[j]) for j in range(nd + 1)]
        log(f"[check] prompt {L}: cache path vs float32 reference, rel err "
            f"prefill {errs[0]:.4f}, decodes "
            + " ".join(f"{e:.4f}" for e in errs[1:])
            + f" (tol {tol}, ref max {np.max(np.abs(ref)):.2f})")
        if not max(errs) <= tol:
            why_not.append(f"logits of prompt {L} differ from the reference "
                           f"by {max(errs):.4f}")
    return why_not


class Loop:
    """The load generator and the server's driver, in one thread."""

    def __init__(self, cell, engine, devices, clock, compiles, ledger,
                 profiler):
        self.cell, self.engine, self.devices = cell, engine, devices
        self.clock, self.compiles, self.ledger = clock, compiles, ledger
        self.profiler = profiler
        self.mix = cell.traffic
        self.slots = cell.config["serving"]["slots"]
        self.vocab = cell.config["vocab_size"]
        self.rid_base = 0                      # request ids never repeat
        self.warm_mark = compiles.mark()       # the warm-up is over

    def _request(self, r: traffic.ServeRequest, rec: Rec):
        from neuronx_distributed_tpu.serving import Request

        win = self.win

        def on_token(req, tok):
            now = self.clock()
            inside = win[0] <= now < win[1]
            if rec.first is None:
                rec.first = now
                if inside:
                    self.prompt_tokens_in_window += rec.prompt_len
                    self.first_token_events.append(
                        (now, rec.prompt_len, rec.seq))
            rec.last = now
            rec.tokens += 1
            if inside:
                self.output_tokens_in_window += 1

        return Request(request_id=r.rid, prompt_ids=r.prompt.tolist(),
                       max_new_tokens=r.max_new, stream_cb=on_token)

    @staticmethod
    def _periodic(mix: dict, reqs) -> dict:
        """Block length and tokens of one block where the mix is periodic:
        a closed loop whose two lengths are stratified in blocks of one
        size (``traffic.draw_lengths`` repeats one order)."""
        block = int(mix["prompt_len"].get("stratify", 0))
        if (mix["loop"] != "closed" or not block
                or block != int(mix["output_len"].get("stratify", 0))):
            return {"block": 0, "block_tokens": 0}
        return {"block": block,
                "block_tokens": sum(len(r.prompt) + r.max_new
                                    for r in reqs[:block])}

    def measure(self, seed: int, seconds: float, mix: Optional[dict] = None):
        """One lead-in + window + drain under ``mix``; returns the samples."""
        mix = mix or self.mix
        engine, clock = self.engine, self.clock
        lead = float(mix["lead_in_s"])
        open_loop = mix["loop"] == "open"
        horizon = lead + seconds + DRAIN_CAP_S
        reqs = traffic.serve_requests(
            mix, self.vocab, seed, horizon,
            n_closed=int(mix.get("closed_requests", 0)))
        # request ids never repeat between the measures of a sweep
        reqs = [dataclasses.replace(r, rid=self.rid_base + r.rid)
                for r in reqs]
        self.rid_base += len(reqs)
        recs: Dict[int, Rec] = {}
        outstanding = set()
        self.prompt_tokens_in_window = self.output_tokens_in_window = 0
        self.first_token_events = []
        samples = {k: [] for k in ("engine_step_ms", "slots_active",
                                   "queue_depth", "pages_in_use_share",
                                   "lateness_ms")}
        reg = engine.registry
        target = self.slots + int(mix.get("backlog", 0))
        profiler = self.profiler
        trace_at, trace_s = (float(mix.get("trace_at_s", 2.0)),
                             float(mix.get("trace_seconds", 3.0)))

        t0 = clock()
        self.win = win = (t0 + lead, t0 + lead + seconds)
        mark = ledger_mark = None
        nxt, steps_in_window = 0, 0
        while True:
            now = clock()
            if mark is None and now >= win[0]:
                mark = self.compiles.mark()
                ledger_mark = self.ledger.mark()
                self.setup_s = clock.since_start()
                log(f"[window] open at {self.setup_s:.1f} s, "
                    f"{len(outstanding)} requests in the engine, "
                    f"{self.compiles.since(self.warm_mark)} compile "
                    "request(s) since the warm-up")
            with annotate("submit"):
                if not open_loop and nxt >= len(reqs):
                    raise RuntimeError(
                        f"the closed loop used all {len(reqs)} requests of "
                        "the mix's closed_requests before the window closed")
                while nxt < len(reqs) and now < win[1] and (
                        (t0 + reqs[nxt].due_s <= now) if open_loop
                        else len(outstanding) < target):
                    r = reqs[nxt]
                    rec = recs[r.rid] = Rec(
                        r.rid, len(r.prompt), r.max_new,
                        due=(t0 + r.due_s) if open_loop else now, seq=nxt)
                    engine.submit(self._request(r, rec))
                    rec.submitted = clock()
                    outstanding.add(r.rid)
                    if win[0] <= rec.due < win[1]:
                        samples["lateness_ms"].append(
                            (rec.submitted - rec.due) * 1e3)
                    nxt += 1
            if now >= win[1]:
                # open loop: every request due in the window gets its first
                # token (below the knee that is a moment); a closed loop's
                # queue is as long as its backlog by construction
                waiting = [r for r in recs.values()
                           if win[0] <= r.due < win[1] and r.first is None
                           and r.state is None] if open_loop else []
                if not waiting or now >= win[1] + DRAIN_CAP_S:
                    break
            if profiler is not None and not profiler.done:
                if not profiler.active and now >= win[0] + trace_at:
                    profiler.start()
                elif profiler.active and now >= win[0] + trace_at + trace_s:
                    profiler.stop()
            if engine.has_work:
                t_s = clock()
                with annotate("engine_step"):
                    outs = engine.step()
                t_e = clock()
                for o in outs:
                    rec = recs[o.request_id]
                    rec.done_at, rec.state = t_e, o.state
                    rec.queue_ms = o.queue_ms
                    outstanding.discard(o.request_id)
                if win[0] <= t_s < win[1]:
                    steps_in_window += 1
                    samples["engine_step_ms"].append((t_e - t_s) * 1e3)
                    samples["slots_active"].append(
                        reg.gauge("serving/slots_active").value)
                    samples["queue_depth"].append(
                        reg.gauge("serving/queue_depth").value)
                    samples["pages_in_use_share"].append(
                        100.0 * reg.gauge("kvcache/pages_in_use").value
                        / max(reg.gauge("kvcache/pages_total").value, 1.0))
            else:
                due_next = (t0 + reqs[nxt].due_s if open_loop
                            and nxt < len(reqs) else win[1])
                with annotate("idle_wait"):
                    time.sleep(max(min(due_next, win[1]) - clock(), 0.0)
                               + 1e-4)
        if profiler is not None and profiler.active:
            profiler.stop()
        self.mem = common.memory(self.devices)

        # everything still outstanding is cancelled; the pool must come
        # back clean (no page leaked by a cancel mid-prefill or mid-decode)
        for rid in list(outstanding):
            engine.cancel(rid)
        for _ in range(64):
            if not engine.has_work:
                break
            for o in engine.step():
                recs[o.request_id].state = recs[o.request_id].state or \
                    "cancelled_after_window"
                outstanding.discard(o.request_id)
        return dict(recs=recs, samples=samples, win=win,
                    open_loop=open_loop,
                    **self._periodic(mix, reqs),
                    mark=mark, ledger_mark=ledger_mark, seconds=seconds,
                    steps_in_window=steps_in_window,
                    first_token_events=self.first_token_events,
                    prompt_tokens=self.prompt_tokens_in_window,
                    output_tokens=self.output_tokens_in_window,
                    undrained=len(outstanding))


def summarize(m: dict) -> dict:
    """Samples of one ``measure`` -> the numbers (end-to-end and notes)."""
    recs, win, seconds = m["recs"], m["win"], m["seconds"]
    due = [r for r in recs.values() if win[0] <= r.due < win[1]]
    ttft = [(r.first - r.due) * 1e3 for r in due if r.first is not None]
    fin = [r for r in recs.values() if r.state == "finished"
           and r.done_at is not None and win[0] <= r.done_at < win[1]]
    tpot = [(r.last - r.first) * 1e3 / (r.tokens - 1) for r in fin
            if r.tokens >= 2]
    no_first = ([r.rid for r in due if r.first is None]
                if m.get("open_loop", True) else [])
    short = [r.rid for r in fin if r.tokens != r.max_new]
    bad_state = [r.rid for r in recs.values()
                 if r.state not in (None, "finished", "cancelled",
                                    "cancelled_after_window")]
    touched = {r.rid for r in fin} | {
        r.rid for r in recs.values()
        if r.first is not None and win[0] <= r.first < win[1]}
    return dict(
        due=len(due), finished=len(fin), touched=len(touched),
        ttft_ms=ttft, tpot_ms=tpot,
        ttft_p50_ms=stats.median(ttft), tpot_p50_ms=stats.median(tpot),
        queue_wait_ms=[r.queue_ms for r in fin if r.queue_ms is not None],
        served_tokens_per_s=served_rate(m),
        served_tokens_per_s_whole_window=(
            m["prompt_tokens"] + m["output_tokens"]) / seconds,
        output_tokens_per_s=m["output_tokens"] / seconds,
        requests_per_s=len(fin) / seconds,
        no_first=no_first, short=short, bad_state=bad_state)


def served_rate(m: dict) -> Optional[float]:
    """Tokens served per second by a closed loop on a periodic mix: prompt
    tokens prefilled plus output tokens streamed, over whole cycles.

    The benchmark sees a prefill only when it ends (the first token), so
    prompt tokens arrive a few thousand at a time, and a rate over the
    wall-clock window swings with the prefills half done at its edges
    (10-20% between seeds on the v5e, whether the edges are the window's or
    first-token events; PR 22).  A closed loop on a periodic mix serves
    block after block of the same requests in the same order and settles
    into a cycle: from the first token of the request at position p of one
    block to that of the request at position p of a block k later, exactly
    k blocks of work were served, whatever was half done at either end.
    The rate is k x (a block's prompt + output tokens) over that time,
    pooled over the positions that occur twice in the window.  None where
    the mix is not periodic or no position occurred twice: the metric has
    this one definition (``served_tokens_per_s_whole_window`` and
    ``output_tokens_per_s`` are other numbers, under their own names)."""
    block, work = int(m.get("block", 0)), m.get("block_tokens", 0)
    if not block or not work:
        return None
    by_pos: Dict[int, list] = {}
    for t, _, seq in sorted(m.get("first_token_events", [])):
        by_pos.setdefault(seq % block, []).append((t, seq))
    blocks = seconds = 0.0
    for events in by_pos.values():
        (t_a, seq_a), (t_b, seq_b) = events[0], events[-1]
        if seq_b > seq_a:
            blocks += (seq_b - seq_a) / block
            seconds += t_b - t_a
    return blocks * work / seconds if seconds > 0 else None


def end_to_end_value(name: str, summary: dict) -> Optional[float]:
    """An end-to-end metric is a number of ``summarize`` under its name."""
    v = summary.get(name)
    return float(v) if isinstance(v, (int, float)) else None


def run(cell, args, devices, peak, clock) -> Outcome:
    from neuronx_distributed_tpu.obs.compile_ledger import CompileLedger
    from neuronx_distributed_tpu.serving import Request, ServingEngine

    cfg = cell.config
    s = cfg["serving"]
    compiles = common.CompileCounter()
    ledger = CompileLedger()
    params, model = build(cell, args, devices, ledger)
    log(f"[setup] weights on the device at {clock.since_start():.1f} s")
    why_not = reference_check(cell, params, model, args.seed)
    log(f"[setup] reference check done at {clock.since_start():.1f} s")

    engine = ServingEngine(
        model, page_size=s["page_size"], num_pages=s["num_pages"],
        prefill_chunk_tokens=s["prefill_chunk_tokens"],
        compile_ledger=ledger)
    if not cell.rehearse and not engine._paged_kernel:
        raise RuntimeError("paged_kernel='auto' took the gather path on a TPU")
    # warm-up: one request whose prompt takes a whole and a ragged chunk,
    # decoded to its end — every program the engine runs — and then a
    # second, short one: the validity insert of a request admitted AFTER a
    # decode sees its array placed otherwise and compiles once more (seen
    # by the benchmark's compile counter, not by the program's ledger)
    W = s["prefill_chunk_tokens"]
    rs = np.random.RandomState(args.seed + 11)
    for i, (plen, new) in enumerate((
            (min(W + W // 3, s["context_len"]), int(s["warmup_new_tokens"])),
            (max(W // 5, 2), 2))):
        engine.submit(Request(
            request_id=-1 - i, max_new_tokens=new,
            prompt_ids=rs.randint(1, cfg["vocab_size"], size=plen).tolist()))
        done = engine.run_until_complete(max_steps=10_000)
        if [o.state for o in done] != ["finished"]:
            raise RuntimeError(f"warm-up request did not finish: {done}")
    engine.declare_warmup_done()
    log(f"[setup] warm-up done at {clock.since_start():.1f} s; compile "
        f"requests {compiles.requests} ({compiles.hits} from the cache)")

    profiler = common.ProfilerWindow(cell.name) if args.trace else None
    loop = Loop(cell, engine, devices, clock, compiles, ledger, profiler)

    if args.sweep:
        # the knee sweep: one set-up, one lead-in + window per rate
        for rate in args.sweep:
            mix = {**cell.traffic, "arrivals": {**cell.traffic["arrivals"],
                                                "rate_per_s": rate}}
            m = loop.measure(args.seed, args.seconds, mix)
            sm = summarize(m)
            q = m["samples"]["queue_depth"]
            half = len(q) // 2
            log("[sweep] " + " ".join(f"{k}={v}" for k, v in dict(
                rate=rate, due=sm["due"], finished=sm["finished"],
                ttft_p50=stats.median(sm["ttft_ms"]),
                ttft_p90=stats.percentile(sm["ttft_ms"], 90),
                tpot_p50=stats.median(sm["tpot_ms"]),
                out_tok_s=round(sm["output_tokens_per_s"], 1),
                queue_first_half=stats.mean(q[:half]),
                queue_second_half=stats.mean(q[half:]),
                queue_end=q[-1] if q else None,
                slots_mean=stats.mean(m["samples"]["slots_active"]),
                step_ms_p50=stats.median(m["samples"]["engine_step_ms"]),
                no_first=len(sm["no_first"])).items()))
            gc.collect()
        raise SystemExit("sweep done (no result line)")

    m = loop.measure(args.seed, args.seconds)
    sm = summarize(m)
    samples = m["samples"]
    in_window = max(compiles.since(m["mark"]),
                    ledger.compiles_since(m["ledger_mark"]))
    kv = getattr(engine, "_kv", None)
    try:
        if kv is not None:
            kv.assert_invariants()
    except AssertionError as e:
        why_not.append(f"page-pool invariants: {e}")
    snap = engine.registry.snapshot()
    if m["undrained"]:
        why_not.append(f"{m['undrained']} request(s) not reclaimed by cancel")
    if sm["no_first"]:
        why_not.append(f"{len(sm['no_first'])} request(s) due in the window "
                       f"had no first token {DRAIN_CAP_S:.0f} s after it")
    if sm["short"]:
        why_not.append(f"{len(sm['short'])} finished request(s) with the "
                       "wrong token count")
    if sm["bad_state"]:
        why_not.append(f"{len(sm['bad_state'])} request(s) failed or timed out")
    if in_window:
        why_not.append(f"{in_window} compile(s) inside the window")
    if not cell.rehearse and snap.get("kvcache/gather_bytes_total", 0):
        why_not.append("the kernel engine gathered pages")
    if not m["first_token_events"]:
        why_not.append("no request got a first token in the window")
    engine.close()

    log(f"[window] {sm['due']} requests due, {sm['finished']} finished, "
        f"{m['steps_in_window']} engine steps in {m['seconds']} s; prompt "
        f"tokens {m['prompt_tokens']}, output tokens {m['output_tokens']}; "
        f"ttft ms p50 {stats.median(sm['ttft_ms'])} p90 "
        f"{stats.percentile(sm['ttft_ms'], 90)} (n={len(sm['ttft_ms'])}); "
        f"tpot ms p50 {stats.median(sm['tpot_ms'])} (n={len(sm['tpot_ms'])}); "
        f"lateness ms p50 {stats.median(samples['lateness_ms'])} max "
        f"{max(samples['lateness_ms'], default=None)}; queue depth mean "
        f"{stats.mean(samples['queue_depth'])} end "
        f"{samples['queue_depth'][-1] if samples['queue_depth'] else None}; "
        f"prefix hits {snap.get('kvcache/prefix_hits_total', 0)}; compile "
        f"requests {compiles.requests} ({compiles.hits} from the cache)")

    failed = len(set(sm["no_first"]) | set(sm["short"]) | set(sm["bad_state"]))
    if m["block"]:
        log(f"[window] served tokens/s over whole cycles "
            f"{sm['served_tokens_per_s']} (blocks of {m['block']} "
            f"requests, {m['block_tokens']} tokens), credited over the whole "
            f"window {sm['served_tokens_per_s_whole_window']:.2f}; "
            f"first-token events {len(m['first_token_events'])}")
        # seconds from the window's opening and position in the submission
        # order: how near an event sits to an edge, and the cycle, by eye
        ev = sorted(m["first_token_events"])
        log("[window] first tokens at (s:position) " + " ".join(
            f"{t - m['win'][0]:.3f}:{seq}" for t, _, seq in ev[:48])
            + (" ..." if len(ev) > 48 else ""))
    e2e = {}
    for metric in cell.end_to_end:
        if metric["name"] == "setup_s":
            continue
        v = end_to_end_value(metric["name"], sm)
        if v is None:
            why_not.append(f"the window gave no {metric['name']}")
        else:
            e2e[metric["name"]] = v
    samples = dict(samples, queue_wait_ms=sm["queue_wait_ms"],
                   ttft_ms=sm["ttft_ms"], tpot_ms=sm["tpot_ms"])
    reading = Reading(
        cell=cell, chips=cell.chips, peak=peak, window_s=m["seconds"],
        samples=samples,
        # the engine registry's scalars under the program's own names, then
        # the benchmark's own counts
        counters={**{k: v for k, v in snap.items()
                     if isinstance(v, (int, float))},
                  "compiles_in_window": in_window,
                  "bytes_in_use": loop.mem.get("bytes_in_use", 0)},
        end_to_end=e2e,
        trace=profiler.reduce(cell.chips) if profiler is not None else None,
        notes={"due": sm["due"], "finished": sm["finished"]})
    attempted = sm["due"] if m["open_loop"] else sm["touched"]
    return Outcome(correct=not why_not, attempted=max(attempted, 1),
                   failed=failed, setup_s=loop.setup_s, reading=reading,
                   memory=loop.mem, why_not=why_not)
