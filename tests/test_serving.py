"""Continuous-batching serving subsystem tests (fast tier: CPU mesh).

Two layers of assurance, mirroring the subsystem's split:

- scheduler/request PROPERTY tests — pure host-side, no compilation: no
  slot leak, FIFO admission order, capacity never exceeded, cancellation
  frees the slot, deadline sweep, lifecycle legality;
- an e2e CPU-tiny-Llama run asserting the acceptance bar: greedy
  continuous-batching outputs under staggered arrivals are token-identical
  to a solo ``ParallelInferenceModel.generate`` of each prompt (per-slot
  offsets and slot-insert prefill introduce zero numerical drift), plus
  per-request rng-stream reproducibility, serving_stats schema validation,
  and the bounded compiled-fn caches;
- hardening (resilience PR): non-finite-logit slot quarantine (the one
  poisoned request FAILs, its co-batch stays token-identical to solo
  generate, the slot is reusable), bounded-admission backpressure, the
  engine step watchdog, and the crash flight dump of ``replay_trace``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params, solo_generate, step_until_decoding
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.resilience import clear_plan, install_plan
from neuronx_distributed_tpu.serving import (
    AdmissionError,
    BackpressureError,
    Request,
    RequestState,
    SamplingParams,
    ServingEngine,
    SlotScheduler,
    replay_trace,
)
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel
from neuronx_distributed_tpu.trace.engine import _CompiledLRU


def _req(rid, plen=4, max_new=4, **kw):
    return Request(request_id=rid, prompt_ids=list(range(1, plen + 1)),
                   max_new_tokens=max_new, **kw)


def _finish(sched, req):
    req.transition(RequestState.DECODE)
    req.transition(RequestState.FINISHED)
    req.finish_reason = "length"
    sched.release(req)


# -- scheduler properties ---------------------------------------------------

def test_fcfs_order_and_capacity():
    sched = SlotScheduler(num_slots=2, context_len=8, max_total_len=16)
    for i in range(5):
        sched.submit(_req(i), now=float(i))
    grants = sched.admit(now=10.0)
    assert [r.request_id for _, r in grants] == [0, 1]  # FIFO heads
    assert sched.active_count == 2 and sched.free_count == 0
    assert sched.admit(now=11.0) == []  # capacity never exceeded
    sched.assert_invariants()

    _finish(sched, grants[0][1])
    grants2 = sched.admit(now=12.0)
    assert [r.request_id for _, r in grants2] == [2]  # next in FIFO order
    sched.assert_invariants()


def test_no_slot_leak_random_lifecycle():
    """Randomized churn: submit/admit/finish/cancel for many rounds; the
    slot table must never leak or double-book."""
    rs = np.random.RandomState(0)
    sched = SlotScheduler(num_slots=3, context_len=8, max_total_len=16)
    rid = 0
    live = []
    for step in range(200):
        now = float(step)
        if rs.rand() < 0.5:
            sched.submit(_req(rid), now=now)
            rid += 1
        if rs.rand() < 0.3 and live:
            victim = live[rs.randint(len(live))]
            sched.cancel(victim.request_id)
        sched.sweep(now)
        for _, r in sched.admit(now):
            live.append(r)
        if rs.rand() < 0.4 and live:
            req = live.pop(rs.randint(len(live)))
            if not req.done:
                if req.state is RequestState.PREFILL:
                    req.transition(RequestState.DECODE)
                req.transition(RequestState.FINISHED)
                req.finish_reason = "length"
                sched.release(req)
        live = [r for r in live if not r.done]
        sched.assert_invariants()
        assert sched.active_count <= 3
        # no reference leak: the scheduler tracks only LIVE requests (a
        # long-lived server must not accumulate one Request per request served)
        assert len(sched._by_id) == sched.active_count + sched.queue_depth
    assert rid > 50  # the run actually exercised churn


def test_cancellation_frees_slot_and_queue():
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16)
    sched.submit(_req(0), now=0.0)
    sched.submit(_req(1), now=0.0)
    [(slot, running)] = sched.admit(now=0.0)
    assert sched.cancel(0) and sched.cancel(1)
    swept = sched.sweep(now=1.0)
    assert {r.request_id for r in swept} == {0, 1}
    assert running.state is RequestState.CANCELLED
    assert sched.free_count == 1 and sched.queue_depth == 0
    sched.assert_invariants()
    assert not sched.cancel(0)  # already terminal


def test_deadline_sweep_times_out_queued_and_running():
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16)
    sched.submit(_req(0, deadline_s=5.0), now=0.0)
    sched.submit(_req(1, deadline_s=2.0), now=0.0)
    sched.admit(now=0.0)
    swept = sched.sweep(now=3.0)  # 1 (queued) exceeds, 0 (running) does not
    assert [r.request_id for r in swept] == [1]
    assert swept[0].state is RequestState.TIMED_OUT
    swept = sched.sweep(now=6.0)
    assert [r.request_id for r in swept] == [0]
    assert sched.free_count == 1
    sched.assert_invariants()


def test_admission_gates():
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16)
    with pytest.raises(AdmissionError, match="prompt_len"):
        sched.submit(_req(0, plen=9))
    with pytest.raises(AdmissionError, match="max_total_len"):
        sched.submit(_req(1, plen=4, max_new=13))
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(_req(2))
        sched.submit(_req(2))


def test_request_lifecycle_legality():
    req = _req(0)
    with pytest.raises(RuntimeError, match="illegal transition"):
        req.transition(RequestState.FINISHED)  # QUEUED cannot finish directly
    req.transition(RequestState.PREFILL)
    req.transition(RequestState.DECODE)
    req.transition(RequestState.FINISHED)
    with pytest.raises(RuntimeError, match="illegal transition"):
        req.transition(RequestState.CANCELLED)  # terminal states are final
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError, match="empty prompt"):
        Request(request_id=9, prompt_ids=[], max_new_tokens=1)


def test_compiled_lru_bounds_and_counts_evictions():
    class Owner:
        metrics_registry = None

    from neuronx_distributed_tpu.obs import MetricRegistry

    owner = Owner()
    owner.metrics_registry = MetricRegistry()
    lru = _CompiledLRU("test", capacity=2, owner=owner)
    lru.put(1, "a"), lru.put(2, "b")
    assert lru.get(1) == "a"  # 1 is now most-recent
    lru.put(3, "c")  # evicts 2
    assert lru.get(2) is None and lru.get(1) == "a" and lru.get(3) == "c"
    assert len(lru) == 2
    assert owner.metrics_registry.snapshot()[
        "trace/compiled_cache_evictions_total"] == 1.0


# -- e2e: CPU tiny Llama ----------------------------------------------------

@pytest.fixture
def served_pool(devices8):
    """B=3 slot-pool model + B=1 solo reference over the SAME params."""
    initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
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


def test_continuous_greedy_matchessolo_generate(served_pool, tmp_path):
    """Acceptance bar: staggered arrivals, slot reuse (5 requests over 3
    slots), every request's greedy tokens identical to its solo generate."""
    cfg, pool, solo = served_pool
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(5)]
    stats_path = str(tmp_path / "serving_stats.jsonl")
    engine = ServingEngine(pool, page_size=4, stats_path=stats_path)

    streamed = {}
    outs = {}
    # staggered: 3 requests up front, 2 more only after the first step —
    # the late ones join mid-decode via slot-insert prefill
    for i in range(3):
        engine.submit(Request(
            request_id=i, prompt_ids=prompts[i], max_new_tokens=4 + i,
            stream_cb=lambda r, t: streamed.setdefault(r.request_id, []).append(t)))
    for out in engine.step():
        outs[out.request_id] = out
    for i in range(3, 5):
        engine.submit(Request(
            request_id=i, prompt_ids=prompts[i], max_new_tokens=4 + i,
            stream_cb=lambda r, t: streamed.setdefault(r.request_id, []).append(t)))
    for out in engine.run_until_complete(max_steps=200):
        outs[out.request_id] = out
    engine.close()

    assert set(outs) == set(range(5))
    for i, p in enumerate(prompts):
        want = solo_generate(solo, p, 4 + i)
        got = list(outs[i].token_ids)
        assert got == want, f"request {i} diverged: {got} vs solo {want}"
        assert streamed[i] == want  # streaming callback saw every token
        assert outs[i].finish_reason == "length"

    # serving_stats.jsonl validates against the checked-in schema
    from neuronx_distributed_tpu.obs.schemas import validate_jsonl

    assert validate_jsonl("serving_stats", stats_path) == 5

    # telemetry: counters/gauges/histograms all present with sane values
    snap = engine.registry.snapshot()
    assert snap["serving/admitted_total"] == 5.0
    assert snap["serving/finished_total"] == 5.0
    assert snap["serving/tokens_total"] == float(sum(4 + i for i in range(5)))
    assert snap["serving/ttft_ms"]["count"] == 5
    assert snap["serving/intertoken_ms"]["count"] > 0
    assert snap["serving/queue_depth"] == 0.0
    assert snap["serving/slots_active"] == 0.0


def test_continuous_sampled_reproducible_across_cobatching(served_pool):
    """Per-request rng streams: a sampled request's tokens must not depend
    on which requests it is co-batched with, and must equal the
    ``generate(request_ids=...)`` stream for the same (rng, id)."""
    cfg, pool, solo = served_pool
    rs = np.random.RandomState(11)
    prompts = {rid: rs.randint(1, cfg.vocab_size, size=6).tolist()
               for rid in (0, 1, 2)}
    rng = jax.random.PRNGKey(42)
    sampling = SamplingParams(temperature=0.9, top_k=0, top_p=1.0)

    def run(rids):
        engine = ServingEngine(pool, page_size=4, rng=rng)
        for rid in rids:
            engine.submit(Request(request_id=rid, prompt_ids=prompts[rid],
                                  max_new_tokens=5, sampling=sampling))
        return {o.request_id: list(o.token_ids)
                for o in engine.run_until_complete(max_steps=200)}

    together = run([0, 1, 2])
    alone = run([1])
    assert together[1] == alone[1], (
        "request 1's sampled tokens changed with its co-batch")

    # and the engine's stream equals generate(request_ids=...)'s
    want = solo_generate(
        solo, prompts[1], 5, temperature=0.9, rng=rng, request_ids=[1])
    assert together[1] == want


def test_engine_cancellation_and_timeout(served_pool):
    cfg, pool, _ = served_pool
    t = [0.0]
    engine = ServingEngine(pool, page_size=4, clock=lambda: t[0])
    # 3 slots: r0 decodes, r1 will be cancelled mid-decode, r2 times out
    # in the queue (deadline passes before any slot frees... force by
    # filling slots first)
    for rid in range(3):
        engine.submit(Request(request_id=rid, prompt_ids=[1, 2, 3],
                              max_new_tokens=8))
    outs = {o.request_id: o for o in engine.step()}
    assert engine.scheduler.active_count == 3
    # submitted only once the pool is full: EDF would otherwise admit the
    # deadline-carrying request AHEAD of the deadline-less ones (the SLO
    # scheduler's intended reordering) instead of leaving it queued
    engine.submit(Request(request_id=3, prompt_ids=[1, 2], max_new_tokens=8,
                          deadline_s=0.5))  # queued behind the full pool
    engine.cancel(1)
    t[0] = 1.0  # past request 3's deadline
    for o in engine.step():
        outs[o.request_id] = o
    assert outs[1].state == "cancelled"
    assert outs[3].state == "timed_out"
    assert outs[3].ttft_ms is None  # never produced a token
    snap = engine.registry.snapshot()
    assert snap["serving/cancelled_total"] == 1.0
    assert snap["serving/timed_out_total"] == 1.0
    # the freed slots are reusable: a new request admits and finishes
    engine.submit(Request(request_id=4, prompt_ids=[5, 6], max_new_tokens=2))
    done = engine.run_until_complete(max_steps=200)
    assert {o.request_id for o in done} >= {0, 2, 4}
    engine.scheduler.assert_invariants()


def test_stop_token_ends_request_early(served_pool):
    """A per-request stop token finishes the request the moment it is
    generated (here: the request's own first greedy token), freeing the
    slot with finish_reason 'stop_token'."""
    cfg, pool, solo = served_pool
    prompt = [3, 1, 4, 1, 5]
    first = solo_generate(solo, prompt, 1)[0]
    engine = ServingEngine(pool, page_size=4)
    engine.submit(Request(request_id=0, prompt_ids=prompt, max_new_tokens=8,
                          stop_token_ids=(first,)))
    [out] = engine.run_until_complete(max_steps=50)
    assert out.finish_reason == "stop_token"
    assert list(out.token_ids) == [first]
    # engine-level eos_token_id behaves the same without per-request config
    engine2 = ServingEngine(pool, page_size=4, eos_token_id=first)
    engine2.submit(Request(request_id=1, prompt_ids=prompt, max_new_tokens=8))
    [out2] = engine2.run_until_complete(max_steps=50)
    assert out2.finish_reason == "stop_token"
    assert list(out2.token_ids) == [first]


# -- hardening (resilience PR) ----------------------------------------------

def test_failed_state_lifecycle():
    """FAILED is terminal and reachable only from the compute states."""
    req = _req(0)
    with pytest.raises(RuntimeError, match="illegal transition"):
        req.transition(RequestState.FAILED)  # QUEUED ran nothing to fail
    req.transition(RequestState.PREFILL)
    req.transition(RequestState.FAILED)
    assert req.done
    with pytest.raises(RuntimeError, match="illegal transition"):
        req.transition(RequestState.DECODE)


def test_scheduler_backpressure_bounds_excess_backlog():
    """max_queue bounds the backlog BEYOND free slots: a burst of
    free_count + max_queue always fits, one more is rejected (transient),
    and draining re-opens admission."""
    sched = SlotScheduler(num_slots=2, context_len=8, max_total_len=16,
                          max_queue=1)
    for i in range(3):  # 2 free slots + 1 excess
        sched.submit(_req(i), now=0.0)
    with pytest.raises(BackpressureError, match="backlog full"):
        sched.submit(_req(3), now=0.0)
    # a never-fits request stays a PERMANENT AdmissionError even under load
    with pytest.raises(AdmissionError, match="prompt_len"):
        sched.submit(_req(99, plen=9), now=0.0)
    grants = sched.admit(now=0.0)  # 2 admitted, queue drops to 1 == max
    with pytest.raises(BackpressureError):
        sched.submit(_req(3), now=0.0)
    _finish(sched, grants[0][1])  # a freed slot re-opens admission
    sched.submit(_req(3), now=1.0)
    sched.assert_invariants()


def test_engine_backpressure_counts_rejections(served_pool):
    cfg, pool, _ = served_pool
    engine = ServingEngine(pool, page_size=4, max_queue=1)
    for rid in range(4):  # B=3 slots + 1 backlog
        engine.submit(Request(request_id=rid, prompt_ids=[1, 2],
                              max_new_tokens=2))
    with pytest.raises(BackpressureError):
        engine.submit(Request(request_id=9, prompt_ids=[1], max_new_tokens=2))
    assert engine.registry.snapshot()["serving/rejected_total"] == 1.0
    outs = engine.run_until_complete(max_steps=200)
    assert len(outs) == 4  # the admitted ones all finish


def test_non_finite_logit_quarantine_decode(served_pool):
    """A slot whose decode logits go non-finite fails THAT request alone:
    terminal state ``failed``, co-batched requests token-identical to their
    solo generates, slot freed and reusable."""
    cfg, pool, solo = served_pool
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, cfg.vocab_size, size=5).tolist()
               for _ in range(3)]
    engine = ServingEngine(pool, page_size=4)
    for rid in range(3):
        engine.submit(Request(request_id=rid, prompt_ids=prompts[rid],
                              max_new_tokens=6))
    step_until_decoding(engine)  # all three; then find request 1's slot
    slot_of = {req.request_id: slot for slot, req in engine.scheduler.active()}
    install_plan({"faults": [{"point": "serving/decode_logits",
                              "action": "nan", "slot": slot_of[1]}]})
    try:
        outs = {o.request_id: o
                for o in engine.run_until_complete(max_steps=200)}
    finally:
        clear_plan()
    assert outs[1].state == "failed"
    assert outs[1].finish_reason == "non_finite_logits"
    for rid in (0, 2):  # co-batch never saw the poison
        assert outs[rid].state == "finished"
        assert list(outs[rid].token_ids) == solo_generate(
            solo, prompts[rid], 6)
    assert engine.registry.snapshot()["serving/failed_total"] == 1.0
    # the quarantined slot is reusable
    engine.submit(Request(request_id=7, prompt_ids=prompts[0],
                          max_new_tokens=3))
    [out7] = engine.run_until_complete(max_steps=100)
    assert out7.state == "finished"
    assert list(out7.token_ids) == solo_generate(solo, prompts[0], 3)
    engine.scheduler.assert_invariants()


def test_non_finite_logit_quarantine_prefill(served_pool, tmp_path):
    """Non-finite PREFILL logits fail the request before it ever decodes
    (no tokens, null ttft) — and the stats record passes the schema."""
    from neuronx_distributed_tpu.obs.schemas import validate_jsonl

    cfg, pool, _ = served_pool
    stats = str(tmp_path / "serving_stats.jsonl")
    engine = ServingEngine(pool, page_size=4, stats_path=stats)
    install_plan({"faults": [{"point": "serving/prefill_logits",
                              "action": "nan", "match": {"request_id": 0}}]})
    try:
        engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3],
                              max_new_tokens=4))
        engine.submit(Request(request_id=1, prompt_ids=[1, 2, 3],
                              max_new_tokens=2))
        outs = {o.request_id: o
                for o in engine.run_until_complete(max_steps=100)}
    finally:
        clear_plan()
    engine.close()
    assert outs[0].state == "failed" and outs[0].token_ids == ()
    assert outs[0].ttft_ms is None
    assert outs[1].state == "finished"
    assert validate_jsonl("serving_stats", stats) == 2


def test_engine_step_watchdog_counts_slow_steps(served_pool):
    """A step slower than step_timeout_s increments the slow-step counter
    (fake clock: each clock() call advances well past the threshold)."""
    cfg, pool, _ = served_pool
    t = [0.0]

    def clock():
        t[0] += 10.0
        return t[0]

    engine = ServingEngine(pool, page_size=4, clock=clock, step_timeout_s=1.0)
    engine.submit(Request(request_id=0, prompt_ids=[1, 2], max_new_tokens=2))
    engine.run_until_complete(max_steps=50)
    snap = engine.registry.snapshot()
    assert snap["serving/slow_steps_total"] >= 1.0
    assert snap["serving/last_step_ms"] > 0.0
    assert snap["serving/step_ms"]["count"] >= 1


def test_replay_trace_dumps_flight_on_crash(served_pool, tmp_path):
    """An unhandled exception out of the drive loop persists the engine
    flight record (the serving twin of fit()'s crash path) and re-raises."""
    from neuronx_distributed_tpu.obs import Observability
    from neuronx_distributed_tpu.obs.schemas import validate_flight_document

    cfg, pool, _ = served_pool
    obs = Observability(str(tmp_path / "obs"))
    engine = ServingEngine(pool, page_size=4, obs=obs)

    reqs = [
        Request(request_id=0, prompt_ids=[1, 2], max_new_tokens=3),
        Request(request_id=1, prompt_ids=[1, 2], max_new_tokens=3,
                stream_cb=lambda r, t: (_ for _ in ()).throw(
                    RuntimeError("poisoned stream_cb"))),
    ]
    with pytest.raises(RuntimeError, match="poisoned stream_cb"):
        replay_trace(engine, [0.0, 0.0], reqs)
    doc = json.load(open(obs.flight_path))
    validate_flight_document(doc)
    assert doc["reason"] == "crash:RuntimeError"
    # engine steps record into the flight ring (queue/slots/step time)
    engine2 = ServingEngine(pool, page_size=4, obs=obs)
    engine2.submit(Request(request_id=5, prompt_ids=[1], max_new_tokens=2))
    engine2.run_until_complete(max_steps=50)
    # (flat step records in the ring; documents at a dump)
    assert any("queue_depth" in r for r in obs.flight.documents())


def test_loop_caches_are_bounded(served_pool):
    """The lazily-jitted per-shape caches are LRU-bounded so a long-lived
    serving process cannot grow them without limit."""
    _, pool, solo = served_pool
    assert isinstance(solo._loop_cache, _CompiledLRU)
    assert solo._loop_cache.capacity > 0
    assert isinstance(pool._serving_cache, _CompiledLRU)
    prompt = jnp.ones((1, 8), jnp.int32)
    for n in (2, 3, 4):
        solo.generate(prompt, n)
    assert len(solo._loop_cache) <= solo._loop_cache.capacity
