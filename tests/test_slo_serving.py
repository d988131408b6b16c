"""Stall-free SLO serving tests (chunked prefill + priority/deadline
scheduling + graceful overload shedding).

Three layers, mirroring the subsystem's split:

- PRIORITY SCHEDULER property tests — pure host-side, no compilation: EDF
  ordering within a class, interactive-over-batch tiering with the
  bounded-wait anti-starvation promotion, preemption victim selection +
  requeue round-trips (original EDF position, absolute submit time),
  deadline-feasibility shedding (the distinct ``SLOInfeasible`` signal),
  and a randomized-churn run over a REAL ``PagedKVManager`` page gate
  asserting invariants after every op and zero page leaks;
- PAGED CHUNKED PREFILL + engine e2e on the CPU tiny Llama — the
  acceptance bar: chunked outputs token-identical to solo ``generate``
  at a one-page chunk and at the context's width (greedy + sampled,
  staggered arrivals, prefix-cache hit and miss), preemption round-trips token-identical, the
  pre-dispatch expiry check (``serving/expired_before_prefill_total``)
  firing before a first chunk AND mid-chunk, and a chaos rung: an
  ``NXD_FAULT_PLAN`` kill mid-chunked-prefill reclaims every page and the
  request requeues cleanly;
- the fleet requeue-deadline satellite: a crashed replica's requeued clone
  carries the ORIGINAL submission instant (absolute deadline through the
  crash) and an already-expired clone fails terminally as TIMED_OUT
  instead of burning a sibling's prefill.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params, solo_generate
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import MetricRegistry
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.resilience import (
    InjectedFault,
    clear_plan,
    install_plan,
)
from neuronx_distributed_tpu.serving import (
    BackpressureError,
    FleetRouter,
    PagedKVManager,
    Replica,
    Request,
    RequestState,
    SamplingParams,
    ServingEngine,
    SLOInfeasible,
    SlotScheduler,
)
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

pytestmark = pytest.mark.slo


def _req(rid, plen=4, max_new=4, **kw):
    return Request(request_id=rid, prompt_ids=list(range(1, plen + 1)),
                   max_new_tokens=max_new, **kw)


def _finish(sched, req):
    if req.state is RequestState.PREFILL:
        req.transition(RequestState.DECODE)
    req.transition(RequestState.FINISHED)
    req.finish_reason = "length"
    sched.release(req)


# -- EDF / priority ordering -------------------------------------------------

def test_edf_orders_within_class_and_fcfs_behind_deadlines():
    sched = SlotScheduler(num_slots=2, context_len=8, max_total_len=16)
    sched.submit(_req(0), now=0.0)                    # no deadline -> inf
    sched.submit(_req(1, deadline_s=9.0), now=1.0)    # abs deadline 10
    sched.submit(_req(2, deadline_s=2.0), now=2.0)    # abs deadline 4: first
    grants = sched.admit(now=3.0)
    assert [r.request_id for _, r in grants] == [2, 1]
    sched.assert_invariants()
    for _, r in grants:
        _finish(sched, r)
    # deadline-less requests order FCFS among themselves, behind deadlines
    sched.submit(_req(3), now=4.0)
    assert [r.request_id for _, r in sched.admit(now=5.0)] == [0, 3]
    sched.assert_invariants()


def test_no_deadline_single_class_reproduces_fcfs():
    """A deadline-less one-class workload is exactly the historical FCFS
    scheduler (EDF keys all inf -> submission order)."""
    sched = SlotScheduler(num_slots=3, context_len=8, max_total_len=16)
    for i in range(5):
        sched.submit(_req(i), now=float(i))
    assert [r.request_id for _, r in sched.admit(now=9.0)] == [0, 1, 2]


def test_interactive_class_granted_before_batch():
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16)
    sched.submit(_req(0, priority="batch", deadline_s=1.0), now=0.0)
    sched.submit(_req(1, priority="interactive"), now=0.5)
    # the interactive head wins even against an urgent batch deadline
    [(_, granted)] = sched.admit(now=0.6)
    assert granted.request_id == 1
    sched.assert_invariants()


def test_bounded_wait_promotes_batch_head():
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16,
                          max_batch_wait_s=10.0)
    sched.submit(_req(0, priority="batch"), now=0.0)
    sched.submit(_req(1, priority="interactive"), now=11.0)
    # the batch head has waited past the bound: it is promoted AHEAD of
    # the interactive queue (anti-starvation)
    [(_, granted)] = sched.admit(now=11.0)
    assert granted.request_id == 0
    sched.assert_invariants()


def test_bounded_wait_promotes_oldest_not_edf_head():
    """Anti-starvation is AGE-keyed: a deadline-less batch request (EDF key
    inf — always behind every deadline-carrying batch arrival) must still
    be promoted once ITS wait exceeds the bound, even while a fresher
    tight-deadline request holds the batch EDF head."""
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16,
                          max_batch_wait_s=5.0)
    sched.submit(_req(100, priority="batch"), now=0.0)  # deadline-less
    sched.submit(_req(1, priority="batch", deadline_s=1.0), now=6.0)  # head
    sched.submit(_req(2, priority="interactive"), now=6.0)
    [(_, granted)] = sched.admit(now=6.0)
    assert granted.request_id == 100, (
        "the starving deadline-less batch request was not promoted")
    sched.assert_invariants()


def test_bounded_wait_batch_drains_under_sustained_interactive_load():
    """Provable batch progress: one slot, a fresh interactive request every
    tick, one batch request submitted at t=0 — it must be admitted within
    the wait bound + one service time, and once running it is immune to
    preemption."""
    bound = 5.0
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16,
                          max_batch_wait_s=bound)
    sched.submit(_req(1000, priority="batch"), now=0.0)
    running = None
    admitted_at = None
    rid = 0
    for tick in range(40):
        t = float(tick)
        if running is not None:  # 1-tick service time
            _finish(sched, running)
            running = None
        sched.submit(_req(rid, priority="interactive"), now=t)
        rid += 1
        picked = sched.pick_preemption(now=t)
        if picked is not None:
            slot, victim = picked
            assert victim.priority == "batch"
            assert t - victim.submit_time <= bound, (
                "an over-bound batch request was offered as a victim")
            sched.requeue(victim)
        grants = sched.admit(now=t)
        for _, r in grants:
            if r.request_id == 1000:
                admitted_at = t
        if admitted_at is not None:
            break
        running = grants[0][1] if grants else None
        sched.assert_invariants()
    assert admitted_at is not None, "batch request starved"
    assert admitted_at <= bound + 2.0


# -- preemption --------------------------------------------------------------

def test_preemption_picks_latest_deadline_victim_and_requeues():
    sched = SlotScheduler(num_slots=2, context_len=8, max_total_len=16)
    sched.submit(_req(0, priority="batch", deadline_s=100.0), now=0.0)
    sched.submit(_req(1, priority="batch", deadline_s=5.0), now=0.0)
    grants = dict((r.request_id, s) for s, r in sched.admit(now=0.0))
    assert sched.pick_preemption(now=1.0) is None  # nothing interactive
    sched.submit(_req(2, priority="interactive"), now=1.0)
    slot, victim = sched.pick_preemption(now=1.0)
    # least urgent (latest deadline) batch victim
    assert victim.request_id == 0 and slot == grants[0]
    victim.generated.append(42)  # partial progress is discarded
    freed = sched.requeue(victim)
    assert freed == slot
    assert victim.state is RequestState.QUEUED
    assert victim.generated == [] and victim.preemptions == 1
    assert victim.submit_time == 0.0  # absolute deadline preserved
    sched.assert_invariants()
    # the freed slot goes to the interactive head; the victim re-queued
    [(_, granted)] = sched.admit(now=1.0)
    assert granted.request_id == 2
    assert sched.pick_preemption(now=1.0) is None  # head no longer blocked
    _finish(sched, granted)
    [(_, back)] = sched.admit(now=2.0)
    assert back.request_id == 0 and back.state is RequestState.PREFILL


def test_preemption_requires_blocked_interactive_head():
    sched = SlotScheduler(num_slots=2, context_len=8, max_total_len=16)
    sched.submit(_req(0, priority="batch"), now=0.0)
    sched.admit(now=0.0)
    sched.submit(_req(1, priority="interactive"), now=1.0)
    # a slot is free: no preemption needed
    assert sched.pick_preemption(now=1.0) is None


def test_slo_infeasible_is_distinct_and_estimator_driven():
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16,
                          shed_infeasible=True)
    # cold estimator: an optimistic deadline is admitted
    sched.submit(_req(0, deadline_s=0.5), now=0.0)
    sched.admit(now=0.0)
    # feed the estimator: recent first tokens took ~2s
    sched.note_first_token(2.0)
    with pytest.raises(SLOInfeasible):
        sched.submit(_req(1, deadline_s=0.5), now=1.0)
    # SLOInfeasible IS a (transient) BackpressureError, but a distinct one
    assert issubclass(SLOInfeasible, BackpressureError)
    # a roomier deadline is still feasible
    sched.submit(_req(2, deadline_s=30.0), now=1.0)
    # an already-dead budget is shed regardless of the estimator: the clone
    # carries its original submit_time, so remaining <= 0
    dead = _req(3, deadline_s=1.0)
    dead.submit_time = 0.0
    with pytest.raises(SLOInfeasible):
        sched.submit(dead, now=5.0)
    sched.assert_invariants()


def test_submit_preserves_preset_submit_time():
    """The fleet's absolute-deadline discipline: a requeued clone carries
    the original submission instant and the sweep times it out against
    THAT, not the resubmission instant."""
    sched = SlotScheduler(num_slots=1, context_len=8, max_total_len=16)
    clone = _req(0, deadline_s=5.0)
    clone.submit_time = 0.0
    sched.submit(clone, now=4.0)
    assert clone.submit_time == 0.0
    swept = sched.sweep(now=5.5)  # 5.5 - 0.0 > 5.0: expired
    assert [r.request_id for r in swept] == [0]
    assert swept[0].state is RequestState.TIMED_OUT


def test_priority_churn_property_no_slot_or_page_leak():
    """Randomized submit/admit/preempt/finish/cancel/sweep churn over a
    REAL PagedKVManager page gate: scheduler + allocator invariants after
    every op, zero leaked pages once drained."""
    rs = np.random.RandomState(0)
    kv = PagedKVManager(num_slots=3, context_len=8, max_total_len=16,
                        page_size=4, num_pages=17, prefix_cache=False)
    sched = SlotScheduler(3, 8, 16, page_gate=kv, max_batch_wait_s=20.0)
    rid = 0
    live = {}  # rid -> (slot, req)

    def check():
        sched.assert_invariants()
        kv.assert_invariants()

    for step in range(300):
        now = float(step)
        if rs.rand() < 0.6:
            try:
                sched.submit(_req(
                    rid, plen=int(rs.randint(1, 9)),
                    max_new=int(rs.randint(1, 5)),
                    priority="batch" if rs.rand() < 0.5 else "interactive",
                    deadline_s=(float(rs.randint(1, 50))
                                if rs.rand() < 0.5 else None)), now=now)
                rid += 1
            except BackpressureError:
                pass
        if rs.rand() < 0.15 and rid:
            sched.cancel(int(rs.randint(rid)))
        for req in sched.sweep(now):
            if req.request_id in live:
                kv.release_slot(live.pop(req.request_id)[0])
            check()
        picked = sched.pick_preemption(now)
        if picked is not None:
            slot, victim = picked
            sched.requeue(victim)
            kv.release_slot(slot)
            live.pop(victim.request_id, None)
            check()
        for slot, req in sched.admit(now):
            L = req.prompt_len
            ids = np.zeros((8,), np.int64)
            ids[8 - L:] = 1 + np.arange(L)
            valid = (np.arange(8) >= 8 - L).astype(np.int32)
            kv.admit_slot(slot, req, ids, valid)
            live[req.request_id] = (slot, req)
            check()
        if live and rs.rand() < 0.5:
            key = list(live)[int(rs.randint(len(live)))]
            slot, req = live.pop(key)
            _finish(sched, req)
            kv.release_slot(slot)
            check()
    # drain: finish everything still live, sweep the queues empty
    for slot, req in live.values():
        _finish(sched, req)
        kv.release_slot(slot)
    for entry in list(sched._by_id.values()):
        sched.cancel(entry.request_id)
    sched.sweep(now=1e9)
    check()
    assert kv.alloc.in_use == 0, "leaked KV pages after full drain"
    assert rid > 100  # the run actually exercised churn


# -- e2e: CPU tiny Llama -----------------------------------------------------

@pytest.fixture
def paged_pool(devices8):
    """B=3 paged pool model + B=1 solo reference over the SAME params
    (page 4 divides C=8 and T=16) — the test_kvcache serving fixture."""
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


def _run_staggered(engine, prompts, max_new=None, sampling=None, n_front=3):
    outs = {}
    for i in range(n_front):
        engine.submit(Request(
            request_id=i, prompt_ids=prompts[i],
            max_new_tokens=(max_new or 4 + i),
            sampling=sampling or SamplingParams()))
    for o in engine.step():
        outs[o.request_id] = o
    for i in range(n_front, len(prompts)):
        engine.submit(Request(
            request_id=i, prompt_ids=prompts[i],
            max_new_tokens=(max_new or 4 + i),
            sampling=sampling or SamplingParams()))
    for o in engine.run_until_complete(max_steps=400):
        outs[o.request_id] = o
    engine.scheduler.assert_invariants()
    engine._kv.assert_invariants()
    return {k: list(v.token_ids) for k, v in outs.items()}


@pytest.mark.parametrize("chunk", [4, 8])
def test_chunked_prefill_token_identical_to_solo_generate(paged_pool, chunk):
    """Acceptance bar: chunked-prefill greedy outputs under staggered
    arrivals + slot reuse are token-identical to solo generate, at a
    1-page chunk budget and at the context's width (2 pages: a prompt in
    ONE chunk, what the engine does when no width is named)."""
    cfg, pool, solo = paged_pool
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(5)]
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=chunk)
    chunked = _run_staggered(engine, prompts)
    for i, p in enumerate(prompts):
        assert chunked[i] == solo_generate(solo, p, 4 + i)
    # no width named is one chunk of the context: same programs, same count
    assert ServingEngine(pool, page_size=4)._chunk_tokens == 8


@pytest.mark.slow
def test_chunked_prefill_sampled_token_identical(paged_pool):
    """Sampled outputs at a 1-page chunk equal those at one chunk of the
    context (the per-request rng streams are keyed on (rng, id, token
    index) — chunking must not shift them)."""
    cfg, pool, _ = paged_pool
    rs = np.random.RandomState(11)
    prompts = [rs.randint(1, cfg.vocab_size, size=6).tolist()
               for _ in range(4)]
    rng = jax.random.PRNGKey(42)
    sampling = SamplingParams(temperature=0.9, top_k=0, top_p=1.0)
    whole = _run_staggered(
        ServingEngine(pool, page_size=4, num_pages=16, rng=rng),
        prompts, max_new=5, sampling=sampling)
    chunked = _run_staggered(
        ServingEngine(pool, page_size=4, num_pages=16, rng=rng,
                      prefill_chunk_tokens=4),
        prompts, max_new=5, sampling=sampling)
    assert chunked == whole


def test_chunked_prefill_prefix_hit_skips_resident_chunks(paged_pool):
    """An exact repeated prompt skips prefill chunks entirely (the cached
    chain serves the logits payload), and the outputs stay identical."""
    cfg, pool, solo = paged_pool
    prompt = [3, 1, 4, 1, 5, 9]
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=4)
    engine.submit(Request(request_id=0, prompt_ids=prompt, max_new_tokens=3))
    [first] = engine.run_until_complete(max_steps=100)
    chunks_before = engine.registry.snapshot()[
        "serving/prefill_chunks_total"]
    assert chunks_before > 0
    engine.submit(Request(request_id=1, prompt_ids=prompt, max_new_tokens=3))
    [second] = engine.run_until_complete(max_steps=100)
    snap = engine.registry.snapshot()
    assert snap["serving/prefill_chunks_total"] == chunks_before, (
        "a full prefix hit must not burn prefill chunks")
    assert snap["kvcache/prefill_skipped_total"] == 1.0
    want = solo_generate(solo, prompt, 3)
    assert list(first.token_ids) == list(second.token_ids) == want


def test_decodes_tick_while_long_prompt_chunks(paged_pool):
    """Stall-free batching: while a full-width prompt trickles in at one
    page per step, an already-decoding request produces a token on EVERY
    engine step (no multi-step inter-token stall)."""
    cfg, pool, solo = paged_pool
    rs = np.random.RandomState(3)
    short = rs.randint(1, cfg.vocab_size, size=3).tolist()
    long_p = rs.randint(1, cfg.vocab_size, size=8).tolist()  # full width
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=4)
    engine.submit(Request(request_id=0, prompt_ids=short, max_new_tokens=8))
    engine.step()  # short decodes from here on
    engine.submit(Request(request_id=1, prompt_ids=long_p, max_new_tokens=2,
                          priority="batch"))
    tokens_per_step = []
    outs = {}
    for _ in range(2):  # the long prompt's 2-page chunked prefill window
        n0 = len(engine.scheduler._by_id[0].generated)
        for o in engine.step():
            outs[o.request_id] = o
        tokens_per_step.append(
            len(engine.scheduler._by_id[0].generated) - n0)
    assert tokens_per_step == [1, 1], (
        "co-batched decode stalled during a chunked prefill")
    for o in engine.run_until_complete(max_steps=200):
        outs[o.request_id] = o
    assert list(outs[0].token_ids) == solo_generate(solo, short, 8)
    assert list(outs[1].token_ids) == solo_generate(solo, long_p, 2)


def test_preemption_e2e_token_identical_and_no_leak(paged_pool):
    """An interactive arrival preempts a decoding batch victim; the victim
    re-prefills later and BOTH finish token-identical to solo generate;
    zero page leak after the drain."""
    cfg, pool, solo = paged_pool
    rs = np.random.RandomState(5)
    prompts = {i: rs.randint(1, cfg.vocab_size, size=5).tolist()
               for i in range(4)}
    engine = ServingEngine(pool, page_size=4, num_pages=13)
    outs = {}
    for i in range(3):
        engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                              max_new_tokens=8, priority="batch"))
    for o in engine.step():
        outs[o.request_id] = o
    assert engine.scheduler.active_count == 3
    engine.submit(Request(request_id=3, prompt_ids=prompts[3],
                          max_new_tokens=3, priority="interactive"))
    for o in engine.run_until_complete(max_steps=400):
        outs[o.request_id] = o
    snap = engine.registry.snapshot()
    assert snap["serving/preemptions_total"] >= 1.0
    preempted = [o for o in outs.values() if o.preemptions > 0]
    assert preempted and all(o.priority == "batch" for o in preempted)
    for i in range(4):
        n = 3 if i == 3 else 8
        assert list(outs[i].token_ids) == solo_generate(
            solo, prompts[i], n), f"request {i} diverged after preemption"
    engine._kv.assert_invariants()
    evictable = (engine._kv.index.evictable_pages()
                 if engine._kv.index is not None else 0)
    assert engine._kv.alloc.in_use == evictable, "leaked pages"


def test_expired_before_prefill_counted_and_reclaimed(paged_pool):
    """A request whose deadline dies between the step-start sweep and its
    prefill dispatch is TIMED_OUT by the pre-dispatch check — no prefill
    compute burned, pages reclaimed, counted."""
    cfg, pool, _ = paged_pool
    t = [0.0]

    def clock():  # each call advances: sweep sees t+0.3, prefill t+0.6
        t[0] += 0.3
        return t[0]

    engine = ServingEngine(pool, page_size=4, num_pages=16, clock=clock)
    engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3],
                          max_new_tokens=4, deadline_s=0.45))
    outs = {o.request_id: o for o in engine.step()}
    assert outs[0].state == "timed_out"
    assert outs[0].token_ids == ()
    snap = engine.registry.snapshot()
    assert snap["serving/expired_before_prefill_total"] == 1.0
    engine.scheduler.assert_invariants()
    engine._kv.assert_invariants()
    assert engine._kv.alloc.in_use == 0


def test_expiry_mid_chunking_reclaims_and_counts(paged_pool):
    """The chunk loop re-checks the deadline before every dispatch: a
    request that expires mid-chunked-prefill stops burning chunks and its
    pages are reclaimed."""
    cfg, pool, _ = paged_pool
    t = [0.0]
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=4, clock=lambda: t[0])
    engine.submit(Request(request_id=0, prompt_ids=list(range(1, 9)),
                          max_new_tokens=4, deadline_s=1.0))
    engine.step()  # admits + first chunk (deadline still live)
    assert 0 in engine._chunking or engine.scheduler.active_count == 1
    chunks = engine.registry.snapshot()["serving/prefill_chunks_total"]
    assert chunks >= 1.0
    t[0] = 2.0  # deadline dead before the next chunk
    outs = {o.request_id: o for o in engine.step()}
    assert outs[0].state == "timed_out"
    snap = engine.registry.snapshot()
    assert snap["serving/prefill_chunks_total"] == chunks, (
        "a dead request burned another chunk")
    # counted either by the sweep or the pre-dispatch check — but the
    # pre-dispatch path must have reclaimed everything
    engine._kv.assert_invariants()
    assert engine._kv.alloc.in_use == (
        engine._kv.index.evictable_pages()
        if engine._kv.index is not None else 0)
    assert not engine._chunking


@pytest.mark.chaos
def test_chaos_kill_mid_chunked_prefill_reclaims_and_requeues(paged_pool):
    """The chaos rung: an injected fault mid-chunked-prefill fails the one
    request transactionally (every page reclaimed, FAILED emitted, fault
    re-raised for the supervisor/fleet layer) and an identical resubmission
    then completes cleanly with token-identical output."""
    cfg, pool, solo = paged_pool
    prompt = list(range(1, 9))
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=4)
    base_in_use = engine._kv.alloc.in_use
    install_plan({"faults": [{"point": "serving/prefill_chunk",
                              "action": "exception",
                              "match": {"request_id": 0}}]})
    try:
        engine.submit(Request(request_id=0, prompt_ids=prompt,
                              max_new_tokens=3))
        with pytest.raises(InjectedFault):
            engine.run_until_complete(max_steps=50)
    finally:
        clear_plan()
    kv = engine._kv
    kv.assert_invariants()
    assert kv.alloc.in_use == base_in_use, "chunk crash leaked pages"
    assert not engine._chunking
    engine.scheduler.assert_invariants()
    snap = engine.registry.snapshot()
    assert snap["serving/failed_total"] == 1.0
    # the request requeues cleanly: an identical clone (fresh id — the
    # fleet preserves the global id; a bare engine needs a new one) runs
    # to completion on the same engine
    engine.submit(Request(request_id=1, prompt_ids=prompt, max_new_tokens=3))
    [out] = engine.run_until_complete(max_steps=100)
    assert out.state == "finished"
    assert list(out.token_ids) == solo_generate(solo, prompt, 3)


def test_serving_stats_v4_fields_emitted(paged_pool, tmp_path):
    """The live emitter writes schema-valid v4 records carrying priority /
    deadline / queue-wait / preemption / shed fields."""
    import json

    from neuronx_distributed_tpu.obs.schemas import validate_jsonl

    cfg, pool, _ = paged_pool
    stats = str(tmp_path / "serving_stats.jsonl")
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=4, stats_path=stats)
    engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3],
                          max_new_tokens=2, priority="batch",
                          deadline_s=60.0))
    engine.run_until_complete(max_steps=100)
    engine.close()
    assert validate_jsonl("serving_stats", stats) == 1
    rec = json.loads(open(stats).read().strip())
    assert rec["priority"] == "batch"
    assert rec["deadline_s"] == 60.0
    assert rec["preemptions"] == 0 and rec["shed_reason"] is None
    assert rec["queue_wait_ms"] == rec["queue_ms"]
    # knob validation (same fixture, no extra AOT compile): chunking needs
    # a page size, page-aligned budgets, and a known priority class
    with pytest.raises(TypeError, match="page_size"):
        ServingEngine(pool, prefill_chunk_tokens=4)
    with pytest.raises(ValueError, match="multiple of page_size"):
        ServingEngine(pool, page_size=4, num_pages=16,
                      prefill_chunk_tokens=6)
    with pytest.raises(ValueError, match="priority"):
        Request(request_id=0, prompt_ids=[1], max_new_tokens=1,
                priority="gold")


# -- fleet requeue deadline satellite ----------------------------------------

def _fake_fleet(clock):
    from test_fleet import _FakeEngine

    return FleetRouter(
        [Replica(i, _FakeEngine, backoff_base_s=0.0, clock=clock)
         for i in range(2)],
        policy="round_robin", clock=clock, sleep=lambda s: None)


def test_fleet_requeue_carries_absolute_deadline():
    """A crashed replica's requeued clone carries the ORIGINAL submission
    instant and priority, so the deadline does not silently re-arm through
    the crash."""
    t = [0.0]
    router = _fake_fleet(lambda: t[0])
    gid = router.submit(_req(0, deadline_s=5.0, priority="batch"))
    holder = router.replicas[router._tracked[gid].replica_id]
    t[0] = 2.0
    holder.engine.crash_next = True
    router.step()  # crash -> drain -> requeue on the sibling
    sibling = next(r for rid, r in router.replicas.items()
                   if r.alive and r.has_work)
    [(clone, _)] = sibling.engine.queue
    assert clone.request_id == gid
    assert clone.submit_time == 0.0, "deadline re-armed through the crash"
    assert clone.deadline_s == 5.0 and clone.priority == "batch"
    router.assert_invariants()
    outs = router.run_until_complete(max_steps=50)
    assert [o.request_id for o in outs] == [gid]


def test_fleet_expired_clone_fails_terminally_as_timed_out():
    """An orphan whose absolute deadline already passed at failover fails
    terminally as TIMED_OUT — no sibling re-prefill is burned, and the
    exactly-once ledger stays balanced."""
    t = [0.0]
    router = _fake_fleet(lambda: t[0])
    gid = router.submit(_req(0, deadline_s=5.0))
    holder = router.replicas[router._tracked[gid].replica_id]
    t[0] = 6.0  # past the absolute deadline
    holder.engine.crash_next = True
    outs = router.step()
    outs += router.step()  # synthetic outputs emit through step()
    done = {o.request_id: o for o in outs}
    assert done[gid].state == "timed_out"
    assert done[gid].finish_reason == "timed_out"
    for r in router.replicas.values():  # nobody got a clone
        if r.alive:
            assert not r.has_work
    router.assert_invariants()
    assert router.inflight == 0

