"""Serving-fleet subsystem tests (fast tier: CPU mesh).

Three layers, mirroring the subsystem's split:

- pure host-side PROPERTY tests over fakes — id allocation, chain
  fingerprints, shadow matching, every routing policy, the shared restart
  backoff, replica lifecycle, the driver loop, and a randomized-churn run
  asserting the zero-loss ledger: across dispatch / requeue / kill /
  cancel / retirement, every accepted request yields EXACTLY ONE terminal
  output — none lost, none duplicated;
- e2e CPU-tiny-Llama runs asserting the acceptance bar: a greedy fleet's
  outputs are token-identical to solo generate under EVERY routing policy,
  sampled outputs are reproducible across fleet shapes (global ids pin the
  rng streams), and a ``chaos``-marked replica-kill rung proves zero
  accepted-request loss with outputs still token-identical (requeue
  re-prefills from the original prompt);
- CLI rungs (``fleet``-marked + slow, out of tier-1): ``runner.py serve
  --replicas`` and ``tools/fleet_bench.py --tiny``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import last_json_line, run_cli, sharded_params, solo_generate
from neuronx_distributed_tpu.kvcache.prefix import (
    PAD,
    PrefixIndex,
    chain_fingerprint,
    page_keys,
    prefix_fingerprints,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import MetricRegistry
from neuronx_distributed_tpu.obs.schemas import validate_jsonl
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.resilience import clear_plan, install_plan
from neuronx_distributed_tpu.resilience.supervisor import RestartBackoff
from neuronx_distributed_tpu.serving import (
    FleetRouter,
    FleetUnavailableError,
    Replica,
    ReplicaState,
    Request,
    SamplingParams,
    ServingEngine,
    poisson_arrivals,
    replay,
)
from neuronx_distributed_tpu.serving.fleet import (
    LeastLoadedPolicy,
    PrefixAffinityPolicy,
    RandomPolicy,
    ReplicaShadow,
    RequestIdAllocator,
    RoundRobinPolicy,
    make_policy,
)
from neuronx_distributed_tpu.serving.fleet.routing import load_score
from neuronx_distributed_tpu.serving.request import RequestOutput
from neuronx_distributed_tpu.serving.scheduler import BackpressureError
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel
from neuronx_distributed_tpu.trace.engine import request_rng

pytestmark = pytest.mark.fleet


def _req(rid, plen=4, max_new=3, **kw):
    return Request(request_id=rid, prompt_ids=list(range(1, plen + 1)),
                   max_new_tokens=max_new, **kw)


# -- global request ids ------------------------------------------------------

def test_request_id_allocator_unique_and_namespaced():
    a = RequestIdAllocator(namespace=3)
    ids = [a.next_id() for _ in range(100)]
    assert len(set(ids)) == 100
    assert all(i >> 32 == 3 for i in ids)
    assert [i & 0xFFFFFFFF for i in ids] == list(range(100))
    b = RequestIdAllocator(namespace=4)
    assert not set(ids) & {b.next_id() for _ in range(100)}
    with pytest.raises(ValueError, match="namespace"):
        RequestIdAllocator(namespace=-1)
    with pytest.raises(ValueError, match="namespace"):
        RequestIdAllocator(namespace=2 ** 31)
    with pytest.raises(ValueError, match="namespace"):
        # 0 would mint sub-2**32 ids colliding with bare-engine caller ids
        RequestIdAllocator(namespace=0)


def test_request_rng_folds_namespace_high_word():
    """Wide (fleet-global) ids draw distinct streams per namespace, while
    ids below 2**32 keep their historical single-fold streams."""
    rng = jax.random.PRNGKey(0)
    legacy = request_rng(rng, 7)
    assert jnp.array_equal(legacy, jax.random.fold_in(rng, jnp.uint32(7)))
    g1 = request_rng(rng, (1 << 32) | 7)
    g2 = request_rng(rng, (2 << 32) | 7)
    assert not jnp.array_equal(g1, g2)       # namespaces diverge
    assert not jnp.array_equal(g1, legacy)   # and differ from the bare id
    # deterministic: the same global id always draws the same stream
    assert jnp.array_equal(g1, request_rng(rng, (1 << 32) | 7))
    # numpy integral ids fold identically (uint32 truncation would
    # silently collide a wide np.int64 with the bare low-word stream)
    assert jnp.array_equal(g1, request_rng(rng, np.int64((1 << 32) | 7)))


# -- chain fingerprints ------------------------------------------------------

def test_chain_fingerprints_roll_and_match_index_truth():
    keys = page_keys(np.arange(1, 9, dtype=np.int64), np.ones(8, np.int32), 4)
    fps = prefix_fingerprints(keys)
    assert len(fps) == 2 and len(set(fps)) == 2
    # rolling: depth-i fingerprint depends on every key before it
    assert fps[0] == chain_fingerprint(0, keys[0])
    assert fps[1] == chain_fingerprint(fps[0], keys[1])
    other = page_keys(np.arange(2, 10, dtype=np.int64), np.ones(8, np.int32), 4)
    assert prefix_fingerprints(other)[0] != fps[0]

    # a live PrefixIndex exports exactly the chains it holds
    from neuronx_distributed_tpu.kvcache.allocator import BlockAllocator

    alloc = BlockAllocator(8)
    idx = PrefixIndex(alloc)
    pages = alloc.alloc(2)
    idx.insert(keys, list(pages))
    assert idx.chain_fingerprints() == set(fps)


def test_shadow_match_depth_stops_at_first_miss():
    sh = ReplicaShadow()
    sh.credit([10, 20, 30])
    assert sh.match_depth([10, 20, 30, 40]) == 3
    assert sh.match_depth([10, 99, 30]) == 1   # 30 present but unreachable
    assert sh.match_depth([99]) == 0
    sh.resync({10})
    assert sh.match_depth([10, 20]) == 1
    sh.clear()
    assert sh.match_depth([10]) == 0


# -- routing policies --------------------------------------------------------

def _views(loads):
    return {rid: {"replica_id": rid, "queue_depth": q, "active": a,
                  "slots": 2, "pages_free": pf, "host_blocked_ms_mean": None}
            for rid, (q, a, pf) in loads.items()}


def test_round_robin_rotates_over_live_candidates():
    p = RoundRobinPolicy()
    picks = [p.choose([0, 2, 5], {}, {}, []).replica_id for _ in range(6)]
    assert picks == [0, 2, 5, 0, 2, 5]


def test_random_policy_is_seeded():
    picks1 = [RandomPolicy(seed=3).choose([0, 1, 2], {}, {}, []).replica_id
              for _ in range(1)]
    p2 = RandomPolicy(seed=3)
    assert picks1[0] == p2.choose([0, 1, 2], {}, {}, []).replica_id


def test_least_loaded_orders_by_queue_then_pages():
    views = _views({0: (4, 2, 10), 1: (0, 1, 10), 2: (0, 1, 20)})
    assert LeastLoadedPolicy().choose(
        [0, 1, 2], views, {}, []).replica_id == 2  # tie on load -> more pages
    assert load_score(views[0]) > load_score(views[1])


def test_prefix_affinity_steers_to_longest_chain():
    shadows = {0: ReplicaShadow(), 1: ReplicaShadow(), 2: ReplicaShadow()}
    shadows[1].credit([10, 20])
    shadows[2].credit([10])
    views = _views({0: (0, 0, 8), 1: (9, 9, 0), 2: (0, 0, 8)})
    d = PrefixAffinityPolicy().choose([0, 1, 2], views, shadows, [10, 20, 30])
    assert d.replica_id == 1 and d.affinity_pages == 2  # chain beats load
    # total miss (or no fingerprints) -> least loaded
    d = PrefixAffinityPolicy().choose([0, 1, 2], views, shadows, [99])
    assert d.replica_id in (0, 2) and d.affinity_pages == 0
    d = PrefixAffinityPolicy().choose([0, 1, 2], views, shadows, [])
    assert d.affinity_pages == 0


def test_make_policy_resolves_names_and_rejects_unknown():
    assert isinstance(make_policy("least_loaded"), LeastLoadedPolicy)
    p = RoundRobinPolicy()
    assert make_policy(p) is p
    with pytest.raises(ValueError, match="unknown routing policy"):
        make_policy("fastest")


# -- restart backoff / replica lifecycle -------------------------------------

def test_restart_backoff_schedule():
    b = RestartBackoff(max_restarts=3, base_s=0.5, max_s=1.5)
    assert [b.next_delay() for _ in range(3)] == [0.5, 1.0, 1.5]  # capped
    assert b.exhausted and b.next_delay() is None
    with pytest.raises(ValueError):
        RestartBackoff(max_restarts=-1)


class _FakeEngine:
    """Host-side engine fake: finishes each request after ``work`` steps,
    optional bounded admission, crash-on-demand via ``crash_next``."""

    def __init__(self, work=2, capacity=None):
        self.work = work
        self.capacity = capacity
        self.queue = []
        self.crash_next = False
        self.closed = False

    def submit(self, req):
        if self.capacity is not None and len(self.queue) >= self.capacity:
            raise BackpressureError("fake full")
        self.queue.append([req, self.work])

    def cancel(self, rid):
        for ent in self.queue:
            if ent[0].request_id == rid and ent[1] >= 0:
                ent[1] = -1  # emit a cancelled output next step
                return True
        return False

    @property
    def has_work(self):
        return bool(self.queue)

    def step(self):
        if self.crash_next:
            self.crash_next = False
            raise RuntimeError("fake engine crash")
        outs, keep = [], []
        for req, left in self.queue:
            if left > 0:
                keep.append([req, left - 1])
                continue
            state = "cancelled" if left < 0 else "finished"
            outs.append(RequestOutput(
                request_id=req.request_id, state=state,
                finish_reason=None if left < 0 else "length",
                prompt_len=len(req.prompt_ids),
                token_ids=() if left < 0 else (1, 2), queue_ms=0.0,
                ttft_ms=None if left < 0 else 1.0, total_ms=2.0))
        self.queue = keep
        return outs

    def close(self):
        self.closed = True


def test_replica_lifecycle_dead_restart_retire():
    t = [0.0]
    rep = Replica(0, _FakeEngine, max_restarts=2, backoff_base_s=1.0,
                  backoff_max_s=10.0, clock=lambda: t[0])
    assert rep.alive
    first = rep.engine
    assert rep.mark_dead("crash") == 1.0
    assert rep.state is ReplicaState.DEAD and first.closed
    with pytest.raises(RuntimeError, match="must not dispatch"):
        rep.submit(_req(0))
    assert not rep.try_restart()          # backoff not expired
    t[0] = 1.5
    assert rep.try_restart() and rep.alive and rep.engine is not first
    assert rep.mark_dead("crash") == 2.0  # exponential
    t[0] = 10.0
    assert rep.try_restart()
    assert rep.mark_dead("crash") is None  # budget spent
    assert rep.state is ReplicaState.RETIRED
    assert not rep.try_restart()


def test_replica_factory_failure_counts_as_crash():
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] == 2:
            raise RuntimeError("oom")
        return _FakeEngine()

    t = [0.0]
    rep = Replica(0, flaky, max_restarts=2, backoff_base_s=1.0,
                  clock=lambda: t[0])
    rep.mark_dead("crash")
    t[0] = 100.0
    assert not rep.try_restart()  # factory raised -> another crash consumed
    assert rep.state is ReplicaState.DEAD and rep.backoff.restarts == 2
    t[0] = 300.0
    assert rep.try_restart() and rep.alive


# -- driver -----------------------------------------------------------------

def test_poisson_arrivals_shapes():
    rs = np.random.RandomState(0)
    arr = poisson_arrivals(10, 5.0, rs)
    assert arr[0] == 0.0 and len(arr) == 10
    assert (np.diff(arr) >= 0).all()
    assert (poisson_arrivals(4, float("inf"), rs) == 0.0).all()  # burst
    with pytest.raises(ValueError):
        poisson_arrivals(0, 5.0, rs)


def test_replay_drives_any_target_and_dumps_on_crash():
    eng = _FakeEngine(work=1)
    outs = replay(eng, [0.0, 0.0], [_req(0), _req(1)],
                  clock=iter(np.arange(0, 100, 0.1)).__next__,
                  sleep=lambda s: None)
    assert set(outs) == {0, 1}

    class Crashy(_FakeEngine):
        def __init__(self):
            super().__init__()
            self.dumped = None

        def step(self):
            raise RuntimeError("boom")

        def dump_flight(self, reason):
            self.dumped = reason

    eng = Crashy()
    with pytest.raises(RuntimeError, match="boom"):
        replay(eng, [0.0], [_req(0)], clock=lambda: 1.0,
               sleep=lambda s: None)
    assert eng.dumped == "crash:RuntimeError"
    with pytest.raises(ValueError, match="pair up"):
        replay(eng, [0.0], [])


# -- router over fakes -------------------------------------------------------

def _fleet(n=3, policy="round_robin", factory=_FakeEngine, **kw):
    return FleetRouter([Replica(i, factory, backoff_base_s=0.0)
                        for i in range(n)], policy=policy, **kw)


def test_router_rekeys_ids_and_tracks_client_ids():
    router = _fleet()
    gid = router.submit(_req(77))
    assert gid >> 32 == 1 and router.client_id(gid) == 77
    outs = router.run_until_complete(max_steps=50)
    assert [o.request_id for o in outs] == [gid]
    router.close()


def test_router_rejects_bad_fleets():
    with pytest.raises(ValueError, match="at least one"):
        FleetRouter([])
    with pytest.raises(ValueError, match="duplicate"):
        FleetRouter([Replica(0, _FakeEngine), Replica(0, _FakeEngine)])

    class WideEngine(_FakeEngine):
        C = 16

    with pytest.raises(ValueError, match="heterogeneous"):
        FleetRouter([Replica(0, _FakeEngine), Replica(1, WideEngine)])

    class ShortEngine(_FakeEngine):
        T = 64  # smaller envelope: a sibling's requeue could never fit

    with pytest.raises(ValueError, match="heterogeneous"):
        FleetRouter([Replica(0, _FakeEngine), Replica(1, ShortEngine)])


def test_router_failover_requeues_on_siblings():
    router = _fleet(n=2, factory=lambda: _FakeEngine(work=3))
    gids = [router.submit(_req(i)) for i in range(4)]
    outs = router.step()
    victim = router.replicas[0]
    victim.engine.crash_next = True
    outs += router.step()  # crash -> drain -> requeue on the sibling
    snap = router.registry.snapshot()
    assert snap["router/failovers_total"] == 1.0
    assert snap["router/requeued_total"] >= 1.0
    outs += router.run_until_complete(max_steps=100)
    router.assert_invariants()
    assert {o.request_id for o in outs} == set(gids)  # exactly-once, all
    assert all(o.state == "finished" for o in outs)
    router.close()


def test_router_parks_on_backpressure_and_bounds_backlog():
    router = _fleet(n=1, factory=lambda: _FakeEngine(capacity=1),
                    max_pending=1)
    router.submit(_req(0))          # fills the engine
    router.submit(_req(1))          # parked router-held
    assert len(router._pending) == 1
    with pytest.raises(BackpressureError, match="router backlog full"):
        router.submit(_req(2))
    outs = router.run_until_complete(max_steps=100)
    assert {o.state for o in outs} == {"finished"} and len(outs) == 2
    router.assert_invariants()
    router.close()


def test_failover_requeue_bypasses_max_pending():
    """max_pending bounds NEW admissions only: orphans requeued off a dead
    replica must force-park even with the backlog bound at zero — an
    accepted request is never dropped by the admission limit."""
    router = _fleet(n=1, factory=lambda: _FakeEngine(work=5), max_pending=0)
    gids = [router.submit(_req(i)) for i in range(3)]
    router.replicas[0].engine.crash_next = True
    outs = router.step()  # crash: orphans park router-held, no raise
    router.assert_invariants()
    outs += router.run_until_complete(max_steps=200)
    assert {o.request_id for o in outs} == set(gids)
    assert all(o.state == "finished" for o in outs)
    router.close()


def test_admission_error_leaves_no_ghost_record():
    """A permanent engine-side rejection passes through submit() without
    corrupting the ledger: no tracked record, caller id restored."""
    from neuronx_distributed_tpu.serving import AdmissionError

    class Rejecting(_FakeEngine):
        def submit(self, req):
            raise AdmissionError("never fits")

    router = _fleet(n=1, factory=Rejecting)
    req = _req(5)
    with pytest.raises(AdmissionError):
        router.submit(req)
    assert router.inflight == 0 and req.request_id == 5
    router.assert_invariants()
    router.close()


def test_router_total_capacity_loss_fails_pending_terminally():
    router = _fleet(n=1, factory=lambda: _FakeEngine(capacity=1))
    router.replicas[0].backoff.max_restarts = 0
    router.submit(_req(0, max_new=5))
    gid1 = router.submit(_req(1))   # parked (engine full)
    router.replicas[0].engine.crash_next = True
    outs = router.run_until_complete(max_steps=50)
    router.assert_invariants()
    by_id = {o.request_id: o for o in outs}
    assert by_id[gid1].state == "failed"
    assert by_id[gid1].finish_reason == "fleet_unavailable"
    assert len(by_id) == 2          # the crashed request also terminates
    with pytest.raises(FleetUnavailableError):
        router.submit(_req(2))
    router.close()


def test_router_cancel_pending_and_placed():
    router = _fleet(n=1, factory=lambda: _FakeEngine(capacity=1))
    g0 = router.submit(_req(0))
    g1 = router.submit(_req(1))     # parked
    assert router.cancel(g1)        # router-held cancel is synchronous
    assert router.cancel(g0)        # placed cancel delegates to the engine
    assert not router.cancel(999)
    outs = router.run_until_complete(max_steps=50)
    states = {o.request_id: o.state for o in outs}
    assert states[g1] == "cancelled" and states[g0] == "cancelled"
    router.assert_invariants()
    router.close()


def test_requeue_rejected_by_sibling_fails_terminally_not_lost():
    """Backstop: if a sibling somehow rejects a requeued clone with a
    permanent error (unreachable on a homogeneous fleet), the request is
    failed terminally — the exactly-once ledger holds instead of the raise
    escaping step() and losing the remaining orphans."""
    from neuronx_distributed_tpu.serving import AdmissionError

    class Hostile(_FakeEngine):
        hostile = False

        def submit(self, req):
            if self.hostile:
                raise AdmissionError("never fits here")
            super().submit(req)

    router = _fleet(n=2, factory=lambda: Hostile(work=4))
    g0 = router.submit(_req(0))   # round-robin: replica 0
    g1 = router.submit(_req(1))   # replica 1
    router.replicas[1].engine.hostile = True
    router.replicas[0].engine.crash_next = True
    outs = router.step()          # crash 0 -> requeue g0 -> 1 rejects it
    outs += router.run_until_complete(max_steps=100)
    by = {o.request_id: o for o in outs}
    assert by[g0].state == "failed"
    assert by[g0].finish_reason == "requeue_rejected:AdmissionError"
    assert by[g1].state == "finished"  # the sibling's own work unharmed
    router.assert_invariants()
    router.close()


def test_granted_cancel_survives_failover():
    """A cancel granted on a replica that crashes before its sweep emits
    the output must NOT be undone by the requeue: the caller who got True
    gets a cancelled terminal output, not a resurrected full generation."""
    router = _fleet(n=2, factory=lambda: _FakeEngine(work=5))
    g0 = router.submit(_req(0))  # round-robin: lands on replica 0
    outs = router.step()
    assert router.cancel(g0)
    router.replicas[0].engine.crash_next = True
    outs += router.step()  # crash before the engine's cancel sweep ran
    outs += router.run_until_complete(max_steps=100)
    by = {o.request_id: o for o in outs}
    assert by[g0].state == "cancelled" and not by[g0].token_ids
    assert router.registry.snapshot()["router/requeued_total"] == 0.0
    router.assert_invariants()
    router.close()


def test_drain_preserves_fcfs_head_on_backpressure():
    """A backpressured head re-parks at the HEAD of the router-held queue
    — it blocks the drain instead of being overtaken every round."""
    router = _fleet(n=1, factory=lambda: _FakeEngine(work=3, capacity=1))
    g0 = router.submit(_req(0))
    g1 = router.submit(_req(1))
    g2 = router.submit(_req(2))
    assert [r.global_id for r in router._pending] == [g1, g2]
    router.step()  # engine still full: g1 bounces but keeps its place
    assert [r.global_id for r in router._pending] == [g1, g2]
    outs = router.run_until_complete(max_steps=100)
    assert [o.request_id for o in outs] == [g0, g1, g2]  # FCFS completion
    router.close()


def test_churn_no_request_lost_or_duplicated():
    """The zero-loss ledger under randomized churn: submits, cancels,
    replica crashes (including past the restart budget), steps — every
    accepted request yields exactly one terminal output."""
    rs = np.random.RandomState(42)
    router = _fleet(n=3, policy="least_loaded",
                    factory=lambda: _FakeEngine(work=int(rs.randint(1, 4)),
                                                capacity=4))
    accepted, outputs = [], {}
    rid = 0
    for step in range(300):
        op = rs.rand()
        if op < 0.45:
            try:
                accepted.append(router.submit(
                    _req(rid, plen=int(rs.randint(2, 6)))))
            except (BackpressureError, FleetUnavailableError):
                pass
            rid += 1
        elif op < 0.55 and accepted:
            router.cancel(accepted[rs.randint(len(accepted))])
        elif op < 0.62:
            live = [r for r in router.replicas.values() if r.alive]
            if live:
                live[rs.randint(len(live))].engine.crash_next = True
        for out in router.step():
            assert out.request_id not in outputs, (
                f"duplicate terminal output for {out.request_id}")
            outputs[out.request_id] = out
        router.assert_invariants()
    for _ in range(200):
        if not router.has_work:
            break
        for out in router.step():
            assert out.request_id not in outputs
            outputs[out.request_id] = out
    router.assert_invariants()
    assert not router.has_work
    missing = [g for g in accepted if g not in outputs]
    assert not missing, f"accepted requests lost: {missing}"
    assert len(accepted) > 60  # the run actually exercised churn
    router.close()


class _FakeKV:
    page_size = 8
    index = object()  # non-None: "prefix cache on"

    def prefix_fingerprints(self):
        return set()

    def pages_free(self):
        return 4

    def pages_capacity(self):
        return 8


class _PagedFake(_FakeEngine):
    C = 32
    _kv = _FakeKV()


def test_affinity_fingerprints_ignore_padding_only_chains():
    """Similar-length prompts share every leading all-PAD page chain (NULL
    pages — zero reuse value); scoring them would hot-spot unrelated short
    prompts onto one replica.  The router drops them: unrelated prompts
    share nothing, identical prompts still match."""
    router = FleetRouter([Replica(0, _PagedFake), Replica(1, _PagedFake)],
                         policy="prefix_affinity")
    fa = router._fingerprints(Request(request_id=0, prompt_ids=[5, 6, 7],
                                      max_new_tokens=2))
    fb = router._fingerprints(Request(request_id=1, prompt_ids=[9, 9, 9],
                                      max_new_tokens=2))
    assert len(fa) == 1 and len(fb) == 1  # 3 pad pages dropped, 1 real
    assert not set(fa) & set(fb)          # unrelated prompts share nothing
    fa2 = router._fingerprints(Request(request_id=2, prompt_ids=[5, 6, 7],
                                       max_new_tokens=2))
    assert fa2 == fa                      # identical prompts still match
    router.close()

    # rotation/random policies never read fingerprints — none are computed
    rr = FleetRouter([Replica(0, _PagedFake)], policy="round_robin")
    assert rr._fingerprints(Request(request_id=0, prompt_ids=[5, 6, 7],
                                    max_new_tokens=2)) == []
    rr.close()


def test_terminal_record_retention_is_bounded():
    """A long-lived router keeps the client_id mapping for the last
    retain_done terminal requests only — memory does not grow with every
    request ever served."""
    router = _fleet(n=1, retain_done=2)
    gids = [router.submit(_req(i)) for i in range(5)]
    router.run_until_complete(max_steps=100)
    assert len(router._tracked) == 2
    assert [router.client_id(g) for g in gids[:3]] == [None] * 3
    assert [router.client_id(g) for g in gids[3:]] == [3, 4]
    router.assert_invariants()
    router.close()


def test_router_stats_jsonl_validates(tmp_path):
    path = str(tmp_path / "router_stats.jsonl")
    router = _fleet(n=2, stats_path=path)
    for i in range(5):
        router.submit(_req(i))
    router.run_until_complete(max_steps=100)
    router.close()
    assert validate_jsonl("router_stats", path) == 5
    recs = [json.loads(l) for l in open(path)]
    assert {r["client_id"] for r in recs} == set(range(5))
    assert all(r["policy"] == "round_robin" and r["dispatches"] == 1
               for r in recs)


# -- e2e: CPU tiny Llama -----------------------------------------------------

@pytest.fixture
def fleet_pool(devices8):
    """One compiled paged tiny-Llama pool model (B=2) + B=1 solo reference
    over the SAME params; every fleet in these tests shares it (one set of
    compiled phase fns)."""
    initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none",
    )
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((2, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=2, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    solo = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=1, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    return cfg, pool, solo


def _paged_factory(pool, seed=0):
    def factory():
        return ServingEngine(pool, rng=jax.random.PRNGKey(seed),
                             registry=MetricRegistry(), page_size=4,
                             num_pages=9)
    return factory


def _shared_prompts(cfg, n, rs):
    """Half share one system preamble (page-aligned length 4), half are
    unrelated — the trace affinity exists for."""
    sys_ids = rs.randint(1, cfg.vocab_size, size=4).tolist()
    return [
        sys_ids + rs.randint(1, cfg.vocab_size, size=3).tolist()
        if i % 2 == 0 else
        rs.randint(1, cfg.vocab_size, size=int(rs.randint(3, 8))).tolist()
        for i in range(n)
    ]


@pytest.mark.parametrize("policy", ["round_robin", "random", "least_loaded",
                                    "prefix_affinity"])
def test_fleet_greedy_identical_to_solo_under_every_policy(fleet_pool, policy):
    """Placement must never change tokens: whichever replica a request
    lands on (any policy, staggered burst arrivals, shared prefixes), its
    greedy output equals the solo generate of its prompt."""
    cfg, pool, solo = fleet_pool
    rs = np.random.RandomState(13)
    prompts = _shared_prompts(cfg, 6, rs)
    router = FleetRouter(
        [Replica(i, _paged_factory(pool)) for i in range(3)],
        policy=policy, seed=1)
    reqs = [Request(request_id=i, prompt_ids=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    outs = replay(router, np.zeros(len(reqs)), reqs, sleep=lambda s: None)
    assert len(outs) == len(prompts)
    for gid, out in outs.items():
        cid = router.client_id(gid)
        assert out.state == "finished"
        want = solo_generate(solo, prompts[cid], 4)
        assert list(out.token_ids) == want, (
            f"request {cid} diverged under {policy}")
    router.assert_invariants()
    router.close()


def test_fleet_sampled_reproducible_across_fleet_shapes(fleet_pool):
    """Sampled outputs depend only on (rng, global id): a 3-replica
    affinity fleet and a 1-replica fleet draw identical tokens for the
    same submissions (the router-assigned ids, not placement, pin the
    streams)."""
    cfg, pool, _ = fleet_pool
    rs = np.random.RandomState(29)
    prompts = _shared_prompts(cfg, 4, rs)

    def run(n_replicas, policy):
        router = FleetRouter(
            [Replica(i, _paged_factory(pool, seed=5))
             for i in range(n_replicas)], policy=policy, namespace=9)
        reqs = [Request(request_id=i, prompt_ids=p, max_new_tokens=4,
                        sampling=SamplingParams(temperature=0.9))
                for i, p in enumerate(prompts)]
        outs = replay(router, np.zeros(len(reqs)), reqs,
                      sleep=lambda s: None)
        got = {router.client_id(g): list(o.token_ids)
               for g, o in outs.items()}
        router.close()
        return got

    assert run(3, "prefix_affinity") == run(1, "round_robin")


@pytest.mark.chaos
def test_fleet_kill_zero_loss_and_token_identical(fleet_pool, tmp_path):
    """The failover acceptance bar, in-process: a replica killed mid-run
    through the NXD_FAULT_PLAN plane loses zero accepted requests, the
    requeued clones re-prefill to the SAME greedy tokens, the restart
    re-enters rotation, and router_stats.jsonl carries the evidence."""
    cfg, pool, solo = fleet_pool
    rs = np.random.RandomState(31)
    prompts = _shared_prompts(cfg, 8, rs)
    stats_path = str(tmp_path / "router_stats.jsonl")
    install_plan({"faults": [{
        "point": "fleet/replica_step", "action": "exception",
        "match": {"replica": 0, "step": 2}, "count": 1}]})
    try:
        router = FleetRouter(
            [Replica(i, _paged_factory(pool), backoff_base_s=0.0)
             for i in range(3)],
            policy="round_robin", stats_path=stats_path)
        reqs = [Request(request_id=i, prompt_ids=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        outs = replay(router, np.zeros(len(reqs)), reqs, sleep=lambda s: None)
        router.assert_invariants()
    finally:
        clear_plan()

    assert len(outs) == len(prompts)                     # zero loss
    assert all(o.state == "finished" for o in outs.values())
    for gid, out in outs.items():
        cid = router.client_id(gid)
        assert list(out.token_ids) == solo_generate(solo, prompts[cid], 4)
    snap = router.registry.snapshot()
    assert snap["router/failovers_total"] == 1.0
    assert snap["router/requeued_total"] >= 1.0
    assert snap["router/restarts_total"] == 1.0
    assert snap["router/replicas_alive"] == 3.0          # back in rotation
    assert validate_jsonl("router_stats", stats_path) == len(prompts)
    recs = [json.loads(l) for l in open(stats_path)]
    assert sum(1 for r in recs if r["requeues"] > 0) >= 1
    router.close()


def test_fleet_shadow_resync_after_restart(fleet_pool):
    """A restarted replica's engine is cold; the router's shadow must not
    keep crediting it with the dead engine's chains."""
    cfg, pool, _ = fleet_pool
    router = FleetRouter(
        [Replica(i, _paged_factory(pool), backoff_base_s=0.0)
         for i in range(2)],
        policy="prefix_affinity")
    rs = np.random.RandomState(3)
    p = rs.randint(1, cfg.vocab_size, size=8).tolist()
    router.submit(Request(request_id=0, prompt_ids=p, max_new_tokens=2))
    router.run_until_complete(max_steps=100)
    hot = [rid for rid, sh in router.shadows.items() if sh.fps]
    assert hot                                            # credit happened
    victim = router.replicas[hot[0]]
    router.submit(Request(request_id=1, prompt_ids=p, max_new_tokens=2))
    install_plan({"faults": [{
        "point": "fleet/replica_step", "action": "exception",
        "match": {"replica": hot[0]}, "count": 1}]})
    try:
        router.run_until_complete(max_steps=100)
    finally:
        clear_plan()
    # the victim restarted (backoff 0) with an empty index; its shadow
    # resynced to that truth instead of keeping phantom chains
    assert router.replicas[hot[0]].alive
    assert router.shadows[hot[0]].fps == victim.prefix_fingerprints()
    router.close()


# -- CLI rungs (out of tier-1) ----------------------------------------------

@pytest.mark.slow
def test_runner_serve_replicas_cli(tmp_path):
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stats = str(tmp_path / "router_stats.jsonl")
    proc = run_cli(
        os.path.join(repo, "examples", "inference", "runner.py"),
        "serve", "--preset", "tiny", "--dtype", "float32", "--batch-size", "2",
        "--context-len", "16", "--max-total-len", "32",
        "--max-new-tokens", "4", "--num-requests", "6", "--rate", "1000",
        "--page-size", "8", "--replicas", "3",
        "--routing", "prefix_affinity", "--stats-out", stats, "--quiet")
    summary = last_json_line(proc.stdout)
    assert summary["replicas"] == 3
    assert summary["routing"] == "prefix_affinity"
    assert summary["finished"] == 6
    assert summary["dispatched"] >= 6
    assert validate_jsonl("router_stats", stats) == 6


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_bench_cli():
    """All three acceptance rungs — N-replica goodput scaling, affinity >
    random prefix-hit rate, zero-loss failover — pass on the CPU smoke."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = run_cli(os.path.join(repo, "tools", "fleet_bench.py"), "--tiny",
                   "--num-requests", "12", "--max-new-tokens", "4")
    rungs = [json.loads(l) for l in proc.stdout.strip().splitlines()
             if l.startswith("{")]
    assert {r["rung"] for r in rungs} == {"scale", "affinity", "failover"}
    assert all(r["ok"] for r in rungs)
    aff = next(r for r in rungs if r["rung"] == "affinity")
    assert (aff["prefix_affinity"]["prefix_hit_rate"]
            > aff["random"]["prefix_hit_rate"])
    fo = next(r for r in rungs if r["rung"] == "failover")
    assert fo["lost"] == 0 and fo["requeued"] >= 1
