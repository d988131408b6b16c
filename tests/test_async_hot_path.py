"""Async hot path (perf PR): device-prefetch input pipeline, deferred
metrics in ``fit()``, pipelined serving decode, and the transfer audit that
makes the no-implicit-transfer invariant enforceable.

Assurance layers (all structural — counters, drains, exact parity — never
wall-clock, so they stay CI-safe):

- **DevicePrefetcher properties** — ordered step-indexed delivery, rewind
  (restage-at-step) semantics, iterator adaptation + exhaustion, error
  propagation, and deterministic drain (no leaked thread, no stale staged
  batch);
- **fit() parity + audit** — the deferred one-step-late metric pipeline is
  loss-identical (EXACT float equality on CPU) to the synchronous loop; the
  steady-state loop under ``transfer_guard="forbid"`` makes zero implicit
  transfers (the h2d guard has real teeth on the CPU mesh) and exactly one
  explicit packed fetch per step/cadence; a host-batch loop under the same
  guard is the negative control;
- **the tier-1 drain smoke** — ``fit(prefetch=2)`` over 20 steps drains
  cleanly on early stop, on a real in-process SIGTERM checkpoint, and
  through a policy rollback (the staged pipeline rewinds to the
  rolled-back step, parity-tested against the unprefetched run);
- **serving pipelining** — the pipelined decode loop's outputs
  token-identical to the solo ``generate`` (greedy under staggered arrivals
  + slot reuse, and a sampled per-request rng stream), with ONE packed
  fetch + ONE packed put per steady engine step, counted by the transfer
  audit;
- **the decode loop one step ahead** — step N+1 is launched from step N's
  tokens on the device before N is fetched: token identity with solo
  ``generate`` whatever stops a request (length, a stop token mid-page and
  at a page's last row, ``eos_token_id``; greedy and sampled; fresh and
  continuing slots in one program), an overrun row reaching nothing, the
  two counters, a request that leaves with a step in flight, and the pool
  after an overrun held cell for cell to the old order's.
"""

import os
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import neuronx_distributed_tpu as nxd
from conftest import (
    lockstep_with_the_old_order,
    readable_cache,
    sharded_params,
    solo_generate,
)
from neuronx_distributed_tpu.data.prefetch import DevicePrefetcher
from neuronx_distributed_tpu.obs import MetricRegistry, Observability, TransferAudit
from neuronx_distributed_tpu.resilience import AnomalyPolicy, clear_plan, install_plan
from neuronx_distributed_tpu.trainer import (
    Callback,
    default_batch_spec,
    fit,
    initialize_parallel_model,
    initialize_parallel_optimizer,
)
from test_trainer import TinyLM, _data, lm_loss


def _live_prefetch_threads():
    return [t for t in threading.enumerate() if "prefetch" in t.name]


# -- DevicePrefetcher properties --------------------------------------------


def test_prefetcher_streams_in_order_with_gauges():
    reg = MetricRegistry()
    pf = DevicePrefetcher(lambda s: {"x": np.full((2,), s, np.int32)},
                          depth=3, registry=reg)
    for step in range(8):
        got = pf.get(step)
        assert int(np.asarray(got["x"])[0]) == step
        assert isinstance(got["x"], jax.Array)  # staged, not host
    pf.close()
    snap = reg.snapshot()
    assert snap["data/prefetch_batches_staged_total"] >= 8.0
    assert snap["data/prefetch_rewinds_total"] == 0.0
    assert snap["data/prefetch_wait_ms"]["count"] == 8
    assert snap["data/prefetch_queue_depth"] == 0.0  # close resets
    assert _live_prefetch_threads() == []


def test_prefetcher_rewind_restages_at_requested_step():
    reg = MetricRegistry()
    calls = []

    def source(step):
        calls.append(step)
        return np.full((1,), step, np.int32)

    with DevicePrefetcher(source, depth=2, registry=reg) as pf:
        assert int(np.asarray(pf.get(0))[0]) == 0
        assert int(np.asarray(pf.get(1))[0]) == 1
        assert int(np.asarray(pf.get(2))[0]) == 2
        # rollback: re-request an earlier step — the pipeline flushes and
        # restages from exactly there
        assert int(np.asarray(pf.get(1))[0]) == 1
        assert int(np.asarray(pf.get(2))[0]) == 2
        assert pf.rewinds == 1
    assert reg.snapshot()["data/prefetch_rewinds_total"] == 1.0
    # the source was re-called for the rewound steps (fresh staging, no
    # stale batch replay)
    assert calls.count(1) >= 2
    assert _live_prefetch_threads() == []


def test_a_rewind_retires_its_worker_before_the_next_one_starts():
    """The worker a rewind retires used to be left to notice on its own
    time: under load it outlived ``close()`` (which joins the newest worker
    only) and could call the source beside its successor.  More threads
    than cores and a short switch interval, up to 200 rewinds in 6 s: never
    two calls of the source at once, never a thread behind."""
    import sys
    import time

    busy = threading.Event()
    in_source = []
    overlaps = []

    def source(step):
        in_source.append(step)
        if len(in_source) > 1:
            overlaps.append(tuple(in_source))
        time.sleep(0)                  # give another worker its chance
        in_source.pop()
        return np.full((1,), step, np.int32)

    def hog():
        while not busy.is_set():
            sum(range(500))

    hogs = [threading.Thread(target=hog, daemon=True) for _ in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for h in hogs:
            h.start()
        deadline = time.monotonic() + 6.0
        for _ in range(100):
            with DevicePrefetcher(source, depth=2) as pf:
                for step in (0, 1, 2, 1, 2, 0):
                    assert int(np.asarray(pf.get(step))[0]) == step
                assert pf.rewinds == 2
            assert _live_prefetch_threads() == []
            if time.monotonic() > deadline:
                break
    finally:
        busy.set()
        sys.setswitchinterval(interval)
        for h in hogs:
            h.join(timeout=5.0)
    assert not any(h.is_alive() for h in hogs)
    assert overlaps == []


def test_prefetcher_iterator_source_exhausts_and_cannot_rewind():
    pf = DevicePrefetcher(iter([{"x": np.zeros(1)} for _ in range(3)]), depth=2)
    for step in range(3):
        pf.get(step)
    with pytest.raises(StopIteration):
        pf.get(3)
    pf.close()

    pf2 = DevicePrefetcher(iter([{"x": np.zeros(1)} for _ in range(8)]), depth=2)
    pf2.get(0), pf2.get(1)
    with pytest.raises(RuntimeError, match="cannot rewind"):
        pf2.get(0)
    pf2.close()
    assert _live_prefetch_threads() == []


def test_prefetcher_source_error_surfaces_on_get():
    def source(step):
        if step == 2:
            raise ValueError("bad shard")
        return np.zeros(1)

    with DevicePrefetcher(source, depth=2) as pf:
        pf.get(0), pf.get(1)
        with pytest.raises(ValueError, match="bad shard"):
            pf.get(2)
    assert _live_prefetch_threads() == []


def test_prefetcher_close_unblocks_worker_stuck_on_full_queue():
    pf = DevicePrefetcher(lambda s: np.zeros(4), depth=1)
    pf.get(0)  # starts the worker; queue (depth 1) fills and put blocks
    import time

    time.sleep(0.2)  # let the worker wedge on the full queue
    pf.close()
    assert _live_prefetch_threads() == []
    with pytest.raises(RuntimeError, match="closed"):
        pf.get(1)


# -- fit(): deferred metrics parity + transfer audit ------------------------


@pytest.fixture
def config(devices8):
    return nxd.training_config(tensor_parallel_size=2, learning_rate=5e-3)


def _bs():
    return {"ids": default_batch_spec(), "labels": default_batch_spec()}


def _host_data(step):
    b = _data(jax.random.PRNGKey(100 + step))
    return {k: np.asarray(v) for k, v in b.items()}  # HOST batches


def _build(config):
    m = initialize_parallel_model(config, TinyLM, (jnp.zeros((1, 8), jnp.int32),))
    o = initialize_parallel_optimizer(config, m)
    return m, o


@pytest.mark.perf
def test_fit_deferred_metrics_loss_identical_to_sync(config):
    """Acceptance bar: the deferred (one-step-late, pipelined-fetch) loop
    reproduces the synchronous loop's per-step losses with EXACT float
    equality, and the eval cadence history matches too."""
    runs = {}
    for mode in (False, True):
        losses = []
        m, o = _build(config)
        res = fit(config, m, o, _host_data, steps=8, loss_fn=lm_loss,
                  batch_spec=_bs(), log_every=0, defer_metrics=mode,
                  eval_data=_host_data, eval_every=3,
                  on_step=lambda s, mm: losses.append((s, mm["loss"])))
        runs[mode] = (losses, res.eval_history, res.final_loss)
    assert runs[True][0] == runs[False][0], "deferred losses diverged"
    assert runs[True][1] == runs[False][1], "eval history diverged"
    assert runs[True][2] == runs[False][2]


def test_fit_defer_auto_keeps_sync_semantics_and_validates(config):
    """auto-defer must not change observable semantics for loops with step
    callbacks: should_stop still stops after the CURRENT step; and the
    explicit-config contracts raise."""

    class StopAt2(Callback):
        def on_step(self, step, metrics):
            if step == 2:
                self.should_stop = True

    m, o = _build(config)
    res = fit(config, m, o, _host_data, steps=10, loss_fn=lm_loss,
              batch_spec=_bs(), log_every=0, callbacks=[StopAt2()],
              prefetch=2)
    assert res.steps_run == 3  # sync semantics preserved under auto
    assert _live_prefetch_threads() == []

    m, o = _build(config)
    with pytest.raises(ValueError, match="defer_metrics=True is incompatible"):
        fit(config, m, o, _host_data, steps=2, loss_fn=lm_loss,
            batch_spec=_bs(), log_every=0, defer_metrics=True,
            ckpt_dir="/tmp/unused", policy=AnomalyPolicy(on_nan="skip"))
    with pytest.raises(ValueError, match="prefetch=N.* needs batch_spec"):
        fit(config, m, o, _host_data, steps=2, loss_fn=lm_loss,
            log_every=0, prefetch=2)
    with pytest.raises(ValueError, match="incompatible with timeline"):
        from neuronx_distributed_tpu.utils.timeline import Timeline

        fit(config, m, o, _host_data, steps=2, loss_fn=lm_loss,
            batch_spec=_bs(), log_every=0, defer_metrics=True,
            timeline=Timeline("/tmp/unused_trace.json"))


@pytest.mark.perf
def test_fit_steady_state_transfer_guard_and_fetch_accounting(config, tmp_path):
    """The transfer-audit acceptance bar: the steady-state deferred loop
    under ``transfer_guard="forbid"`` performs ZERO implicit transfers
    (jax's h2d guard enforces for real on the CPU mesh) and EXACTLY one
    explicit packed fetch per step plus one per eval cadence; the same loop
    fed host batches without prefetch is the negative control."""
    obs = Observability(str(tmp_path / "obs"), detectors=[])
    m, o = _build(config)
    res = fit(config, m, o, _host_data, steps=6, loss_fn=lm_loss,
              batch_spec=_bs(), log_every=0, defer_metrics=True,
              prefetch=2, transfer_guard="forbid", obs=obs,
              eval_data=_host_data, eval_every=3)
    assert res.steps_run == 6
    snap = obs.registry.snapshot()
    # 6 per-step packed fetches + 2 eval-cadence fetches, nothing else
    assert snap["transfer/explicit_fetches_total"] == 8.0
    assert snap["train/host_blocked_ms"]["count"] == 8
    assert snap["transfer/guarded_sections_total"] == 6.0
    assert snap["data/prefetch_batches_staged_total"] >= 6.0

    # negative control: host batches straight into the jitted step are an
    # implicit h2d transfer — the guard must refuse them
    m, o = _build(config)
    with pytest.raises(Exception, match="Disallowed host-to-device"):
        fit(config, m, o, _host_data, steps=2, loss_fn=lm_loss,
            batch_spec=_bs(), log_every=0, defer_metrics=True,
            transfer_guard="forbid")


@pytest.mark.perf
def test_fit_prefetch_drain_smoke(config, tmp_path):
    """Tier-1 drain smoke (satellite): fit(prefetch=2) for 20 steps drains
    the staging thread cleanly on (a) callback early stop, (b) a real
    in-process SIGTERM checkpoint, (c) a policy rollback — which must also
    rewind the staged pipeline to the rolled-back step with a loss
    trajectory identical to the unprefetched run."""
    # (a) early stop
    class StopAt5(Callback):
        def on_step(self, step, metrics):
            if step == 5:
                self.should_stop = True

    m, o = _build(config)
    res = fit(config, m, o, _host_data, steps=20, loss_fn=lm_loss,
              batch_spec=_bs(), log_every=0, prefetch=2,
              callbacks=[StopAt5()])
    assert res.steps_run == 6
    assert _live_prefetch_threads() == []

    # (b) SIGTERM: the signal lands mid-run, the loop finishes the step,
    # writes the final checkpoint, and the prefetcher is drained
    class KillAt4(Callback):
        def on_step(self, step, metrics):
            if step == 4:
                os.kill(os.getpid(), signal.SIGTERM)

    ck = str(tmp_path / "ck_sig")
    m, o = _build(config)
    res = fit(config, m, o, _host_data, steps=20, loss_fn=lm_loss,
              batch_spec=_bs(), log_every=0, prefetch=2, ckpt_dir=ck,
              checkpoint_on_signal=True, callbacks=[KillAt4()])
    assert 0 < res.steps_run < 20
    tags = [d for d in os.listdir(ck) if d.startswith("step_")]
    assert f"step_{res.steps_run}" in tags
    assert _live_prefetch_threads() == []
    # fit restored the previous SIGTERM disposition
    assert signal.getsignal(signal.SIGTERM) in (signal.SIG_DFL, signal.default_int_handler)

    # (c) policy rollback rewinds the staged pipeline (no stale batch)
    def run(prefetch, ckpt_dir, registry_obs=None):
        install_plan({"faults": [
            {"point": "fit/loss", "action": "nan", "match": {"step": 7}}]})
        losses = []
        try:
            m, o = _build(config)
            res = fit(config, m, o, _host_data, steps=12, loss_fn=lm_loss,
                      batch_spec=_bs(), log_every=0, prefetch=prefetch,
                      ckpt_dir=ckpt_dir, ckpt_every=5, obs=registry_obs,
                      policy=AnomalyPolicy(on_nan="rollback", max_rollbacks=2),
                      on_step=lambda s, mm: losses.append((s, mm["loss"])))
        finally:
            clear_plan()
        return losses, res

    obs = Observability(str(tmp_path / "obs_rb"), detectors=[])
    pf_losses, pf_res = run(2, str(tmp_path / "ck_rb_pf"), obs)
    raw_losses, raw_res = run(0, str(tmp_path / "ck_rb_raw"))
    assert [e["action"] for e in pf_res.policy_events] == ["rollback"]
    assert [e["action"] for e in raw_res.policy_events] == ["rollback"]
    assert pf_losses == raw_losses, "rollback trajectory diverged under prefetch"
    assert obs.registry.snapshot()["data/prefetch_rewinds_total"] == 1.0
    assert _live_prefetch_threads() == []


# -- serving: pipelined decode parity + packed-fetch accounting -------------


@pytest.fixture
def pool_factory(devices8):
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
    from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

    initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none")
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((3, 8), jnp.int32)))

    def make(batch_size=3):
        return ParallelInferenceModel(
            module, params,
            InferenceConfig(batch_size=batch_size, context_len=8,
                            max_total_len=16, kv_cache_dtype=jnp.float32))

    return cfg, make


@pytest.mark.perf
def test_serving_pipelined_token_identical_to_solo_generate(pool_factory):
    """Acceptance bar: the pipelined engine's outputs are token-identical
    to the solo ``generate`` of each prompt on the same weights — greedy
    under staggered arrivals with slot reuse (5 requests over 3 slots), and
    a sampled request on its per-request rng stream — and streaming
    callbacks still see every token in order."""
    from neuronx_distributed_tpu.serving import Request, SamplingParams, ServingEngine

    cfg, make = pool_factory
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 8)).tolist()
               for _ in range(5)]
    rng = jax.random.PRNGKey(42)
    temps = {i: 0.8 if i == 2 else 0.0 for i in range(5)}

    streamed = {}
    engine = ServingEngine(make(), page_size=4, rng=rng)
    outs = {}

    def submit(i):
        engine.submit(Request(
            request_id=i, prompt_ids=prompts[i], max_new_tokens=4 + i,
            sampling=SamplingParams(temperature=temps[i]),
            stream_cb=lambda r, t: streamed.setdefault(
                r.request_id, []).append(t)))

    for i in range(3):
        submit(i)
    for out in engine.step():
        outs[out.request_id] = out
    for i in range(3, 5):  # late joiners: slot reuse mid-decode
        submit(i)
    for out in engine.run_until_complete(max_steps=200):
        outs[out.request_id] = out

    solo = make(batch_size=1)
    assert set(outs) == set(range(5))
    for i, out in outs.items():
        kw = (dict(temperature=temps[i], rng=rng, request_ids=[i])
              if temps[i] else {})
        want = solo_generate(solo, prompts[i], 4 + i, **kw)
        assert list(out.token_ids) == want, f"request {i} diverged"
        assert out.finish_reason == "length"
        assert streamed[i] == want  # every token streamed, in order


@pytest.mark.perf
def test_serving_one_packed_fetch_and_put_per_steady_step(pool_factory):
    """Acceptance bar: one packed explicit fetch (tokens + finite flags)
    and one packed explicit put (token feed / offsets / indices) per
    steady-state engine step, under the real transfer guard — and the host
    wait exports as serving/host_blocked_ms."""
    from neuronx_distributed_tpu.serving import Request, ServingEngine, replay_trace

    _, make = pool_factory
    engine = ServingEngine(make(), page_size=4, transfer_guard="forbid")
    engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3],
                          max_new_tokens=8))
    engine.step()  # admission step (prefill fetch happens here)
    snap0 = engine.registry.snapshot()
    for _ in range(5):
        engine.step()
    snap1 = engine.registry.snapshot()
    assert snap1["transfer/explicit_fetches_total"] \
        - snap0["transfer/explicit_fetches_total"] == 5.0
    assert snap1["transfer/explicit_puts_total"] \
        - snap0["transfer/explicit_puts_total"] == 5.0
    assert snap1["serving/host_blocked_ms"]["count"] \
        >= snap0["serving/host_blocked_ms"]["count"] + 5

    # replay_trace over a fresh engine: every fetch the drive loop causes
    # is a packed, audited one (fetch count == host_blocked observations)
    engine2 = ServingEngine(make(), page_size=4, transfer_guard="forbid")
    reqs = [Request(request_id=i, prompt_ids=[1, 2, 3], max_new_tokens=4)
            for i in range(4)]
    outs = replay_trace(engine2, [0.0, 0.0, 0.0, 0.01], reqs)
    assert len(outs) == 4
    snap = engine2.registry.snapshot()
    assert snap["transfer/explicit_fetches_total"] == \
        snap["serving/host_blocked_ms"]["count"]
    assert snap["transfer/explicit_fetches_total"] <= engine2._steps + 4


# -- serving: the decode loop one step ahead --------------------------------

C_, PAGE_ = 8, 4          # pool_factory's context_len and the page the tests use


def _cut_at_stop(tokens, max_new, stops):
    """What a request generates: the solo tokens up to ``max_new``, cut
    after the first one in ``stops`` (the stop token itself is kept)."""
    out = []
    for t in tokens[:max_new]:
        out.append(t)
        if t in stops:
            break
    return out


def _first_seen_at(tokens, j, last):
    """The least index >= ``j`` (and <= ``last``) at which ``tokens`` shows a
    token for the first time: a stop on it ends the request exactly there."""
    for k in range(j, last + 1):
        if tokens[k] not in tokens[:k]:
            return k
    raise AssertionError(f"no fresh token in {tokens} from {j} to {last}")


def _committed_offsets_hold(engine):
    """Between steps a decoding slot's COMMITTED offset is where its last
    token will be written — whatever is in flight was advanced on credit at
    the launch and is not in ``_offsets`` — and every other slot is parked."""
    from neuronx_distributed_tpu.serving import RequestState

    live = {}
    for slot, req in engine.scheduler.active():
        if req.state is RequestState.DECODE:
            live[slot] = engine.C + len(req.generated) - 1
    for slot in range(engine.B):
        assert engine._offsets[slot] == live.get(slot, engine.T), (
            slot, engine._offsets, live)


def _drain(engine, outs, max_steps=300):
    """Step to the end, holding the committed offsets and the allocator's
    invariants after every step."""
    steps = 0
    while engine.has_work:
        for o in engine.step():
            outs[o.request_id] = o
        _committed_offsets_hold(engine)
        engine._kv.assert_invariants()
        steps += 1
        assert steps < max_steps


@pytest.fixture
def ahead_case(pool_factory):
    """Six prompts and what each generates alone, greedy and sampled."""
    cfg, make = pool_factory
    rs = np.random.RandomState(11)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(6)]
    rng = jax.random.PRNGKey(42)
    solo = make(batch_size=1)

    def alone(i, sampled, n=8):
        kw = (dict(temperature=0.8, rng=rng, request_ids=[i])
              if sampled else {})
        return solo_generate(solo, prompts[i], n, **kw)

    return cfg, make, prompts, rng, alone


@pytest.mark.perf
@pytest.mark.parametrize("eos", [False, True], ids=["stop_ids", "eos"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_runahead_token_identical_whatever_stops_a_request(ahead_case,
                                                           sampled, eos):
    """Acceptance bar of the run-ahead loop: six requests over three slots,
    admitted at different steps so that fresh and continuing slots share a
    program, stopped by length, by a stop token mid-page, by a stop token
    whose step wrote a page's LAST row (the overrun lands on the next
    page's first), and by the engine's ``eos_token_id`` — each generates
    what it generates alone, streams exactly that, and nothing else is
    counted."""
    from neuronx_distributed_tpu.serving import Request, SamplingParams, ServingEngine

    cfg, make, prompts, rng, alone = ahead_case
    want = {i: alone(i, sampled) for i in range(6)}
    max_new = {0: 5, 1: 7, 2: 7, 3: 6, 4: 7, 5: 3}
    # stop on the token first seen at (or after) index 2 / 4 / 1: request 2's
    # stopping step writes cell C + 3, the last row of its page
    at = {1: _first_seen_at(want[1], 2, 5), 2: _first_seen_at(want[2], 4, 5),
          4: _first_seen_at(want[4], 1, 5)}
    stop_ids = {i: (want[i][j],) for i, j in at.items()}
    eos_id = want[0][_first_seen_at(want[0], 3, 3)] if eos else None
    stops = {i: set(stop_ids.get(i, ())) | ({eos_id} if eos else set())
             for i in range(6)}
    expect = {i: _cut_at_stop(want[i], max_new[i], stops[i]) for i in range(6)}
    lands = {(C_ + len(expect[i]) - 1) % PAGE_ for i in at
             if expect[i][-1] in stops[i]}
    assert 0 in lands and lands - {0}, f"stops land at {lands}: {expect}"

    streamed = {}
    engine = ServingEngine(make(), page_size=PAGE_, rng=rng,
                           eos_token_id=eos_id)

    def submit(i):
        engine.submit(Request(
            request_id=i, prompt_ids=prompts[i], max_new_tokens=max_new[i],
            stop_token_ids=stop_ids.get(i, ()),
            sampling=SamplingParams(temperature=0.8 if sampled else 0.0),
            stream_cb=lambda r, t: streamed.setdefault(
                r.request_id, []).append(t)))

    outs = {}
    for i in range(3):
        submit(i)
    for _ in range(3):               # all three decoding, a step in flight
        for o in engine.step():
            outs[o.request_id] = o
    for i in range(3, 6):            # late joiners: fresh rows beside fed ones
        submit(i)
    _drain(engine, outs)
    assert set(outs) == set(range(6))
    for i in range(6):
        assert list(outs[i].token_ids) == expect[i], f"request {i} diverged"
        assert streamed[i] == expect[i]
        by_token = expect[i][-1] in stops[i]
        assert outs[i].finish_reason == ("stop_token" if by_token
                                         else "length")
    snap = engine.registry.snapshot()
    assert snap["serving/tokens_total"] == sum(map(len, expect.values()))
    # one row a request that a TOKEN stopped in a decode step short of its
    # length; none for a stop by length
    assert snap["serving/decode_overrun_rows_total"] == sum(
        1 for i in range(6) if expect[i][-1] in stops[i]
        and 1 < len(expect[i]) < max_new[i])
    engine.scheduler.assert_invariants()


@pytest.mark.perf
@pytest.mark.parametrize("how", ["stop_token", "eos", "non_finite", "length"])
def test_an_overrun_row_reaches_nothing(ahead_case, how):
    """A request that a token (or a non-finite row) stops is found one
    launch late: the row already queued for it is counted as an overrun and
    its token reaches neither ``generated``, the stream, the stats nor
    ``serving/tokens_total``; its co-batch never notices.  A stop by length
    is a count the host holds: no row, no overrun."""
    from neuronx_distributed_tpu.serving import Request, ServingEngine

    cfg, make, prompts, rng, alone = ahead_case
    want = {i: alone(i, False) for i in (1, 2)}
    j = _first_seen_at(want[1], 2, 5)
    stop = want[1][j]
    engine = ServingEngine(make(), page_size=PAGE_,
                           eos_token_id=stop if how == "eos" else None)
    streamed = {}
    for i in (1, 2):
        engine.submit(Request(
            request_id=i, prompt_ids=prompts[i], max_new_tokens=7,
            stop_token_ids=(stop,) if how == "stop_token" and i == 1 else (),
            stream_cb=lambda r, t: streamed.setdefault(
                r.request_id, []).append(t)))
    outs = {}
    if how == "non_finite":
        from conftest import step_until_decoding

        step_until_decoding(engine)
        slot = {r.request_id: s for s, r in engine.scheduler.active()}[1]
        install_plan({"faults": [{"point": "serving/decode_logits",
                                  "action": "nan", "slot": slot}]})
    try:
        _drain(engine, outs)
    finally:
        clear_plan()
    expect = {i: _cut_at_stop(want[i], 7, {stop} if how == "eos" or (
        how == "stop_token" and i == 1) else ()) for i in (1, 2)}
    if how == "non_finite":
        assert outs[1].state == "failed"
        expect[1] = list(outs[1].token_ids)
        assert expect[1] == want[1][:len(expect[1])] and len(expect[1]) < 7
    for i in (1, 2):
        assert list(outs[i].token_ids) == expect[i]
        assert streamed[i] == expect[i]
    snap = engine.registry.snapshot()
    assert snap["serving/tokens_total"] == len(expect[1]) + len(expect[2])
    # found in a decode step's fetch (a first token that stops is found at
    # the prefill's own fetch: no decode row was ever launched for it)
    late = (1 if how == "non_finite"
            else sum(1 < len(expect[i]) < 7 for i in (1, 2)))
    assert late == (how != "length") or how == "eos"
    assert snap["serving/decode_overrun_rows_total"] == late


@pytest.mark.perf
def test_runahead_is_every_decode_step_but_the_first_of_a_busy_stretch(
        pool_factory):
    """``serving/decode_runahead_total`` counts the decode steps launched
    behind an unfetched one: over two busy stretches, every step but each
    stretch's first — and the loop still makes one fetch a decode step."""
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.serving.engine import SAMPLER_PATHS

    _, make = pool_factory
    engine = ServingEngine(make(), page_size=PAGE_, transfer_guard="forbid")

    def decode_steps():
        snap = engine.registry.snapshot()
        return sum(snap[f"serving/sampler_steps_total/{p}"]
                   for p in SAMPLER_PATHS)

    for stretch, n in enumerate((6, 4), start=1):
        for rid in range(2):
            engine.submit(Request(request_id=10 * stretch + rid,
                                  prompt_ids=[1, 2, 3 + rid],
                                  max_new_tokens=n + rid))
        assert len(engine.run_until_complete(max_steps=100)) == 2
        assert not engine.has_work and not engine._inflight
        snap = engine.registry.snapshot()
        assert decode_steps() > 2 * stretch
        assert snap["serving/decode_runahead_total"] \
            == decode_steps() - stretch
    assert snap["serving/decode_overrun_rows_total"] == 0.0


@pytest.mark.perf
@pytest.mark.parametrize("how", ["cancel", "timeout", "preempt"])
def test_a_request_that_leaves_with_a_step_in_flight(ahead_case, how):
    """Cancelled, timed out or preempted between two steps — a decode is in
    flight then: the token in flight is dropped with the advance its launch
    took on credit (the committed offset stands, :func:
    `_committed_offsets_hold` after every step), a cancelled or expired
    request keeps exactly the tokens it had, a preempted one resumes
    token-identically, and the co-batch generates what it generates alone."""
    from neuronx_distributed_tpu.serving import Request, ServingEngine

    cfg, make, prompts, rng, alone = ahead_case
    t = [0.0]
    engine = ServingEngine(make(), page_size=PAGE_, num_pages=13,
                           clock=lambda: t[0])
    outs = {}
    for i in range(3):
        engine.submit(Request(
            request_id=i, prompt_ids=prompts[i], max_new_tokens=8,
            priority="batch" if how == "preempt" else "interactive",
            deadline_s=5.0 if how == "timeout" and i == 1 else None))
    for _ in range(5):
        for o in engine.step():
            outs[o.request_id] = o
        _committed_offsets_hold(engine)
    assert engine._inflight and not outs
    had = {r.request_id: list(r.generated)
           for _, r in engine.scheduler.active()}
    assert all(len(g) >= 2 for g in had.values())
    if how == "cancel":
        assert engine.cancel(1)
    elif how == "timeout":
        t[0] = 10.0
    else:
        engine.submit(Request(request_id=3, prompt_ids=prompts[3],
                              max_new_tokens=3, priority="interactive"))
    _drain(engine, outs)
    snap = engine.registry.snapshot()
    full = {i: alone(i, False) for i in range(4)}
    if how == "preempt":
        assert snap["serving/preemptions_total"] >= 1.0
        assert any(o.preemptions for o in outs.values())
        assert list(outs[3].token_ids) == full[3][:3]
    else:
        assert outs[1].state == ("cancelled" if how == "cancel"
                                 else "timed_out")
        # what it had committed, and not the token that was in flight
        assert list(outs[1].token_ids) == had[1] == full[1][:len(had[1])]
    for i in range(3):
        if how == "preempt" or i != 1:
            assert list(outs[i].token_ids) == full[i], f"request {i}"
    assert snap["serving/decode_overrun_rows_total"] == 0.0
    evictable = engine._kv.index.evictable_pages()
    assert engine._kv.alloc.in_use == evictable, "leaked pages"


@pytest.mark.perf
def test_pool_after_an_overrun_is_cell_for_cell_the_old_orders(ahead_case):
    """Pool safety: the same requests through the run-ahead loop and through
    the order it replaced (fetch, then launch: no row is ever computed for a
    stopped request), step for step.  After every step — the one in which a
    stop token is found and its overrun row is already queued among them —
    every page the prefix index holds, every valid cell of every live slot
    (the next occupant of the released slot too) and every output is bit
    for bit the same, and both allocators pass their invariants.  An
    overrun writes a decode page only, and the index holds prompt pages."""
    from neuronx_distributed_tpu.serving import Request, ServingEngine

    cfg, make, prompts, rng, alone = ahead_case
    # prompts of whole pages (4 or 8 tokens): an index page has no pad cell
    # that only a former occupant of the physical page could have written
    whole = {i: (prompts[i] * 3)[:8 if i % 2 else 4] for i in range(6)}
    solo = make(batch_size=1)
    want = {i: solo_generate(solo, whole[i], 8) for i in range(6)}
    # two of the first three stop on a token: one whose stopping step wrote
    # its page's last row (index 4: the overrun opens the next page), one
    # mid-page
    fresh = {i: [k for k in range(1, 6) if want[i][k] not in want[i][:k]]
             for i in range(3)}
    last_row = next(i for i in range(3) if 4 in fresh[i])
    mid = next(i for i in range(3) if i != last_row and {2, 3} & set(fresh[i]))
    at = {last_row: 4, mid: min({2, 3} & set(fresh[mid]))}

    def requests():
        return [Request(request_id=i, prompt_ids=whole[i], max_new_tokens=7,
                        stop_token_ids=(want[i][at[i]],) if i in at else ())
                for i in range(6)]

    ahead, old, got = lockstep_with_the_old_order(
        lambda: ServingEngine(make(), page_size=PAGE_, num_pages=14),
        requests)
    assert len(got) == 6
    for i in at:
        assert got[i][1] == "stop_token"
        assert list(got[i][2]) == want[i][:at[i] + 1]
    assert ahead.registry.snapshot()[
        "serving/decode_overrun_rows_total"] == len(at)
    assert old.registry.snapshot()["serving/decode_overrun_rows_total"] == 0
    assert old.registry.snapshot()["serving/decode_runahead_total"] == 0
    assert any(k[0] == "index" for k in readable_cache(ahead))
