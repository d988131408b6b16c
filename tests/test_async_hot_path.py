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
  audit.
"""

import os
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import neuronx_distributed_tpu as nxd
from conftest import sharded_params, solo_generate
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
