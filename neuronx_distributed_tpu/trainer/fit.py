"""`fit()` — the batteries-included training loop.

Closes the reference's Lightning residual (VERDICT r3 Missing #1): what
``NeuronLTModule`` + Lightning's ``Trainer.fit`` orchestrate there —
train/eval cadence, checkpoint cadence and resume including skipping
consumed batches (reference ``lightning/module.py:24-103`` and the hand-
rolled loop in ``run_llama_nxd.py:233-257``) plus logging/metrics wiring —
was previously re-implemented by each example launcher (~100-300 lines
each).  One function owns it now; the launchers shrink to config + data +
``fit()``.

Design choices (TPU-native, not a PTL port):

- **The data source is step-indexed.**  ``data(step) -> batch`` makes exact
  resume trivial: restoring ``step`` from the checkpoint and continuing the
  loop IS skipping the consumed batches — no sampler state to serialize
  (the reference replays its DistributedSampler and manually fast-forwards,
  ``run_llama_nxd.py:233-257``).  Iterators are also accepted and fast-
  forwarded ``start_step`` times on resume.
- **One jitted step.**  ``make_train_step``'s donated-buffer step is the
  whole hot path; the loop never touches device data except the metric
  scalars it prints.
- **The hot path is asynchronous.**  ``prefetch=N`` stages batches onto the
  device ahead of the step that consumes them
  (:class:`~..data.prefetch.DevicePrefetcher`), and ``defer_metrics`` keeps
  the step's loss/grad-norm as device futures, fetched with one explicit
  packed ``device_get`` AFTER the next step is dispatched — the jit
  analogue of torch-xla's ``MpDeviceLoader`` staging + lazy-dispatch
  pipelining (SURVEY §L1): the device never idles waiting for the host.
  ``transfer_guard="forbid"`` makes the no-implicit-transfer invariant
  enforced (:mod:`~..obs.transfer_audit`), and the deferred loop is
  parity-tested loss-identical to the synchronous one.
- **LR/step state lives in the optimizer.**  Resume restores the optax
  count with the optimizer state, so schedules continue exactly (tested by
  the interrupted-vs-uninterrupted identity test).
"""

from __future__ import annotations

import dataclasses
import json
import signal as _signal
import threading
import time
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp

from neuronx_distributed_tpu.config import TrainingConfig
from neuronx_distributed_tpu.obs import startup
from neuronx_distributed_tpu.resilience.faults import fault_point, perturb
from neuronx_distributed_tpu.trainer.checkpoint import (
    load_checkpoint,
    newest_tag,
    save_checkpoint,
    wait_for_checkpoint,
)
from neuronx_distributed_tpu.trainer.metrics import Throughput, mfu
from neuronx_distributed_tpu.trainer.trainer import (
    make_eval_step,
    make_train_step,
)
from neuronx_distributed_tpu.utils.checkpoint_library import checkpoint_library
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)


@dataclasses.dataclass
class FitResult:
    """Outcome of :func:`fit`: final states plus summary numbers."""

    params: Any
    opt_state: Any
    final_loss: float
    steps_run: int
    start_step: int
    peak_seq_per_sec: float
    eval_history: list  # [(step, eval_loss)]
    policy_events: list = dataclasses.field(default_factory=list)
    # [{"action", "reason", "step", "message"}] — skips/rollbacks/watchdog
    # warnings taken by the AnomalyPolicy (empty without policy=)


class Callback:
    """Extension hooks for :func:`fit` — observe every cadence event
    without forking the loop (the reference's Lightning layer offers the
    same through ``NeuronLTModule``'s hook overrides,
    ``lightning/module.py:138-309``; here it is a plain object, no
    framework).

    Subclass and override any subset; all hooks default to no-ops.  Hooks
    receive plain Python data (step numbers, metric dicts with host floats
    for ``loss``/``grad_norm``/``seq_per_sec``; other entries may still be
    device scalars — convert with ``float()`` only if needed, each
    conversion is a device sync).  Setting ``self.should_stop = True``
    inside any hook ends the loop after the current step (early stopping);
    the final checkpoint and summary metrics are still written for the
    steps actually run."""

    should_stop: bool = False

    def on_fit_start(self, step: int, params: Any, opt_state: Any) -> None:
        """Called once before the first step; ``step`` is the resume
        start step (0 for a fresh run)."""

    def on_step(self, step: int, metrics: dict) -> None:
        """Called after every optimizer step with the step metrics."""

    def on_params(self, step: int, params: Any, opt_state: Any) -> None:
        """Called after every optimizer step with the LIVE device params
        (unlike :meth:`on_step`, which sees only host metrics).  This is
        the hand-off point for co-located serving: a callback may pass
        ``params`` straight to ``WeightSwapper.swap(..., source="memory")``
        to hot-swap a running engine without a checkpoint round-trip.
        Fires even on deferred-metrics iterations — the params are always
        current; only their metrics lag.  Do NOT mutate ``params``."""

    def on_eval(self, step: int, metrics: dict) -> None:
        """Called after each eval-cadence evaluation (``eval_loss`` key)."""

    def on_checkpoint(self, step: int, path: str) -> None:
        """Called after each checkpoint save (cadence and final)."""

    def on_fit_end(self, result: "FitResult") -> None:
        """Called once with the final :class:`FitResult`."""


@startup.phased("step0")
def fit(
    config: TrainingConfig,
    model: Any,
    optimizer: Any,
    data: "Callable[[int], dict] | Iterable[dict]",
    *,
    steps: int,
    loss_fn: Optional[Callable] = None,
    batch_spec: Optional[Any] = None,
    grad_accum_steps: int = 1,
    eval_data: "Callable[[int], dict] | None" = None,
    eval_every: int = 0,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 0,
    keep_ckpts: int = 3,
    resume: bool = False,
    async_save: bool = True,
    ckpt_save_dtype: Optional[Any] = None,
    log_every: int = 10,
    scalar_dir: Optional[str] = None,
    metrics: Optional[Any] = None,
    timeline: Optional[Any] = None,
    obs: "Any | str | None" = None,
    flops_per_token: Optional[float] = None,
    peak_flops: Optional[float] = None,
    step_rng: bool = False,
    on_step: Optional[Callable[[int, dict], None]] = None,
    callbacks: "tuple[Callback, ...] | list" = (),
    checkpoint_on_signal: bool = False,
    policy: "Any | None" = None,
    prefetch: int = 0,
    defer_metrics: "bool | str" = "auto",
    transfer_guard: str = "off",
) -> FitResult:
    """Run the training loop: steps, eval cadence, checkpoint cadence with
    resume, scalar/throughput logging.

    Args:
      data: ``data(step) -> batch`` (preferred — exact resume for free), or
        an iterable of batches (fast-forwarded on resume).
      steps: total global steps (the loop runs ``start_step..steps``).
      loss_fn / batch_spec / grad_accum_steps: forwarded to
        :func:`make_train_step` (``loss_fn`` unused for pipelined models).
      eval_data / eval_every: when both set, runs ``make_eval_step`` on
        ``eval_data(step)`` every ``eval_every`` steps, recorded in
        ``FitResult.eval_history`` (reference ``run_eval`` cadence).
      ckpt_dir / ckpt_every: tagged ``step_N`` checkpoints with rotation;
        ``resume=True`` restores the newest tag's params/opt state and
        continues from its recorded step.  A final checkpoint is always
        written when ``ckpt_dir`` is set.
      ckpt_save_dtype: e.g. ``jnp.bfloat16`` — downcast the MODEL payload
        on save (half-size checkpoints; optimizer masters stay fp32).
      metrics: a ``TrainingMetrics`` to fill with final summary numbers.
      timeline: a ``utils.Timeline`` for per-step host events.
      obs: an :class:`~..obs.Observability` instance, or a directory path
        (one is built there).  Wires the unified telemetry layer into the
        loop: per-step flight records with the host/device/data-wait time
        breakdown, anomaly detectors (NaN loss, loss spike, throughput
        regression), a compile-time HLO collective audit of the train step,
        registry dumps each ``log_every``, and a flight-record dump on
        crash/SIGTERM and at exit.  ``tools/obs_report.py`` merges the
        artifacts into one run summary.
      flops_per_token / peak_flops: enable the MFU summary metric.
      step_rng: pass a per-step PRNG key to the train step (dropout models);
        default None keeps deterministic-eval semantics.
      on_step: shorthand callback ``(step, metrics_dict)`` after every step
        (equivalent to a :class:`Callback` overriding only ``on_step``).
      callbacks: :class:`Callback` instances receiving every cadence event
        (fit start/end, step, eval, checkpoint); any callback setting
        ``should_stop`` ends the loop after the current step.
      checkpoint_on_signal: install SIGTERM/SIGINT handlers for the run
        (restored on exit): the first signal finishes the current step,
        writes the final checkpoint, and returns normally — TPU-pod
        maintenance events and preemptions send SIGTERM, so this turns a
        preemption into a clean ``resume=True`` restart instead of losing
        the work since the last cadence save.  Requires ``ckpt_dir``.
      policy: a :class:`~..resilience.AnomalyPolicy` — turns detections into
        actions instead of warnings.  NaN / loss-spike steps can be
        *skipped* (pre-step params and optimizer state restored — costs one
        device-side copy of both per step while armed — the batch counts as
        consumed, no eval/checkpoint/callbacks fire for the discarded step)
        or *rolled back* (reload the newest checkpoint, rewind the step
        counter and with it the step-indexed data position; requires
        ``ckpt_dir`` and callable ``data`` — an iterator cannot rewind; an
        initial checkpoint is written when none exists so a rollback target
        is always available).  Budgets (``max_skips`` / ``max_rollbacks``)
        raise ``RetriesExhausted`` when exhausted; the optional step-latency
        watchdog warns or halts on stalled steps.  Actions taken are
        returned in ``FitResult.policy_events`` and counted in the obs
        registry (``resilience/*_total``).  Policy actions force the
        synchronous metrics path (see ``defer_metrics``) — exact
        skip/rollback needs the step's loss on the host before the next
        step is dispatched.
      prefetch: staged-ahead depth for the device-prefetch input pipeline
        (0 = off).  ``prefetch=N`` wraps the data source in a
        :class:`~..data.prefetch.DevicePrefetcher`: a background thread
        calls ``data(step)`` up to ``N`` steps ahead and
        ``jax.device_put``'s each batch against the step's batch shardings,
        so the jitted step never blocks on a host→device copy.
        Step-indexed and rewindable: a policy rollback that rewinds the
        step counter flushes and restages the pipeline at the rolled-back
        step.  Requires ``batch_spec`` for non-pipelined models (the
        staging target sharding).  The prefetcher is drained (thread
        joined, staged batches dropped) on every exit path, including
        early stop and signal checkpointing.
      defer_metrics: ``"auto"`` (default) / ``True`` / ``False``.  When
        deferred, ``m["loss"]``/``m["grad_norm"]`` stay device futures and
        are fetched with ONE explicit packed ``device_get`` one step late —
        step N's scalars are read after step N+1 is dispatched, so the
        device never idles waiting for the host between steps (the
        torch-xla ``MpDeviceLoader`` + lazy-dispatch overlap, SURVEY §L1,
        in jit terms).  Per-step consumers (scalars, callbacks, obs flight
        records) still see every step's host floats, in step order, one
        dispatch behind.  ``"auto"`` defers only when the loop has no
        consumer that needs same-step floats: no ``policy``, no armed
        flight-recorder anomaly detectors, no ``timeline``, and no step
        callbacks (a ``should_stop`` raised from a one-step-late hook
        would stop one step later than the synchronous loop; pass
        ``defer_metrics=True`` to accept that).  ``True`` with ``policy=``
        raises.  The deferred loop is parity-tested loss-identical (exact
        float equality on CPU) to the synchronous loop.  Eval-cadence
        losses are routed through the same deferred fetch in BOTH modes,
        so an eval never stalls the next train step's dispatch.
      transfer_guard: ``"off"`` (default) / ``"forbid"``.  ``"forbid"``
        wraps every steady-state step dispatch in
        ``jax.transfer_guard("disallow")`` (via
        :class:`~..obs.transfer_audit.TransferAudit`): an *implicit*
        host↔device transfer inside the hot path raises instead of
        silently draining the device — use with ``prefetch`` (host batches
        would trip it) to make the no-sync invariant enforced, not
        aspirational.  Cadence work (checkpoint saves, log prints) runs
        outside the guard; metric fetches go through the audit's explicit
        ``device_get`` and are counted
        (``transfer/explicit_fetches_total``, ``train/host_blocked_ms``).
    """
    if checkpoint_on_signal:
        if not ckpt_dir:
            raise ValueError("checkpoint_on_signal requires ckpt_dir")
        if threading.current_thread() is not threading.main_thread():
            raise ValueError(
                "checkpoint_on_signal requires the main thread (Python "
                "signal handlers cannot be installed elsewhere); run fit() "
                "on the main thread or drop the flag")
    step_fn = make_train_step(
        config, model, optimizer, loss_fn, batch_spec=batch_spec,
        grad_accum_steps=grad_accum_steps,
    )
    eval_fn = None
    if eval_data is not None and eval_every > 0:
        eval_fn = make_eval_step(config, model, loss_fn, batch_spec=batch_spec)

    params, opt_state = model.params, optimizer.state
    start_step = 0
    resumed_user: dict = {}
    if ckpt_dir:
        # a run that names a checkpoint pays for the library here (the
        # start-up account's ``import``), not inside step ``ckpt_every``
        checkpoint_library()
    if resume and ckpt_dir and newest_tag(ckpt_dir):
        params, opt_state, _, user = load_checkpoint(
            ckpt_dir, model_template=params, optimizer_template=opt_state
        )
        resumed_user = dict(user or {})
        start_step = int(resumed_user.get("step", 0))
        logger.info("resumed from step %d (%s)", start_step, newest_tag(ckpt_dir))

    from neuronx_distributed_tpu.trainer.scalar_log import ScalarWriter

    scalars = ScalarWriter(scalar_dir) if scalar_dir else None

    obs_rt = None
    if obs is not None:
        from neuronx_distributed_tpu.obs import Observability

        obs_rt = obs if isinstance(obs, Observability) else Observability(
            str(obs), timeline=timeline)
    obs_audited = False

    # resource ledgers (Observability(ledgers=True)): the compile ledger
    # books the train-step compile (cold wall-time; the pipelined engine's
    # schedule compiles inside the same jit, so this site covers it too)
    # and treats any compile after step 0 as a storm; the memory ledger
    # accounts params + optimizer state and dumps memory_breakdown.json on
    # a RESOURCE_EXHAUSTED crash.  Both None by default — every hook below
    # guards on `is not None`.
    compile_led = getattr(obs_rt, "compile_ledger", None)
    memory_led = getattr(obs_rt, "memory_ledger", None)
    if compile_led is not None:
        from neuronx_distributed_tpu.obs.compile_ledger import jit_cache_size
    if memory_led is not None:
        memory_led.account_tree("params", params)
        memory_led.account_tree("opt_state", opt_state)
        memory_led.poll_device()

    policy_rt = None
    if policy is not None:
        from neuronx_distributed_tpu.resilience.policy import PolicyEngine

        if policy.wants_rollback:
            if not ckpt_dir:
                raise ValueError("policy rollback requires ckpt_dir (the "
                                 "newest checkpoint is the rollback target)")
            if not callable(data):
                raise ValueError(
                    "policy rollback requires step-indexed data(step): an "
                    "iterator's consumed batches cannot be re-wound")
        policy_rt = PolicyEngine(
            policy, registry=obs_rt.registry if obs_rt is not None else None)
        if policy.wants_rollback and newest_tag(ckpt_dir) is None:
            # guarantee a rollback target before the first cadence save: an
            # anomaly at step 0..ckpt_every would otherwise have nothing to
            # roll back to
            save_checkpoint(ckpt_dir, f"step_{start_step}", params, opt_state,
                            user_content={"step": start_step,
                                          "batches_consumed": start_step},
                            num_kept_ckpts=keep_ckpts,
                            save_dtype=ckpt_save_dtype)

    if callable(data):
        next_batch = data
    else:
        it = iter(data)
        for consumed in range(start_step):  # iterator resume: skip consumed
            try:
                next(it)
            except StopIteration:
                raise ValueError(
                    f"resume fast-forward: the data iterator was exhausted "
                    f"after {consumed} batches while seeking start step "
                    f"{start_step}; the checkpoint records batches_consumed="
                    f"{resumed_user.get('batches_consumed', 'unrecorded')} — "
                    "the resumed data source is shorter than the one the "
                    "checkpointed run consumed (wrong data file, un-reset "
                    "epoch, or a differently-seeded shuffle)") from None

        def next_batch(step):
            return next(it)

    from neuronx_distributed_tpu.obs.transfer_audit import TransferAudit

    if transfer_guard not in ("off", "forbid"):
        raise ValueError(
            f"transfer_guard must be 'off' or 'forbid', got {transfer_guard!r}")
    audit = TransferAudit(
        obs_rt.registry if obs_rt is not None else None,
        mode="forbid" if transfer_guard == "forbid" else "observe")

    prefetcher = None
    if prefetch:
        from neuronx_distributed_tpu.data.prefetch import DevicePrefetcher
        from neuronx_distributed_tpu.pipeline.engine import PipelinedModel
        from neuronx_distributed_tpu.trainer.trainer import _batch_shardings

        if batch_spec is not None:
            stage_shardings = _batch_shardings(model.mesh, batch_spec)
        elif isinstance(model, PipelinedModel):
            from jax.sharding import NamedSharding, PartitionSpec as P

            from neuronx_distributed_tpu.parallel.mesh import BATCH_AXES

            # every pipelined batch array is batch-dim-0 sharded; one
            # sharding broadcasts over the batch tree
            stage_shardings = NamedSharding(model.mesh, P(BATCH_AXES))
        else:
            raise ValueError(
                "fit(prefetch=N) needs batch_spec: staged batches must be "
                "device_put against the step's batch sharding (otherwise "
                "they would land committed to one device and fight the "
                "jitted step's placement)")
        prefetcher = DevicePrefetcher(
            next_batch, depth=prefetch, shardings=stage_shardings,
            registry=obs_rt.registry if obs_rt is not None else None)
        next_batch = prefetcher.get

    thr: Optional[Throughput] = None
    tokens_per_batch = None
    eval_history: list = []
    loss = float("nan")
    rng0 = jax.random.PRNGKey(config.seed)

    cbs = list(callbacks)
    if on_step is not None:
        legacy = Callback()
        legacy.on_step = on_step  # type: ignore[method-assign]
        cbs.append(legacy)
    for cb in cbs:
        cb.should_stop = False  # instances are reusable across fit() calls
        cb.on_fit_start(start_step, params, opt_state)

    if defer_metrics not in ("auto", True, False):
        raise ValueError(
            f"defer_metrics must be 'auto', True or False, got {defer_metrics!r}")
    if defer_metrics is True and policy is not None:
        raise ValueError(
            "defer_metrics=True is incompatible with policy=: skip/rollback "
            "decisions need the step's loss on the host BEFORE the next "
            "step is dispatched (the per-step sync IS the exactness "
            "guarantee); drop the policy or use defer_metrics='auto'")
    if defer_metrics is True and timeline is not None:
        raise ValueError(
            "defer_metrics=True is incompatible with timeline=: the "
            "timeline's per-step device attribution is the in-event sync "
            "the deferred mode removes; drop the timeline or use "
            "defer_metrics='auto'")
    if defer_metrics == "auto":
        # defer only when nothing in the loop needs same-step host floats:
        # a policy acts on them, flight detectors fire on them, a timeline
        # times the sync, and a callback's should_stop would otherwise land
        # one step late
        deferred = (policy is None and timeline is None and not cbs
                    and (obs_rt is None or not obs_rt.flight.detectors))
    else:
        deferred = bool(defer_metrics)

    # one-step-delayed metric pipeline: at most one pending train step and
    # one pending eval, each fetched with ONE explicit packed device_get
    # AFTER the next step's dispatch (deferred mode) so the host wait
    # overlaps device compute
    pending: list = []       # [(step, m, timing dict)]
    pending_eval: list = []  # [(eval_step, ev)]

    moe_running = None       # [L, E] loads summed since the run began
    starting = True          # until a step's loss has reached the host

    def _started() -> None:
        """The first loss is here: the process is ``ready`` (``obs.startup``;
        once a process, so a later ``fit()`` changes nothing), and the
        ``step0`` phase this call opened ends."""
        nonlocal starting
        starting = False
        startup.account().ready(
            "fit", obs_rt.registry if obs_rt is not None else None)

    def _book_moe(fetched: dict) -> None:
        """A routed model's step: its expert loads into the registry's
        ``moe/*`` counters, as the serving engine books a program's."""
        nonlocal moe_running
        if obs_rt is None or "moe_load" not in fetched:
            return
        from neuronx_distributed_tpu.parallel.moe import (
            book_expert_loads,
            set_expert_load_gauge,
        )

        moe_running = book_expert_loads(
            obs_rt.registry, "train_step",
            {"load": fetched["moe_load"],
             "assigned": fetched.get("moe_assigned"),
             "computed": fetched.get("moe_computed")}, moe_running)
        set_expert_load_gauge(obs_rt.registry, moe_running)

    def _flush_step_metrics() -> None:
        nonlocal loss
        if not pending:
            return
        pstep, pm, pt = pending.pop()
        t_w = time.perf_counter()
        # everything the step handed on, in the one fetch (a routed model's
        # expert loads ride it as they ride the token fetch in serving)
        fetched = audit.fetch(pm, label="train")
        wait_s = time.perf_counter() - t_w
        if starting:
            _started()
        ploss = perturb("fit/loss", float(fetched["loss"]), step=pstep)
        pgrad = float(fetched["grad_norm"])
        loss = ploss
        _book_moe(fetched)
        if obs_rt is not None:
            # host_s = dispatch, device_s = the (overlapped) fetch wait; the
            # two no longer tile one wall-clock step the way the sync loop's
            # do — train/host_blocked_ms carries the overlap story
            obs_rt.observe_step(
                pstep, loss=ploss, grad_norm=pgrad, seq_per_sec=pt["seqs"],
                step_time_s=pt["dispatch_s"] + wait_s, host_s=pt["dispatch_s"],
                device_s=wait_s, data_wait_s=pt["data_wait_s"])
        if scalars:
            scalars.scalars(pstep, loss=ploss, grad_norm=pgrad,
                            seq_per_sec=pt["seqs"])
        step_metrics = dict(fetched)
        step_metrics.update(loss=ploss, grad_norm=pgrad, seq_per_sec=pt["seqs"])
        for cb in cbs:
            cb.on_step(pstep, step_metrics)
        if log_every and (pstep % log_every == 0 or pstep == steps - 1):
            if obs_rt is not None:
                obs_rt.dump_scalars(pstep)
            print(json.dumps({
                "step": pstep, "loss": round(ploss, 4),
                "seq_per_sec": round(pt["seqs"], 2),
                "grad_norm": round(pgrad, 4),
            }), flush=True)

    def _flush_eval() -> None:
        if not pending_eval:
            return
        estep, ev = pending_eval.pop()
        eval_loss = float(audit.fetch(ev["loss"], label="train"))
        eval_history.append((estep, eval_loss))
        if scalars:
            scalars.scalars(estep - 1, eval_loss=eval_loss)
        for cb in cbs:
            cb.on_eval(estep, {"eval_loss": eval_loss})

    prev_handlers = {}
    signal_seen: list = []
    if checkpoint_on_signal:
        def _on_signal(signum, frame):
            # only append to a list (async-signal-safe — no logging/IO:
            # a reentrant stderr write would raise inside the handler and
            # skip the very checkpoint this feature exists to write); the
            # loop logs when it observes the flag.  Restore the previous
            # handlers immediately so a SECOND signal terminates normally —
            # a preemptor's escalation must never be swallowed while the
            # final checkpoint drains.  A None previous handler (installed
            # by non-Python code, unrecoverable from Python) restores
            # SIG_DFL — default termination beats a swallowed signal.
            signal_seen.append(signum)
            for s, h in prev_handlers.items():
                _signal.signal(s, h if h is not None else _signal.SIG_DFL)

        for sig in (_signal.SIGTERM, _signal.SIGINT):
            prev_handlers[sig] = _signal.signal(sig, _on_signal)

    final_step = steps
    last_saved_step = -1
    step_cache_size = None  # train-step jit cache size at the last poll
    try:
        step = start_step
        while step < steps:
            if signal_seen:
                # checked at the TOP of the loop so no path can outrun a
                # pending preemption notice — the policy skip/rollback
                # `continue`s land here instead of running another step
                final_step = step
                logger.info("stopping on signal %s after step %d (checkpoint "
                            "follows)", signal_seen[0], final_step)
                if obs_rt is not None:
                    # flight evidence lands BEFORE the final checkpoint
                    # drains — a second (fatal) signal still leaves the
                    # dump behind
                    obs_rt.dump_flight(f"signal_{signal_seen[0]}")
                break
            fault_point("fit/step_start", step=step, start_step=start_step)
            t_data = time.perf_counter()
            batch = next_batch(step)
            data_wait_s = time.perf_counter() - t_data
            snap = None
            if policy_rt is not None and policy.wants_snapshot:
                # the jitted step donates params/opt buffers; a skip-update
                # decision needs the pre-step state back, so keep a copy
                snap = (jax.tree.map(jnp.copy, params),
                        jax.tree.map(jnp.copy, opt_state))
            if thr is None:
                leaves = jax.tree.leaves(batch)
                bsz = leaves[0].shape[0]
                # tokens/batch from a [B, S] leaf (MFU summary); batches of
                # 1-D-only arrays simply have no token notion
                two_d = [x for x in leaves if x.ndim >= 2]
                tokens_per_batch = bsz * two_d[0].shape[1] if two_d else None
                thr = Throughput(bsz)
            rng = jax.random.fold_in(rng0, step) if step_rng else None
            if obs_rt is not None and not obs_audited:
                obs_audited = True
                # one extra AOT lower+compile for the audit; the persistent
                # compilation cache (when enabled) dedupes the XLA work
                try:
                    t_aot = time.perf_counter()
                    with startup.account().phase("audit"):
                        compiled = step_fn.lower(
                            params, opt_state, batch, rng).compile()
                    if compile_led is not None:
                        compile_led.record_compile(
                            "train_step", "aot_audit",
                            (time.perf_counter() - t_aot) * 1e3,
                            kind="aot", compiled=compiled)
                    obs_rt.audit_executable("train_step", compiled)
                except Exception as e:
                    logger.warning("obs: train-step HLO audit failed: %s", e)
            t0 = time.perf_counter()
            if timeline is not None:
                # timeline implies the synchronous path (resolved above):
                # the in-event float is what attributes device time to the
                # step's trace slice
                with timeline.event("train_step"):
                    params, opt_state, m = step_fn(params, opt_state, batch, rng)
                    t_dispatch = time.perf_counter()
                    loss = float(m["loss"])  # device sync
                t_done = time.perf_counter()  # BEFORE the trace-file flush:
                # step_time_s must compose identically with/without a timeline
                timeline.mark_step_end(step)  # flushes the event buffer to disk
                loss = perturb("fit/loss", loss, step=step)
                seqs = thr.step()
                grad_norm = float(m["grad_norm"])
            else:
                with audit.section("fit/step"):
                    params, opt_state, m = step_fn(params, opt_state, batch, rng)
                t_dispatch = time.perf_counter()
                seqs = thr.step()
                if deferred:
                    # the pipelined fetch: publish step N-1's scalars now
                    # that step N is in flight — the host blocks on a
                    # device that is already doing useful work
                    _flush_step_metrics()
                    pending.append((step, m, {
                        "seqs": seqs, "dispatch_s": t_dispatch - t0,
                        "data_wait_s": data_wait_s}))
                else:
                    m = audit.fetch(m, label="train")
                    loss = perturb("fit/loss", float(m["loss"]), step=step)
                    grad_norm = float(m["grad_norm"])
                    t_done = time.perf_counter()
                    _book_moe(m)
            if starting and not deferred:
                _started()      # deferred: the flush that fetches a loss
            if compile_led is not None:
                n = jit_cache_size(step_fn)
                if step == start_step:
                    # the first executed step's dispatch wall IS its
                    # trace+compile cost (jit compiles synchronously before
                    # dispatch returns); everything is warm after it, so
                    # any later compile is a storm
                    compile_led.record_compile(
                        "train_step", "step0", (t_dispatch - t0) * 1e3,
                        kind="jit")
                    compile_led.declare_warmup_done("fit_step0")
                elif n is not None and step_cache_size is not None \
                        and n > step_cache_size:
                    # the jit cache grew mid-run: a silent retrace/recompile
                    # (shape or placement drift) — booked with no wall time
                    # (it happened inside dispatch), flagged as a storm
                    compile_led.record_compile(
                        "train_step", f"cache_size_{n}", None, kind="jit")
                step_cache_size = n
            if not deferred and obs_rt is not None:
                obs_rt.observe_step(
                    step, loss=loss, grad_norm=grad_norm, seq_per_sec=seqs,
                    step_time_s=t_done - t0, host_s=t_dispatch - t0,
                    device_s=t_done - t_dispatch, data_wait_s=data_wait_s)
            if policy_rt is not None:
                decision = policy_rt.decide(step, loss=loss,
                                            grad_norm=grad_norm,
                                            step_time_s=t_done - t0)
                if decision is not None and decision.action == "skip":
                    # discard the update: pre-step params/opt restored, the
                    # batch counts as consumed (scalars/eval/checkpoint/
                    # callbacks do not fire for the discarded step).  A
                    # pending eval from the PREVIOUS step's cadence is real
                    # completed work — publish it before bailing out, as the
                    # pre-deferral loop did at its cadence
                    _flush_eval()
                    params, opt_state = snap
                    step += 1
                    continue
                if decision is not None and decision.action == "rollback":
                    _flush_eval()  # ditto: flush before the timeline rewinds
                    wait_for_checkpoint()
                    params, opt_state, _, user = load_checkpoint(
                        ckpt_dir, model_template=params,
                        optimizer_template=opt_state)
                    rb_step = int((user or {}).get("step", 0))
                    if rb_step > step:
                        # the newest tag is AHEAD of this run: ckpt_dir holds
                        # another run's checkpoints (resume=False into a used
                        # dir) — "rolling back" onto them would teleport the
                        # run forward onto foreign params and mark the result
                        # complete
                        raise RuntimeError(
                            f"policy rollback loaded step {rb_step} > current "
                            f"step {step} from {newest_tag(ckpt_dir)}: "
                            f"{ckpt_dir} holds checkpoints this run did not "
                            "write (stale dir? missing resume=True?)")
                    step = rb_step
                    logger.warning("policy: rolled back to step %d (%s)",
                                   step, newest_tag(ckpt_dir))
                    continue
            if not deferred:
                if scalars:
                    scalars.scalars(step, loss=loss, grad_norm=grad_norm,
                                    seq_per_sec=seqs)
                step_metrics = dict(m)
                step_metrics.update(loss=loss, grad_norm=grad_norm,
                                    seq_per_sec=seqs)
                for cb in cbs:
                    cb.on_step(step, step_metrics)
                if log_every and (step % log_every == 0 or step == steps - 1):
                    if obs_rt is not None:
                        obs_rt.dump_scalars(step)
                    # stdout JSON lines — the launcher-harness contract the
                    # example scripts (and their tests) have always exposed
                    print(json.dumps({
                        "step": step, "loss": round(loss, 4),
                        "seq_per_sec": round(seqs, 2),
                        "grad_norm": round(grad_norm, 4),
                    }), flush=True)
            for cb in cbs:
                # unconditional (even when metrics are deferred): the params
                # themselves are never stale, and a swap-every-K callback
                # must not miss its cadence step to a deferral window
                cb.on_params(step, params, opt_state)
            _flush_eval()  # last cadence's eval: fetched one iteration late
            if eval_fn is not None and (step + 1) % eval_every == 0:
                # dispatch now, fetch on the NEXT iteration (or at loop
                # exit): an eval cadence no longer stalls the next train
                # step's dispatch behind a bare float() of its loss
                pending_eval.append((step + 1, eval_fn(params, eval_data(step))))
            if ckpt_dir and ckpt_every and (step + 1) % ckpt_every == 0 \
                    and step + 1 < steps:
                # a cadence save is already a device sync point (it reads
                # the params), so the deferred pipeline flushes first: the
                # step's scalars/log line become durable BEFORE the
                # checkpoint that supersedes them — a crash mid-save can
                # never lose a step that the resume won't re-run
                _flush_step_metrics()
                _flush_eval()
                path = save_checkpoint(ckpt_dir, f"step_{step + 1}", params, opt_state,
                                       user_content={"step": step + 1,
                                                     "batches_consumed": step + 1},
                                       num_kept_ckpts=keep_ckpts, async_save=async_save,
                                       save_dtype=ckpt_save_dtype)
                last_saved_step = step + 1
                for cb in cbs:
                    cb.on_checkpoint(step + 1, path)
            if any(cb.should_stop for cb in cbs):
                final_step = step + 1
                logger.info("callback requested stop after step %d", final_step)
                break
            step += 1

        # drain the metric pipeline: the last step's (and last eval's)
        # deferred fetch lands before the final checkpoint and summary on
        # every non-exception exit (loop end, early stop, signal)
        _flush_step_metrics()
        _flush_eval()

        ran_any = start_step < steps
        if not ran_any:
            # resumed past the end: nothing to train, nothing to overwrite — the
            # existing final checkpoint and metrics file stay authoritative
            logger.info("resume step %d >= steps %d: nothing to do", start_step, steps)
        if ckpt_dir and ran_any:
            if last_saved_step != final_step:
                # skip when an early stop landed exactly on a cadence save — a
                # rewrite would rmtree the just-written tag and double-notify
                path = save_checkpoint(ckpt_dir, f"step_{final_step}", params, opt_state,
                                       user_content={"step": final_step,
                                                     "batches_consumed": final_step},
                                       num_kept_ckpts=keep_ckpts,
                                       save_dtype=ckpt_save_dtype)
                wait_for_checkpoint()
                for cb in cbs:
                    cb.on_checkpoint(final_step, path)
            else:
                wait_for_checkpoint()  # cadence save may be async: make it durable
    except BaseException as e:
        # the step completed right before the crash may still sit in the
        # deferred pipeline — land it in scalars/flight BEFORE the dump
        # (pending was popped before any fetch, so a crash INSIDE the flush
        # cannot recurse), but never let the flush mask the real exception
        try:
            _flush_step_metrics()
            _flush_eval()
        except Exception as flush_err:
            logger.warning("deferred-metric flush failed during crash "
                           "handling: %s", flush_err)
        if memory_led is not None:
            # RESOURCE_EXHAUSTED forensics: name the biggest HBM holders in
            # memory_breakdown.json before the process dies (no-op for
            # non-OOM exceptions; IO failures must not mask the crash)
            try:
                memory_led.oom_dump(e)
            except Exception as dump_err:
                logger.warning("obs: OOM breakdown dump failed: %s", dump_err)
        if obs_rt is not None:
            # the crash dump is the flight recorder's whole purpose: persist
            # the last K steps before the exception unwinds the process — but
            # a telemetry I/O failure (disk full, dir removed) must never
            # mask the real training exception
            try:
                obs_rt.close(f"crash:{type(e).__name__}")
            except Exception as dump_err:
                logger.warning("obs: crash dump failed: %s", dump_err)
        raise
    finally:
        if prefetcher is not None:
            # every exit path drains the staging thread: no orphan worker
            # after early stop / SIGTERM / crash, no stale staged batch
            # surviving into a resumed run
            prefetcher.close()
        # None = previous handler came from non-Python code and cannot be
        # re-installed from Python: SIG_DFL beats leaving OUR handler
        # appending to a list nothing reads anymore
        for _sig, _h in prev_handlers.items():
            _signal.signal(_sig, _h if _h is not None else _signal.SIG_DFL)
    if scalars:
        scalars.close()
    if obs_rt is not None:
        obs_rt.close(f"signal_{signal_seen[0]}" if signal_seen else "fit_end")
    if metrics is not None and ran_any:
        summary = {
            "final_loss": loss,
            "steps": steps,
            "completed_steps": final_step,
            "resumed_from_step": start_step,
            "peak_seq_per_sec": thr.peak if thr else 0.0,
        }
        if policy_rt is not None:
            summary["policy_skipped_updates"] = policy_rt.skips
            summary["policy_rollbacks"] = policy_rt.rollbacks
        if flops_per_token and peak_flops and thr and thr.window \
                and tokens_per_batch:
            toks_per_sec = thr.batch_size * len(thr.window) / max(
                sum(thr.window), 1e-9) * (tokens_per_batch / thr.batch_size)
            summary["mfu"] = mfu(toks_per_sec, flops_per_token, peak_flops)
        metrics.update(**summary)
        metrics.write()

    result = FitResult(
        params=params,
        opt_state=opt_state,
        final_loss=loss,
        steps_run=max(0, final_step - start_step),
        start_step=start_step,
        peak_seq_per_sec=thr.peak if thr else 0.0,
        eval_history=eval_history,
        policy_events=list(policy_rt.events) if policy_rt is not None else [],
    )
    for cb in cbs:
        cb.on_fit_end(result)
    return result
