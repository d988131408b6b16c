"""The runner of ``"runner": "train"`` configurations.

The normal path of the library, nothing else: ``initialize_model_parallel``
-> ``training_config`` -> ``initialize_parallel_model`` ->
``initialize_parallel_optimizer`` -> ``make_train_step`` inside ``fit()``,
with a fresh seeded host batch every step through ``fit()``'s batch
function.  The benchmark's own code is the batch function and one
``Callback``; every time is read in that callback, on the benchmark's clock,
when ``fit()`` hands it a step's loss — which it has just fetched from the
device, so each stamp follows a ``block_until_ready`` of that loss.

``fit(defer_metrics=True)`` is the loop a training user runs: step N+1 is
dispatched before step N's loss is fetched, so the device never waits for
the host.  The callback therefore hears of step N one dispatch late; the
window opens at the stamp that ends step ``skip_steps - 1`` and closes at
the first stamp at least ``--seconds`` later, and holds whole steps only.
"""

from __future__ import annotations

import math
from typing import List

import numpy as np

from benchmarks.harness import common, flops, traffic
from benchmarks.harness.common import Outcome, Reading, annotate, log


def run(cell, args, devices, peak, clock) -> Outcome:
    import jax.numpy as jnp

    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
    from neuronx_distributed_tpu.trainer import (
        Callback,
        default_batch_spec,
        fit,
        initialize_parallel_model,
        initialize_parallel_optimizer,
    )

    cfg, mix = cell.config, cell.traffic
    tr_opts, layout = cfg["training"], cfg["layout"]
    seq, batch = mix["seq_len"], mix["batch"]
    tokens_per_step = seq * batch
    compiles = common.CompileCounter()

    mesh_kw = dict(tensor_parallel_size=layout["tensor_parallel_size"])
    nxd.initialize_model_parallel(devices=devices, **mesh_kw)
    config = nxd.training_config(
        learning_rate=tr_opts["learning_rate"],
        zero_one_enabled=tr_opts["zero_one_enabled"],
        compute_dtype=tr_opts["compute_dtype"],
        param_dtype=tr_opts["param_dtype"], seed=args.seed, **mesh_kw)
    module_cls, model_cfg = common.program_config(
        {**cfg["program"],
         "kwargs": {**cfg["program"]["kwargs"], "max_seq_len": seq}})
    model = initialize_parallel_model(
        config, lambda: module_cls(model_cfg),
        (jnp.zeros((1, seq), jnp.int32),), seed=args.seed)
    opt = initialize_parallel_optimizer(config, model)
    log(f"[setup] {model.num_parameters() / 1e6:.0f}M parameters on mesh "
        f"{dict(model.mesh.shape)} at {clock.since_start():.1f} s")
    loss_fn = make_causal_lm_loss_sum(chunk_size=tr_opts["loss_chunk"])
    bspec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
    vocab = cfg["vocab_size"]

    # -- the reference's loss of the step-0 batch, before fit() donates the
    # parameters: the plain float32 decoder on the same weights
    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    batch0 = traffic.train_batch(mix, vocab, args.seed, 0)
    ref_w = cell.reference_weights(model.params)
    ref_loss = ref_mod.loss(ref_w, shape, batch0["ids"], batch0["labels"])
    del ref_w
    log(f"[setup] reference loss of the step-0 batch {ref_loss:.5f} at "
        f"{clock.since_start():.1f} s")

    skip = int(mix["skip_steps"])
    profiler = common.ProfilerWindow(cell.name) if args.trace else None
    trace_from, trace_steps = int(mix["trace_from_step"]), int(mix["trace_steps"])

    class Window(Callback):
        """Stamps, the window's edges and the traced sub-window."""

        def __init__(self):
            self.losses: List[float] = []
            self.stamps: List[float] = []
            self.t_open = self.t_close = None
            self.setup_s = None
            self.mark = 0
            self.mem = {}

        def on_step(self, step, m):
            with annotate("on_step"):
                now = clock()
                if self.t_close is not None:
                    return
                self.losses.append(float(m["loss"]))
                if step == skip - 1:
                    self.t_open = now
                    self.setup_s = clock.since_start()
                    self.mark = compiles.mark()
                    log(f"[window] open after step {step} at "
                        f"{self.setup_s:.1f} s")
                elif self.t_open is not None:
                    self.stamps.append(now)
                    n = len(self.stamps)
                    if profiler is not None and not profiler.done:
                        if n == trace_from and not profiler.active:
                            profiler.start()
                        elif profiler.active and n >= trace_from + trace_steps:
                            profiler.stop()
                    if now - self.t_open >= args.seconds and not (
                            profiler is not None and profiler.active):
                        self.t_close = now
                        self.mem = common.memory(devices)
                        self.should_stop = True

    win = Window()

    def data(step):
        with annotate("batch"):
            return traffic.train_batch(mix, vocab, args.seed, step)

    fit(config, model, opt, data, steps=10 ** 9, loss_fn=loss_fn,
        batch_spec=bspec, callbacks=[win], defer_metrics=True, log_every=0)
    if profiler is not None and profiler.active:
        profiler.stop()
    if win.t_close is None:
        raise RuntimeError("fit() returned before the window closed")

    n_steps = len(win.stamps)
    window_s = win.t_close - win.t_open
    edges = [win.t_open] + win.stamps
    step_ms = [(b - a) * 1e3 for a, b in zip(edges, edges[1:])]
    tokens_per_s_chip = n_steps * tokens_per_step / window_s / cell.chips
    in_window = compiles.since(win.mark)

    why_not = []
    bad = [i for i, x in enumerate(win.losses) if not math.isfinite(x)]
    if bad:
        why_not.append(f"non-finite loss at steps {bad[:5]}")
    tol = cfg["tolerances"]["step0_loss_rel"]
    err = abs(win.losses[0] - ref_loss) / abs(ref_loss)
    log(f"[check] step-0 loss {win.losses[0]:.5f} vs reference "
        f"{ref_loss:.5f}: rel diff {err:.2e} (tol {tol})")
    if not err <= tol:
        why_not.append(f"step-0 loss differs from the reference by {err:.2e}")
    if in_window:
        why_not.append(f"{in_window} compile request(s) inside the window")
    log(f"[window] {n_steps} steps of {tokens_per_step} tokens in "
        f"{window_s:.3f} s; step ms p50 {np.median(step_ms):.2f} min "
        f"{min(step_ms):.2f} max {max(step_ms):.2f}; losses "
        f"{win.losses[0]:.4f} -> {win.losses[-1]:.4f}; compile requests "
        f"{compiles.requests} ({compiles.hits} from the cache)")

    reading = Reading(
        cell=cell, chips=cell.chips, peak=peak, window_s=window_s,
        samples={"train_step_ms": step_ms},
        counters={"compiles_in_window": in_window,
                  "bytes_in_use": win.mem.get("bytes_in_use", 0)},
        end_to_end={"train_tokens_per_s_per_chip": tokens_per_s_chip},
        trace=profiler.reduce(cell.chips) if profiler is not None else None,
        notes={"tokens_per_step": tokens_per_step, "seq_len": seq,
               "batch": batch,
               "flops_per_token": flops.train_flops_per_token(cfg, seq)})
    return Outcome(correct=not why_not, attempted=len(win.losses),
                   failed=len(bad), setup_s=win.setup_s, reading=reading,
                   memory=win.mem, why_not=why_not)
