"""The runner of ``"runner": "train_routed"`` configurations: a model with a
layer list and routed, HELD experts trained on the library's normal path.

The timed path is ``train_runner``'s, file for file: ``training_config`` ->
``initialize_parallel_model`` -> ``initialize_parallel_optimizer`` ->
``make_train_step`` inside ``fit()``, a fresh seeded host batch every step
through ``fit()``'s batch function, every time read in one ``Callback`` on
the benchmark's clock (see that module for how the window opens and
closes).  What differs is what a routed model needs of a yardstick:

- the routers' correction biases are BALANCED in set-up
  (``serve_ssm_runner.balance_router``: a seeded router prefers some experts,
  and the share that falls to the held ones then swings with the seed);
- model FLOPs count the HELD assignments the program counted
  (``lfm2_flops``), and the program's ``moe/*`` counters, fed by ``fit()``
  from the loads that ride each step's loss fetch, are the reading's;
- ``correct`` rests on more than a loss, all at the published widths and
  the timed sizes, on the step-0 batch, against the float32 reference
  (``reference/lfm2_moe_f32.py``) given the same held share: (1) the loss
  and ``grad_norm`` that the TIMED step itself hands ``fit()``'s callback
  at step 0, and what that step LEFT: the parameters' change, leaf by
  leaf, against AdamW's first update from the reference's gradients (a
  state left unchanged reads 1), and the first moment it stored, which is
  the timed program's gradient, group by group under the gradients'
  limits; (2) the program's own ``value_and_grad`` of the same loss
  function, a relative error and a cosine for every parameter group
  (``reference/lfm2_moe_weights.py::GROUPS``) against the reference made
  to FOLLOW the program's routing (``forced=``); (3) the share of rows
  whose chosen experts are the reference's own, a routed layer, and the
  share whose choice stands further from it than rounding explains, by the
  reference's own scores; (4) one routed layer's grouped matmuls ALONE at
  the timed size — the data and weight gradients the backward kernels
  write — against float32 (``kernel_readings``: a kernel's own rounding
  hides under the 3% every end-to-end gradient shares); (5) finite losses
  and no compile inside the window.  Each limit is in the configuration
  file with its reason.

Set-up order is memory's: the program's gradients and the reference's are
each moved to the host before the next is computed (``step0_readings``;
``limits_broken`` holds them to the limits, and ``tools/lfm2_faults.py``
runs the same functions with one fault patched in at a time), and the
optimizer's state (two moments a parameter) is made only after both.
"""

from __future__ import annotations

import math
import os
import tempfile
from typing import List, Optional

import numpy as np

from benchmarks.harness import common, lfm2_flops, traffic
from benchmarks.harness.common import Outcome, Reading, annotate, log


def group_errors(got: dict, want: dict) -> dict:
    """``{group: (relative error, cosine)}`` of two ``{group: [host
    arrays]}``: the groups' arrays taken as one vector."""
    out = {}
    for group, ws in want.items():
        dot = gg = ww = dd = 0.0
        for g, w in zip(got[group], ws):
            g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
            dot += float(np.sum(g * w))
            gg += float(np.sum(g * g))
            ww += float(np.sum(w * w))
            dd += float(np.sum((g - w) ** 2))
        out[group] = (math.sqrt(dd / max(ww, 1e-300)),
                      dot / max(math.sqrt(gg * ww), 1e-300))
    return out


def to_host(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def _norm(groups: dict) -> float:
    return math.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                         for gs in groups.values() for g in gs))


def _sown(module):
    """``module`` for a loss function, keeping (``.variables``) the
    collections that its apply for ``moe_stats`` handed back: the routing of
    the very forward the loss and its gradients came from (another
    program's forward flips some bf16 near-ties the other way: its choice
    forced on the reference read every gradient 10-20% off, my chip run,
    PR 43)."""

    class Sown:
        hidden = type(module).hidden    # a loss function reads its signature
        variables = None

        def apply(self, *args, **kwargs):
            out = module.apply(*args, **kwargs)
            if "moe_stats" in (kwargs.get("mutable") or ()):
                self.variables = out[1]
            return out

    return Sown()


def step0_readings(cell, module, params, batch0, program_params=None) -> dict:
    """What the limits read of the step-0 batch: the program's own
    ``value_and_grad`` of the cell's loss function (over ``program_params``
    where a fault is being shown, else ``params``) and the routing of that
    forward (``_sown``), then the reference on ``params`` made to FOLLOW
    that routing (a flipped near-tie would otherwise send a row through
    other experts in the two, and every gradient would differ by the rows
    that flipped).  Each side's gradients are moved to the host before the
    other's are computed; the reference's and the parameters stay there, by
    group, for ``update_readings``."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
    from neuronx_distributed_tpu.models.llama import moe_layer_stats

    cfg = cell.config
    n_layers = cfg["num_hidden_layers"]
    ref_mod, adapter = cell.reference(), manifest_weights(cell)
    dev_batch = {k: jnp.asarray(v) for k, v in batch0.items()}
    check_fn = make_causal_lm_loss_sum(
        chunk_size=cfg["training"]["loss_chunk"])

    @jax.jit
    def own_grads(p):
        def mean_loss(p):
            sown = _sown(module)
            loss_sum, tok = check_fn(sown, p, dev_batch)[:2]
            return loss_sum / jnp.maximum(tok, 1.0), moe_layer_stats(
                sown.variables, module.config.moe_layers)["choice"]
        return jax.value_and_grad(mean_loss, has_aux=True)(p)

    (own_loss, chosen), grads = own_grads(
        params if program_params is None else program_params)
    own = adapter.by_group(adapter.adapt(to_host(grads), n_layers))
    chosen = np.asarray(chosen)
    del grads
    ref_sum, ref_tok, ref_grads, ref_chosen, margin = ref_mod.loss_and_grads(
        adapter.adapt(params, n_layers), ref_mod.Shape.from_config(cfg),
        batch0["ids"], batch0["labels"], forced=chosen)
    ref = adapter.by_group(to_host(ref_grads))
    del ref_grads
    return {
        "params": adapter.by_group(adapter.adapt(to_host(params), n_layers)),
        "ref_grads": ref,
        "own_loss": float(own_loss), "ref_loss": ref_sum / ref_tok,
        "own_norm": _norm(own), "ref_norm": _norm(ref),
        "grads": group_errors(own, ref),
        # rows whose experts are the reference's own, a routed layer, and
        # how far each row's choice stands from it by its own scores
        "same": np.mean(np.all(np.sort(chosen, -1) == np.sort(
            np.asarray(ref_chosen), -1), axis=-1), axis=1),
        "margin": np.asarray(margin)}


def warmup_from_the_first_step(tr_opts: dict):
    """``lr(step) = peak x (step + 1) / warmup_steps`` up to the peak.  The
    library's own warm-up (``warmup_steps``) starts AT zero, so its first
    update moves no weight and ``correct`` could not tell it from an
    optimizer that never does; counted from one, step 0 moves every weight
    by ``peak / warmup_steps``."""
    import optax

    peak, n = tr_opts["learning_rate"], tr_opts["warmup_steps"]
    return optax.linear_schedule(peak / n, peak, n - 1)


def adam_first_moment(opt_state):
    """The first moments of the one ``scale_by_adam`` in an optimizer's
    state, a tree shaped like the parameters (a frozen leaf's is an empty
    node)."""
    import jax
    import optax

    def is_adam(x):
        return isinstance(x, optax.ScaleByAdamState)

    (adam,) = [x for x in jax.tree.leaves(opt_state, is_leaf=is_adam)
               if is_adam(x)]
    return adam.mu


def update_readings(cell, r: dict, params1, moment1, oc, lr0: float) -> dict:
    """What a train step LEFT, against the reference's gradients
    ``r["ref_grads"]`` of the same batch: ``params1`` and ``moment1`` are
    the parameters and Adam's first moments after the step (host trees),
    ``r["params"]`` the parameters before it, ``oc`` the optimizer's
    configuration and ``lr0`` its learning rate at that step.

    ``update_rel``, a group's worst LEAF (its leaves under 1,024 elements
    read as one: a flip among 64 elements is a quarter alone): the norm of
    (the parameters' change - AdamW's first update from the reference's
    gradients) over the norm of that update, float32 as
    ``make_train_step`` composes it (the
    clip by the global norm, then ``optax.adamw``: at the first step ``m^ /
    (sqrt(v^) + eps)`` is ``g / (|g| + eps)``, so the update is ``-lr (sign
    g + decay p)`` and what the reading counts is the elements whose SIGN
    the two gradients disagree on, two a flip: a share ``f`` of flips reads
    ``2 sqrt(f)``, and ``flipped`` is that share over all the elements; a
    state left unchanged reads 1).  ``timed_grads``:
    ``group_errors`` of the stored moment over ``1 - beta1``, which is the
    timed program's clipped gradient, against the reference's, clipped."""
    adapter, n_layers = manifest_weights(cell), cell.config["num_hidden_layers"]
    after = adapter.by_group(adapter.adapt(params1, n_layers))
    moment = adapter.by_group(adapter.adapt(moment1, n_layers))
    f32 = np.float32
    clip = f32(min(oc.max_grad_norm / (r["ref_norm"] + 1e-6), 1.0)
               if oc.grad_clipping else 1.0)
    update_rel, clipped, timed = {}, {}, {}
    flipped = elements = 0
    for group, grads in r["ref_grads"].items():
        worst = 0.0
        clipped[group] = [g * clip for g in grads]
        timed[group] = [m / f32(1.0 - oc.beta1) for m in moment[group]]
        leaves = list(zip(r["params"][group], after[group], clipped[group]))
        small = [leaf for leaf in leaves if leaf[0].size < 1024]
        if small:
            leaves = [leaf for leaf in leaves if leaf[0].size >= 1024] + [
                tuple(np.concatenate([a.reshape(-1) for a in side])
                      for side in zip(*small))]
        for p0, p1, g in leaves:
            step = g / (np.abs(g) + f32(oc.eps)) + f32(oc.weight_decay) * p0
            want = (p0 + f32(-lr0) * step) - p0
            diff = np.asarray((p1 - p0) - want, np.float64)
            flipped += int(np.count_nonzero((p1 - p0) * want < 0))
            elements += want.size
            worst = max(worst, math.sqrt(
                float(np.sum(diff * diff))
                / max(float(np.sum(np.square(want, dtype=np.float64))),
                      1e-300)))
        update_rel[group] = worst
    return {"update_rel": update_rel, "flipped": flipped / elements,
            "timed_grads": group_errors(timed, clipped)}


def kernel_readings(cell, seed: int, plain_round=None) -> dict:
    """One routed layer's grouped matmuls ALONE at the timed size, held to
    float32: ``{"gate.dx", "gate.dw", "down.dx", "down.dw"}``, the relative
    error of the data gradient (the flipped ``gmm``) and of the weight
    gradient (``tgmm``) that ``jax.vjp`` of ``parallel.moe.grouped_matmul``
    gives for ``[held, hidden, width]`` (gate, and up alike) and ``[held,
    width, hidden]`` (down).  Seeded bfloat16 operands of unit scale; every
    assignment row of a step laid out, the rows of the held experts first
    and the other ranks' in no group, as ``_dropless`` lays them; the plain
    side is a masked dense loop over the experts in float32 at ``highest``
    on the same operands, ROUNDED to the kernels' bfloat16 output as they
    round theirs (once): a faithful kernel then differs only where two
    orders of a float32 sum fall on either side of a rounding, and a
    bfloat16 accumulator over four row tiles an expert stands out alone
    (against the unrounded float32 the two read 0.166% and 0.237%, my chip
    run, PR 43: one shared rounding hid most of it).
    ``plain_round``: a dtype the plain side rounds its operands to first
    (``tools/lfm2_faults.py``'s reading one precision lower)."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.parallel.moe import grouped_matmul

    cfg, mix = cell.config, cell.traffic
    held, of = cfg["experts_held"]["count"], cfg["experts_held"]["of"]
    rows = mix["batch"] * mix["seq_len"] * cfg["num_experts_per_tok"]
    sizes = np.bincount(np.random.RandomState(seed % 2 ** 32).randint(
        0, of, rows), minlength=of)[:held]
    n = int(sizes.sum())
    group = np.full(rows, -1, np.int32)
    group[:n] = np.repeat(np.arange(held, dtype=np.int32), sizes)
    dsizes, dgroup = jnp.asarray(sizes, jnp.int32), jnp.asarray(group[:n])

    @jax.jit
    def kernels(x, w, dy):
        _, vjp = jax.vjp(
            lambda x, w: grouped_matmul(x, w, dsizes, jnp.bfloat16), x, w)
        dx, dw = vjp(dy)
        return dx[:n], dw

    @jax.jit
    def plain(x, w, dy):
        x, w, dy = (a.astype(plain_round or a.dtype).astype(jnp.float32)
                    for a in (x[:n], w, dy[:n]))
        dx, dws = 0.0, []
        with jax.default_matmul_precision("highest"):
            for g in range(held):
                dy_g = jnp.where((dgroup == g)[:, None], dy, 0.0)
                dx = dx + dy_g @ w[g].T
                dws.append(x.T @ dy_g)
        return dx.astype(jnp.bfloat16), jnp.stack(dws).astype(jnp.bfloat16)

    def rel(got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    out = {}
    H, I = cfg["hidden_size"], cfg["moe_intermediate_size"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 6))
    for name, (k, m) in (("gate", (H, I)), ("down", (I, H))):
        x = jax.random.normal(next(keys), (rows, k), jnp.bfloat16)
        w = (jax.random.normal(next(keys), (held, k, m), jnp.float32)
             / math.sqrt(k)).astype(jnp.bfloat16)
        dy = jax.random.normal(next(keys), (rows, m), jnp.bfloat16)
        (dx, dw), (dx32, dw32) = kernels(x, w, dy), plain(x, w, dy)
        out[name + ".dx"], out[name + ".dw"] = rel(dx, dx32), rel(dw, dw32)
    return out


def limits_broken(r: dict, tol: dict, loss: float, grad_norm: float,
                  update: Optional[dict] = None) -> List[str]:
    """The limits of ``tolerances`` that ``step0_readings`` ``r`` (with
    ``kernel_readings`` under ``"kernel"``), a step's own ``loss`` and
    ``grad_norm`` and what it left (``update_readings``; ``None``: no step
    was run, a fault shown in the program's gradients alone) break, each as
    a sentence."""
    out = []
    for name, got, want in (("loss", loss, r["ref_loss"]),
                            ("grad_norm", grad_norm, r["ref_norm"])):
        err = abs(got - want) / abs(want)
        log(f"[check] step-0 {name} {got:.5f} vs reference {want:.5f}: rel "
            f"diff {err:.2e} (tol {tol['step0_' + name + '_rel']})")
        if not err <= tol["step0_" + name + "_rel"]:
            out.append(f"step-0 {name} differs from the reference by "
                       f"{err:.2e}")
    for group, (err, cos) in r["grads"].items():
        lim = tol["grad_rel"].get(group, tol["grad_rel"]["default"])
        log(f"[check] gradient of {group}: rel err {err:.3e} (tol {lim}), "
            f"cosine {cos:.6f} (min {tol['grad_cosine_min']})")
        if not (err <= lim and cos >= tol["grad_cosine_min"]):
            out.append(f"gradient of {group} differs from the reference: "
                       f"rel {err:.3e}, cosine {cos:.6f}")
    for what, err in r["kernel"].items():
        log(f"[check] grouped matmul alone, {what}: rel err {err:.3e} (tol "
            f"{tol['kernel_rel']})")
        if not err <= tol["kernel_rel"]:
            out.append(f"the grouped matmul's {what} differs from float32 "
                       f"by {err:.3e}")
    if update is not None:
        rel = update["update_rel"]
        log(f"[check] the parameters' change over step 0 against AdamW's "
            f"from the reference's gradients, a group's worst leaf: "
            f"{ {g: round(e, 4) for g, e in rel.items()} } (tol "
            f"{tol['step0_update_rel']}; unchanged reads 1); elements "
            f"moved the other way {update['flipped']:.5f}")
        if not max(rel.values()) <= tol["step0_update_rel"]:
            out.append(f"the parameters' change over step 0 differs from "
                       f"the reference's update: {max(rel.values()):.3f} "
                       f"in {max(rel, key=rel.get)}")
        for group, (err, cos) in update["timed_grads"].items():
            lim = tol["grad_rel"].get(group, tol["grad_rel"]["default"])
            log(f"[check] the timed step's gradient of {group} (its first "
                f"moment): rel err {err:.3e} (tol {lim}), cosine {cos:.6f}")
            if not (err <= lim and cos >= tol["grad_cosine_min"]):
                out.append(f"the timed step's gradient of {group} differs "
                           f"from the reference: rel {err:.3e}, cosine "
                           f"{cos:.6f}")
    # only the FIRST routed layer's input has passed one layer's rounding:
    # deeper, rounding's own margins grow to what a dropped bias gives
    same, margin = r["same"], r["margin"]
    far = float(np.mean(margin[0] > tol["routing_margin"]))
    log(f"[check] rows whose chosen experts are the reference's own, a "
        f"routed layer: {[round(float(s), 5) for s in same]} (min "
        f"{tol['routing_rows_same_min']}, the first "
        f"{tol['routing_first_layer_same_min']}); first layer's rows "
        f"chosen further than {tol['routing_margin']} from it by its "
        f"scores: {far:.6f} (max {tol['routing_first_layer_far_max']}); "
        f"margin p99.9 "
        f"{[round(float(np.quantile(m, 0.999)), 5) for m in margin]} max "
        f"{[round(float(m.max()), 5) for m in margin]}")
    if not (np.all(same >= tol["routing_rows_same_min"])
            and same[0] >= tol["routing_first_layer_same_min"]
            and far <= tol["routing_first_layer_far_max"]):
        out.append(f"routing differs from the reference: same {same}, "
                   f"first layer far {far}")
    return out


def run(cell, args, devices, peak, clock) -> Outcome:
    import jax.numpy as jnp

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness.serve_ssm_runner import balance_router
    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
    from neuronx_distributed_tpu.obs import Observability
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
        param_dtype=tr_opts["param_dtype"],
        seed=args.seed, **mesh_kw)
    module_cls, model_cfg = common.program_config(
        {**cfg["program"],
         "kwargs": {**cfg["program"]["kwargs"], "max_seq_len": seq}})
    model = initialize_parallel_model(
        config, lambda: module_cls(model_cfg),
        (jnp.zeros((1, seq), jnp.int32),), seed=args.seed)
    vocab = cfg["vocab_size"]
    model.params, _, skew = balance_router(model.module, model.params,
                                           args.seed, vocab)
    log(f"[setup] {model.num_parameters() / 1e6:.0f}M parameters on mesh "
        f"{dict(model.mesh.shape)} at {clock.since_start():.1f} s")
    loss_fn = make_causal_lm_loss_sum(chunk_size=tr_opts["loss_chunk"])
    bspec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
    # -- the step-0 batch through the program's own value_and_grad and
    # through the reference, before fit() donates the parameters
    batch0 = traffic.train_batch(mix, vocab, args.seed, 0)
    readings = step0_readings(cell, model.module, model.params, batch0)
    readings["kernel"] = kernel_readings(cell, args.seed)
    log(f"[setup] reference on the step-0 batch, following the program's "
        f"routing: loss {readings['ref_loss']:.5f}, grad norm "
        f"{readings['ref_norm']:.5f}; own loss {readings['own_loss']:.5f} "
        f"at {clock.since_start():.1f} s")

    lr = warmup_from_the_first_step(tr_opts)
    opt = initialize_parallel_optimizer(config, model, learning_rate=lr)
    obs = Observability(tempfile.mkdtemp(prefix="nxd_bench_obs_"),
                        detectors=[])

    skip = int(mix["skip_steps"])
    profiler = common.ProfilerWindow(cell.name) if args.trace else None
    trace_from, trace_steps = int(mix["trace_from_step"]), int(mix["trace_steps"])

    class Window(Callback):
        """Stamps, the window's edges and the traced sub-window
        (``train_runner.Window``), step 0's ``grad_norm`` and what step 0
        left (parameters and first moments, on the host)."""

        def __init__(self):
            self.losses: List[float] = []
            self.grad_norm0 = self.after0 = None
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
                if step == 0:
                    self.grad_norm0 = float(m["grad_norm"])
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

        def on_params(self, step, params, opt_state):
            if step == 0:
                self.after0 = (to_host(params),
                               to_host(adam_first_moment(opt_state)))

    win = Window()

    def data(step):
        with annotate("batch"):
            return traffic.train_batch(mix, vocab, args.seed, step)

    fit(config, model, opt, data, steps=10 ** 9, loss_fn=loss_fn,
        batch_spec=bspec, callbacks=[win], defer_metrics=True, log_every=0,
        obs=obs)
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
    snap = {k: v for k, v in obs.registry.snapshot().items()
            if isinstance(v, (int, float))}

    # (1)-(4): the TIMED step's own step-0 loss and grad_norm and what it
    # left, the gradients by group, the routing and the kernels alone, each
    # against its limit
    why_not = limits_broken(
        readings, cfg["tolerances"], win.losses[0], win.grad_norm0,
        update_readings(cell, readings, *win.after0, config.optimizer,
                        float(lr(0))))
    bad = [i for i, x in enumerate(win.losses) if not math.isfinite(x)]
    if bad:
        why_not.append(f"non-finite loss at steps {bad[:5]}")
    if in_window:
        why_not.append(f"{in_window} compile request(s) inside the window")
    calls = snap.get("moe/layer_calls_total/train_step", 0)
    routed_layers = len(model_cfg.moe_layers)
    # held assignments a token, the mean over the routed layers and the run
    held_per_token = (snap.get("moe/assignments_held_total/train_step", 0)
                      / max(calls, 1) / tokens_per_step)
    log(f"[window] {n_steps} steps of {tokens_per_step} tokens in "
        f"{window_s:.3f} s; step ms p50 {np.median(step_ms):.2f} min "
        f"{min(step_ms):.2f} max {max(step_ms):.2f}; losses "
        f"{win.losses[0]:.4f} -> {win.losses[-1]:.4f}; held assignments a "
        f"token {held_per_token:.4f} over {routed_layers} routed layers, "
        f"busiest expert over the mean after balancing {skew}; compile "
        f"requests {compiles.requests} ({compiles.hits} from the cache)")

    reading = Reading(
        cell=cell, chips=cell.chips, peak=peak, window_s=window_s,
        samples={"train_step_ms": step_ms},
        counters={**snap, "compiles_in_window": in_window,
                  "bytes_in_use": win.mem.get("bytes_in_use", 0)},
        end_to_end={"train_tokens_per_s_per_chip": tokens_per_s_chip},
        trace=profiler.reduce(cell.chips) if profiler is not None else None,
        notes={"tokens_per_step": tokens_per_step, "seq_len": seq,
               "batch": batch, "routed_layers": routed_layers,
               "flops_per_token": lfm2_flops.train_flops_per_token(
                   cfg, seq, held_per_token)})
    return Outcome(correct=not why_not, attempted=len(win.losses),
                   failed=len(bad), setup_s=win.setup_s, reading=reading,
                   memory=win.mem, why_not=why_not)


def manifest_weights(cell):
    """The configuration's weights adapter as a module (``Cell
    .reference_weights`` calls its ``adapt``; the gradient comparison needs
    ``by_group`` too)."""
    from benchmarks.harness import manifest

    name = cell.config["reference"]["weights_from"] + "_weights"
    return manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "reference", name + ".py"),
        "benchmarks_reference_" + name)
