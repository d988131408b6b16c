#!/usr/bin/env python
"""lfm2_faults.py — which limit of ``lfm2-8b-a1b.train-seq8k`` catches which
fault, on the chip, at the published widths and the timed sizes.

    chiprun --timeout 2400 -- python benchmarks/tools/lfm2_faults.py [--faults a,b] [--seed N]

The cell's ``correct`` compares the program's own ``value_and_grad`` of the
step-0 batch with the float32 reference following the program's routing,
one routed layer's grouped matmuls alone with float32, and what the timed
step 0 LEFT (the parameters' change, the first moments) with AdamW's first
update from the reference's gradients (``harness/train_routed_runner.py``).
This tool runs that comparison — the runner's own functions, the library's
one train step on the step-0 batch, no ``fit()`` — for the program AS IT IS
and then with ONE fault at a time, and prints for each the numbers the
limits read beside the limits.  Faults of the PROGRAM (its gradients and
kernels are read; no step is run under them):

- ``tgmm_bf16_acc``: the weight gradient of an expert summed over its row
  tiles in bfloat16 (the rows of every expert in four strided parts, each
  part's ``tgmm`` rounded to bfloat16, the parts added in bfloat16 — what a
  bfloat16 accumulator over ``tm = 512`` rows of ~2,048 would do);
- ``conv_bf16``: the convolution's taps multiplied and summed in bfloat16;
- ``bias_dropped``: the routers' correction bias left out of the choice;
- ``silu_on_taps``: Mamba-2's activation left on the taps' sum;
- ``qk_norm_full_width``: the q/k statistic over all heads at once.

faults of the STEP (the program's gradients are ``none``'s; the step is
run under them):

- ``update_frozen``: the optimizer's update computed and not applied
  (``optax.apply_updates`` hands the parameters back as they were);
- ``half_batch``: the step trains on the first sequence of the batch alone;

and, the program AS IT IS, one reading of the yardstick itself:

- ``reference_e4m3``: the REFERENCE reading the weights rounded to
  float8_e4m3, the nearest precision below the configuration's bfloat16 (it
  has to come out as not correct).

``--kernels`` reads the grouped matmuls alone under each fault named (no
model, no gradients, no step: ``kernel_rel`` is the one limit in play, and
under ``reference_e4m3`` the plain side rounds its operands to float8_e4m3).
``--rehearse`` runs the control flow at the cell's tiny sizes on the CPU (no
number of it says anything about the chip).  Rows go to
``chiprun_out/lfm2_faults.json``.
"""

import argparse
import contextlib
import functools
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

STEP_FAULTS = ("update_frozen", "half_batch")
FAULTS = ("none", "tgmm_bf16_acc", "conv_bf16", "bias_dropped",
          "silu_on_taps", "qk_norm_full_width", *STEP_FAULTS,
          "reference_e4m3")


@contextlib.contextmanager
def fault(name):
    """The program with ``name`` patched in; ``"none"``: as it is."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.ops import ssm_scan
    from neuronx_distributed_tpu.parallel import moe

    undo = []

    def patch(obj, attr, new):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "tgmm_bf16_acc":
        real = moe._megablox()

        def tgmm(lhs, rhs, sizes, dtype, tiling, **kw):
            rows = jnp.arange(rhs.shape[0])[:, None]
            parts = [real.tgmm(lhs, jnp.where(rows % 4 == j, rhs, 0), sizes,
                               jnp.bfloat16, tiling, **kw) for j in range(4)]
            return functools.reduce(
                lambda a, b: (a + b).astype(jnp.bfloat16), parts).astype(dtype)

        patch(moe, "_megablox",
              lambda: types.SimpleNamespace(gmm=real.gmm, tgmm=tgmm))
    elif name == "conv_bf16":
        def conv(x, taps, weight, bias, valid, silu=True, scope="ssm_conv"):
            S, K = x.shape[1], weight.shape[0]
            full = jnp.concatenate([taps.astype(x.dtype), x], axis=1)
            w = weight.astype(x.dtype)
            with jax.named_scope(scope):
                y = sum(full[:, k:k + S] * w[k] for k in range(K))
            return y.astype(x.dtype), full[:, S:]

        patch(ssm_scan, "causal_conv", conv)
    elif name == "silu_on_taps":
        real_conv = ssm_scan.causal_conv
        patch(ssm_scan, "causal_conv",
              lambda *a, silu=True, **kw: real_conv(*a, silu=True, **kw))
    elif name == "qk_norm_full_width":
        class FullWidth(llama.RMSNorm):
            """The statistic over every head of a ``[B, S, heads, D]``
            input; the weight ``[D]`` as it is."""

            @llama.nn.compact
            def __call__(self, x):
                weight = self.param("weight", llama.nn.initializers.ones_init(),
                                    (x.shape[-1],), self.param_dtype)
                xf = x.astype(jnp.float32)
                axes = (-2, -1) if x.ndim == 4 else (-1,)
                var = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
                return (xf * jax.lax.rsqrt(var + self.eps)
                        * weight.astype(jnp.float32)).astype(self.dtype)

        patch(llama, "RMSNorm", FullWidth)
    elif name == "update_frozen":
        import optax

        patch(optax, "apply_updates", lambda params, updates: params)
    try:
        yield
    finally:
        for obj, attr, old in reversed(undo):
            setattr(obj, attr, old)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lfm2-8b-a1b.train-seq8k")
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seed", type=int, default=2700000001)
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import common, manifest, traffic
    from benchmarks.harness.serve_ssm_runner import balance_router
    from benchmarks.harness.train_routed_runner import (
        adam_first_moment,
        kernel_readings,
        limits_broken,
        step0_readings,
        to_host,
        update_readings,
        warmup_from_the_first_step,
    )
    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
    )

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    devices, _ = common.check_devices(cell, args.rehearse)
    cfg, mix, tol = cell.config, cell.traffic, cell.config["tolerances"]
    names = [f for f in args.faults.split(",") if f]
    if args.kernels:
        rows = []
        for name in names:
            jax.clear_caches()
            with fault(name):
                read = kernel_readings(
                    cell, args.seed,
                    jnp.float8_e4m3fn if name == "reference_e4m3" else None)
            rows.append({"fault": name, "kernel_rel": read,
                         "past_kernel_rel": [k for k, e in read.items()
                                             if not e <= tol["kernel_rel"]]})
            print(json.dumps(rows[-1]), flush=True)
        return write(rows, "lfm2_kernel_faults.json", devices, args, tol)
    seq, vocab = mix["seq_len"], cfg["vocab_size"]
    nxd.initialize_model_parallel(devices=devices, tensor_parallel_size=1)
    tr_opts = cfg["training"]
    config = nxd.training_config(
        learning_rate=tr_opts["learning_rate"],
        zero_one_enabled=tr_opts["zero_one_enabled"],
        compute_dtype=tr_opts["compute_dtype"],
        param_dtype=tr_opts["param_dtype"], seed=args.seed,
        tensor_parallel_size=1)
    lr = warmup_from_the_first_step(tr_opts)
    module_cls, model_cfg = common.program_config(
        {**cfg["program"],
         "kwargs": {**cfg["program"]["kwargs"], "max_seq_len": seq}})
    model = initialize_parallel_model(
        config, lambda: module_cls(model_cfg),
        (jnp.zeros((1, seq), jnp.int32),), seed=args.seed)
    params, _, _ = balance_router(model.module, model.params, args.seed, vocab)
    batch0 = traffic.train_batch(mix, vocab, args.seed, 0)

    def zero_bias(tree):
        return jax.tree_util.tree_map_with_path(
            lambda p, x: jnp.zeros_like(x)
            if "router_bias" in jax.tree_util.keystr(p) else x, tree)

    def e4m3(tree):
        return jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), tree)

    def on_host(tree):
        return jax.tree.map(np.asarray, tree)

    def step0(name):
        """The library's train step on the step-0 batch under the step's
        fault ``name``: its loss, its ``grad_norm`` and what it left, on
        the host.  The step donates the parameters: they come back from
        the host's copy."""
        nonlocal params
        host = on_host(params)
        model.params = params
        opt = initialize_parallel_optimizer(config, model, learning_rate=lr)
        loss_fn = whole = make_causal_lm_loss_sum(
            chunk_size=tr_opts["loss_chunk"])
        if name == "half_batch":
            def loss_fn(module, p, batch, rng):
                return whole(module, p,
                             {k: v[:1] for k, v in batch.items()}, rng)
        step = make_train_step(
            config, model, opt, loss_fn, batch_spec={
                "ids": default_batch_spec(), "labels": default_batch_spec()})
        after, state, m = step(params, opt.state,
                               {k: jnp.asarray(v) for k, v in batch0.items()},
                               None)
        left = (to_host(after), to_host(adam_first_moment(state)))
        loss, norm = float(m["loss"]), float(m["grad_norm"])
        del after, state, opt, step
        params = model.params = jax.tree.map(jnp.asarray, host)
        return loss, norm, left

    if names[0] != "none":
        names.insert(0, "none")    # the step's faults read its gradients
    rows, asis = [], {}
    for name in names:
        # a custom_vjp's backward is traced once a signature and kept: a
        # patch inside one (the weight gradient's) needs the caches empty
        jax.clear_caches()
        with fault(name):
            if name in STEP_FAULTS:
                r = asis["r"]
                loss, norm, left = step0(name)
            elif name == "reference_e4m3":
                # both sets of weights from the HOST, the device's own copy
                # let go for the while: the program's gradients alone ask
                # for 9.2 of the chip's 16 GB, and a second copy of 606M
                # float32 parameters beside the first does not fit
                host = on_host(params)
                params = model.params = None
                r = step0_readings(cell, model.module, e4m3(host), batch0,
                                   host)
                params = model.params = jax.tree.map(jnp.asarray, host)
                # the program and its step are none's
                r.update(params=asis["r"]["params"], kernel=kernel_readings(
                    cell, args.seed, jnp.float8_e4m3fn))
                loss, norm, left = asis["step"]
            else:
                r = step0_readings(
                    cell, model.module, params, batch0,
                    zero_bias(params) if name == "bias_dropped" else None)
                r["kernel"] = kernel_readings(cell, args.seed)
                # no step under a fault of the program: the loss and the
                # grad_norm are its own value_and_grad's
                loss, norm, left = r["own_loss"], r["own_norm"], None
                if name == "none":
                    loss, norm, left = asis["step"] = step0(name)
                    asis["r"] = r
        upd = left and update_readings(cell, r, *left, config.optimizer,
                                       float(lr(0)))
        broken = limits_broken(r, tol, loss, norm, upd)
        margin = r["margin"]
        row = {
            "fault": name,
            "step0_loss_rel": abs(loss - r["ref_loss"]) / r["ref_loss"],
            "step0_grad_norm_rel": abs(norm - r["ref_norm"]) / r["ref_norm"],
            "kernel_rel": r["kernel"],
            "step0_update_rel": upd and upd["update_rel"],
            "step0_update_flipped": upd and upd["flipped"],
            "timed_grad_rel": upd and {
                g: e for g, (e, _) in upd["timed_grads"].items()},
            "timed_grad_cosine_min": upd and min(
                c for _, c in upd["timed_grads"].values()),
            "grad_rel": {g: e for g, (e, _) in r["grads"].items()},
            "grad_cosine_min": min(c for _, c in r["grads"].values()),
            "routing_rows_same": [float(x) for x in r["same"]],
            "margin_p999": [float(np.quantile(m, 0.999)) for m in margin],
            "margin_max": [float(m.max()) for m in margin],
            # the first routed layer's rows further off than each threshold
            "first_layer_rows_far": {str(t): float(np.mean(margin[0] > t))
                                     for t in (0.002, 0.004, 0.008, 0.012,
                                               0.02)},
            "not_correct_because": broken,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    write(rows, "lfm2_faults.json", devices, args, tol)


def write(rows, name, devices, args, tol):
    from benchmarks.harness import manifest

    out = os.path.join(manifest.REPO_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, name), "w") as f:
        json.dump({"device": str(devices[0].device_kind), "seed": args.seed,
                   "tolerances": tol, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
