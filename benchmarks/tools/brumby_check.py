#!/usr/bin/env python
"""brumby_check.py — the lower-precision controls of the Brumby cell's
limits, on the chip at published widths: what each of ``tolerances``
(``logits_rel``, ``state_rel``, ``decay_abs``) reads for the faithful
program and for a computation one precision lower somewhere, through the
cell's own probe and reference (``harness/serve_retention_runner.readings``).

    python benchmarks/tools/brumby_check.py --workload brumby-14b.serve-continuations

Variants (``--variants``, all by default):

- ``faithful``: the program as it is served;
- ``e4m3_reference``: the REFERENCE reading the weights rounded to float8
  e4m3, the nearest precision below the bf16 the configuration states (the
  program keeps them);
- ``bf16_state``: the program's state rows rounded to bfloat16 as each call
  leaves them;
- ``bf16_decay``: the program's log decay rounded to bfloat16.

One table to the log and ``chiprun_out/brumby_check.json``.  ``--rehearse``
runs the configuration's tiny sizes on any platform (a control-flow check).
"""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


@contextlib.contextmanager
def variant(name):
    """The program's own functions, one precision lower, while a variant's
    programs are traced."""
    import jax

    from neuronx_distributed_tpu.models import hybrid
    from neuronx_distributed_tpu.ops import power_retention as pr

    undo = []

    def bf16(x):
        # an explicit rounding: the compiler may drop a convert to a
        # narrower type and back (it did, on the v5e: PERF.md, PR 32)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def patch(obj, attr, new):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "bf16_state":
        step, chunk = pr.retention_step, pr.retention_chunk

        def step_rounded(states, *a, **k):
            states, num = step(states, *a, **k)
            return bf16(states), num

        def chunk_rounded(*a, **k):
            o, states, zs = chunk(*a, **k)
            return o, bf16(states), zs

        patch(pr, "retention_step", step_rounded)
        patch(pr, "retention_chunk", chunk_rounded)
    elif name == "bf16_decay":
        decay = hybrid._log_decay
        patch(hybrid, "_log_decay", lambda gate, bias: bf16(decay(gate, bias)))
    elif name not in ("faithful", "e4m3_reference"):
        raise SystemExit(f"unknown variant {name!r}")
    try:
        yield
    finally:
        for obj, attr, old in reversed(undo):
            setattr(obj, attr, old)


def e4m3_weights(cell):
    """``params -> `` the reference's weights with every matrix rounded to
    e4m3 (4 exponent and 3 mantissa bits), a layer at a time as the
    reference reaches it: a rounded copy of all of them beside the served
    ones does not fit the chip."""
    import jax

    def rounded(tree):
        return {k: jax.lax.reduce_precision(v, 4, 3)
                if getattr(v, "ndim", 0) >= 2 else v for k, v in tree.items()}

    class Layers:
        def __init__(self, layers):
            self.layers = layers

        def __len__(self):
            return len(self.layers)

        def __iter__(self):
            return (rounded(lw) for lw in self.layers)

    def build(params):
        w = cell.reference_weights(params)
        return {**rounded({k: v for k, v in w.items() if k != "layers"}),
                "layers": Layers(w["layers"])}

    return build


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1234567891)
    ap.add_argument("--variants",
                    default="faithful,e4m3_reference,bf16_state,bf16_decay")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import common, manifest, serve_retention_runner
    from benchmarks.harness import serve_runner

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(manifest.REPO_ROOT, ".jax_cache", cell.name))
    from neuronx_distributed_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.obs.compile_ledger import CompileLedger
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    devices, _ = common.check_devices(cell, args.rehearse)
    params, model = serve_runner.build(cell, args, devices, CompileLedger())
    s = cell.config["serving"]
    table = {}
    for name in args.variants.split(","):
        ref_w = e4m3_weights(cell) if name == "e4m3_reference" else None
        with variant(name):
            # a variant's programs are its own: a new wrapper, new traces
            fresh = ParallelInferenceModel(
                model.module, params,
                InferenceConfig(batch_size=s["slots"],
                                context_len=s["context_len"],
                                max_total_len=s["max_total_len"],
                                kv_cache_dtype=getattr(
                                    jnp, s["kv_cache_dtype"])))
            rows = serve_retention_runner.readings(cell, params, fresh,
                                                   args.seed, ref_w)
        table[name] = rows
        for r in rows:
            common.log(
                f"[variant {name}] prompt {r['len']}: logits_rel "
                + " ".join(f"{e:.4f}" for e in r["logits"]) + " (rms "
                + " ".join(f"{e:.4f}" for e in r["rms"]) + "); state_rel "
                + " ".join(f"{e:.2e}" for e in r["state"]) + "; decay_abs "
                + " ".join(f"{e:.2e}" for e in r["decay"]))
    out = os.path.join(manifest.REPO_ROOT, "chiprun_out", "brumby_check.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"workload": cell.name, "seed": args.seed,
                   "variants": table}, f, indent=1)
    print(json.dumps({name: {k: max(max(r[k]) for r in rows)
                             for k in ("logits", "state", "decay")}
                      for name, rows in table.items()}))


if __name__ == "__main__":
    main()
