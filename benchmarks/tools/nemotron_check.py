#!/usr/bin/env python
"""nemotron_check.py — the lower-precision controls of the Nemotron cell's
three limits, on the chip at published widths: what each of ``tolerances``
(``logits_rel``, ``routing_sigmas``, ``state_rel``) reads for the faithful
program and for a program one precision lower somewhere, through the cell's
own probe and reference (``harness/serve_ssm_runner.readings``).

    python benchmarks/tools/nemotron_check.py --workload nemotron-3-nano.serve-agents

Variants (``--variants``, all by default):

- ``faithful``: the program as it is served;
- ``bf16_state``: every scan state rounded to bfloat16 as a call leaves it
  (what a bfloat16 state array would hold) — passes the logits, must fail
  ``state_rel`` by 10 x or more;
- ``e4m3_experts``: the held experts' up and down weights rounded to
  float8 e4m3 as the grouped matmuls read them (the reference keeps the
  bf16 weights);
- ``bf16_router``: the router's logits and sigmoid scores in bfloat16.

One table to the log and ``chiprun_out/nemotron_check.json``.  ``--rehearse``
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
    import jax.numpy as jnp

    from neuronx_distributed_tpu.ops import ssm_scan as ssm
    from neuronx_distributed_tpu.parallel import moe

    undo = []

    def bf16(x):
        # an explicit rounding: the compiler may drop a convert to a
        # narrower type and back (it did, on the v5e: PERF.md, PR 32)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def patch(obj, attr, new):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "bf16_state":
        scan = ssm.ssm_scan

        def rounded(*a, **k):
            y, st = scan(*a, **k)
            return y, bf16(st)

        patch(ssm, "ssm_scan", rounded)
    elif name == "e4m3_experts":
        gmm = moe.grouped_matmul
        # e4m3: 4 exponent and 3 mantissa bits
        patch(moe, "grouped_matmul", lambda x, w, *a, **k: gmm(
            x, jax.lax.reduce_precision(w, 4, 3), *a, **k))
    elif name == "bf16_router":
        sigmoid = jax.nn.sigmoid
        patch(jax.nn, "sigmoid", lambda x: bf16(sigmoid(bf16(x))))
    elif name != "faithful":
        raise SystemExit(f"unknown variant {name!r}")
    try:
        yield
    finally:
        for obj, attr, old in reversed(undo):
            setattr(obj, attr, old)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1234567891)
    ap.add_argument("--variants",
                    default="faithful,bf16_state,e4m3_experts,bf16_router")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import common, manifest, serve_ssm_runner
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    devices, _ = common.check_devices(cell, args.rehearse)
    params, model = serve_ssm_runner.build(cell, args, devices, None)
    tol = cell.config["tolerances"]
    table = {}
    for name in args.variants.split(","):
        with variant(name):
            # a model of its own: its programs are traced under the patch
            m = ParallelInferenceModel(model.module, params, model.config)
            rows = serve_ssm_runner.readings(cell, params, m, args.seed)
        table[name] = rows
        for r in rows:
            a = r["agree"]
            common.log(
                f"[control] {name}: prompt {r['prompt']}: logits "
                f"{r['logits_rel']:.4f} ({r['logits_rel'] / tol['logits_rel']:.2f}"
                f" x its limit), state {r['state_rel']:.2e} "
                f"({r['state_rel'] / tol['state_rel']:.3g} x), experts "
                f"{a['agree_share']:.4f} agree, {a['accepted']} accepted "
                f"(nearest {a['worst_accepted_gap_over_allowance']:.2f} x the "
                f"allowance at {tol['routing_sigmas']} sigma), "
                f"{a['refused']} refused (worst "
                f"{a['worst_refused_gap_over_allowance']:.2f} x)")
        del m
    out = os.path.join(manifest.REPO_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "nemotron_check.json"), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
