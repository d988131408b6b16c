#!/usr/bin/env python
"""smallthinker_check.py — the lower-precision controls of the SmallThinker
cell's limits, on the chip at published widths: what each of ``tolerances``
(``logits_rel``, ``routing_sigmas``) reads for the faithful program and for a
program one precision lower somewhere, through the cell's own probe — two
page kinds, window pages given back — and reference
(``harness/serve_window_runner.readings``).

    python benchmarks/tools/smallthinker_check.py --workload smallthinker-21b-a3b.serve-longdocs

Variants (``--variants``, all by default):

- ``faithful``: the program as it is served;
- ``e4m3_experts``: the experts' gate, up and down weights rounded to
  float8 e4m3 as the grouped matmuls read them (the reference keeps the
  bf16 weights);
- ``bf16_router``: the router's logits and softmax scores in bfloat16.

One table to the log and ``chiprun_out/smallthinker_check.json``.
``--rehearse`` runs the configuration's tiny sizes on any platform (a
control-flow check).
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

    from neuronx_distributed_tpu.parallel import moe

    undo = []

    def bf16(x):
        # an explicit rounding: the compiler may drop a convert to a
        # narrower type and back (it did, on the v5e: PERF.md, PR 32)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def patch(obj, attr, new):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "e4m3_experts":
        gmm = moe.grouped_matmul
        # e4m3: 4 exponent and 3 mantissa bits
        patch(moe, "grouped_matmul", lambda x, w, *a, **k: gmm(
            x, jax.lax.reduce_precision(w, 4, 3), *a, **k))
    elif name == "bf16_router":
        softmax = jax.nn.softmax
        patch(jax.nn, "softmax",
              lambda x, *a, **k: bf16(softmax(bf16(x), *a, **k)))
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
                    default="faithful,e4m3_experts,bf16_router")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import common, manifest, serve_window_runner
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    devices, _ = common.check_devices(cell, args.rehearse)
    params, model = serve_window_runner.build(cell, args, devices, None)
    tol = cell.config["tolerances"]
    table = {}
    for name in args.variants.split(","):
        with variant(name):
            # a model of its own: its programs are traced under the patch
            m = ParallelInferenceModel(model.module, params, model.config)
            rows = serve_window_runner.readings(cell, params, m, args.seed)
        table[name] = rows
        for r in rows:
            a = r["agree"]
            common.log(
                f"[control] {name}: prompt {r['prompt']}: logits "
                f"{r['logits_rel']:.4f} ({r['logits_rel'] / tol['logits_rel']:.2f}"
                f" x its limit), {r['freed']} window page(s) given back, "
                f"experts "
                f"{a['agree_share']:.4f} agree, {a['accepted']} accepted "
                f"(nearest {a['worst_accepted_gap_over_allowance']:.2f} x the "
                f"allowance at {tol['routing_sigmas']} sigma), "
                f"{a['refused']} refused (worst "
                f"{a['worst_refused_gap_over_allowance']:.2f} x)")
        del m
    out = os.path.join(manifest.REPO_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "smallthinker_check.json"), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
