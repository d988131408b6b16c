#!/usr/bin/env python
"""granite_check.py — the lower-precision controls of the Granite cell's two
limits, on the chip at published widths: what each of ``tolerances``
(``logits_rel``, ``state_rel``) reads for the faithful program and for a
program one precision lower somewhere, through the cell's own probe and
reference (``harness/serve_ssm_dense_runner.readings``).

    python benchmarks/tools/granite_check.py --workload granite-4.0-h-micro.serve-sessions

Variants (``--variants``, all by default):

- ``faithful``: the program as it is served;
- ``bf16_state``: every scan state rounded to bfloat16 as a call leaves it
  (what a bfloat16 state array would hold) — passes the logits, must fail
  ``state_rel`` by 10 x or more;
- ``e4m3_stream``: the residual stream rounded to float8 e4m3 where the
  program rounds it to bfloat16 (after every scaled branch) — must fail
  ``logits_rel``;
- ``bf16_dt``: the scan's ``dt`` (after its softplus, float32 in the
  program) rounded to bfloat16 — a control of what ``logits_rel`` does NOT
  separate from bfloat16 activations.

One table to the log and ``chiprun_out/granite_check.json``.  ``--rehearse``
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

    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.ops import ssm_scan as ssm

    undo = []

    def rounded(x, exponent_bits, mantissa_bits):
        # an explicit rounding: the compiler may drop a convert to a
        # narrower type and back (it did, on the v5e: PERF.md, PR 32)
        return jax.lax.reduce_precision(x, exponent_bits, mantissa_bits)

    def patch(obj, attr, new):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    scan = ssm.ssm_scan
    if name == "bf16_state":
        def low_state(*a, **k):
            y, st = scan(*a, **k)
            return y, rounded(st, 8, 7)

        patch(ssm, "ssm_scan", low_state)
    elif name == "bf16_dt":
        patch(ssm, "ssm_scan", lambda x, Bm, Cm, dt, *a, **k: scan(
            x, Bm, Cm, rounded(dt, 8, 7), *a, **k))
    elif name == "e4m3_stream":
        residual = llama._residual
        patch(llama, "_residual",
              lambda x, h, scale: rounded(residual(x, h, scale), 4, 3))
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
                    default="faithful,bf16_state,e4m3_stream,bf16_dt")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import (
        common,
        manifest,
        serve_runner,
        serve_ssm_dense_runner,
    )
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    devices, _ = common.check_devices(cell, args.rehearse)
    params, model = serve_runner.build(cell, args, devices, None)
    tol = cell.config["tolerances"]
    table = {}
    for name in args.variants.split(","):
        with variant(name):
            # a model of its own: its programs are traced under the patch
            m = ParallelInferenceModel(model.module, params, model.config)
            rows = serve_ssm_dense_runner.readings(cell, params, m, args.seed)
        table[name] = rows
        for r in rows:
            common.log(
                f"[control] {name}: prompt {r['prompt']}: logits "
                f"{r['logits_rel']:.4f} ({r['logits_rel'] / tol['logits_rel']:.2f}"
                f" x its limit), state {r['state_rel']:.2e} "
                f"({r['state_rel'] / tol['state_rel']:.3g} x)")
        del m
    out = os.path.join(manifest.REPO_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "granite_check.json"), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
