#!/usr/bin/env python
"""deepseek_v2_check.py — the lower-precision and omission controls of the
DeepSeek-V2 cell's four limits, on the chip at published widths: what each of
``tolerances`` (``logits_rel``, ``routing_sigmas``, ``latent_rel``,
``latent_rms``) reads for the faithful program and for a program one
precision lower, or one term short, somewhere — through the cell's own probe
and reference (``harness/serve_latent_runner.readings``) and the run's own
comparison (``serve_latent_runner.verdict``): a control that comes out
``correct`` is named as such.

    python benchmarks/tools/deepseek_v2_check.py --workload deepseek-v2.serve-repo-context

Variants (``--variants``, all by default).  ``faithful``, ``e4m3_latents``,
``int8_latents``, ``e4m3_experts``, ``no_mscale`` and ``plain_rope`` are
``xing4_check.py``'s (the two cells share the latent pool, the grouped
matmuls and YaRN); this model's own:

- ``bf16_router``: the routers' scores rounded to bfloat16 before and after
  the softmax (the program computes them in float32);
- ``no_group_limit``: the same weights routed by the plain top 6 of 160
  (``moe_n_group = 1``), the published group limit left out.

``--prompt-lens`` probes other prompts than the configuration's;
``--unscaled`` probes the seeded weights as drawn, without the set-up's
scaling of the residual writers.  One table
to the log and ``chiprun_out/deepseek_v2_check.json``.  ``--rehearse`` runs
the configuration's tiny sizes on any platform (a control-flow check; its
table goes to ``deepseek_v2_check.rehearsal.json``).
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

SHARED = ("faithful", "e4m3_latents", "int8_latents", "e4m3_experts",
          "no_mscale", "plain_rope")
VARIANTS = SHARED + ("bf16_router", "no_group_limit")


@contextlib.contextmanager
def bf16_router():
    """``jax.nn.softmax`` rounding what it reads and what it returns to
    bfloat16 while a variant's programs are traced: the paged programs call
    it for the routers' scores alone (attention's is inside the kernels)."""
    import jax

    softmax = jax.nn.softmax

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    jax.nn.softmax = lambda x, *a, **k: bf16(softmax(bf16(x), *a, **k))
    try:
        yield
    finally:
        jax.nn.softmax = softmax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1234567891)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--prompt-lens", default=None,
                    help="comma-separated, in place of probe.prompt_lens")
    ap.add_argument("--unscaled", action="store_true",
                    help="the seeded weights as drawn: the residual writers "
                         "not scaled (what the set-up's scaling is for)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    from benchmarks.harness import (
        common,
        manifest,
        serve_latent_runner,
        serve_latent_share_runner,
        serve_runner,
    )
    from benchmarks.tools.xing4_check import variant as shared_variant
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    if args.prompt_lens:
        cell.config["probe"]["prompt_lens"] = [
            int(n) for n in args.prompt_lens.split(",")]
    devices, _ = common.check_devices(cell, args.rehearse)
    build = (serve_runner.build if args.unscaled
             else serve_latent_share_runner.build)
    params, model = build(cell, args, devices, None)
    tol = cell.config["tolerances"]
    table = {}
    for name in args.variants.split(","):
        if name not in VARIANTS:
            raise SystemExit(f"unknown variant {name!r}")
        module = model.module
        if name == "no_group_limit":
            module = type(module)(dataclasses.replace(
                module.config, moe_n_group=1, moe_topk_group=1))
        patched = (bf16_router() if name == "bf16_router"
                   else shared_variant(name if name in SHARED else "faithful"))
        with patched:
            # a model of its own: its programs are traced under the patch
            jax.clear_caches()
            m = ParallelInferenceModel(module, params, model.config)
            rows = serve_latent_runner.readings(cell, params, m, args.seed)
        why_not = serve_latent_runner.verdict(rows, tol)
        table[name] = rows + [{"not correct": why_not}]
        for r in rows:
            a = r["agree"]
            common.log(
                f"[control] {name}: prompt {r['prompt']}: logits "
                f"{r['logits_rel']:.4f} ({r['logits_rel'] / tol['logits_rel']:.2f}"
                f" x its limit), latent rows {r['latent_rel']:.5f} "
                f"({r['latent_rel'] / tol['latent_rel']:.2f} x), their rms "
                f"{r['latent_rms']:.5f} "
                f"({r['latent_rms'] / tol['latent_rms']:.2f} x), experts "
                f"{a['agree_share']:.4f} agree, {a['accepted']} accepted "
                f"(nearest {a['worst_accepted_gap_over_allowance']:.2f} x the "
                f"allowance at {tol['routing_sigmas']} sigma), "
                f"{a['refused']} refused (worst "
                f"{a['worst_refused_gap_over_allowance']:.2f} x)")
        common.log(f"[control] {name}: " + (
            "NOT correct: " + "; ".join(why_not) if why_not
            else "correct: inside every limit"))
        del m
    out = os.path.join(manifest.REPO_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    name = "deepseek_v2_check" + (".unscaled" if args.unscaled else "") + (
        ".rehearsal.json" if args.rehearse else ".json")
    with open(os.path.join(out, name), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
