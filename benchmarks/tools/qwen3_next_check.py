#!/usr/bin/env python
"""qwen3_next_check.py — the reference comparison of the Qwen3-Next cell at
published widths, runnable ALONE on the chip (no window, no engine): what
each of ``tolerances`` (``logits_rel``, ``state_rel``, ``state_rel_deep``,
the refused expert choices under ``routing_sigmas``) reads for the faithful
program and for a program that is wrong or one precision lower somewhere,
through the cell's own probe and reference
(``harness/serve_gdn_runner.readings``), each reading beside its limit and
then the verdict a run would get (``serve_gdn_runner.why_not``, what
``reference_check`` returns): the faithful program has to come out correct
and every other variant NOT correct, by the limit its line below names — the
tool exits 1 otherwise (not under ``--rehearse``, whose toy is float32).  A later PR that touches the delta kernels re-reads
it without a whole cell run.

    python benchmarks/tools/qwen3_next_check.py --workload qwen3-next-80b-a3b.serve-longdocs

Variants (``--variants``; ``faithful`` by default):

- ``faithful``: the program as it is served;
- ``bf16_decay``: the running sums of the log decays inside a block of the
  chunked form (the one ``jnp.cumsum`` of ``ops/gated_delta.py``, float32 in
  the program) summed in bfloat16 — must fail ``state_rel``;
- ``dropped_beta``: the chunks' ``beta`` taken as 1 — must fail both state
  limits;
- ``e4m3_stream``: the residual stream rounded to float8 e4m3 where the
  program rounds it to bfloat16 — must fail ``logits_rel``.

``--prompts 400,3000`` probes other lengths than the configuration's.  One
table to the log and ``chiprun_out/qwen3_next_check.json``.  ``--rehearse``
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
    """The program's own functions, wrong or one precision lower, while a
    variant's programs are traced."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models import llama
    from neuronx_distributed_tpu.ops import gated_delta as gd

    undo = []

    def patch(obj, attr, new):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "bf16_decay":
        import inspect

        # the module's one cumsum is the decay sums': the module sees a
        # jax.numpy whose cumsum sums in bfloat16 (the program keeps no
        # hook for a control)
        assert inspect.getsource(gd).count("jnp.cumsum(") == 1

        class LowerSums:
            def __getattr__(self, attr):
                return getattr(jnp, attr)

            @staticmethod
            def cumsum(x, axis):
                return jnp.cumsum(x.astype(jnp.bfloat16),
                                  axis=axis).astype(jnp.float32)

        patch(gd, "jnp", LowerSums())
    elif name == "dropped_beta":
        chunk = gd.gdn_chunk
        patch(gd, "gdn_chunk", lambda q, k, v, g, beta, *a, **kw: chunk(
            q, k, v, g, jnp.ones_like(beta), *a, **kw))
    elif name == "e4m3_stream":
        block_add = llama.LlamaBlock._add
        # an explicit rounding: the compiler may drop a convert to a
        # narrower type and back (it did, on the v5e: PERF.md, PR 32)
        patch(llama.LlamaBlock, "_add", lambda self, x, h, *a: (
            jax.lax.reduce_precision(block_add(self, x, h, *a), 4, 3)))
    elif name != "faithful":
        raise SystemExit(f"unknown variant {name!r}")
    try:
        # the ops' jitted implementations keep no trace of an older variant
        jax.clear_caches()
        yield
    finally:
        for obj, attr, old in reversed(undo):
            setattr(obj, attr, old)
        jax.clear_caches()


# the limit a variant has to fail (the faithful program fails none)
MUST_FAIL = {"faithful": None, "bf16_decay": "state_rel",
             "dropped_beta": "state_rel", "e4m3_stream": "logits_rel"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1234567891)
    ap.add_argument("--variants", default="faithful")
    ap.add_argument("--prompts", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmarks.harness import common, manifest, serve_gdn_runner
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    if args.prompts:
        cell.config["probe"]["prompt_lens"] = [
            int(n) for n in args.prompts.split(",")]
    devices, _ = common.check_devices(cell, args.rehearse)
    params, model = serve_gdn_runner.build(cell, args, devices, None)
    tol = cell.config["tolerances"]
    table, wrong = {}, []
    for name in args.variants.split(","):
        with variant(name):
            # a model of its own: its programs are traced under the patch
            m = ParallelInferenceModel(model.module, params, model.config)
            rows = serve_gdn_runner.readings(cell, params, m, args.seed)
        for r in rows:
            common.log(
                f"[control] {name}: prompt {r['prompt']}: " + ", ".join(
                    f"{key} {r[key]:.3g} of {tol[key]} "
                    f"({r[key] / tol[key]:.2f} x)"
                    for key in ("logits_rel", "state_rel", "state_rel_deep"))
                + f"; expert choices refused {r['agree']['refused']} at "
                f"{tol['routing_sigmas']} sigma (nearest accepted "
                f"{r['agree']['worst_accepted_gap_over_allowance']:.2f} x "
                "the allowance)")
        reasons = serve_gdn_runner.why_not(cell, rows)
        must = MUST_FAIL[name]
        as_wanted = (not reasons if must is None
                     else any(r[must] > tol[must] for r in rows))
        common.log(f"[control] {name}: a run would be "
                   + ("correct" if not reasons else "NOT correct: "
                      + "; ".join(reasons))
                   + ("" if as_wanted else " -- NOT what this variant has to "
                      f"read ({must or 'correct'})"))
        if not as_wanted:
            wrong.append(name)
        table[name] = {"correct": not reasons, "why_not": reasons,
                       "readings": [{**r, "agree": {k: r["agree"][k] for k in (
                           "agree_share", "accepted", "refused",
                           "worst_accepted_gap_over_allowance")}}
                           for r in rows]}
        del m
    out = os.path.join(manifest.REPO_ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "qwen3_next_check.json"), "w") as f:
        json.dump(table, f, indent=1)
    if wrong and not args.rehearse:
        raise SystemExit(f"variants that did not read as they have to: {wrong}")


if __name__ == "__main__":
    main()
