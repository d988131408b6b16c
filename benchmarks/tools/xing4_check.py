#!/usr/bin/env python
"""xing4_check.py — the lower-precision and omission controls of the Xing4.0
cell's four limits, on the chip at published widths: what each of
``tolerances`` (``logits_rel``, ``routing_sigmas``, ``latent_rel``,
``latent_rms``) reads for the faithful program and for a program one
precision lower, or one term short, somewhere — through the cell's own probe
and reference (``harness/serve_latent_runner.readings``) and the run's own
comparison (``serve_latent_runner.verdict``): a control that comes out
``correct`` is named as such.

    python benchmarks/tools/xing4_check.py --workload xing4.0-29b-a4b.serve-longdocs

Variants (``--variants``, all by default), each the program's own functions
patched while its programs are traced:

- ``faithful``: the program as it is served;
- ``e4m3_latents`` / ``int8_latents``: every latent row rounded to float8
  e4m3, or to 255 levels of its own largest element, as it is written to the
  pool (what such a pool would hold);
- ``bf16_maps``: the residual's mixing maps and every Sinkhorn sweep rounded
  to bfloat16;
- ``e4m3_experts``: the experts' weights rounded to e4m3 as the grouped
  matmuls read them (the reference keeps the bf16 weights);
- ``no_mscale``: the softmax scale without YaRN's ``mscale^2``;
- ``plain_rope``: RoPE's own frequencies where the config asks for YaRN's;
- ``sweeps_19``: one Sinkhorn sweep fewer than ``hc_sinkhorn_iters``.

``--prompt-lens`` probes other prompts than the configuration's (the
reference of the longest is most of a variant's time).  One table to the log
and ``chiprun_out/xing4_check.json`` (a variant's readings, then ``"not
correct"``: the verdict's reasons, empty where the control passes).
``--rehearse`` runs the configuration's tiny sizes on any platform (a
control-flow check; its table goes to ``xing4_check.rehearsal.json``).
"""

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

VARIANTS = ("faithful", "e4m3_latents", "int8_latents", "bf16_maps",
            "e4m3_experts", "no_mscale", "plain_rope", "sweeps_19")


@contextlib.contextmanager
def variant(name):
    """The program's own functions, one precision lower or one term short,
    while a variant's programs are traced."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models import hybrid, llama
    from neuronx_distributed_tpu.ops import kv_pool_write
    from neuronx_distributed_tpu.parallel import moe

    undo = []

    def bf16(x):
        # an explicit rounding: the compiler may drop a convert to a
        # narrower type and back (it did, on the v5e: PERF.md, PR 32)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def patch(obj, attr, new):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name in ("e4m3_latents", "int8_latents"):
        write = kv_pool_write.write_pool_rows

        def coarse(new):
            f = new.astype(jnp.float32)
            if name == "e4m3_latents":
                return jax.lax.reduce_precision(f, 4, 3).astype(new.dtype)
            step = jnp.max(jnp.abs(f), axis=-1, keepdims=True) / 127.0
            return (jnp.round(f / jnp.where(step == 0, 1.0, step)) * step
                    ).astype(new.dtype)

        patch(kv_pool_write, "write_pool_rows",
              lambda pool, new, *a, **k: write(pool, coarse(new), *a, **k))
    elif name == "bf16_maps":
        read, wrote = llama.hc_read, llama.hc_write

        def sweeps(z, iters, eps):
            m = bf16(jnp.exp(bf16(z.astype(jnp.float32))))
            for _ in range(iters):
                m = bf16(m / bf16(jnp.sum(m, axis=-2, keepdims=True) + eps))
                m = bf16(m / bf16(jnp.sum(m, axis=-1, keepdims=True) + eps))
            return m

        patch(llama, "sinkhorn", sweeps)
        patch(llama, "hc_read", lambda x, pre: read(x, bf16(pre)))
        patch(llama, "hc_write", lambda x, y, post, res: wrote(
            x, y, bf16(post), bf16(res)))
    elif name == "e4m3_experts":
        gmm = moe.grouped_matmul
        patch(moe, "grouped_matmul", lambda x, w, *a, **k: gmm(
            x, jax.lax.reduce_precision(w, 4, 3), *a, **k))
    elif name == "no_mscale":
        patch(hybrid, "mla_softmax_scale", lambda cfg: (
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)
    elif name == "plain_rope":
        patch(llama, "yarn_inv_freq",
              lambda head_dim, theta, *_: 1.0 / theta ** (
                  jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    elif name == "sweeps_19":
        sweep = llama.sinkhorn
        patch(llama, "sinkhorn", lambda z, iters, eps: sweep(
            z, iters - 1, eps))
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
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--prompt-lens", default=None,
                    help="comma-separated, in place of probe.prompt_lens")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    from benchmarks.harness import common, manifest, serve_latent_runner
    from neuronx_distributed_tpu.trace import ParallelInferenceModel

    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    if args.prompt_lens:
        cell.config["probe"]["prompt_lens"] = [
            int(n) for n in args.prompt_lens.split(",")]
    devices, _ = common.check_devices(cell, args.rehearse)
    params, model = serve_latent_runner.build(cell, args, devices, None)
    tol = cell.config["tolerances"]
    table = {}
    for name in args.variants.split(","):
        with variant(name):
            # a model of its own: its programs are traced under the patch
            jax.clear_caches()
            m = ParallelInferenceModel(model.module, params, model.config)
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
    name = "xing4_check.rehearsal.json" if args.rehearse else "xing4_check.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
