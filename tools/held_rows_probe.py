#!/usr/bin/env python
"""held_rows_probe.py — what a held expert share costs over the rows it holds, alone.

    chiprun -- python tools/held_rows_probe.py [--slabs 0,4096,6144,...]

ONE routed layer (``parallel.moe.ExpertParallelMLP``, dropless, sigmoid
scores and a bias, gated experts stored apart) at ``--rows`` token rows of
``--widths H,I``, ``--top-k`` experts a token, ``--held`` of ``--experts``
held (default LFM2-8B-A1B's train step: 16,384 x 2048 x 1792, 4 a token, 8
of 32), forward AND backward under ``jax.checkpoint`` with the model's
selective policy, alone in a ``jit``, bfloat16 compute over float32
parameters.  For every first-span size in ``--slabs`` (0: the block once over
the whole array, ``held_rows_slab``'s answer where it declines; ``rule``:
what the rule itself gives; the second span is the rest of the rows) and two routers — ``balanced`` (a seeded router whose
bias was balanced over all experts: a quarter of the rows held) and
``all-held`` (a bias that sends EVERY row to the held experts: every slab
runs) — it reports ms a call (host clock around ``block_until_ready``,
three calls queued a sample, the median of ``--samples``), the seconds
``lower().compile()`` took, the serialized executable's bytes, the rows the
walk computed, and every gradient's distance from the whole-array block's.
``--forward`` times the forward alone (a serving program's question).
``--primitives`` also times, alone, the row gather and the row scatter-add
of one slab, the gather of all ``N * K`` rows, and the inverse of the sort's
permutation as a scatter and as a second sort.  Rows are printed as they come and written to
``chiprun_out/held_rows_probe.json``.  ``--rehearse`` runs a toy shape on
the CPU (no number of it is a device number).
"""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--widths", default="2048,1792")
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--held", type=int, default=8)
    ap.add_argument("--experts", type=int, default=32)
    ap.add_argument("--slabs", default="0,rule,4096,8192,9216,16384,18432")
    ap.add_argument("--routers", default="balanced,all-held")
    ap.add_argument("--samples", type=int, default=10)
    ap.add_argument("--forward", action="store_true")
    ap.add_argument("--primitives", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.rows, args.widths, args.samples = 256, "64,48", 2

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuronx_distributed_tpu.parallel import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("no TPU: a probe's numbers are the chip's "
                         "(--rehearse runs the control flow on the CPU)")
    H, I = (int(v) for v in args.widths.split(","))
    N, K, E, Eg = args.rows, args.top_k, args.held, args.experts
    layer = moe.ExpertParallelMLP(
        num_experts=E, intermediate_size=I, top_k=K, dispatch="dropless",
        fused_gate_up=False, num_experts_global=Eg, first_expert=0,
        router_scores="sigmoid", router_bias=True, dtype=jnp.bfloat16,
        param_dtype=jnp.float32, kernel_init=moe.per_expert_lecun)
    x = jax.random.normal(jax.random.PRNGKey(1), (N, H), jnp.bfloat16)
    cot = jax.random.normal(jax.random.PRNGKey(2), (N, H), jnp.bfloat16)
    from flax.core import meta

    params = meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(0), x))

    def with_bias(bias):
        p = jax.tree.map(lambda a: a, params)
        p["params"]["router_bias"] = jnp.asarray(bias, jnp.float32)
        return p

    # balanced: a few rounds of the bias against the counted load, over ALL
    # experts (the held ones then take about E / Eg of the assignments)
    scores = jax.nn.sigmoid(x.astype(jnp.float32)
                            @ params["params"]["router"].astype(jnp.float32))
    bias = np.zeros(Eg, np.float32)
    for _ in range(40):
        _, choice = jax.lax.top_k(scores + bias[None, :], K)
        took = np.bincount(np.asarray(choice).reshape(-1), minlength=Eg)
        bias -= 0.02 * np.sign(took - took.mean())
    routers = {"balanced": with_bias(bias),
               "all-held": with_bias(np.where(np.arange(Eg) < E, 10., 0.))}

    rule = moe.held_rows_slab
    slabs = [rule(N * K, E, Eg) if s == "rule" else int(s)
             for s in args.slabs.split(",")]

    def program(slab):
        def loss(p, x):
            block = jax.checkpoint(
                lambda p, x: layer.apply(p, x, mutable=["moe_stats"]),
                policy=jax.checkpoint_policies.
                checkpoint_dots_with_no_batch_dims, prevent_cse=False)
            (y, _), sown = block(p, x)
            stats = sown["moe_stats"]
            rows = (stats["computed"][-1] if "computed" in stats
                    else jnp.int32(N * K))
            return (jnp.sum(y.astype(jnp.float32) * cot.astype(jnp.float32)),
                    (rows, jnp.sum(stats["load"][-1])))

        def forward(p, x):
            value, counted = loss(p, x)
            return counted, value

        fn = forward if args.forward else jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)
        moe.held_rows_slab = lambda *a: slab
        try:
            start = time.perf_counter()
            compiled = jax.jit(fn).lower(routers["balanced"], x).compile()
            seconds = time.perf_counter() - start
        finally:
            moe.held_rows_slab = rule
        try:
            from jax.experimental import serialize_executable

            size = len(serialize_executable.serialize(compiled)[0])
        except Exception as e:      # a backend that cannot serialize
            size = f"{type(e).__name__}"
        return compiled, seconds, size

    def timed(call):
        jax.block_until_ready(call())
        out = []
        for _ in range(args.samples):
            start = time.perf_counter()
            for _ in range(3):
                r = call()
            jax.block_until_ready(r)
            out.append((time.perf_counter() - start) / 3 * 1e3)
        return statistics.median(out)

    def rel(a, b):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    rows_out, whole = [], {}
    for slab in slabs:
        try:
            compiled, seconds, size = program(slab)
        except Exception as e:
            row = {"slab": slab, "refused": f"{type(e).__name__}: "
                   + str(e).splitlines()[0][:300]}
            rows_out.append(row)
            print(json.dumps(row), flush=True)
            continue
        for name in args.routers.split(","):
            p = routers[name]
            out = compiled(p, x)
            ms = timed(lambda: compiled(p, x))
            row = {"slab": slab, "router": name, "ms": round(ms, 3),
                   "compile_s": round(seconds, 1), "executable_bytes": size}
            if args.forward:
                (computed, held), _ = out
            else:
                (_, (computed, held)), grads = out
                grads = {"x": grads[1], **{k: v for k, v in
                                           grads[0]["params"].items()
                                           if k != "router_bias"}}
                if slab == 0:
                    whole[name] = jax.tree.map(np.asarray, grads)
                elif name in whole:
                    row["grad_rel_to_whole"] = {
                        k: round(rel(v, whole[name][k]), 6)
                        for k, v in grads.items()}
            row.update(rows_computed=int(computed), rows_held=int(held),
                       rows_laid_out=N * K)
            rows_out.append(row)
            print(json.dumps(row), flush=True)
        del compiled

    if args.primitives:
        S = min(rule(N * K, E, Eg) or 6144, N)
        tok = jax.random.randint(jax.random.PRNGKey(3), (S,), 0, N)
        upd = jax.random.normal(jax.random.PRNGKey(4), (S, H), jnp.float32)
        base = jnp.zeros((N, H), jnp.float32)
        cases = {
            "gather_bf16": (jax.jit(lambda t: x[t]), (tok,)),
            "gather_f32": (jax.jit(lambda t: base[t]), (tok,)),
            "scatter_add_f32": (jax.jit(lambda b, t, u: b.at[t].add(u),
                                        donate_argnums=0), None),
            "scatter_add_f32_sorted": (jax.jit(
                lambda b, t, u: b.at[t].add(u, indices_are_sorted=True),
                donate_argnums=0), None),
            "scatter_add_f32_sorted_unique": (jax.jit(
                lambda b, t, u: b.at[t].add(u, indices_are_sorted=True,
                                            unique_indices=True),
                donate_argnums=0), None),
            "scatter_add_scalars": (jax.jit(
                lambda t, u: jnp.zeros((N * K,), jnp.float32).at[t].add(
                    u[:, 0])), (tok, upd)),
        }
        perm = jax.random.permutation(jax.random.PRNGKey(6), N * K)
        cases.update({
            "inverse_by_scatter_all": (jax.jit(
                lambda o: jnp.zeros((N * K,), jnp.int32).at[o].set(
                    jnp.arange(N * K, dtype=jnp.int32))), (perm,)),
            "inverse_by_argsort_all": (jax.jit(jnp.argsort), (perm,)),
            "gather_bf16_all": (jax.jit(lambda o: x[o // K]), (perm,)),
        })
        uniq = jnp.sort(jax.random.permutation(jax.random.PRNGKey(5), N)[:S])
        for name, (fn, call_args) in cases.items():
            if call_args is None:
                t = uniq if "sorted" in name else tok
                state = {"b": base + 0}

                def call(fn=fn, t=t, state=state):
                    state["b"] = fn(state["b"], t, upd)
                    return state["b"]
            else:
                def call(fn=fn, call_args=call_args):
                    return fn(*call_args)
            row = {"primitive": name, "rows": N * K if name.endswith("_all")
                   else S, "ms": round(timed(call), 4)}
            rows_out.append(row)
            print(json.dumps(row), flush=True)

    out = os.path.join(ROOT, "chiprun_out", "held_rows_probe.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"device": str(dev.device_kind), "rows": N,
                   "widths": [H, I], "experts": [E, Eg], "top_k": K,
                   "forward_only": args.forward, "programs": rows_out}, f,
                  indent=1)


if __name__ == "__main__":
    main()
