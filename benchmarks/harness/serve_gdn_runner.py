"""The runner of ``"runner": "serve_gdn"`` configurations: a served model
whose layers are a GATED DELTA RULE (a state row of two arrays a slot: the
float32 state, the convolution's last inputs) or a gated softmax attention
that keeps pages, each over a routed block that HOLDS a share of its experts
beside a gated shared expert (``models/hybrid.py``, ``ops/gated_delta.py``,
``parallel/moe.py``; Qwen3-Next).

Everything of the serving run is ``serve_runner``'s — the warm-up, the
one-thread ``Loop``, ``summarize``, ``served_rate``, the ``Outcome``.  The
probe is ``serve_ssm_runner``'s (each prompt prefilled in chunks by a
one-row program told its state row, then decodes of all rows at once, the
experts of every row of every layer taken, the first state array of every
recurrent layer read back before and after each decode).  What is this
file's:

1. the SEEDED weights are left as an initialisation leaves them
   (:func:`build`): the projections that write into the residual times ``(2
   x published layers)^-1/2`` (``serve_latent_runner.
   scale_residual_projections``) and an embedding that leads the stream
   (:data:`EMBED_STD`) — ``serve_window_runner.build``'s recipe, for its
   reasons.  This family's router has no correction bias to balance
   through: the routers are left as drawn;
2. the reference follows the PROGRAM's experts at every row of every layer
   (``qwen3_next_f32.forward(choice=)``) and holds them to its own choice
   (``routing_agreement``: a different set is accepted only where the
   reference's logits of the experts swapped lie within
   ``tolerances.routing_sigmas`` of what bfloat16 rounding moves them by);
3. logits — the last prompt position and each decode, of the
   configuration's probe prompts (several short ones and one at the
   traffic's median length, so that half the slots are live at the decodes)
   — against that forward (``tolerances.logits_rel``);
4. the DELTA STATE of every delta layer, as the prompt's chunks left it and
   as the last decode left it, against the state the reference's
   token-by-token recurrence holds after the same tokens
   (``qwen3_next_f32.state_error``): the FIRST delta layer's, whose input
   is the embedding's own and whose reading is therefore the rule's
   arithmetic alone, to ``tolerances.state_rel`` — decay sums in bfloat16
   read nine times the program's there — and every later layer's, which
   also carries what bfloat16 activations moved its inputs by, to
   ``tolerances.state_rel_deep``: a chunk that drops ``beta`` or hands the
   next chunk a wrong state moves these readings whatever the logits do.
"""

from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.harness import check, serve_runner, serve_ssm_runner
from benchmarks.harness.common import log
from benchmarks.harness.serve_latent_runner import scale_residual_projections

# the seeded table's standard deviation: it LEADS the stream, which the 96
# scaled sublayers' outputs of the published depth would add ~0.5 to
# (serve_window_runner.EMBED_STD, and why); a layer-list model's table is
# drawn at DRAWN_STD (models/hybrid.py SEEDED_EMBED_STD)
EMBED_STD, DRAWN_STD = 0.25, 0.1


def lead_with_the_embedding(params):
    """The SEEDED embedding table at :data:`EMBED_STD`: what was drawn at
    :data:`DRAWN_STD` times their ratio, in place."""
    import jax
    import jax.numpy as jnp

    scale = jax.jit(lambda v: (v.astype(jnp.float32) * (EMBED_STD / DRAWN_STD)
                               ).astype(v.dtype), donate_argnums=0)
    model = dict(params["params"]["model"])
    embed = dict(model["embed"])
    table = embed["embedding"]
    embed["embedding"] = (table.replace(value=scale(table.value))
                          if hasattr(table, "value") else scale(table))
    model["embed"] = embed
    return {**params, "params": {**params["params"], "model": model}}


def readings(cell, params, model, seed) -> List[dict]:
    """Prefill-then-decode through the pages and the state rows
    (``serve_ssm_runner.probe``) against the plain float32 reference's full
    forward of the same tokens on the program's experts, a prompt of
    ``probe.prompt_lens`` at a time: ``{"prompt", "logits_rel" (worst of the
    last prompt position and each decode), "state_rel" (the first delta
    layer, after the chunks and after the last decode), "state_rel_deep"
    (the worst delta layer), "agree": routing_agreement}``, each logged as
    it is read."""
    cfg = cell.config
    s, nd = cfg["serving"], cfg["probe"]["decodes"]
    lens = cfg["probe"]["prompt_lens"]
    rs = np.random.RandomState(seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    got, choices, steps = serve_ssm_runner.probe(model, s, seqs, lens, nd)

    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = cell.reference_weights(params)
    tol = cfg["tolerances"]
    sigmas = tol["routing_sigmas"]
    out = []
    for b, L in enumerate(lens):
        ref, info = ref_mod.forward(
            ref_w, shape, seqs[b], list(range(L - 1, L + nd)),
            choice=choices[b], state_at=(L, L + nd))
        ref = np.asarray(ref, np.float32)
        agree = ref_mod.routing_agreement(info, choices[b], sigmas)
        errs = [check.rel_err(got[(b, j)], ref[j]) for j in range(nd + 1)]
        rms = [float(np.sqrt(np.mean((got[(b, j)] - ref[j]) ** 2)
                             / np.mean(ref[j] ** 2))) for j in range(nd + 1)]
        log(f"[check] prompt {L}: pages and state rows vs float32 reference, "
            f"rel err prefill {errs[0]:.4f}, decodes "
            + " ".join(f"{e:.4f}" for e in errs[1:])
            + f" (tol {tol['logits_rel']}, ref max {np.max(np.abs(ref)):.2f}; "
            "rms " + " ".join(f"{e:.4f}" for e in rms) + "); experts chosen: "
            f"{agree['agree_share']:.4f} of {agree['pairs']} (layer, row) "
            f"sets agree, {agree['accepted']} difference(s) accepted within "
            f"{sigmas} sigma (nearest "
            f"{agree['worst_accepted_gap_over_allowance']:.2f} x the "
            f"allowance), {agree['refused']} refused" + (
                f" (worst {agree['worst_refused_gap_over_allowance']:.2f} x)"
                if agree["refused"] else ""))
        # the state the chunks left (what the first decode found) and the
        # state the last decode left, layer by layer
        drift = [[ref_mod.state_error(have[i], info["states"][n][i])
                  for i in range(len(have))]
                 for have, n in ((steps[(b, 1)][0], L),
                                 (steps[(b, nd)][1], L + nd))]
        log(f"[check] prompt {L}: delta state vs the float32 recurrence, rel "
            "err a layer after the chunks "
            + " ".join(f"{e:.2e}" for e in drift[0]) + "; after "
            f"{nd} decodes " + " ".join(f"{e:.2e}" for e in drift[1])
            + f" (tol {tol['state_rel']} the first, {tol['state_rel_deep']} "
            "the others)")
        out.append({"prompt": L, "logits_rel": max(errs),
                    "state_rel": max(d[0] for d in drift),
                    "state_rel_deep": max(max(d) for d in drift),
                    "agree": agree})
    return out


def why_not(cell, rows: List[dict]) -> List[str]:
    """:func:`readings` held to the cell's limits (``tolerances.logits_rel``,
    ``routing_sigmas`` — a refused expert choice — ``state_rel`` and
    ``state_rel_deep``): why the run is not correct, if it is not."""
    tol = cell.config["tolerances"]
    out = []
    for r in rows:
        L, agree = r["prompt"], r["agree"]
        for key, which in (("state_rel", "the first delta layer's state"),
                           ("state_rel_deep", "a delta layer's state")):
            if not r[key] <= tol[key]:
                out.append(f"{which} of prompt {L} differs from the "
                           f"recurrence's by {r[key]:.2e}")
        if not r["logits_rel"] <= tol["logits_rel"]:
            out.append(f"logits of prompt {L} differ from the reference "
                       f"by {r['logits_rel']:.4f}")
        if agree["refused"]:
            out.append(
                f"prompt {L}: {agree['refused']} expert choice(s) differ "
                "from the reference's by more than rounding explains (worst "
                f"{agree['worst_refused_gap_over_allowance']:.2f} x the "
                "allowance)")
    return out


def reference_check(cell, params, model, seed) -> List[str]:
    """What decides ``correct``: :func:`why_not` of :func:`readings`."""
    return why_not(cell, readings(cell, params, model, seed))


def build(cell, args, devices, ledger):
    """``serve_runner.build``, then the SEEDED weights as an initialisation
    leaves them: the residual writers scaled by the PUBLISHED depth and the
    embedding leading the stream.  The served weights and the reference's
    are these."""
    params, model = _build(cell, args, devices, ledger)
    params = lead_with_the_embedding(scale_residual_projections(
        params, cell.config["published"]["num_hidden_layers"]))
    model.params = params
    return params, model


_build = serve_runner.build


def run(cell, args, devices, peak, clock):
    """``serve_runner.run`` with this module's build and reference check,
    then the run's routing from its counters."""
    theirs = serve_runner.reference_check, serve_runner.build
    serve_runner.reference_check, serve_runner.build = reference_check, build
    try:
        out = serve_runner.run(cell, args, devices, peak, clock)
    finally:
        serve_runner.reference_check, serve_runner.build = theirs
    c = out.reading.counters
    made = c.get("moe/assignments_total")
    if made:
        log(f"[routing] over the run: busiest held expert over the mean, a "
            f"layer, {c.get('moe/expert_load_max_over_mean', 0.0):.3f}; held "
            f"share of assignments "
            f"{c.get('moe/assignments_held_total', 0) / made:.4f}; grouped "
            "matmuls lowered "
            f"{ {k.rsplit('/', 1)[-1]: v for k, v in c.items() if k.startswith('moe/gmm_lowered_total/')} }")
    return out
