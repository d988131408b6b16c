"""The runner of ``"runner": "serve_latent"`` configurations: a served model
whose attention layers keep pages of ONE latent row a token (MLA:
``models/hybrid.py::MLAMixer``, ``ops/latent_attention.py``), whose residual
is several streams mixed a token by maps of their own, and whose
feed-forward parts are a dense layer and routed blocks in one layer list.

Everything of the serving run is ``serve_runner``'s — the build, the
warm-up, the one-thread ``Loop``, ``summarize``, ``served_rate``, the
``Outcome``.  What differs is the reference check.  This file's
:func:`reference_check`, through the paged programs the engine dispatches,
at the cell's sizes:

1. takes the experts the program chose for EVERY row of each probed sequence
   in every routed layer (``ParallelInferenceModel.take_moe_stats``) and
   holds them to the reference's own choice: a different set is accepted
   only where the reference's biased scores of the experts swapped lie
   within ``tolerances.routing_sigmas`` of what bfloat16 rounding moves them
   by (``xing4_f32.routing_agreement``).  A refused difference makes the run
   not correct;
2. compares logits — the last prompt position and each decode, of prompts
   that span one chunk, several chunks and the longest context — with the
   reference (expanded attention, no cache, float32) evaluated on the
   PROGRAM's experts at every row, so that an accepted near-tie does not
   widen the logits tolerance (``tolerances.logits_rel``);
3. reads the FIRST layer's latent rows of each probed sequence back from the
   pool and holds them to the reference's ``[RMSNorm(ckv) | RoPE(k_rope)]``
   by their largest error (``tolerances.latent_rel``) and by the root of
   their mean squared error (``tolerances.latent_rms``): what the pool
   holds, with one projection's rounding in it and no depth — other RoPE
   frequencies, a row written to the wrong cell, a pool in a lower
   precision (a row in 255 levels of its largest element passes the first
   and fails the second: ``xing4_f32.latent_rms_errors``).

The run itself is ``serve_runner.run`` with this check in the place of its
own, and with two things done to the SEEDED weights after they are drawn, as
a training run's initialisation and its load balancing would leave them: the
projections that write into the residual streams are scaled by ``(2 x
published layers)^-1/2`` (:func:`scale_residual_projections`), and the
routers' correction biases are BALANCED (``serve_ssm_runner.balance_router``,
imported), so that a seeded router spreads its tokens as a trained one would,
whatever the seed.  The served weights and the reference's are those.
"""

from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from benchmarks.harness import check, serve_runner
from benchmarks.harness.common import log
from benchmarks.harness.serve_ssm_runner import balance_router


def probe(model, serving: dict, seqs, lens, nd: int):
    """The probe's walk through the paged programs the engine dispatches:
    each prompt (``seqs[b][:lens[b]]``, left-padded into cells ``[C - L,
    C)`` as the engine lays it out) prefilled in chunks by the one-row
    program, then ``nd`` decodes of all rows at once (teacher forced from
    ``seqs``).  Returns ``(logits, choices, latents)``: ``logits[(b, j)]``
    with ``j = 0`` the last prompt position and ``j >= 1`` the decodes;
    ``choices[b] [Le, L + nd, K]`` the experts of every row of the sequence
    in every routed layer; ``latents[b] [L + nd, R]`` the first layer's pool
    rows of the sequence, read back after the last decode."""
    import jax.numpy as jnp

    s = serving
    page, C, T, B = (s["page_size"], s["context_len"], s["max_total_len"],
                     s["slots"])
    W, PP = s["prefill_chunk_tokens"], T // page
    tables = np.zeros((B, PP), np.int32)
    valid = np.zeros((B, T), np.int32)
    nxt = 1
    for b, L in enumerate(lens):
        for lp in range((C - L) // page, (C + nd - 1) // page + 1):
            tables[b, lp] = nxt
            nxt += 1
        valid[b, C - L:C] = 1
    caches = model.make_page_pool(max(s["num_pages"], nxt + 1), page).caches
    model.take_moe_stats()
    got: Dict[tuple, np.ndarray] = {}
    chosen: Dict[int, list] = {}
    for b, L in enumerate(lens):
        row = np.zeros((C,), np.int32)
        row[C - L:] = seqs[b][:L]
        off, rows = (C - L) // page * page, []
        logits = None
        while off < C:
            width = min(W, C - off)
            ids = np.zeros((1, W), np.int32)
            ids[0, :width] = row[off:off + width]
            logits, caches = model.prefill_chunk_pages(
                jnp.asarray(ids), off, tables[b][None, :], caches,
                valid[b][None, :], last_row=width - 1)
            rows.append(np.asarray(
                model.take_moe_stats()[-1]["choice"])[:, :width])
            off += width
        got[(b, 0)] = np.asarray(logits[0], np.float32)
        chosen[b] = [np.concatenate(rows, axis=1)[:, -L:]]  # the left pad cut
    dvalid = jnp.asarray(valid)
    for j in range(nd):
        tok = np.zeros((B, 1), np.int32)
        offs = np.full((B,), T, np.int32)  # parked
        for b, L in enumerate(lens):
            tok[b, 0] = seqs[b][L + j]
            offs[b] = C + j
        logits, caches, dvalid = model.decode_pages(
            jnp.asarray(tok), offs, tables, caches, dvalid)
        lg = np.asarray(logits, np.float32)
        choice = np.asarray(model.take_moe_stats()[-1]["choice"])
        for b in range(len(lens)):
            got[(b, j + 1)] = lg[b]
            chosen[b].append(choice[:, b:b + 1])
    first = model.module.config.latent_layers[0]
    latents = {}
    for b, L in enumerate(lens):
        pages = tables[b, (C - L) // page:(C + nd - 1) // page + 1]
        rows = np.asarray(caches[first][0][jnp.asarray(pages)], np.float32)
        rows = rows.reshape(-1, rows.shape[-1])
        lead = (C - L) % page
        latents[b] = rows[lead:lead + L + nd]
    del caches, dvalid, logits
    gc.collect()
    return got, {b: np.concatenate(c, axis=1) for b, c in chosen.items()}, \
        latents


def readings(cell, params, model, seed) -> List[dict]:
    """Prefill-then-decode through the latent pages (:func:`probe`) against
    the plain float32 reference's full forward of the same tokens, a prompt
    of ``probe.prompt_lens`` at a time: ``{"prompt", "logits_rel" (worst of
    the last prompt position and each decode), "latent_rel", "latent_rms",
    "agree": routing_agreement}``, each logged as it is read."""
    cfg = cell.config
    s, nd = cfg["serving"], cfg["probe"]["decodes"]
    lens = cfg["probe"]["prompt_lens"]
    rs = np.random.RandomState(seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    got, choices, latents = probe(model, s, seqs, lens, nd)

    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = cell.reference_weights(params)
    tol = cfg["tolerances"]
    out = []
    for b, L in enumerate(lens):
        rows = list(range(L - 1, L + nd))
        # ONE forward: the reference follows the program's experts, and its
        # own scores at the hidden state they led to say whether each
        # choice was one rounding explains
        ref, info = ref_mod.forward(ref_w, shape, seqs[b], rows,
                                    choice=choices[b])
        ref = np.asarray(ref, np.float32)
        agree = ref_mod.routing_agreement(info, choices[b],
                                          tol["routing_sigmas"])
        errs = [check.rel_err(got[(b, j)], ref[j]) for j in range(nd + 1)]
        rms = [float(np.sqrt(np.mean((got[(b, j)] - ref[j]) ** 2)
                             / np.mean(ref[j] ** 2))) for j in range(nd + 1)]
        lat_parts = ref_mod.latent_errors(latents[b], info["latents"],
                                          shape.kv_rank)
        lat = max(lat_parts)
        rms_parts = ref_mod.latent_rms_errors(latents[b], info["latents"],
                                              shape.kv_rank)
        log(f"[check] prompt {L}: latent pages vs float32 reference, rel "
            f"err prefill {errs[0]:.4f}, decodes "
            + " ".join(f"{e:.4f}" for e in errs[1:])
            + f" (tol {tol['logits_rel']}, ref max {np.max(np.abs(ref)):.2f};"
            " rms " + " ".join(f"{e:.4f}" for e in rms) + "); first layer's "
            f"latent rows rel err {lat:.5f} (latent {lat_parts[0]:.5f}, RoPE "
            f"key {lat_parts[1]:.5f}; tol {tol['latent_rel']}), rms "
            f"{rms_parts[0]:.5f} {rms_parts[1]:.5f} (tol "
            f"{tol['latent_rms']}); "
            f"experts chosen: {agree['agree_share']:.4f} of {agree['pairs']} "
            f"(layer, row) sets agree, {agree['accepted']} difference(s) "
            f"accepted within {tol['routing_sigmas']} sigma (nearest "
            f"{agree['worst_accepted_gap_over_allowance']:.2f} x the "
            f"allowance), {agree['refused']} refused" + (
                f" (worst {agree['worst_refused_gap_over_allowance']:.2f} x)"
                if agree["refused"] else ""))
        out.append({"prompt": L, "logits_rel": max(errs), "latent_rel": lat,
                    "latent_rms": max(rms_parts), "agree": agree})
    return out


def verdict(rows: List[dict], tol: dict) -> List[str]:
    """:func:`readings` held to the cell's four limits
    (``tolerances.logits_rel``, ``routing_sigmas`` — a refused expert
    choice — ``latent_rel`` and ``latent_rms``): why the run is not correct,
    if it is not.  The one comparison: the run's check and the controls of
    ``tools/xing4_check.py`` both end here."""
    why_not = []
    for r in rows:
        L, agree = r["prompt"], r["agree"]
        if not r["latent_rel"] <= tol["latent_rel"]:
            why_not.append(f"latent rows of prompt {L} differ from the "
                           f"reference's by {r['latent_rel']:.5f}")
        if not r["latent_rms"] <= tol["latent_rms"]:
            why_not.append(f"latent rows of prompt {L} differ from the "
                           f"reference's by {r['latent_rms']:.5f} in the "
                           "root of the mean square")
        if not r["logits_rel"] <= tol["logits_rel"]:
            why_not.append(f"logits of prompt {L} differ from the reference "
                           f"by {r['logits_rel']:.4f}")
        if agree["refused"]:
            why_not.append(
                f"prompt {L}: {agree['refused']} expert choice(s) differ "
                "from the reference's by more than rounding explains (worst "
                f"{agree['worst_refused_gap_over_allowance']:.2f} x the "
                "allowance)")
    return why_not


def reference_check(cell, params, model, seed) -> List[str]:
    """The check of every run: :func:`verdict` on :func:`readings`."""
    return verdict(readings(cell, params, model, seed),
                   cell.config["tolerances"])


RESIDUAL_WRITERS = (("attn", "o_proj", "kernel"), ("mlp", "down", "kernel"),
                    ("moe_mlp", "down"), ("moe_mlp", "shared_down", "kernel"))


def scale_residual_projections(params, layers: int):
    """The seeded weights of every projection that WRITES into the residual
    streams (the mixer's output projection, the dense layer's, each
    expert's and the shared expert's down-projection) times ``(2 layers)^
    -1/2``, ``layers`` the PUBLISHED depth: the initialisation of deep
    pre-norm decoders (GPT-2's and Megatron's scaled residual
    initialisation), which keeps what 2 x 40 sublayers add to a stream the
    size of the stream.  A seeded model needs it for the same reason as a
    trained one: drawn at their fan-in, the sublayers' outputs are ten times
    the embedding they are added to, every later sublayer then reads mostly
    what earlier ones wrote, and bfloat16 rounding compounds layer over
    layer — the logits of a 400-token prompt read 6.7% from the float32
    reference and 4-49 expert choices a prompt differed by more than one
    rounding explains (my chip run, PR 36, before this)."""
    import jax
    import jax.numpy as jnp

    factor = (2.0 * layers) ** -0.5
    # in place (the old buffer is donated): a second copy of six layers'
    # expert stacks beside the first is 2.9 GB the chip does not have to
    # spare beside the check (peak 15.6 GiB with it: my chip run, PR 36)
    scale = jax.jit(lambda v: (v.astype(jnp.float32) * factor
                               ).astype(v.dtype), donate_argnums=0)

    def scaled(path, leaf):
        names = tuple(str(getattr(k, "key", getattr(k, "name", k)))
                      for k in path)
        if not any(names[-len(w):] == w for w in RESIDUAL_WRITERS):
            return leaf
        if hasattr(leaf, "value"):
            return leaf.replace(value=scale(leaf.value))
        return scale(leaf)

    return jax.tree_util.tree_map_with_path(
        scaled, params, is_leaf=lambda x: hasattr(x, "value"))


def build(cell, args, devices, ledger):
    """``serve_runner.build``, then the residual writers scaled
    (:func:`scale_residual_projections`) and the routers' correction biases
    balanced (``serve_ssm_runner.balance_router``): the served weights and
    the reference's are those."""
    params, model = _build(cell, args, devices, ledger)
    params = scale_residual_projections(
        params, cell.config["published"]["num_hidden_layers"])
    params, _, _ = balance_router(model.module, params, args.seed,
                                  cell.config["vocab_size"])
    model.params = params
    return params, model


_build = serve_runner.build


def run(cell, args, devices, peak, clock):
    """``serve_runner.run`` with this module's build and reference check."""
    theirs = serve_runner.reference_check, serve_runner.build
    serve_runner.reference_check, serve_runner.build = reference_check, build
    try:
        return serve_runner.run(cell, args, devices, peak, clock)
    finally:
        serve_runner.reference_check, serve_runner.build = theirs
