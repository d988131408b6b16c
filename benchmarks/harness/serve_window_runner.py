"""The runner of ``"runner": "serve_window"`` configurations: a served model
whose attention layers come in KINDS — layers with a causal window, whose
pages the pool gets back once every row that can still be queried has moved
past them, beside global layers that keep a sequence's whole history — and
whose routed blocks score the attention's input (SmallThinker:
``models/llama.py`` ``sliding_window`` / ``attn_rope`` a layer,
``moe_router_input``; ``kvcache/pool.py::PageKinds``; ``serving/paged.py``).

Everything of the serving run is ``serve_runner``'s — the build, the
warm-up, the one-thread ``Loop``, ``summarize``, ``served_rate``, the
``Outcome``; ``serving.num_pages`` is a list, one count a page kind, and the
engine takes it as it is.  What differs is the reference check.  This file's
:func:`reference_check` walks the probe through the paged programs the
engine dispatches WITH THE ENGINE'S OWN PAGE BOOKKEEPING
(``serving.paged.PagedKVManager``: one block table a kind, a window kind's
pages taken as the writes reach them and given back behind the band — at
the tightest point the engine may, the row after the one just computed), so
that the compared logits of a prompt longer than the window were computed
AFTER pages of its slot were freed and, with three slots probed in turn,
after some of them were handed to another slot.  It then

1. takes the experts the program chose for EVERY row of each probed sequence
   in every layer (``ParallelInferenceModel.take_moe_stats``) and holds them
   to the reference's own choice (``smallthinker_f32.routing_agreement``): a
   different set is accepted only where the reference's router logits of the
   experts swapped lie within ``tolerances.routing_sigmas`` of what bfloat16
   rounding of the router's input moves them by.  A refused difference makes
   the run not correct;
2. compares logits — the last prompt position and each decode — with the
   reference (a full forward, float32, the window a MASK, no pages)
   evaluated on the PROGRAM's experts at every row, so that an accepted
   near-tie does not widen ``tolerances.logits_rel``.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from benchmarks.harness import check, serve_runner
from benchmarks.harness.common import log
from benchmarks.harness.serve_latent_runner import scale_residual_projections


def probe(model, serving: dict, seqs, lens, nd: int):
    """Each prompt (``seqs[b][:lens[b]]``, left-padded into cells ``[C - L,
    C)`` of slot ``b``) prefilled in chunks by the one-row program, then
    ``nd`` decodes of all rows at once (teacher forced), every program handed
    the manager's tables.  Returns ``(logits, choices, freed)``:
    ``logits[(b, j)]`` with ``j = 0`` the last prompt position and ``j >= 1``
    the decodes; ``choices[b] [L, len + nd, K]`` the experts of every row;
    ``freed[b]`` the window pages slot ``b`` had given back when its last
    compared row was computed."""
    import jax.numpy as jnp

    from neuronx_distributed_tpu.kvcache.pool import page_kinds
    from neuronx_distributed_tpu.serving import Request
    from neuronx_distributed_tpu.serving.paged import PagedKVManager

    s = serving
    page, C, T, B = (s["page_size"], s["context_len"], s["max_total_len"],
                     s["slots"])
    W = s["prefill_chunk_tokens"]
    kv = PagedKVManager(
        num_slots=B, context_len=C, max_total_len=T, page_size=page,
        num_pages=s["num_pages"], prefix_cache=False,
        kinds=page_kinds(model.module.config), chunk_tokens=W)
    caches = model.make_page_pool(s["num_pages"], page).caches
    model.take_moe_stats()
    valid = np.zeros((B, T), np.int32)
    got: Dict[tuple, np.ndarray] = {}
    chosen: Dict[int, list] = {}
    given_back = np.zeros((B,), np.int64)

    def release(b, oldest):
        before = sum(a.in_use for a in kv.allocs)
        kv.release_behind(b, oldest)
        given_back[b] += before - sum(a.in_use for a in kv.allocs)

    for b, L in enumerate(lens):
        row = np.zeros((C,), np.int32)
        row[C - L:] = seqs[b][:L]
        valid[b, C - L:C] = 1
        kv.admit_slot(b, Request(request_id=b, prompt_ids=seqs[b][:L].tolist(),
                                 max_new_tokens=nd), row, valid[b, :C])
        off, rows = (C - L) // page * page, []
        logits = None
        while off < C:
            width = min(W, C - off)
            ids = np.zeros((1, W), np.int32)
            ids[0, :width] = row[off:off + width]
            kv.extend_window(b, off + width - 1)
            logits, caches = model.prefill_chunk_pages(
                jnp.asarray(ids), off,
                kv.tables[..., b, :][..., None, :].copy(), caches,
                valid[b][None, :], last_row=width - 1,
                want_logits=off + width >= C)
            rows.append(np.asarray(
                model.take_moe_stats()[-1]["choice"])[:, :width])
            off += width
            release(b, off)
        got[(b, 0)] = np.asarray(logits[0], np.float32)
        chosen[b] = [np.concatenate(rows, axis=1)[:, -L:]]  # the left pad cut
    freed = {b: int(given_back[b]) for b in range(len(lens))}
    dvalid = jnp.asarray(valid)
    for j in range(nd):
        tok = np.zeros((B, 1), np.int32)
        offs = np.full((B,), T, np.int32)  # parked
        for b, L in enumerate(lens):
            tok[b, 0], offs[b] = seqs[b][L + j], C + j
            kv.extend_window(b, C + j)
        logits, caches, dvalid = model.decode_pages(
            jnp.asarray(tok), offs, kv.tables.copy(), caches, dvalid)
        lg = np.asarray(logits, np.float32)
        choice = np.asarray(model.take_moe_stats()[-1]["choice"])
        for b in range(len(lens)):
            got[(b, j + 1)] = lg[b]
            chosen[b].append(choice[:, b:b + 1])
            release(b, C + j + 1)
    kv.assert_invariants()
    for b in range(len(lens)):
        kv.release_slot(b)
    if any(a.in_use for a in kv.allocs):
        raise AssertionError("the probe's slots left pages behind")
    del caches, dvalid, logits
    gc.collect()
    return (got, {b: np.concatenate(c, axis=1) for b, c in chosen.items()},
            freed)


def readings(cell, params, model, seed) -> List[dict]:
    """:func:`probe` against the plain float32 reference's full forward of
    the same tokens, a prompt of ``probe.prompt_lens`` at a time:
    ``{"prompt", "logits_rel" (worst of the last prompt position and each
    decode), "freed", "agree": routing_agreement}``, each logged as read."""
    cfg = cell.config
    s, nd = cfg["serving"], cfg["probe"]["decodes"]
    lens = cfg["probe"]["prompt_lens"]
    rs = np.random.RandomState(seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    t0 = time.perf_counter()
    got, choices, freed = probe(model, s, seqs, lens, nd)
    log(f"[check] the probe's chunks and decodes took "
        f"{time.perf_counter() - t0:.1f} s (their compiles included)")

    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = cell.reference_weights(params)
    tol = cfg["tolerances"]["logits_rel"]
    sigmas = cfg["tolerances"]["routing_sigmas"]
    out = []
    for b, L in enumerate(lens):
        rows = list(range(L - 1, L + nd))
        # ONE forward: the reference follows the program's experts, and its
        # own logits at the hidden state they led to say whether each
        # choice was one rounding explains
        t0 = time.perf_counter()
        ref, info = ref_mod.forward(ref_w, shape, seqs[b], rows,
                                    choice=choices[b])
        ref = np.asarray(ref, np.float32)
        t_ref = time.perf_counter() - t0
        agree = ref_mod.routing_agreement(info, choices[b], sigmas)
        errs = [check.rel_err(got[(b, j)], ref[j]) for j in range(nd + 1)]
        log(f"[check] prompt {L}: two page kinds, {freed[b]} window page(s) "
            f"given back before its last chunk ended, vs float32 reference "
            f"(window a mask, {t_ref:.1f} s), rel err prefill {errs[0]:.4f}, "
            "decodes "
            + " ".join(f"{e:.4f}" for e in errs[1:])
            + f" (tol {tol}, ref max {np.max(np.abs(ref)):.2f}); experts "
            f"chosen: {agree['agree_share']:.4f} of {agree['pairs']} (layer, "
            f"row) sets agree, {agree['accepted']} difference(s) accepted "
            f"within {sigmas} sigma (nearest "
            f"{agree['worst_accepted_gap_over_allowance']:.2f} x the "
            f"allowance), {agree['refused']} refused" + (
                f" (worst {agree['worst_refused_gap_over_allowance']:.2f} x)"
                if agree["refused"] else ""))
        out.append({"prompt": L, "logits_rel": max(errs), "freed": freed[b],
                    "agree": agree})
    return out


def reference_check(cell, params, model, seed) -> List[str]:
    """:func:`readings` held to the cell's limits (``tolerances.logits_rel``
    and ``routing_sigmas`` — a refused expert choice), and to the probe's
    purpose: a prompt longer than window + chunk must have given pages
    back before its compared rows."""
    cfg = cell.config
    tol, s = cfg["tolerances"], cfg["serving"]
    window = min(w for w in (cfg["program"]["kwargs"]["sliding_window"])
                 if w is not None)
    why_not = []
    for r in readings(cell, params, model, seed):
        L, agree = r["prompt"], r["agree"]
        if not r["logits_rel"] <= tol["logits_rel"]:
            why_not.append(f"logits of prompt {L} differ from the reference "
                           f"by {r['logits_rel']:.4f}")
        if agree["refused"]:
            why_not.append(
                f"prompt {L}: {agree['refused']} expert choice(s) differ "
                "from the reference's by more than rounding explains (worst "
                f"{agree['worst_refused_gap_over_allowance']:.2f} x the "
                "allowance)")
        if L > window + s["prefill_chunk_tokens"] + s["page_size"] \
                and not r["freed"]:
            why_not.append(f"prompt {L} outgrew the window of {window} and "
                           "gave no page back")
    return why_not


# the seeded table's standard deviation: it LEADS the stream (the 24 scaled
# sublayers' outputs add up to ~0.5), so a row's router input is mostly its
# own token and a seeded router's choice hardly leans on what the rows share.
# At 0.1 (what the layer-list configurations' tables are drawn at) the
# busiest expert still took 1.56-1.91 x the mean and the step's time moved
# 0.4% with the seed (my chip runs, PR 48)
EMBED_STD = 0.25


def lead_with_the_embedding(params):
    """The SEEDED embedding table at :data:`EMBED_STD` and not at flax's
    0.02: the table's ``0.02 N(0, 1)`` times their ratio, in place."""
    import jax
    import jax.numpy as jnp

    scale = jax.jit(lambda v: (v.astype(jnp.float32) * (EMBED_STD / 0.02)
                               ).astype(v.dtype), donate_argnums=0)
    model = dict(params["params"]["model"])
    embed = dict(model["embed"])
    table = embed["embedding"]
    embed["embedding"] = (table.replace(value=scale(table.value))
                          if hasattr(table, "value") else scale(table))
    model["embed"] = embed
    return {**params, "params": {**params["params"], "model": model}}


def build(cell, args, devices, ledger):
    """``serve_runner.build``, then the SEEDED weights left as a training
    run's initialisation leaves them, the recipe of the latent cells: the
    projections that write into the residual times ``(2 x published
    layers)^-1/2`` (``serve_latent_runner.scale_residual_projections``) and
    an embedding that leads the stream (:func:`lead_with_the_embedding`).
    Drawn at their fan-in the sublayers' outputs are fifty times the
    embedding they are added to; every hidden state of a sequence then
    collapses onto one direction of that sequence's own, a layer's 16 decode
    rows choose 41 experts of 64 where tokens of their own would choose 51,
    the busiest expert takes 3.1 x the mean — and by how much swings with
    the seed, and with it the bytes ``gmm`` reads and the cell's rate (four
    seeds 11,549-11,760 tokens/s, step 38.36-38.86 ms: my chip runs, PR 48).
    No transform of the router cures that (the common direction is each
    sequence's own: projecting the sample's mean out of the routers' columns
    balanced the sample and nothing else).  The routers are left as drawn;
    the served weights and the reference's are these."""
    params, model = _build(cell, args, devices, ledger)
    params = lead_with_the_embedding(scale_residual_projections(
        params, cell.config["published"]["num_hidden_layers"]))
    model.params = params
    return params, model


_build = serve_runner.build


def run(cell, args, devices, peak, clock):
    """``serve_runner.run`` with this module's build and reference check,
    then the run's page accounting from its counters."""
    theirs = serve_runner.reference_check, serve_runner.build
    serve_runner.reference_check, serve_runner.build = reference_check, build
    try:
        out = serve_runner.run(cell, args, devices, peak, clock)
    finally:
        serve_runner.reference_check, serve_runner.build = theirs
    c = out.reading.counters
    calls, hit = c.get("moe/layer_calls_total"), c.get(
        "moe/experts_hit_total")
    if calls and hit is not None:
        log(f"[routing] over the run: {hit / calls:.2f} of "
            f"{cell.config['num_experts']} experts hit a layer call, busiest "
            f"expert over the mean "
            f"{c.get('moe/expert_load_max_over_mean', 0.0):.3f}")
    held, unfreed = (c.get("kvcache/window_pages_held_total"),
                     c.get("kvcache/window_pages_unfreed_total"))
    if held and unfreed:
        log(f"[pages] over the run: window pages held {held:.0f} slot-steps "
            f"against {unfreed:.0f} under a mask alone "
            f"({100.0 * held / unfreed:.1f}%); given back "
            f"{c.get('kvcache/window_pages_freed_total', 0):.0f}")
    return out
