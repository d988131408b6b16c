"""The runner of ``"runner": "serve_ssm"`` configurations: a served model
whose layers are ONE sublayer each — Mamba-2 scans that keep a state row of
two arrays a slot (scan state, convolution taps), attention layers that keep
pages, routed expert blocks that keep nothing and hold a share of their
experts (``models/hybrid.py``, ``parallel/moe.py``).

Everything of the serving run is ``serve_runner``'s — the build, the
warm-up, the one-thread ``Loop``, ``summarize``, ``served_rate``, the
``Outcome``.  What differs is the reference check.  ``serve_runner``'s hands
a one-row prefill no state row; ``serve_state_runner``'s takes a pool entry
of length one for THE state array and reads page selections.  This file's
:func:`reference_check`

1. tells each one-row prefill its state row (probe row ``b`` prefills into
   state row ``b`` and decodes as batch row ``b``);
2. takes the experts the program chose for EVERY row of each probed sequence
   in every expert layer (``ParallelInferenceModel.take_moe_stats``) and
   holds them to the reference's own choice: a different set is accepted
   only where the reference's biased scores of the experts swapped lie
   within ``tolerances.routing_sigmas`` of what bfloat16 rounding moves them
   by (``nemotron_h_f32.routing_agreement``).  A refused difference makes
   the run not correct;
3. compares logits — the last prompt position and each decode, of prompts
   that span one chunk, several chunks and the longest context — with the
   reference evaluated on the PROGRAM's experts at every row, so that an
   accepted near-tie (which also moves the K/V and the scan state every
   later row reads) does not widen the logits tolerance;
4. holds the Mamba-2 layers' SCAN STATE to the recurrence: with the state
   rows read back before and after each decode of the probe, ``after -
   diag(a) before`` must be one outer product a group for the right
   per-head decays (``nemotron_h_f32.state_step_error``,
   ``tolerances.state_rel``).  The logits tolerance leaves room for bfloat16
   activations and so for a bfloat16 state; this reading has no activations
   in it, and the program exports nothing for it.

The run itself is ``serve_runner.run`` with this check in the place of its
own, and with the routers' correction biases BALANCED after the weights are
drawn (:func:`balance_router`: what the published training's load balancing
does to that bias, in a few sweeps a layer), so that a seeded router hands this
chip's experts the share a trained one would, whatever the seed.
"""

from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from benchmarks.harness import check, serve_runner
from benchmarks.harness.common import log


def probe(model, serving: dict, seqs, lens, nd: int):
    """The probe's walk through the paged programs the engine dispatches:
    each prompt (``seqs[b][:lens[b]]``, left-padded into cells ``[C - L,
    C)`` as the engine lays it out) prefilled in chunks by a one-row program
    told its state row ``b``, then ``nd`` decodes of all rows at once
    (teacher forced from ``seqs``).  Returns ``(logits, choices, steps)``:
    ``logits[(b, j)]`` with ``j = 0`` the last prompt position and ``j >=
    1`` the decodes; ``choices[b] [Le, L + nd, K]`` the experts of every row
    of the sequence in every expert layer; ``steps[(b, j)]`` (decodes only)
    ``(before, after)``: the row's scan state in every Mamba-2 layer ``[Lm,
    NH, P, N]`` as the decode found and left it."""
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
                valid[b][None, :], last_row=width - 1, state_row=b)
            rows.append(np.asarray(
                model.take_moe_stats()[-1]["choice"])[:, :width])
            off += width
        got[(b, 0)] = np.asarray(logits[0], np.float32)
        chosen[b] = [np.concatenate(rows, axis=1)[:, -L:]]  # the left pad cut
    dvalid = jnp.asarray(valid)
    n = len(lens)

    recurrent = model.module.config.recurrent_layers

    def scan_states():
        # a Mamba-2 layer's entry of the pool: (scan state, taps)
        return np.stack([np.asarray(caches[i][0][:n]) for i in recurrent])

    steps: Dict[tuple, tuple] = {}
    before = scan_states()                                # [Lm, n, NH, P, N]
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
        after = scan_states()
        for b in range(n):
            got[(b, j + 1)] = lg[b]
            chosen[b].append(choice[:, b:b + 1])
            steps[(b, j + 1)] = (before[:, b], after[:, b])
        before = after
    del caches, dvalid, logits
    gc.collect()
    return got, {b: np.concatenate(c, axis=1) for b, c in chosen.items()}, \
        steps


def readings(cell, params, model, seed) -> List[dict]:
    """Prefill-then-decode through the pages and the state rows
    (:func:`probe`) against the plain float32 reference's full forward of
    the same tokens, a prompt of ``probe.prompt_lens`` at a time: ``{"prompt",
    "logits_rel" (worst of the last prompt position and each decode),
    "state_rel" (worst decode, worst layer), "agree": routing_agreement}``,
    each logged as it is read."""
    cfg = cell.config
    s, nd = cfg["serving"], cfg["probe"]["decodes"]
    lens = cfg["probe"]["prompt_lens"]
    rs = np.random.RandomState(seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    got, choices, steps = probe(model, s, seqs, lens, nd)

    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = cell.reference_weights(params)
    tol = cfg["tolerances"]["logits_rel"]
    sigmas = cfg["tolerances"]["routing_sigmas"]
    state_tol = cfg["tolerances"]["state_rel"]
    out = []
    for b, L in enumerate(lens):
        rows = list(range(L - 1, L + nd))
        # ONE forward: the reference follows the program's experts, and its
        # own scores at the hidden state they led to say whether each
        # choice was one rounding explains
        ref, info = ref_mod.forward(ref_w, shape, seqs[b], rows,
                                    choice=choices[b])
        ref = np.asarray(ref, np.float32)
        agree = ref_mod.routing_agreement(info, choices[b], sigmas)
        errs = [check.rel_err(got[(b, j)], ref[j]) for j in range(nd + 1)]
        # root-mean-square beside the maximum: an error spread over the
        # logits (a hidden state off) or held by a few of them
        rms = [float(np.sqrt(np.mean((got[(b, j)] - ref[j]) ** 2)
                             / np.mean(ref[j] ** 2))) for j in range(nd + 1)]
        log(f"[check] prompt {L}: pages and state rows vs float32 reference, "
            f"rel err prefill {errs[0]:.4f}, decodes "
            + " ".join(f"{e:.4f}" for e in errs[1:])
            + f" (tol {tol}, ref max {np.max(np.abs(ref)):.2f}; rms "
            + " ".join(f"{e:.4f}" for e in rms) + "); experts chosen: "
            f"{agree['agree_share']:.4f} of {agree['pairs']} (layer, row) "
            f"sets agree, {agree['accepted']} difference(s) accepted within "
            f"{sigmas} sigma (nearest "
            f"{agree['worst_accepted_gap_over_allowance']:.2f} x the "
            f"allowance), {agree['refused']} refused" + (
                f" (worst {agree['worst_refused_gap_over_allowance']:.2f} x)"
                if agree["refused"] else ""))
        # the scan state over each decoded token, layer by layer: what the
        # step leaves beside one outer product a group
        drift = [max(ref_mod.state_step_error(bef[i], aft[i], shape.n_groups)
                     for i in range(len(bef)))
                 for bef, aft in (steps[(b, j)] for j in range(1, nd + 1))]
        log(f"[check] prompt {L}: scan state over a decoded token vs the "
            "recurrence (diag(a) S + one outer product a group), rel err a "
            "decode " + " ".join(f"{e:.2e}" for e in drift)
            + f" (tol {state_tol:.0e})")
        out.append({"prompt": L, "logits_rel": max(errs),
                    "state_rel": max(drift, default=0.0), "agree": agree})
    return out


def reference_check(cell, params, model, seed) -> List[str]:
    """:func:`readings` held to the cell's three limits
    (``tolerances.logits_rel``, ``routing_sigmas`` — a refused expert
    choice — and ``state_rel``): why the run is not correct, if it is not."""
    tol = cell.config["tolerances"]
    why_not = []
    for r in readings(cell, params, model, seed):
        L, agree = r["prompt"], r["agree"]
        if not r["state_rel"] <= tol["state_rel"]:
            why_not.append(f"scan state of prompt {L} leaves the recurrence "
                           f"by {r['state_rel']:.2e}")
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


BALANCE_SWEEPS, BALANCE_RATE, BALANCE_ROWS, BALANCED = 4, 0.05, (4, 512), 1.25


def balance_router(module, params, seed: int, vocab: int, log=log):
    """The correction bias of every routed layer, balanced as the published
    training balances it (the bias is no function of the loss: it is moved
    against each expert's load until the loads are even) — layer by layer
    from the first (a later layer's input depends on the earlier ones'
    routing), up to ``BALANCE_SWEEPS`` sweeps a layer of ``b_e -=
    BALANCE_RATE * log(load_e / mean load)`` over ``BALANCE_ROWS`` seeded
    tokens through the program's own uncached forward, until the busiest
    expert's load is under ``BALANCED`` x the mean.  A SEEDED network needs
    it: its layers' outputs share a token-independent part (a relu2
    expert's hidden units are positive), so a seeded router prefers some
    experts (the busiest 2-5 x the mean) and the share that falls to
    experts 0-63 swings with the seed, and with it the cell's tokens/s
    (PERF.md, PR 32).  Returns the parameters with the new biases (the same
    tree otherwise) and the busiest expert's load over the mean, a layer,
    before and after."""
    import jax
    import jax.numpy as jnp

    from neuronx_distributed_tpu.models.llama import moe_layer_stats

    cfg = module.config
    layers, E = cfg.moe_layers, cfg.num_experts
    ids = jnp.asarray(np.random.RandomState(seed + 13).randint(
        1, vocab, size=BALANCE_ROWS), jnp.int32)

    @jax.jit
    def choices(p):
        _, stats = module.apply(p, ids, method="hidden",
                                mutable=["moe_stats"])
        return moe_layer_stats(stats, layers)["choice"]

    def loads(p):
        ch = np.asarray(choices(p)).reshape(len(layers), -1)
        return np.stack([np.bincount(c, minlength=E + 1)[:E] for c in ch]
                        ).astype(np.float64)

    def skew(load):
        return [round(float(r.max() / r.mean()), 2) for r in load]

    def moved(tree, i, step):
        model = dict(tree["params"]["model"])
        layer = dict(model[f"layer_{i}"])
        moe = dict(layer["moe_mlp"])
        bias = moe["router_bias"]
        new = getattr(bias, "value", bias) - jnp.asarray(step, jnp.float32)
        moe["router_bias"] = (bias.replace(value=new)
                              if hasattr(bias, "value") else new)
        layer["moe_mlp"] = moe
        model[f"layer_{i}"] = layer
        return {**tree, "params": {**tree["params"], "model": model}}

    tree, forwards = params, 1
    load = loads(tree)
    before = skew(load)
    for n, i in enumerate(layers):
        for _ in range(BALANCE_SWEEPS):
            if load[n].max() < BALANCED * load[n].mean():
                break
            tree = moved(tree, i, BALANCE_RATE * np.log(
                (load[n] + 1.0) / (load[n].mean() + 1.0)))
            load = loads(tree)
            forwards += 1
    log(f"[setup] router biases balanced in {forwards} forwards of "
        f"{ids.size} seeded tokens: busiest expert over the mean, a layer, "
        f"{before} -> {skew(load)}")
    return tree, before, skew(load)


def build(cell, args, devices, ledger):
    """``serve_runner.build``, then the routers' correction biases balanced
    (:func:`balance_router`): the served weights and the reference's are the
    balanced ones."""
    params, model = _build(cell, args, devices, ledger)
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
