"""The runner of ``"runner": "serve_retention"`` configurations: a served
model whose EVERY layer is recurrent — power retention (``models/hybrid.py``),
a state row of two float32 arrays a layer a slot, and no page at all.

Everything of the serving run is ``serve_runner``'s — the build, the
warm-up, the one-thread ``Loop``, ``summarize``, ``served_rate``, the
``Outcome``.  What differs is the reference check, which ``serve_runner``
cannot make for such a model: its one-row prefill is told only the row's
block table, and nothing says whose recurrent state it continues.  This
file's :func:`reference_check`

1. walks each probe through the timed path's own programs — chunks of the
   engine's width into state row ``b`` (``state_row=b``), then decodes of
   all rows at once — and compares the logits at the last prompt position
   and at each decoded position with the reference's full QUADRATIC forward
   of the same tokens (``tolerances.logits_rel``);
2. holds the STATE ROWS to the recurrence: with every layer's row read back
   before and after each decode of the probe, ``after - g * before`` must be
   ONE outer product a head whatever the layout of the symmetric square
   (``brumby_f32.state_step_error``, ``tolerances.state_rel``), ``g`` the
   scalar a head that leaves the smallest remainder — itself held to the
   reference's decay at that token (``tolerances.decay_abs``).  The logits
   tolerance leaves room for bfloat16 activations and so for a bfloat16
   state; this reading has no activation in it but the decay, and the
   program exports nothing for it.

The run itself is ``serve_runner.run`` with this check in the place of its
own.
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
    (teacher forced from ``seqs``).  Returns ``(logits, steps)``, each ``{(b,
    j): ...}`` with ``j = 0`` the last prompt position and ``j >= 1`` the
    decodes; ``steps`` (decodes only) is ``(before, after)``: the row's state
    in every layer ``[L, NKV, d, D]`` as the decode found and left it."""
    import jax.numpy as jnp

    s = serving
    page, C, T, B = (s["page_size"], s["context_len"], s["max_total_len"],
                     s["slots"])
    W, PP = s["prefill_chunk_tokens"], T // page
    # no layer keeps a page: every table entry is the NULL page
    tables = np.zeros((B, PP), np.int32)
    valid = np.zeros((B, T), np.int32)
    for b, L in enumerate(lens):
        valid[b, C - L:C] = 1
    caches = model.make_page_pool(s["num_pages"], page).caches
    got: Dict[tuple, np.ndarray] = {}
    for b, L in enumerate(lens):
        row = np.zeros((C,), np.int32)
        row[C - L:] = seqs[b][:L]
        off = (C - L) // page * page
        logits = None
        while off < C:
            width = min(W, C - off)
            ids = np.zeros((1, W), np.int32)
            ids[0, :width] = row[off:off + width]
            logits, caches = model.prefill_chunk_pages(
                jnp.asarray(ids), off, tables[b][None, :], caches,
                valid[b][None, :], last_row=width - 1, state_row=b,
                want_logits=off + width >= C)
            off += width
        got[(b, 0)] = np.asarray(logits[0], np.float32)
    dvalid = jnp.asarray(valid)
    n = len(lens)

    def state_rows():
        # a layer's entry of the pool: its state array, then the normaliser
        return [np.asarray(c[0][:n]) for c in caches]

    steps: Dict[tuple, tuple] = {}
    before = state_rows()                                  # L x [n,NKV,d,D]
    for j in range(nd):
        tok = np.zeros((B, 1), np.int32)
        offs = np.full((B,), T, np.int32)  # parked
        for b, L in enumerate(lens):
            tok[b, 0] = seqs[b][L + j]
            offs[b] = C + j
        logits, caches, dvalid = model.decode_pages(
            jnp.asarray(tok), offs, tables, caches, dvalid)
        lg = np.asarray(logits, np.float32)
        after = state_rows()
        for b in range(n):
            got[(b, j + 1)] = lg[b]
            steps[(b, j + 1)] = ([x[b] for x in before],
                                 [x[b] for x in after])
        before = after
    del caches, dvalid, logits
    gc.collect()
    return got, steps


def readings(cell, params, model, seed, ref_weights=None) -> List[dict]:
    """Chunks-then-decodes through the state rows (:func:`probe`) against
    the plain float32 reference's full forward of the same tokens, a probe a
    dict: ``logits`` (``rel_err`` at the last prompt position and at each
    decoded position) and their ``rms``, the reference's largest logit, and
    a decoded token each ``state`` (what the rows' step leaves beside ``g *
    before`` and one outer product a head, the worst layer's) and ``decay``
    (the fitted ``g`` beside the reference's).  ``ref_weights(params)``: what
    the reference reads in the place of the program's parameters (a control;
    called once the probe's state rows are off the device)."""
    cfg = cell.config
    s, nd = cfg["serving"], cfg["probe"]["decodes"]
    lens = cfg["probe"]["prompt_lens"]
    rs = np.random.RandomState(seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    got, steps = probe(model, s, seqs, lens, nd)

    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = (ref_weights or cell.reference_weights)(params)
    out = []
    for b, L in enumerate(lens):
        ref, info = ref_mod.forward(ref_w, shape, seqs[b],
                                    list(range(L - 1, L + nd)))
        ref = np.asarray(ref, np.float32)
        # the state rows over each decoded token, layer by layer
        steps_read = []
        for j in range(1, nd + 1):
            before, after = steps[(b, j)]
            steps_read.append([
                ref_mod.state_step_error(before[i], after[i],
                                         info["lg"][i, j])
                for i in range(len(before))])
        out.append({
            "len": L, "ref_max": float(np.max(np.abs(ref))),
            "logits": [check.rel_err(got[(b, j)], ref[j])
                       for j in range(nd + 1)],
            # root-mean-square beside the maximum: an error spread over the
            # logits (a hidden state off) or held by a few of them
            "rms": [float(np.sqrt(np.mean((got[(b, j)] - ref[j]) ** 2)
                                  / np.mean(ref[j] ** 2)))
                    for j in range(nd + 1)],
            "state": [max(r[0] for r in read) for read in steps_read],
            "decay": [max(r[1] for r in read) for read in steps_read]})
    return out


def reference_check(cell, params, model, seed) -> List[str]:
    """:func:`readings` held to the cell's ``tolerances``."""
    tol = cell.config["tolerances"]
    why_not = []
    for r in readings(cell, params, model, seed):
        L, errs = r["len"], r["logits"]
        log(f"[check] prompt {L}: chunks and decodes through the state rows "
            f"vs float32 quadratic reference, rel err prefill {errs[0]:.4f}, "
            "decodes " + " ".join(f"{e:.4f}" for e in errs[1:])
            + f" (tol {tol['logits_rel']}, ref max {r['ref_max']:.2f}; rms "
            + " ".join(f"{e:.4f}" for e in r["rms"]) + ")")
        log(f"[check] prompt {L}: state rows over a decoded token vs the "
            "recurrence (g S + one outer product a head), rel err a decode "
            + " ".join(f"{e:.2e}" for e in r["state"])
            + f" (tol {tol['state_rel']:.0e}); fitted decay vs the "
            "reference's, abs " + " ".join(f"{e:.2e}" for e in r["decay"])
            + f" (tol {tol['decay_abs']:.0e})")
        if not max(r["state"], default=0.0) <= tol["state_rel"]:
            why_not.append(f"state rows of prompt {L} leave the recurrence "
                           f"by {max(r['state']):.2e}")
        if not max(r["decay"], default=0.0) <= tol["decay_abs"]:
            why_not.append(f"state rows of prompt {L} decay by another "
                           f"factor than the reference's: "
                           f"{max(r['decay']):.2e}")
        if not max(errs) <= tol["logits_rel"]:
            why_not.append(f"logits of prompt {L} differ from the reference "
                           f"by {max(errs):.4f}")
    return why_not


def run(cell, args, devices, peak, clock):
    """``serve_runner.run`` with this module's reference check."""
    theirs = serve_runner.reference_check
    serve_runner.reference_check = reference_check
    try:
        return serve_runner.run(cell, args, devices, peak, clock)
    finally:
        serve_runner.reference_check = theirs
