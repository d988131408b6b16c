"""The runner of ``"runner": "serve_state"`` configurations: a served model
whose layers keep more per sequence than K/V pages — a recurrent state row a
slot, pages chosen per query (``models/hybrid.py``).

Everything of the serving run is ``serve_runner``'s — the build, the
warm-up, the one-thread ``Loop``, ``summarize``, ``served_rate``, the
``Outcome``.  What differs is the reference check, which ``serve_runner``
cannot make for such a model: its one-row prefill is told only the row's
block table, and nothing says whose recurrent state it continues.  This
file's :func:`reference_check`

1. tells each one-row prefill its state row (``state_row=b``: probe row
   ``b`` prefills into state row ``b`` and decodes as batch row ``b``);
2. takes the pages the program's block-sparse layers CHOSE for each probed
   row (``ParallelInferenceModel.take_sparse_stats``), turns them from
   entries of the slot's table into blocks of the sequence, and holds them
   to the reference's own choice: a different set is accepted only where
   the reference's block scores of the blocks swapped lie within
   ``tolerances.selection_sigmas`` of what bfloat16 rounding moves them by
   (``minicpm_sala_f32.selection_agreement``).  A refused difference makes
   the run not correct;
3. compares logits with the reference attending the PROGRAM's blocks at the
   probed rows, so that an accepted near-tie does not widen the logits
   tolerance;
4. holds the recurrent layers' STATE ROWS to the recurrence ``S' = lambda S
   + k^T v``: with the rows read back before and after each decode of the
   probe, ``after - lambda * before`` must be one outer product a head
   (``minicpm_sala_f32.state_step_error``, ``tolerances.state_rel``).
   The logits tolerance leaves room for bfloat16 activations and so for a
   bfloat16 state; this reading has no activations in it, and the program
   exports nothing for it.

The run itself is ``serve_runner.run`` with this check in the place of its
own.
"""

from __future__ import annotations

import gc
from typing import Dict, List

import numpy as np

from benchmarks.harness import check, serve_runner
from benchmarks.harness.common import log


def chosen_blocks(chosen, first_page: int, num_blocks: int) -> np.ndarray:
    """``chosen [Ls, NKV, PP]`` (entries of the slot's table) -> ``[Ls, NKV,
    num_blocks]`` (blocks of the sequence): block ``b`` is the table's entry
    ``first_page + b``."""
    c = np.asarray(chosen).astype(bool)
    out = np.zeros(c.shape[:2] + (num_blocks,), bool)
    n = min(num_blocks, c.shape[2] - first_page)
    out[:, :, :n] = c[:, :, first_page:first_page + n]
    return out


def probe(model, serving: dict, seqs, lens, nd: int):
    """The probe's walk through the paged programs the engine dispatches:
    each prompt (``seqs[b][:lens[b]]``, left-padded into cells ``[C - L,
    C)`` as the engine lays it out) prefilled in chunks by a one-row program
    told its state row ``b``, then ``nd`` decodes of all rows at once
    (teacher forced from ``seqs``).  Returns ``(logits, chosen, steps)``,
    each ``{(b, j): ...}`` with ``j = 0`` the last prompt position and ``j
    >= 1`` the decodes; ``chosen [Ls, NKV, PP]`` is what the block-sparse
    layers picked for that row, in entries of the slot's table; ``steps``
    (decodes only) is ``(before, after)``: the row's state in every
    recurrent layer ``[Lr, NH, D, D]`` as the decode found and left it."""
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
    model.take_sparse_stats()
    got: Dict[tuple, np.ndarray] = {}
    picked: Dict[tuple, np.ndarray] = {}
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
                valid[b][None, :], last_row=width - 1, state_row=b)
            off += width
        got[(b, 0)] = np.asarray(logits[0], np.float32)
        # the last chunk's last row is the prompt's last position
        picked[(b, 0)] = np.asarray(
            model.take_sparse_stats()[-1]["chosen"])[:, 0]
    dvalid = jnp.asarray(valid)
    n = len(lens)

    def state_rows():
        # a recurrent layer's entry of the pool is its state array alone
        return np.stack([np.asarray(c[0][:n]) for c in caches if len(c) == 1])

    steps: Dict[tuple, tuple] = {}
    before = state_rows()                                  # [Lr, n, NH, D, D]
    for j in range(nd):
        tok = np.zeros((B, 1), np.int32)
        offs = np.full((B,), T, np.int32)  # parked
        for b, L in enumerate(lens):
            tok[b, 0] = seqs[b][L + j]
            offs[b] = C + j
        logits, caches, dvalid = model.decode_pages(
            jnp.asarray(tok), offs, tables, caches, dvalid)
        lg = np.asarray(logits, np.float32)
        chosen = np.asarray(model.take_sparse_stats()[-1]["chosen"])
        after = state_rows()
        for b in range(n):
            got[(b, j + 1)] = lg[b]
            picked[(b, j + 1)] = chosen[:, b]
            steps[(b, j + 1)] = (before[:, b], after[:, b])
        before = after
    del caches, dvalid, logits
    gc.collect()
    return got, picked, steps


def reference_check(cell, params, model, seed) -> List[str]:
    """Prefill-then-decode through the pages and the state rows
    (:func:`probe`) against the plain float32 reference's full forward of
    the same tokens: the blocks chosen, and the logits, at the last prompt
    position and at each decoded position; and the state rows over each
    decoded token against the recurrence."""
    cfg = cell.config
    s, nd = cfg["serving"], cfg["probe"]["decodes"]
    page, C = s["page_size"], s["context_len"]
    lens = cfg["probe"]["prompt_lens"]
    rs = np.random.RandomState(seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    got, picked, steps = probe(model, s, seqs, lens, nd)

    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = cell.reference_weights(params)
    tol = cfg["tolerances"]["logits_rel"]
    sigmas = cfg["tolerances"].get("selection_sigmas", 4.0)
    state_tol = cfg["tolerances"]["state_rel"]
    why_not = []
    for b, L in enumerate(lens):
        rows = list(range(L - 1, L + nd))
        nb = -(-(L + nd) // shape.block_size)
        first_page = (C - L) // page
        theirs = np.stack([chosen_blocks(picked[(b, j)], first_page, nb)
                           for j in range(nd + 1)], axis=1)  # [Ls,R,NKV,NB]
        _, info = ref_mod.forward(ref_w, shape, seqs[b], rows, prompt_len=L)
        agree = ref_mod.selection_agreement(info, theirs, sigmas)
        # a dense row's choice is every visible block on both sides: it
        # agrees by construction and says nothing of the selection
        ref = np.asarray(ref_mod.forward(
            ref_w, shape, seqs[b], rows, prompt_len=L, selection=theirs)[0],
            np.float32)
        errs = [check.rel_err(got[(b, j)], ref[j]) for j in range(nd + 1)]
        # root-mean-square beside the maximum: an error spread over the
        # logits (a hidden state off) or held by a few of them
        rms = [float(np.sqrt(np.mean((got[(b, j)] - ref[j]) ** 2)
                             / np.mean(ref[j] ** 2))) for j in range(nd + 1)]
        log(f"[check] prompt {L}: pages and state rows vs float32 reference, "
            f"rel err prefill {errs[0]:.4f}, decodes "
            + " ".join(f"{e:.4f}" for e in errs[1:])
            + f" (tol {tol}, ref max {np.max(np.abs(ref)):.2f}; rms "
            + " ".join(f"{e:.4f}" for e in rms) + "); blocks "
            f"chosen: {agree['agree_share']:.4f} of {agree['pairs']} (layer, "
            f"row, kv head) sets agree, {agree['accepted']} difference(s) "
            f"accepted within {sigmas} sigma (nearest "
            f"{agree['worst_accepted_gap_over_allowance']:.2f} x the "
            f"allowance), {agree['refused']} refused" + (
                f" ({agree['least_refused_gap_over_allowance']:.2f} to "
                f"{agree['worst_refused_gap_over_allowance']:.2f} x)"
                if agree["refused"] else ""))
        # the state rows over each decoded token, layer by layer: what the
        # step leaves beside one outer product a head
        drift = [max(ref_mod.state_step_error(bef[i], aft[i])
                     for i in range(len(bef)))
                 for bef, aft in (steps[(b, j)] for j in range(1, nd + 1))]
        log(f"[check] prompt {L}: state rows over a decoded token vs the "
            "recurrence (lambda S + one outer product a head), rel err a "
            "decode " + " ".join(f"{e:.2e}" for e in drift)
            + f" (tol {state_tol:.0e})")
        if not max(drift, default=0.0) <= state_tol:
            why_not.append(f"state rows of prompt {L} leave the recurrence "
                           f"by {max(drift):.2e}")
        if not max(errs) <= tol:
            why_not.append(f"logits of prompt {L} differ from the reference "
                           f"by {max(errs):.4f}")
        if agree["refused"]:
            why_not.append(
                f"prompt {L}: {agree['refused']} block selection(s) differ "
                "from the reference's by more than rounding explains (worst "
                f"{agree['worst_refused_gap_over_allowance']:.2f} x the "
                "allowance)")
    return why_not


def run(cell, args, devices, peak, clock):
    """``serve_runner.run`` with this module's reference check."""
    theirs = serve_runner.reference_check
    serve_runner.reference_check = reference_check
    try:
        return serve_runner.run(cell, args, devices, peak, clock)
    finally:
        serve_runner.reference_check = theirs
