"""The reference check of a ROUTED model: what ``serve_runner.reference_check``
does — chunked prefill, then decodes, through the page pool by the programs
the engine dispatches — and, beside the logits, the experts the program
chose for every prompt row and every decoded row
(``ParallelInferenceModel.take_moe_stats``), held to the reference's own
routing (``reference/olmoe_f32.py::routing_agreement``).

``serve_runner`` hands a reference the tokens and takes back logits, so the
runs of a cell check logits only; this module is what
``tools/olmoe_check.py`` runs on the chip at published widths and what
``tests/test_olmoe.py`` runs at toy widths.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.harness import check


def paged_logits_and_choices(model, serving: dict, seqs: List[np.ndarray],
                             decodes: int):
    """``seqs[b]`` is prompt + ``decodes`` tokens of slot ``b``.  Returns
    ``(logits, choices)``: ``logits[(b, j)]`` the row after the prompt
    (``j`` = 0) and after each decode; ``choices[b] [L, len(seq), K]`` the
    experts of every row of the sequence, in order."""
    import jax.numpy as jnp

    page, C, T, B = (serving["page_size"], serving["context_len"],
                     serving["max_total_len"], serving["slots"])
    W = serving["prefill_chunk_tokens"]
    lens = [len(s) - decodes for s in seqs]
    tables = np.zeros((B, T // page), np.int32)
    valid = np.zeros((B, T), np.int32)
    nxt = 1
    for b, n in enumerate(lens):
        for lp in range((C - n) // page, (C + decodes - 1) // page + 1):
            tables[b, lp] = nxt
            nxt += 1
        valid[b, C - n:C] = 1
    caches = model.make_page_pool(serving["num_pages"], page).caches
    model.take_moe_stats()
    logits_at: Dict[tuple, np.ndarray] = {}
    chosen: Dict[int, list] = {}
    for b, n in enumerate(lens):
        row = np.zeros((C,), np.int32)
        row[C - n:] = seqs[b][:n]
        off, rows = (C - n) // page * page, []
        while off < C:
            width = min(W, C - off)
            ids = np.zeros((1, W), np.int32)
            ids[0, :width] = row[off:off + width]
            logits, caches = model.prefill_chunk_pages(
                jnp.asarray(ids), off, tables[b][None, :], caches,
                valid[b][None, :], last_row=width - 1)
            choice = np.asarray(model.take_moe_stats()[-1]["choice"])
            rows.append(choice[:, :width])
            off += width
        logits_at[(b, 0)] = np.asarray(logits[0], np.float32)
        chosen[b] = [np.concatenate(rows, axis=1)[:, -n:]]  # the left pad cut
    dvalid = jnp.asarray(valid)
    for j in range(decodes):
        tok = np.zeros((B, 1), np.int32)
        offs = np.full((B,), T, np.int32)  # parked
        for b, n in enumerate(lens):
            tok[b, 0], offs[b] = seqs[b][n + j], C + j
        logits, caches, dvalid = model.decode_pages(
            jnp.asarray(tok), offs, tables, caches, dvalid)
        stats = model.take_moe_stats()[-1]
        lg, choice = np.asarray(logits, np.float32), np.asarray(stats["choice"])
        for b in range(len(lens)):
            logits_at[(b, j + 1)] = lg[b]
            chosen[b].append(choice[:, b:b + 1])
    return logits_at, {b: np.concatenate(c, axis=1) for b, c in chosen.items()}


def reference(ref_mod, ref_w, shape, seqs) -> list:
    """``(logits [S, V], routing)`` of the reference for every row of
    each sequence."""
    return [(np.asarray(lg, np.float32), routing) for lg, routing in (
        ref_mod.forward(ref_w, shape, seq, list(range(len(seq))))
        for seq in seqs)]


def compare(ref_mod, refs, decodes, logits_at, choices,
            sigmas: float) -> List[dict]:
    """One verdict a sequence: the worst relative logit error of its probed
    rows (``check.rel_err``: the largest difference over the largest
    logit, what the runs of a cell are held to), their root-mean-square
    error over the root-mean-square logit, and ``routing_agreement`` over
    all its rows and layers."""
    out = []
    for b, (ref_logits, routing) in enumerate(refs):
        n = len(ref_logits) - decodes
        got = np.stack([logits_at[(b, j)] for j in range(decodes + 1)])
        want = ref_logits[n - 1:]
        out.append({"prompt": n,
                    "logits_rel_err": max(check.rel_err(g, w)
                                          for g, w in zip(got, want)),
                    "logits_rms_err": float(np.sqrt(np.mean((got - want) ** 2)
                                                    / np.mean(want ** 2))),
                    **ref_mod.routing_agreement(routing, choices[b], sigmas)})
    return out
