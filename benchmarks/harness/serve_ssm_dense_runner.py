"""The runner of ``"runner": "serve_ssm_dense"`` configurations: a served
model of Mamba-2 layers (a state row of two arrays a slot: scan state,
convolution taps) beside attention layers that keep pages, with a DENSE
feed-forward part in every layer and no router anywhere
(``models/hybrid.py``; Granite-4.0-H).

Everything of the serving run is ``serve_runner``'s — the build, the
warm-up, the one-thread ``Loop``, ``summarize``, ``served_rate``, the
``Outcome``.  The probe is ``serve_ssm_runner``'s (each prompt prefilled in
chunks by a one-row program told its state row, then decodes of all rows at
once, the scan state of every Mamba-2 layer read back before and after each
decode), and so is the reading of the state (``state_step_error`` of the
configuration's own reference: what a decode leaves beside ``diag(a) S`` and
one outer product a group, ``tolerances.state_rel``).  What
``serve_ssm_runner`` does besides is left out: there is no router to
balance and no expert choice to take or to hold to the reference's, and the
logits are compared with the reference's own forward.
"""

from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.harness import check, serve_runner, serve_ssm_runner
from benchmarks.harness.common import log


class _NoRouter:
    """The model as ``serve_ssm_runner.probe`` asks for it: a dense model's
    ``take_moe_stats()`` is empty, and the probe reads the last program's
    ``choice [expert layers, rows, K]`` — here of no layer."""

    def __init__(self, model, rows: int):
        self._model = model
        self._none = [{"choice": np.zeros((0, rows, 0), np.int32)}]

    def take_moe_stats(self):
        self._model.take_moe_stats()
        return self._none

    def __getattr__(self, name):
        return getattr(self._model, name)


def probe(model, serving: dict, seqs, lens, nd: int):
    """``serve_ssm_runner.probe`` without its expert choices: ``(logits,
    steps)``."""
    rows = max(serving["prefill_chunk_tokens"], serving["slots"])
    got, _, steps = serve_ssm_runner.probe(
        _NoRouter(model, rows), serving, seqs, lens, nd)
    return got, steps


def readings(cell, params, model, seed) -> List[dict]:
    """Chunks-then-decodes through the pages and the state rows
    (:func:`probe`) against the plain float32 reference's full forward of
    the same tokens, a prompt of ``probe.prompt_lens`` at a time:
    ``{"prompt", "logits_rel" (worst of the last prompt position and each
    decode), "state_rel" (worst decode, worst layer)}``, each logged as it is
    read."""
    cfg = cell.config
    s, nd = cfg["serving"], cfg["probe"]["decodes"]
    lens = cfg["probe"]["prompt_lens"]
    rs = np.random.RandomState(seed + 7)
    seqs = [rs.randint(1, cfg["vocab_size"], size=L + nd).astype(np.int32)
            for L in lens]
    got, steps = probe(model, s, seqs, lens, nd)

    ref_mod = cell.reference()
    shape = ref_mod.Shape.from_config(cfg)
    ref_w = cell.reference_weights(params)
    tol, state_tol = (cfg["tolerances"]["logits_rel"],
                      cfg["tolerances"]["state_rel"])
    out = []
    for b, L in enumerate(lens):
        ref = np.asarray(ref_mod.logits_at(
            ref_w, shape, seqs[b], list(range(L - 1, L + nd))), np.float32)
        errs = [check.rel_err(got[(b, j)], ref[j]) for j in range(nd + 1)]
        # root-mean-square beside the maximum: an error spread over the
        # logits (a hidden state off) or held by a few of them
        rms = [float(np.sqrt(np.mean((got[(b, j)] - ref[j]) ** 2)
                             / np.mean(ref[j] ** 2))) for j in range(nd + 1)]
        log(f"[check] prompt {L}: pages and state rows vs float32 reference, "
            f"rel err prefill {errs[0]:.4f}, decodes "
            + " ".join(f"{e:.4f}" for e in errs[1:])
            + f" (tol {tol}, ref max {np.max(np.abs(ref)):.3f}; rms "
            + " ".join(f"{e:.4f}" for e in rms) + ")")
        drift = [max(ref_mod.state_step_error(bef[i], aft[i],
                                              shape.mamba_n_groups)
                     for i in range(len(bef)))
                 for bef, aft in (steps[(b, j)] for j in range(1, nd + 1))]
        log(f"[check] prompt {L}: scan state over a decoded token vs the "
            "recurrence (diag(a) S + one outer product a group), rel err a "
            "decode " + " ".join(f"{e:.2e}" for e in drift)
            + f" (tol {state_tol:.0e})")
        out.append({"prompt": L, "logits_rel": max(errs),
                    "state_rel": max(drift, default=0.0)})
    return out


def reference_check(cell, params, model, seed) -> List[str]:
    """:func:`readings` held to the cell's two limits
    (``tolerances.logits_rel``, ``state_rel``): why the run is not correct,
    if it is not."""
    tol = cell.config["tolerances"]
    why_not = []
    for r in readings(cell, params, model, seed):
        L = r["prompt"]
        if not r["state_rel"] <= tol["state_rel"]:
            why_not.append(f"scan state of prompt {L} leaves the recurrence "
                           f"by {r['state_rel']:.2e}")
        if not r["logits_rel"] <= tol["logits_rel"]:
            why_not.append(f"logits of prompt {L} differ from the reference "
                           f"by {r['logits_rel']:.4f}")
    return why_not


def refuse_a_program_without_the_layout(cell):
    """A program older than ``kvcache.pool.page_layout`` keeps a head of 64
    alone in a 128-lane row and its paged kernels do not lower at that width
    (Mosaic refuses the walk's slice after the weights are drawn and the
    first program is traced): say so at once, before anything is built."""
    from neuronx_distributed_tpu.kvcache import pool

    head_dim = cell.config["program"]["kwargs"].get("head_dim")
    if head_dim == 64 and not hasattr(pool, "page_layout"):
        raise SystemExit(
            f"cell {cell.name}: this program's page pool has no layout for "
            "heads of 64 (kvcache.pool.page_layout): its paged kernels do "
            "not lower at that width")


def run(cell, args, devices, peak, clock):
    """``serve_runner.run`` with this module's reference check."""
    refuse_a_program_without_the_layout(cell)
    theirs = serve_runner.reference_check
    serve_runner.reference_check = reference_check
    try:
        return serve_runner.run(cell, args, devices, peak, clock)
    finally:
        serve_runner.reference_check = theirs
