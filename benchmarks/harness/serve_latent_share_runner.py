"""The runner of ``"runner": "serve_latent_share"`` configurations: a served
model whose attention layers keep pages of ONE latent row a token (MLA), under
a plain residual, whose routed layers choose under a GROUP LIMIT with no
correction bias and HOLD one expert-parallel rank's share of their experts
(DeepSeek-V2: ``parallel/moe.py``, ``n_group`` > 1 with ``experts_held``).

The probe through the paged programs, the readings against the reference and
the verdict are ``serve_latent_runner``'s, imported: logits at the last
prompt position and each decode of three prompt lengths, every routed row's
experts (all ``num_experts_per_tok`` of them, held here or not) held to the
reference's own group-limited choice, and the first layer's latent rows.
Everything of the serving run is ``serve_runner``'s.

What differs is the set-up of the SEEDED weights (:func:`build`): the
projections that write into the residual are scaled by ``(2 x published
layers)^-1/2`` (``serve_latent_runner.scale_residual_projections``), and the
routers are LEFT AS DRAWN — this family has no correction bias to balance
through, and a seeded softmax router over a normed input spreads its tokens
evenly enough that the cell's rate does not swing with the seed (the
configuration's ``assumed.routers`` gives the readings).  After the run the
routing of the whole run is logged from the engine's counters: the busiest
held expert over the mean, the held share of assignments and the share of
rows that reached a held expert."""

from __future__ import annotations

from benchmarks.harness import serve_runner
from benchmarks.harness.common import log
from benchmarks.harness.serve_latent_runner import (
    reference_check,
    scale_residual_projections,
)


def build(cell, args, devices, ledger):
    """``serve_runner.build``, then the residual writers scaled: the served
    weights and the reference's are those."""
    params, model = _build(cell, args, devices, ledger)
    params = scale_residual_projections(
        params, cell.config["published"]["num_hidden_layers"])
    model.params = params
    return params, model


_build = serve_runner.build


def run(cell, args, devices, peak, clock):
    """``serve_runner.run`` with this module's build and the latent
    reference check, then the run's routing from its counters."""
    theirs = serve_runner.reference_check, serve_runner.build
    serve_runner.reference_check, serve_runner.build = reference_check, build
    try:
        out = serve_runner.run(cell, args, devices, peak, clock)
    finally:
        serve_runner.reference_check, serve_runner.build = theirs
    c = out.reading.counters
    made, routed = c.get("moe/assignments_total"), c.get(
        "moe/rows_routed_total")
    if made and routed:
        log(f"[routing] over the run: busiest held expert over the mean, a "
            f"layer, {c.get('moe/expert_load_max_over_mean', 0.0):.3f}; held "
            f"share of assignments "
            f"{c.get('moe/assignments_held_total', 0) / made:.4f}; rows "
            f"reaching a held expert "
            f"{c.get('moe/rows_reaching_held_total', 0) / routed:.4f}")
    return out
