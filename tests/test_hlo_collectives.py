"""Compiled-HLO collective-count canary for the TP+SP hot path.

A sharding regression in the train step (a dropped activation constraint,
an accidentally replicated parameter, a batch resharded per layer) shows up
as extra all-gathers/all-reduces in the partitioned program long before
anyone can measure it on hardware.  This test compiles the real train step
on the 8-device mesh and asserts GENEROUS upper bounds on collective
counts — loose enough to survive XLA version drift (the CPU backend also
legitimately lowers reduce-scatter as all-reduce+slice, so op MIX is not
pinned), tight enough that a per-layer replication blow-up (which
multiplies counts) fails loudly.

The tiny model's projections stay under ``collective_matmul``'s rule
(``GATHER_MIN_WIDTH``), so its two budgets below are those of GSPMD's own
text and stand as they were; the last case widens q/k/v past the rule and
counts what the cut adds.

Reference counterpart: none — the reference has no compile-time collective
accounting; its perf regressions surface only on Trn1 metrics dashboards.
"""

import re

import jax
import jax.numpy as jnp

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    causal_lm_loss,
)
from neuronx_distributed_tpu.trainer import (
    default_batch_spec,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
)

_OPS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
        "all-to-all")


def _collective_counts(txt: str):
    return {op: len(re.findall(rf"{op}(?:-start)?\(", txt)) for op in _OPS}


def _compiled_step_text(num_layers: int, **overrides):
    nxd.destroy_model_parallel()
    nxd.initialize_model_parallel(tensor_parallel_size=8)
    config = nxd.training_config(tensor_parallel_size=8, compute_dtype="float32")
    cfg = LlamaConfig.tiny(
        num_layers=num_layers, sequence_parallel=True, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=64,
        **overrides)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg), (jnp.zeros((1, 64), jnp.int32),))
    opt = initialize_parallel_optimizer(config, model)
    step = make_train_step(
        config, model, opt, causal_lm_loss,
        batch_spec={"ids": default_batch_spec(), "labels": default_batch_spec()})
    ids = jnp.zeros((8, 64), jnp.int32)
    return step.lower(model.params, opt.state,
                      {"ids": ids, "labels": ids}, None).compile().as_text()


def test_tp_sp_train_step_collective_budget(devices8):
    """2-layer tp=8+SP train step: measured today at ~25 all-reduce /
    ~19 all-gather on this backend; the budget below is ~2x headroom.
    A replication regression multiplies counts well past it."""
    counts = _collective_counts(_compiled_step_text(num_layers=2))
    assert counts["all-reduce"] <= 50, counts
    assert counts["all-gather"] <= 40, counts
    # nothing in the dense TP+SP path should need a2a or permutes
    assert counts["all-to-all"] == 0, counts
    assert counts["collective-permute"] == 0, counts


def test_collectives_scale_linearly_with_depth(devices8):
    """Per-layer collective cost must be constant: doubling the layer count
    may at most double the per-layer share (catches per-layer reshard
    leaks that grow superlinearly)."""
    c2 = _collective_counts(_compiled_step_text(num_layers=2))
    c4 = _collective_counts(_compiled_step_text(num_layers=4))
    for op in ("all-reduce", "all-gather"):
        # fixed part (loss/optimizer) + per-layer part: c4 <= c2 * 2 holds
        # whenever the per-layer share doesn't grow
        assert c4[op] <= 2 * c2[op] + 4, (op, c2, c4)


def test_a_cut_gather_adds_its_pieces_and_nothing_else(devices8, monkeypatch):
    """q/k/v widened past the rule (local columns (8 + 2 x 8) x 512 / 8 =
    1536): cut in pieces, a layer holds (pieces - 1) more forward all-gathers
    and, this step keeping no remat, the backward's own gather of the input
    where the whole form reused the forward's — linear in depth, and not one
    all-reduce, permute or all-to-all more."""
    from neuronx_distributed_tpu.parallel import collective_matmul as cm

    counts = {}
    for form, width in (("cut", cm.GATHER_MIN_WIDTH), ("whole", 1 << 30)):
        monkeypatch.setattr(cm, "GATHER_MIN_WIDTH", width)
        for layers in (2, 4):
            counts[form, layers] = _collective_counts(
                _compiled_step_text(layers, head_dim=512))
    for layers in (2, 4):
        cut, whole = counts["cut", layers], counts["whole", layers]
        assert cut["all-gather"] - whole["all-gather"] == layers * cm.GATHER_PIECES, counts
        for op in ("all-reduce", "collective-permute", "all-to-all",
                   "reduce-scatter"):
            assert cut[op] == whole[op], (op, counts)
