"""Brumby-14B-Base through the paged server, at a toy size on the CPU: every
layer power retention (``models/hybrid.py::PowerRetentionMixer``), a state
row of two float32 arrays a slot a layer and NO page — held to the plain
float32 reference ``benchmarks/reference/brumby_f32.py`` (the quadratic
form; seeded weights) through the benchmark's own probe
(``benchmarks/harness/serve_retention_runner.py``)."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.kvcache.pool import LayerStates, PagePool
from neuronx_distributed_tpu.kvcache.transfer import TransferError
from neuronx_distributed_tpu.models import hybrid
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.ops import power_retention as pr
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.harness import serve_retention_runner as runner  # noqa: E402

CELL_CFG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "brumby-14b.serve-1chip.json")))
TOL = CELL_CFG["tolerances"]      # the cell's written limits
L, B, C, T, PAGE, W = 2, 3, 48, 64, 4, 8
SERVING = dict(slots=B, context_len=C, max_total_len=T, page_size=PAGE,
               num_pages=2, prefill_chunk_tokens=W)
LENS, ND = (7, 14, 45), 2


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("brumby_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("brumby_f32")
adapter = _load("brumby_weights")


def toy_config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=L,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        rope_theta=1e6, rms_eps=1e-6, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32,
        mixer_types=("power-retention",) * L), **over})


SHAPE = ref.Shape(vocab=128, hidden=64, inter=96, layers=L, heads=4,
                  kv_heads=2, head_dim=16, eps=1e-6, theta=1e6)


@pytest.fixture(scope="module")
def toy():
    """``(module, params, the reference's weights)``: seeded, the norm
    weights moved off 1 so that a norm left out shows, and the decay's bias
    drawn for half-lives of 2 to 40 tokens: prompts of 7-45 tokens then see
    the decay as the cell's 2k-16k see half-lives of 64-8,192."""
    module = LlamaForCausalLM(toy_config())
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(3), x.shape)
        if x.ndim == 1 else x, params)
    for i in range(L):
        attn = params["params"]["model"][f"layer_{i}"]["attn"]
        half = jnp.asarray([2.0, 40.0]) if i == 0 else jnp.asarray([9.0, 4.0])
        g = jnp.exp(-np.log(2.0) / half)
        attn["gate_bias"] = jax.tree.map(
            lambda x: (jnp.log(g) - jnp.log1p(-g)).astype(x.dtype),
            attn["gate_bias"])
    return module, params, adapter.adapt(params, L)


def served(module, params):
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32))


def seqs_for(lens, nd, seed=11):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 128, size=n + nd).astype(np.int32) for n in lens]


def readings(model, w, shape=SHAPE):
    """The cell's three readings of the probe (chunks then decodes through
    the state rows, against the reference): the worst ``logits_rel``,
    ``state_rel`` and ``decay_abs``."""
    seqs = seqs_for(LENS, ND)
    got, steps = runner.probe(model, SERVING, seqs, LENS, ND)
    worst = dict(logits_rel=0.0, state_rel=0.0, decay_abs=0.0)
    for b, n in enumerate(LENS):
        want, info = ref.forward(w, shape, seqs[b], list(range(n - 1, n + ND)))
        want = np.asarray(want)
        for j in range(ND + 1):
            err = np.max(np.abs(got[(b, j)] - want[j])) / np.max(np.abs(want[j]))
            worst["logits_rel"] = max(worst["logits_rel"], float(err))
        for j in range(1, ND + 1):
            before, after = steps[(b, j)]
            for i in range(L):
                rest, gate = ref.state_step_error(before[i], after[i],
                                                  info["lg"][i, j])
                worst["state_rel"] = max(worst["state_rel"], rest)
                worst["decay_abs"] = max(worst["decay_abs"], gate)
    return worst


def test_the_probe_reads_the_faithful_program_far_under_every_limit(toy):
    module, params, w = toy
    assert sorted(k for k in TOL if k != "why") == [
        "decay_abs", "logits_rel", "state_rel"]
    got = readings(served(module, params), w)
    assert got["logits_rel"] < 1e-4 < TOL["logits_rel"] / 100
    assert got["state_rel"] < 1e-6 < TOL["state_rel"] / 50
    assert got["decay_abs"] < 1e-5 < TOL["decay_abs"] / 50


# -- "the check catches" -------------------------------------------------------


def _no_decay(monkeypatch):
    monkeypatch.setattr(hybrid, "_log_decay", lambda gate, bias: jnp.zeros(
        gate.shape, jnp.float32))


def _no_normaliser(monkeypatch):
    monkeypatch.setattr(pr, "_normalise", lambda num, den, d: num)


def _state_not_carried(monkeypatch):
    fresh = hybrid._fresh
    monkeypatch.setattr(
        hybrid, "_fresh", lambda positions, live: (
            jnp.ones((positions.shape[0],), bool) if positions.shape[1] > 1
            else fresh(positions, live)))


def _bf16_state(monkeypatch):
    step, chunk = pr.retention_step, pr.retention_chunk
    rounded = lambda s: s.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def step_rounded(states, *a, **kw):
        states, num = step(states, *a, **kw)
        return rounded(states), num

    def chunk_rounded(*a, **kw):
        o, states, zs = chunk(*a, **kw)
        return o, rounded(states), zs

    monkeypatch.setattr(pr, "retention_step", step_rounded)
    monkeypatch.setattr(pr, "retention_chunk", chunk_rounded)


DEPARTURES = {
    # name: (patch of the program, change of the reference, the limit that
    # fails, by at least what factor of the WRITTEN limit at this size).
    # Measured here (logits_rel / state_rel / decay_abs; faithful 2.4e-6 /
    # 1.1e-7 / 3.8e-8): missing decay 1.66 / 0.49 / 4e-3 (the search's
    # edge); p = 1 (in the reference), a missing normaliser and a state not
    # carried 1.62 / 1.81 / 1.31 of the logits; a bfloat16 state 0.013 /
    # 2.7e-3 / 2.9e-4 — state_rel is its limit: on the chip, at half-lives
    # of 64-8,192 tokens, its logits read as the faithful program's
    # (0.39-0.47%) and pass
    # (the fitted decay stops at the edge of its search, 4e-3 from the
    # reference's: past the limit, and the rows then leave the recurrence)
    "missing_decay": (_no_decay, {}, "decay_abs", 2.5),
    "missing_decay_in_the_state": (_no_decay, {}, "state_rel", 1000.0),
    "missing_decay_in_the_logits": (_no_decay, {}, "logits_rel", 50.0),
    "degree_one": (None, {"power": 1}, "logits_rel", 50.0),
    "missing_normaliser": (_no_normaliser, {}, "logits_rel", 50.0),
    "state_not_carried_between_chunks": (_state_not_carried, {},
                                         "logits_rel", 40.0),
    "bfloat16_state": (_bf16_state, {}, "state_rel", 10.0),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_the_check_catches(toy, monkeypatch, name):
    """Each departure from the layer as written fails one of the cell's
    WRITTEN limits on the probe, by the stated factor at this size."""
    import dataclasses

    module, params, w = toy
    patch, change, limit, factor = DEPARTURES[name]
    if patch is not None:
        patch(monkeypatch)
    # the ops' own jits keep what they traced: a patch is seen by a new trace
    for fn in (pr._retention_chunk_impl, pr._retention_step_impl):
        fn.clear_cache()
    try:
        got = readings(served(LlamaForCausalLM(toy_config()), params), w,
                       dataclasses.replace(SHAPE, **change))
    finally:
        for fn in (pr._retention_chunk_impl, pr._retention_step_impl):
            fn.clear_cache()
    assert got[limit] / TOL[limit] > factor, f"{name}: {got}"


# -- the pool and the engine: a model that keeps no page --------------------------


def test_pool_of_a_model_without_a_page_is_its_state_rows():
    cfg = toy_config()
    assert cfg.layer_caches == ("state",) * L and cfg.recurrent_layers == (0, 1)
    D = pr.phi_dim(16)
    assert cfg.state_arrays == (((2, 16, D), "float32"),
                                ((2, 16, 16), "float32"))
    layers = LayerStates.for_config(cfg, PAGE, state_rows=B)
    assert (layers.paged, layers.recurrent) == (0, L)
    pool = PagePool(L, 2, PAGE, 2, 16, jnp.float32, layers=layers)
    assert [len(c) for c in pool.caches] == [2] * L
    assert pool.caches[0][0].shape == (B, 2, 16, D)
    assert pool.page_bytes == 0
    assert pool.state_bytes == B * L * 2 * (16 * D + 16 * 16) * 4
    assert pool.total_bytes == sum(x.size * x.dtype.itemsize
                                   for x in jax.tree.leaves(pool.caches))
    # no page to buy, whatever the budget
    assert PagePool.pages_for_budget(10 ** 9, L, PAGE, 2, 16, jnp.float32,
                                     layers=layers) == 0
    with pytest.raises(ValueError, match="one kind of recurrent layer"):
        toy_config(mixer_types=("power-retention", "lightning-attn"))


def engine_for(model, **kw):
    return ServingEngine(model, page_size=PAGE, prefill_chunk_tokens=W, **kw)


PROMPT_LENS = (7, 30, 45, 20, 13)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
def test_served_model_is_the_reference_and_keeps_no_page(toy, kernel):
    """Five requests through three slots (chunks of 8, then decodes; the
    Pallas calls interpreted under ``kernels``): admitted, finished and
    freed by STATE ROWS alone — no page is ever taken — and each one's greedy
    tokens are the argmax of the reference's full forward of its own
    sequence, its logits within rounding."""
    module, params, w = toy
    eng = engine_for(served(module, params), paged_kernel=kernel)
    kv = eng._kv
    assert kv.pageless and kv.index is None and kv.num_pages == (2,)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, 128, size=n).tolist() for n in PROMPT_LENS]
    for i, p in enumerate(prompts):
        req = Request(request_id=i, prompt_ids=p, max_new_tokens=5)
        assert kv.pages_needed(req) == 0
        eng.submit(req)
    outs, rows_peak = [], 0
    for _ in range(400):
        if not eng.has_work:
            break
        outs += eng.step()
        kv.assert_invariants()
        assert kv.alloc.in_use == 0 and not kv._tables.any()
        rows_peak = max(rows_peak, sum(r is not None for r in kv.state_rows))
    assert rows_peak == B and not any(kv.state_rows)
    assert sorted(o.request_id for o in outs) == list(range(5))
    snap = eng.registry.snapshot()
    assert snap["kvcache/pages_in_use"] == 0
    assert snap["kvcache/state_rows_in_use"] == 0
    assert snap["kvcache/state_bytes"] == B * L * 2 * (
        16 * pr.phi_dim(16) + 256) * 4
    assert snap["serving/retention_tokens_total/chunk"] == sum(PROMPT_LENS)
    assert snap["serving/retention_tokens_total/step"] > 0
    assert snap.get("serving/kv_rows_written_total", 0) == 0
    for o in outs:
        assert o.state == "finished" and len(o.token_ids) == 5
        p = prompts[o.request_id]
        seq = np.asarray(p + list(o.token_ids), np.int32)
        want = np.asarray(ref.logits_at(w, SHAPE, seq,
                                        list(range(len(p) - 1, len(seq) - 1))))
        assert list(o.token_ids) == np.argmax(want, axis=-1).tolist()
    eng.close()


def test_engine_refuses_by_name_what_state_rows_do_not_carry(toy):
    module, params, _ = toy
    model = served(module, params)
    for kw, what in ((dict(spec_k=2, draft=model), "speculative"),
                     (dict(kv_quant="int8"), "int8"),
                     (dict(adapter_store=object()), "LoRA"),
                     (dict(prefix_cache=True), "prefix index")):
        with pytest.raises(ValueError, match=what) as e:
            engine_for(model, **kw)
        assert "power-retention" in str(e.value)
    eng = engine_for(model)
    with pytest.raises(TransferError, match="recurrent"):
        eng._refuse_migration()
    eng.close()
