"""The serving sampler does only the work its batch asks for.

``serving/engine.py::_sample_rows`` picks ONE branch of a ``lax.switch`` a
batch from the parameter vectors it is handed (argmax / temperature-only
categorical / the full top-k + nucleus filter).  Checked here:

- over a matrix of parameter mixes, tokens and ``finite`` flags equal, bit
  for bit, a plain ``vmap`` of ``_sample_logits`` over the rows — the body
  the function had before, kept below as the reference;
- the program holds the choice as a top-level ``cond`` whose last branch
  alone sorts (a ``cond`` under ``vmap`` would be a ``select_n`` of both
  sides), and a non-finite row is flagged on every branch;
- the engine: a sampled request after a greedy warm-up compiles nothing,
  ``serving/sampler_steps_total/<path>`` books what the decoding rows ask
  for, and a parked slot's row goes back to greedy.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params
from test_device_names import _walk
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import CompileLedger, MetricRegistry
from neuronx_distributed_tpu.obs.compile_ledger import jit_cache_size
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.serving import (
    Request,
    SamplingParams,
    ServingEngine,
)
from neuronx_distributed_tpu.serving.engine import (
    SAMPLER_PATHS,
    _sample_rows,
    _sampler_path,
)
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel
from neuronx_distributed_tpu.trace.engine import _sample_logits

V = 211


@jax.jit
def _reference_rows(logits, base_keys, tok_idx, temperature, top_k, top_p):
    """``_sample_rows`` as it was: everything for every row, selected after."""
    def row(lg, key, idx, t, k, p):
        tok = _sample_logits(lg, jax.random.fold_in(key, idx), t, k, p)
        return tok, jnp.all(jnp.isfinite(lg.astype(jnp.float32)))

    return jax.vmap(row)(logits, base_keys, tok_idx, temperature, top_k,
                         top_p)


#: name -> (per-row (temperature, top_k, top_p), the path the batch takes)
MIXES = {
    "all_greedy": ([(0.0, 0, 1.0)] * 5, "greedy"),
    "greedy_rows_that_set_filters": (
        [(0.0, 0, 1.0), (0.0, 7, 1.0), (0.0, 0, 0.5), (0.0, 3, 0.9)],
        "greedy"),
    "all_temperature": (
        [(0.7, 0, 1.0), (1.0, 0, 1.0), (1.6, 0, 1.0), (0.05, 0, 1.0)],
        "temperature"),
    "temperature_among_greedy": (
        [(0.0, 0, 1.0), (0.9, 0, 1.0), (0.0, 5, 0.5), (1.3, 0, 1.0)],
        "temperature"),
    "greedy_top_k_top_p_mixed": (
        [(0.0, 0, 1.0), (0.8, 5, 1.0), (1.0, 0, 0.9), (0.7, 9, 0.6),
         (1.2, 0, 1.0)], "filtered"),
    "all_top_p": ([(0.8, 0, 0.9)] * 4, "filtered"),
    # a freed slot whose row was never cleared: at this level it is one more
    # sampling row (the whole batch filters); the engine clears it on park
    "dead_row_with_a_stale_temperature": (
        [(0.0, 0, 1.0), (0.0, 0, 1.0), (0.8, 0, 0.9), (0.0, 0, 1.0)],
        "filtered"),
    "one_row_greedy": ([(0.0, 0, 1.0)], "greedy"),
    "one_row_temperature": ([(0.8, 0, 1.0)], "temperature"),
    "one_row_filtered": ([(0.8, 40, 0.95)], "filtered"),
}


def _batch(rows, dtype=jnp.float32, seed=0):
    b = len(rows)
    rs = np.random.RandomState(seed)
    logits = jnp.asarray(rs.randn(b, V) * 3.0, dtype)
    keys = jnp.asarray(rs.randint(0, 2**31, (b, 2)), jnp.uint32)
    idx = jnp.asarray(rs.randint(0, 50, (b,)), jnp.int32)
    t, k, p = (np.asarray(col, dt) for col, dt in
               zip(zip(*rows), (np.float32, np.int32, np.float32)))
    return logits, keys, idx, t, k, p


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mix", list(MIXES))
def test_tokens_and_finite_match_the_per_row_reference(mix, dtype):
    rows, path = MIXES[mix]
    for seed in range(3):
        logits, keys, idx, t, k, p = _batch(rows, dtype, seed)
        assert SAMPLER_PATHS[int(_sampler_path(t, k, p))] == path
        toks, finite = _sample_rows(logits, keys, idx, t, k, p)
        want, want_finite = _reference_rows(logits, keys, idx, t, k, p)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(finite),
                                      np.asarray(want_finite))
        assert toks.dtype == jnp.int32 and bool(jnp.all(finite))


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf],
                         ids=["nan", "inf", "neg_inf"])
@pytest.mark.parametrize("mix", ["all_greedy", "temperature_among_greedy",
                                 "greedy_top_k_top_p_mixed"])
def test_a_non_finite_row_is_flagged_on_every_path(mix, bad):
    rows, _ = MIXES[mix]
    logits, keys, idx, t, k, p = _batch(rows)
    logits = logits.at[1, 17].set(bad)
    toks, finite = _sample_rows(logits, keys, idx, t, k, p)
    want, want_finite = _reference_rows(logits, keys, idx, t, k, p)
    assert [bool(f) for f in finite] == [i != 1 for i in range(len(rows))]
    np.testing.assert_array_equal(np.asarray(finite), np.asarray(want_finite))
    # the healthy rows draw what they would have drawn alone
    keep = np.arange(len(rows)) != 1
    np.testing.assert_array_equal(np.asarray(toks)[keep],
                                  np.asarray(want)[keep])


# -- the shape of the program -------------------------------------------------

def test_the_sort_lives_in_one_branch_of_a_batch_level_cond():
    def count(jaxpr):
        return collections.Counter(prim for prim, _, _ in _walk(jaxpr))

    args = _batch(MIXES["greedy_top_k_top_p_mixed"][0])
    jaxpr = jax.make_jaxpr(_sample_rows)(*args).jaxpr
    # ONE cond survives tracing, so its predicate is the batch's: under the
    # vmap a per-row cond would have become a select_n of both sides
    [cond] = [params for prim, _, params in _walk(jaxpr) if prim == "cond"]
    greedy, temperature, filtered = (count(b.jaxpr)
                                     for b in cond["branches"])
    assert set(greedy) == {"argmax"}
    heavy = ("sort", "gather", "cumsum", "exp")
    assert temperature["random_bits"] and not any(
        temperature[h] for h in heavy)
    # all of the program's heavy operations are the filtered branch's
    whole = count(jaxpr)
    assert all(filtered[h] and whole[h] == filtered[h] for h in heavy)
    # and the optimized program keeps a conditional, under the scope that
    # sampler_time_share reads
    hlo = _sample_rows.lower(*args).compile().as_text()
    assert " conditional(" in hlo and "sample/cond" in hlo


# -- the engine ---------------------------------------------------------------

@pytest.fixture
def tiny_engine(devices8):
    """A B=3 chunked paged engine with an rng, a ledger and its registry."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(sequence_parallel=False, dtype=jnp.float32,
                           param_dtype=jnp.float32, max_seq_len=32,
                           remat="none")
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((3, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    reg = MetricRegistry()
    led = CompileLedger(registry=reg)
    pool.compile_ledger = led
    engine = ServingEngine(pool, page_size=4, num_pages=16,
                           prefill_chunk_tokens=4, compile_ledger=led,
                           rng=jax.random.PRNGKey(7))
    yield cfg, engine, led, reg
    engine.close()
    pool.compile_ledger = None


def _request(cfg, rid, plen, new, **sampling):
    rs = np.random.RandomState(100 + rid)
    return Request(request_id=rid, max_new_tokens=new,
                   prompt_ids=rs.randint(1, cfg.vocab_size, plen).tolist(),
                   sampling=SamplingParams(**sampling))


def _steps_by_path(engine):
    return {p: int(engine.registry.counter(
        f"serving/sampler_steps_total/{p}").value) for p in SAMPLER_PATHS}


def test_a_sampled_request_after_a_greedy_warmup_compiles_nothing(
        tiny_engine):
    cfg, engine, led, reg = tiny_engine
    engine.submit(_request(cfg, 0, 6, 3))
    engine.submit(_request(cfg, 1, 5, 3))
    assert len(engine.run_until_complete(max_steps=100)) == 2
    engine.declare_warmup_done()
    requests = reg.counter("trace/compile_requests_total").value
    programs = jit_cache_size(_sample_rows)
    mark = led.mark()
    engine.submit(_request(cfg, 2, 6, 4, temperature=0.8, top_p=0.9))
    engine.submit(_request(cfg, 3, 7, 4, temperature=1.1))
    outs = engine.run_until_complete(max_steps=100)
    assert sorted(o.request_id for o in outs) == [2, 3]
    assert all(o.state == "finished" for o in outs)
    assert reg.counter("trace/compile_requests_total").value == requests
    assert jit_cache_size(_sample_rows) == programs  # one a shape, as before
    assert led.compiles_since(mark) == 0 and led.storms == 0


def test_sampler_steps_are_booked_by_what_the_decoding_rows_ask_for(
        tiny_engine):
    cfg, engine, _, _ = tiny_engine
    assert _steps_by_path(engine) == {p: 0 for p in SAMPLER_PATHS}

    def serve(*reqs):
        before = _steps_by_path(engine)
        for r in reqs:
            engine.submit(r)
        assert len(engine.run_until_complete(max_steps=100)) == len(reqs)
        after = _steps_by_path(engine)
        return {p: after[p] - before[p] for p in SAMPLER_PATHS}

    # 1 + 3 tokens: the first comes from the prefill's own B=1 call
    assert serve(_request(cfg, 0, 6, 4)) == {
        "greedy": 3, "temperature": 0, "filtered": 0}
    assert serve(_request(cfg, 1, 6, 4, temperature=0.8)) == {
        "greedy": 0, "temperature": 3, "filtered": 0}
    # a greedy co-batch rides the sampled request's path while it lives and
    # is back on greedy the step after it finishes
    mixed = serve(_request(cfg, 2, 6, 3, temperature=0.8, top_p=0.9),
                  _request(cfg, 3, 6, 6))
    assert mixed["filtered"] == 2 and mixed["temperature"] == 0
    assert mixed["greedy"] >= 3  # request 3's 5 steps less the shared ones
    # top_k alone filters; a greedy request that sets it does not
    assert serve(_request(cfg, 4, 6, 3, temperature=1.0, top_k=5)) == {
        "greedy": 0, "temperature": 0, "filtered": 2}
    assert serve(_request(cfg, 5, 6, 3, top_k=5, top_p=0.5)) == {
        "greedy": 2, "temperature": 0, "filtered": 0}
    # every row is parked greedy again: nothing stale holds the batch
    assert not engine._temps.any()


@pytest.mark.parametrize("how", ["finish", "cancel", "chunking"])
def test_a_row_that_is_not_decoding_does_not_choose_the_path(tiny_engine,
                                                             how):
    cfg, engine, _, _ = tiny_engine
    sampled = _request(cfg, 0, 8 if how == "chunking" else 4, 8,
                       temperature=0.8, top_p=0.9)
    engine.submit(sampled)
    if how == "chunking":
        # two chunks of 4: after one step the slot is still prefilling and
        # its row must not have been written yet
        engine.step()
        assert engine._chunking and not engine._temps.any()
        engine.step()
        assert not engine._chunking and engine._temps.max() > 0
        return
    engine.step()
    engine.step()
    assert engine._temps.max() > 0
    if how == "cancel":
        engine.cancel(0)
        engine.step()
    else:
        engine.run_until_complete(max_steps=100)
    assert not engine._temps.any() and engine._sampling_dirty
    before = _steps_by_path(engine)
    engine.submit(_request(cfg, 1, 4, 3))
    engine.run_until_complete(max_steps=100)
    after = _steps_by_path(engine)
    assert after["filtered"] == before["filtered"]
    assert after["greedy"] == before["greedy"] + 2
