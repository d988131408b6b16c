"""The Mamba-2 one-token step as ONE Pallas call on the state array where it
lies (``ops.ssm_scan.ssm_step``, interpreted on the CPU): the rows that are
tokens stepped as the reference steps them, every other row left to the bit,
``y`` of a skipped row exactly 0 — and a tiny Mamba-2 hybrid served through
``ServingEngine`` by the kernel and by the XLA form to the same tokens.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from neuronx_distributed_tpu.models import hybrid
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.ops import ssm_scan as ssm
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

R, B, NH, P, N = 7, 6, 8, 8, 16


def inputs(G, seed=0, rows=R, batch=B):
    rs = np.random.RandomState(seed)
    f32 = jnp.float32
    return dict(
        state=jnp.asarray(rs.randn(rows, NH, P, N), f32),
        x=jnp.asarray(rs.randn(batch, NH, P), f32),
        Bm=jnp.asarray(rs.randn(batch, G, N), f32),
        Cm=jnp.asarray(rs.randn(batch, G, N), f32),
        dt=jnp.asarray(rs.uniform(0.01, 0.7, (batch, NH)), f32),
        A=-jnp.asarray(rs.uniform(1.0, 16.0, (NH,)), f32),
        D=jnp.asarray(rs.randn(NH), f32))


# which batch rows are tokens / begin their sequence / which state row each
# continues (None: its own)
CASES = {
    "all_live": (np.ones(B, bool), None, None),
    "none_live": (np.zeros(B, bool), None, None),
    "scattered": (np.array([1, 0, 0, 1, 0, 1], bool), None, None),
    "ids_out_of_order": (np.array([1, 1, 0, 1, 1, 0], bool), None,
                         np.array([5, 0, 6, 3, 1, 2])),
    "a_fresh_row": (np.array([0, 1, 1, 0, 1, 1], bool),
                    np.array([0, 0, 1, 0, 0, 1], bool), None),
    # a decode over fewer rows than the array holds, by its ids
    "fewer_rows": (np.array([1, 0, 1], bool), np.array([0, 0, 1], bool),
                   np.array([4, 6, 1])),
}


def one_step(w, live, fresh, rows, oracle):
    """The step of the live rows by ``oracle`` (a scan over one token from
    the rows' states, a fresh row's zeros), put back where the rows lay."""
    n = live.shape[0]
    rows = np.arange(n) if rows is None else rows
    fresh = np.zeros(n, bool) if fresh is None else fresh
    before = jnp.where(jnp.asarray(fresh)[:, None, None, None], 0.0,
                       w["state"][rows])
    y, after = oracle(
        w["x"][:n, None], w["Bm"][:n, None], w["Cm"][:n, None],
        w["dt"][:n, None], w["A"], w["D"], jnp.asarray(live)[:, None], before)
    return y[:, 0], w["state"].at[rows[live]].set(after[live])


def kernel_step(w, live, fresh, rows):
    n = live.shape[0]
    return ssm.ssm_step(w["state"], w["x"][:n], w["Bm"][:n], w["Cm"][:n],
                        w["dt"][:n], w["A"], w["D"], live, fresh, rows)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("G", [1, 8])
def test_the_kernel_steps_the_live_rows_as_the_reference_and_no_others(G, case):
    live, fresh, rows = CASES[case]
    w = inputs(G)
    y, state = kernel_step(w, live, fresh, rows)
    want_y, want_state = one_step(w, live, fresh, rows,
                                  ssm.ssm_scan_reference)
    np.testing.assert_allclose(state, want_state, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    # a skipped row yields exact zeros, and every row no live token names
    # keeps its bits
    assert not np.any(np.asarray(y[~live]))
    ids = np.arange(live.shape[0]) if rows is None else rows
    idle = np.setdiff1d(np.arange(R), ids[live])
    np.testing.assert_array_equal(np.asarray(state)[idle],
                                  np.asarray(w["state"])[idle])


@pytest.mark.parametrize("G", [1, 8])
def test_the_kernel_is_the_xla_step_and_updates_by_one_outer_product(G):
    """Against the ``S == 1`` branch of ``ssm_scan`` (the form that runs
    where the kernel does not) on the live rows; and what the benchmark's
    state check reads: ``after - a * before`` is ONE outer product across
    the heads of a group, ``(dt x) (x) B``, nothing of it rounded below
    float32."""
    live, fresh, rows = CASES["ids_out_of_order"]
    w = inputs(G, seed=3)
    y, state = kernel_step(w, live, fresh, rows)
    want_y, want_state = one_step(w, live, fresh, rows, ssm.ssm_scan)
    np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    a = np.exp(np.asarray(w["dt"])[:, :, None, None]
               * np.asarray(w["A"])[None, :, None, None])
    moved = (np.asarray(state)[rows] - a * np.asarray(w["state"])[rows])[live]
    dtx = (np.asarray(w["dt"])[:, :, None] * np.asarray(w["x"]))[live]
    for g in range(G):
        heads = slice(g * NH // G, (g + 1) * NH // G)
        block = moved[:, heads].reshape(moved.shape[0], -1, N)
        want = dtx[:, heads].reshape(moved.shape[0], -1, 1) \
            * np.asarray(w["Bm"])[live][:, g, None, :]
        np.testing.assert_allclose(block, want, rtol=0, atol=2e-6)
        # ... of rank one: the second singular value is rounding
        sv = np.linalg.svd(block.astype(np.float64), compute_uv=False)
        assert np.all(sv[:, 1] < 1e-6 * sv[:, 0])


@pytest.mark.parametrize("block_bytes,heads", [(1 << 30, 8), (2 * P * N * 4, 2)])
def test_blocks_of_a_few_heads_step_as_a_whole_row_does(monkeypatch,
                                                        block_bytes, heads):
    monkeypatch.setattr(ssm, "_STEP_BLOCK_BYTES", block_bytes)
    ssm._ssm_step_impl.clear_cache()
    assert ssm._step_heads(NH, P, N) == heads
    live, fresh, rows = CASES["a_fresh_row"]
    w = inputs(2, seed=5)
    y, state = kernel_step(w, live, fresh, rows)
    want_y, want_state = one_step(w, live, fresh, rows,
                                  ssm.ssm_scan_reference)
    np.testing.assert_allclose(state, want_state, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=1e-5)
    ssm._ssm_step_impl.clear_cache()


@pytest.mark.parametrize("live", [
    [1, 1, 1, 1, 1], [0, 0, 0, 0, 0], [0, 1, 0, 0, 1], [1, 0, 1, 1, 0]])
def test_live_rows_come_first_in_their_order(live):
    order, count = ssm.live_rows_first(jnp.asarray(live, bool))
    live = np.asarray(live, bool)
    n = int(live.sum())
    assert int(count[0]) == n
    assert list(order[:n]) == list(np.flatnonzero(live))
    assert list(order[n:]) == list(np.flatnonzero(~live))


# ---------------------------------------------------------------------------
# through the mixer and the engine
# ---------------------------------------------------------------------------

SLOTS, C, T, PAGE, W = 3, 16, 32, 4, 4


def toy_config(groups):
    types = ["mamba2", "attention", "mamba2"]
    return LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_layers=len(types), num_heads=2, num_kv_heads=2, head_dim=16,
        max_seq_len=64, rms_eps=1e-5, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32, mixer_types=types,
        ffn_types=["mlp"] * len(types), ssm_heads=NH, ssm_head_dim=P,
        ssm_groups=groups, ssm_state_size=N, ssm_conv_kernel=4,
        ssm_chunk_rows=4, attn_rope=False, tie_word_embeddings=True)


@functools.lru_cache(maxsize=None)
def toy(groups):
    module = LlamaForCausalLM(toy_config(groups))
    params = nn.unbox(module.init(jax.random.PRNGKey(groups),
                                  jnp.zeros((1, 4), jnp.int32)))
    return module, params


def served(groups, **kw):
    module, params = toy(groups)
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=SLOTS, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32), **kw)


def run(groups, kernel):
    engine = ServingEngine(served(groups, paged_kernel=kernel),
                           page_size=PAGE, num_pages=40,
                           prefill_chunk_tokens=W)
    rs = np.random.RandomState(7)
    # four requests over three slots, prompts of several chunks: a slot
    # prefills (or stands empty) beside slots that decode
    for i, L in enumerate([5, 14, 9, 11]):
        engine.submit(Request(request_id=i, max_new_tokens=6,
                              prompt_ids=rs.randint(1, 64, L).tolist()))
    done = {o.request_id: tuple(o.token_ids)
            for o in engine.run_until_complete(max_steps=400)}
    snap = engine.registry.snapshot()
    engine.close()
    return done, snap


@pytest.mark.parametrize("groups", [1, 2])
def test_served_tokens_by_the_kernel_are_the_xla_forms(groups):
    by_kernel, snap = run(groups, True)
    by_xla, _ = run(groups, False)
    assert sorted(by_kernel) == [0, 1, 2, 3]
    assert by_kernel == by_xla
    # decodes ran over slots that did not decode, and the counter says how
    # many: the rows they were launched over less the rows they stepped
    stepped = snap["serving/ssm_state_rows_stepped_total"]
    skipped = snap["serving/ssm_state_rows_skipped_total"]
    assert stepped == snap["serving/ssm_tokens_total/step"] == 4 * 5
    assert skipped > 0 and (stepped + skipped) % SLOTS == 0


def _mixer_jaxpr(S, kernel, rows):
    cfg = toy_config(2)
    mixer = hybrid.Mamba2Mixer(cfg)
    x = jnp.zeros((rows, S, cfg.hidden_size))
    positions = jnp.zeros((rows, S), jnp.int32) + 3
    cache = tuple(jnp.zeros((SLOTS,) + shape, dtype)
                  for shape, dtype in hybrid.state_arrays(cfg, "mamba2"))
    params = mixer.init(jax.random.PRNGKey(0), x, positions)
    fn = lambda p, x, cache, off, valid, sr: mixer.apply(  # noqa: E731
        p, x, positions, kv_cache=cache, cache_offset=off, kv_valid=valid,
        paged_kernel=kernel, state_rows=sr)
    return jax.make_jaxpr(fn)(
        params, x, cache, jnp.zeros((rows,), jnp.int32),
        jnp.ones((rows, T), jnp.int32), jnp.arange(rows, dtype=jnp.int32))


def test_a_chunks_program_is_what_it_was():
    """The ``S > 1`` path does not know the kernel: a chunk's mixer traces
    to the same jaxpr whatever ``paged_kernel`` says, with no Pallas call,
    its state row gathered and scattered as before."""
    with_kernel, without = (str(_mixer_jaxpr(W, k, 1)) for k in (True, False))
    assert with_kernel == without
    assert "pallas_call" not in with_kernel and "ssm_step" not in with_kernel
    assert "scatter" in with_kernel


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "xla"])
def test_a_decodes_mixer_gathers_no_state_row_where_the_kernel_runs(kernel):
    text = str(_mixer_jaxpr(1, kernel, SLOTS))
    assert ("pallas_call" in text and "ssm_step" in text) == kernel
    # the state array [SLOTS, NH, P, N] is gathered and scattered by the XLA
    # form alone (the taps, [SLOTS, K - 1, channels], by both)
    shape = f"f32[{SLOTS},{NH},{P},{N}]"
    scattered = [ln for ln in text.splitlines()
                 if "scatter" in ln and f":{shape}" in ln.split("=")[0]]
    assert bool(scattered) != kernel


def test_ssm_step_probe_prints_a_line_a_variant():
    """`tools/ssm_step_probe.py --cpu --tiny`: the XLA step, the tool's bare
    copy of the live rows and the library's kernel through the interpreter,
    each a line with no device number off the chip; the kernel's state and
    ``y`` are the XLA form's and the rows that are no token keep their bits
    (a knock-out's are not the step's and are not compared)."""
    import json

    from conftest import run_cli

    proc = run_cli("tools/ssm_step_probe.py", "--cpu", "--tiny")
    rows = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")]
    assert [r["variant"].split(":")[0].split("@")[0] for r in rows] == [
        "xla", "xla", "copy", "copy", "step", "step", "step", "step",
        "step!col!read"]
    for r in rows:
        assert "error" not in r, r
        assert r["kernel_us"] is None and "gb_per_s" not in r
        if r["variant"].startswith("copy"):
            assert r["state_kept"]
        elif "!" not in r["variant"]:
            assert r["state_rel"] < 1e-6 and r["y_rel"] < 1e-5
            assert r["idle_rows_kept"]
    assert [r["block_heads"] for r in rows[4:]] == [4, 4, 2, 4, 4]
