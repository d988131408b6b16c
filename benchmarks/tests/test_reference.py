"""The plain float32 decoder against the program's ``LlamaForCausalLM`` at a
tiny size on the CPU: with a sliding window, with QKV biases, with a group
of 7 query heads per kv head.  Both sides in float32, so they agree to
rounding; the loss agrees with the program's chunked loss head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest, traffic

CASES = {
    "window": dict(num_heads=8, num_kv_heads=4, head_dim=16,
                   sliding_window=24),
    "bias_group7": dict(num_heads=14, num_kv_heads=2, head_dim=16,
                        qkv_bias=True, rope_theta=1e6, rms_eps=1e-6),
    "window_and_bias": dict(num_heads=8, num_kv_heads=2, head_dim=16,
                            sliding_window=17, qkv_bias=True),
}


@pytest.fixture(scope="module")
def ref():
    import os

    return manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "reference", "decoder_f32.py"),
        "benchmarks_reference_decoder_f32")


@pytest.fixture(scope="module")
def adapt():
    import os

    return manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "reference", "llama_weights.py"),
        "benchmarks_reference_llama_weights").adapt


def build(case):
    from flax import linen as nn

    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    kw = CASES[case]
    cfg = LlamaConfig.tiny(
        num_layers=3, max_seq_len=64, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    module = LlamaForCausalLM(cfg)
    params = nn.unbox(module.init(jax.random.PRNGKey(3),
                                  jnp.zeros((1, 8), jnp.int32)))
    if kw.get("qkv_bias"):
        # biases initialise to zero: make them count
        def bump(path, x):
            name = jax.tree_util.keystr(path)
            if "bias" in name:
                return jax.random.normal(
                    jax.random.PRNGKey(hash(name) % 2 ** 31), x.shape,
                    x.dtype) * 0.5
            return x
        params = jax.tree_util.tree_map_with_path(bump, params)
    published = {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
        "sliding_window": cfg.sliding_window,
        "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size}
    return cfg, module, params, published


@pytest.mark.parametrize("case", sorted(CASES))
def test_logits_agree_with_the_program(ref, adapt, case):
    cfg, module, params, published = build(case)
    ids = np.random.RandomState(0).randint(1, cfg.vocab_size, size=48)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(module.apply(params, jnp.asarray(ids[None, :]))[0])
    w = adapt(params, cfg.num_layers)
    shape = ref.Shape.from_config(published)
    assert shape.sliding_window == cfg.sliding_window
    want = np.asarray(ref.logits_at(w, shape, ids, list(range(48))))
    assert got.shape == want.shape == (48, cfg.vocab_size)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 2e-5, err
    if cfg.sliding_window:
        # the window matters at this length: without it the last rows differ
        wide = ref.Shape.from_config({**published, "sliding_window": None})
        other = np.asarray(ref.logits_at(w, wide, ids, [47]))
        assert np.max(np.abs(other - want[47:])) / np.max(np.abs(want)) > 1e-3


def test_a_published_window_that_is_switched_off_is_ignored(ref):
    shape = ref.Shape.from_config({
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
        "sliding_window": 131072, "use_sliding_window": False})
    assert shape.sliding_window is None and shape.head_dim == 16


def test_loss_agrees_with_the_programs_chunked_head(ref, adapt):
    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum

    cfg, module, params, published = build("window")
    batch = traffic.train_batch({"batch": 2, "seq_len": 32}, cfg.vocab_size,
                                seed=4, step=0)
    with jax.default_matmul_precision("highest"):
        loss_sum, tok = make_causal_lm_loss_sum(chunk_size=16)(
            module, params, {k: jnp.asarray(v) for k, v in batch.items()})
    got = float(loss_sum) / float(tok)
    assert int(tok) == 2 * 31                 # the last label of a row is -1
    want = ref.loss(adapt(params, cfg.num_layers),
                    ref.Shape.from_config(published), batch["ids"],
                    batch["labels"])
    assert got == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize("cell_name", ["qwen2-7b.serve-chat",
                                       "mistral-7b.serve-docs"])
def test_what_the_logits_comparison_sees_of_an_int8_kv_pool(cell_name):
    """The serve runner's own reference check at rehearsal size, through
    the bf16 page pool and through the program's int8 pool (int8 pages with
    a scale and zero per page).  The int8 pool errs more than bf16, but by
    less than a factor of two: a tolerance of twice the bf16 maximum (what
    the configuration files hold for the chip) sits at the int8 pool's
    level and does not promise to catch it; a tolerance between the two
    does, and the comparison is wired so that it would."""
    import functools
    import types

    import neuronx_distributed_tpu as nxd
    from benchmarks.harness import serve_runner
    from neuronx_distributed_tpu.obs.compile_ledger import CompileLedger

    worst = {}
    for pool in ("bf16", "int8"):
        cell = manifest.Cell(cell_name, rehearse=True)
        cell.config["tolerances"]["logits_rel"] = 0.015
        params, model = serve_runner.build(
            cell, types.SimpleNamespace(seed=3), jax.devices()[:1],
            CompileLedger())
        if pool == "int8":
            model.make_page_pool = functools.partial(model.make_page_pool,
                                                     quant="int8")
        try:
            why_not = serve_runner.reference_check(cell, params, model, 3)
        finally:
            nxd.destroy_model_parallel()
        worst[pool] = max([float(w.rsplit(" ", 1)[1]) for w in why_not],
                          default=0.0)
        assert bool(why_not) == (pool == "int8"), (pool, why_not)
    assert 0.015 < worst["int8"] < 0.03      # bf16 here: 0.009-0.013
