"""Llama end-to-end tests: TP+SP+GQA+ZeRO-1 training on the 8-device mesh —
the reference's Llama-2-7B pretrain slice (Llama-shaped model, TP=8, SP,
ZeRO-1), mirroring the reference's model-level convergence tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import neuronx_distributed_tpu as nxd
from neuronx_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    apply_rope,
    causal_lm_loss,
    rope_sin_cos,
)
from neuronx_distributed_tpu.trainer import (
    default_batch_spec,
    initialize_parallel_model,
    initialize_parallel_optimizer,
    make_train_step,
)


def test_rope_matches_hf_convention():
    B, S, N, D = 1, 6, 2, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, N, D))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    sin, cos = rope_sin_cos(pos, D, 10000.0)
    y = apply_rope(x, sin, cos)
    # position 0 must be identity
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]), rtol=1e-6)
    # rotation preserves pairwise norms
    xf = np.asarray(x, np.float64).reshape(B, S, N, 2, D // 2)
    yf = np.asarray(y, np.float64).reshape(B, S, N, 2, D // 2)
    np.testing.assert_allclose(
        (xf**2).sum(-2), (yf**2).sum(-2), rtol=1e-5
    )
    # dot product between rotated q/k depends only on relative position
    q = jax.random.normal(jax.random.PRNGKey(1), (1, S, 1, D))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, S, 1, D))
    qr = apply_rope(jnp.broadcast_to(q[:, :1], q.shape), sin, cos)
    kr = apply_rope(jnp.broadcast_to(k[:, :1], k.shape), sin, cos)
    dots = np.einsum("bsnd,bsnd->s", np.asarray(qr), np.asarray(kr))
    # relative position 0 for every s → all equal
    np.testing.assert_allclose(dots, np.full_like(dots, dots[0]), rtol=1e-4)


@pytest.mark.parametrize("sp", [False, True], ids=["nosp", "sp"])
def test_forward_matches_dense_reference(devices8, sp):
    """TP=8 sharded forward == TP=1 (single-device-mesh) forward with the
    same params: the dense-vs-sharded oracle at model level."""
    cfg = LlamaConfig.tiny(sequence_parallel=sp, remat="none",
                           dtype=jnp.float32, param_dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, cfg.vocab_size)

    nxd.initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    params = model.init(jax.random.PRNGKey(1), ids)
    from flax import linen as nn

    raw = nn.unbox(params)
    logits_dense = np.asarray(jax.jit(lambda p, i: model.apply(p, i))(raw, ids))
    nxd.destroy_model_parallel()

    nxd.initialize_model_parallel(tensor_parallel_size=8, devices=devices8)
    from conftest import sharded_params

    p = sharded_params(params)
    logits_tp = np.asarray(jax.jit(lambda p, i: model.apply(p, i))(p, ids))
    np.testing.assert_allclose(logits_tp, logits_dense, rtol=5e-4, atol=5e-4)


def test_gqa_llama_with_kv_multiplier(devices8):
    """70B-style GQA: num_kv_heads=2 < tp=8 needs kv_size_multiplier=4."""
    cfg = LlamaConfig.tiny(num_heads=8, num_kv_heads=2, sequence_parallel=True,
                           remat="none", dtype=jnp.float32, param_dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, cfg.vocab_size)

    nxd.initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    params = model.init(jax.random.PRNGKey(1), ids)
    from flax import linen as nn

    raw = nn.unbox(params)
    logits_dense = np.asarray(jax.jit(lambda p, i: model.apply(p, i))(raw, ids))
    nxd.destroy_model_parallel()

    nxd.initialize_model_parallel(tensor_parallel_size=8, kv_size_multiplier=4, devices=devices8)
    from conftest import sharded_params

    p = sharded_params(params)
    logits_tp = np.asarray(jax.jit(lambda p, i: model.apply(p, i))(p, ids))
    np.testing.assert_allclose(logits_tp, logits_dense, rtol=5e-4, atol=5e-4)


def test_train_loop_tp_sp_zero1(devices8):
    """TP+SP+ZeRO-1 — loss must go down."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    config = nxd.training_config(tensor_parallel_size=2, learning_rate=1e-3,
                                 compute_dtype="float32")
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg), (jnp.zeros((1, 16), jnp.int32),)
    )
    opt = initialize_parallel_optimizer(config, model)
    step = make_train_step(
        config, model, opt, causal_lm_loss,
        batch_spec={"ids": default_batch_spec(), "labels": default_batch_spec()},
    )
    params, state = model.params, opt.state
    losses = []
    data_key = jax.random.PRNGKey(42)
    ids = jax.random.randint(data_key, (8, 16), 0, cfg.vocab_size)
    batch = {"ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    for i in range(8):
        params, state, m = step(params, state, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
        assert np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
    assert losses[-1] < losses[0] * 0.8, losses


@pytest.mark.parametrize("sp", [False, True], ids=["nosp", "sp"])
def test_chunked_loss_head_matches_unchunked(devices8, sp):
    """make_causal_lm_loss_sum(chunk_size) — the no-[B,S,V]-materialization
    loss head — must match the plain (loss_sum, tok) path in value AND
    gradients, incl. ignore-index masking (VERDICT r3 #1c)."""
    from neuronx_distributed_tpu.models import (
        causal_lm_loss_sum,
        make_causal_lm_loss_sum,
    )

    cfg = LlamaConfig.tiny(sequence_parallel=sp, remat="none",
                           dtype=jnp.float32, param_dtype=jnp.float32)
    config = nxd.training_config(tensor_parallel_size=2, learning_rate=1e-3,
                                 compute_dtype="float32")
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg), (jnp.zeros((1, 16), jnp.int32),)
    )
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, cfg.vocab_size)
    labels = np.asarray(jnp.roll(ids, -1, axis=1)).copy()
    labels[1, 5:] = -100  # uneven masking
    batch = {"ids": ids, "labels": jnp.asarray(labels)}

    chunked = make_causal_lm_loss_sum(chunk_size=8)  # 16 -> 2 chunks

    def total(fn):
        def f(p):
            s, t = fn(model.module, p, batch)
            return s / jnp.maximum(t, 1.0)
        return jax.jit(jax.value_and_grad(f))

    l_ref, g_ref = total(causal_lm_loss_sum)(model.params)
    l_chk, g_chk = total(chunked)(model.params)
    assert float(l_chk) == pytest.approx(float(l_ref), rel=1e-6)
    for (kp, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(g_ref)[0],
        jax.tree_util.tree_flatten_with_path(g_chk)[0],
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-5,
                                   atol=1e-7, err_msg=jax.tree_util.keystr(kp))

    # non-divisible chunk_size falls back to a divisor of S, still exact
    l_odd, _ = total(make_causal_lm_loss_sum(chunk_size=6))(model.params)
    assert float(l_odd) == pytest.approx(float(l_ref), rel=1e-6)


def test_chunked_loss_trains(devices8):
    """End-to-end: make_train_step with the chunked head, loss decreases."""
    from neuronx_distributed_tpu.models import make_causal_lm_loss_sum

    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    config = nxd.training_config(tensor_parallel_size=2, learning_rate=1e-3,
                                 compute_dtype="float32")
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg), (jnp.zeros((1, 16), jnp.int32),)
    )
    opt = initialize_parallel_optimizer(config, model)
    step = make_train_step(
        config, model, opt, make_causal_lm_loss_sum(chunk_size=8),
        batch_spec={"ids": default_batch_spec(), "labels": default_batch_spec()},
    )
    params, state = model.params, opt.state
    ids = jax.random.randint(jax.random.PRNGKey(42), (8, 16), 0, cfg.vocab_size)
    batch = {"ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    losses = []
    for i in range(8):
        params, state, m = step(params, state, batch, None)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8, losses


def test_remat_matches_no_remat(devices8):
    """selective/full remat must not change numerics."""
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    nxd.initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    from conftest import sharded_params

    outs = {}
    grads = {}
    for mode in ("none", "selective", "full"):
        cfg = LlamaConfig.tiny(remat=mode, dtype=jnp.float32, param_dtype=jnp.float32)
        model = LlamaForCausalLM(cfg)
        params = model.init(jax.random.PRNGKey(1), ids)
        p = sharded_params(params)

        @jax.jit
        def loss(p, ids):
            return jnp.mean(model.apply(p, ids).astype(jnp.float32) ** 2)

        outs[mode] = float(loss(p, ids))
        g = jax.jit(jax.grad(loss))(p, ids)
        grads[mode] = float(
            jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in jax.tree.leaves(g)))
        )
    assert outs["selective"] == pytest.approx(outs["none"], rel=1e-5)
    assert outs["full"] == pytest.approx(outs["none"], rel=1e-5)
    assert grads["selective"] == pytest.approx(grads["none"], rel=1e-4)
    assert grads["full"] == pytest.approx(grads["none"], rel=1e-4)


def test_packed_segment_ids_block_cross_document(devices8):
    """data.packing -> segment-id attention masking: a packed row must give
    each document exactly the logits it gets alone in its own row."""
    from neuronx_distributed_tpu.data.packing import pack_documents

    nxd.initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    cfg = LlamaConfig.tiny(sequence_parallel=False, remat="none",
                           dtype=jnp.float32, param_dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    from flax import linen as nn
    params = nn.unbox(params)

    doc_a = np.arange(1, 7)   # 6 tokens
    doc_b = np.arange(20, 27)  # 7 tokens
    ids, labels, segs = pack_documents([doc_a, doc_b], seq_len=16, eos_id=99)
    assert ids.shape == (1, 16)
    jids, jsegs = jnp.asarray(ids), jnp.asarray(segs)
    # positions restart per document (like the packer's framing)
    pos = jnp.asarray(np.concatenate([np.arange(7), np.arange(8), [0]])[None, :])

    packed = jax.jit(
        lambda p, i: model.apply(p, i, positions=pos, segment_ids=jsegs)
    )(params, jids)

    # doc B alone in its own (unpacked) row
    alone_ids = jnp.asarray(np.concatenate([doc_b, [99]])[None, :].astype(np.int32))
    alone = jax.jit(lambda p, i: model.apply(p, i))(params, alone_ids)
    np.testing.assert_allclose(
        np.asarray(packed[0, 7:15]), np.asarray(alone[0]), rtol=2e-4, atol=2e-4,
        err_msg="doc B's logits depend on doc A despite segment masking",
    )


def test_packed_training_via_loss_batch_keys(devices8):
    """causal_lm_loss forwards positions/segment_ids from the batch — packed
    pretraining works through the standard train step."""
    from neuronx_distributed_tpu.data.packing import pack_documents
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec, initialize_parallel_model,
        initialize_parallel_optimizer, make_train_step,
    )
    from neuronx_distributed_tpu.models.llama import causal_lm_loss

    nxd.initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    cfg = LlamaConfig.tiny(sequence_parallel=False, remat="none",
                           dtype=jnp.float32, param_dtype=jnp.float32)
    config = nxd.training_config(tensor_parallel_size=2, learning_rate=3e-3,
                                 compute_dtype="float32")
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg), (jnp.zeros((1, 16), jnp.int32),))
    opt = initialize_parallel_optimizer(config, model)
    spec = default_batch_spec()
    step = make_train_step(config, model, opt, causal_lm_loss,
                           batch_spec={"ids": spec, "labels": spec,
                                       "positions": spec, "segment_ids": spec})
    rngs = np.random.RandomState(0)
    docs = [rngs.randint(1, 200, size=rngs.randint(3, 12)) for _ in range(24)]
    ids, labels, segs = pack_documents(docs, seq_len=16, eos_id=255)
    n = (ids.shape[0] // 8) * 8
    assert n >= 8
    # per-document positions from segment boundaries
    pos = np.zeros_like(ids)
    for r in range(ids.shape[0]):
        c = 0
        for j in range(ids.shape[1]):
            if j and segs[r, j] != segs[r, j - 1]:
                c = 0
            pos[r, j] = c
            c += 1
    batch = {"ids": jnp.asarray(ids[:n]), "labels": jnp.asarray(labels[:n]),
             "positions": jnp.asarray(pos[:n]), "segment_ids": jnp.asarray(segs[:n])}
    params, state = model.params, opt.state
    losses = []
    for i in range(6):
        params, state, m = step(params, state, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.3, losses


def test_scan_layers_matches_unrolled(devices8):
    """lax.scan-over-layers (scan_layers=True) is the same function as the
    unrolled stack — logits parity on shared weights, and HF conversion
    handles the stacked layout."""
    import transformers
    import torch
    from neuronx_distributed_tpu.convert import llama_params_from_hf

    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=3,
        num_attention_heads=8, num_key_value_heads=2, max_position_embeddings=64,
        rms_norm_eps=1e-5, tie_word_embeddings=False)
    torch.manual_seed(7)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval().float()
    ids = jnp.asarray(torch.randint(0, 128, (2, 16)).numpy())

    nxd.initialize_model_parallel(tensor_parallel_size=2, devices=devices8)
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=3,
                num_heads=8, num_kv_heads=2, max_seq_len=64, rms_eps=1e-5,
                sequence_parallel=False, remat="none",
                dtype=jnp.float32, param_dtype=jnp.float32)
    cfg_u = LlamaConfig(**base)
    cfg_s = LlamaConfig(**base, scan_layers=True)
    p_u = jax.tree.map(jnp.asarray, llama_params_from_hf(hf.state_dict(), cfg_u))
    p_s = jax.tree.map(jnp.asarray, llama_params_from_hf(hf.state_dict(), cfg_s))
    # scanned tree carries one stacked [L, ...] subtree
    assert p_s["params"]["model"]["layers"]["attn"]["qkv"]["q_kernel"].shape[0] == 3

    out_u = jax.jit(lambda p, i: LlamaForCausalLM(cfg_u).apply(p, i))(p_u, ids)
    out_s = jax.jit(lambda p, i: LlamaForCausalLM(cfg_s).apply(p, i))(p_s, ids)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_u),
                               rtol=2e-5, atol=2e-5)

    # and it trains: init native scanned params, loss decreases
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec, initialize_parallel_model,
        initialize_parallel_optimizer, make_train_step)
    from neuronx_distributed_tpu.models.llama import causal_lm_loss

    config = nxd.training_config(tensor_parallel_size=2, learning_rate=3e-3,
                                 compute_dtype="float32")
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg_s), (jnp.zeros((1, 16), jnp.int32),))
    opt = initialize_parallel_optimizer(config, model)
    step = make_train_step(config, model, opt, causal_lm_loss,
                           batch_spec={"ids": default_batch_spec(),
                                       "labels": default_batch_spec()})
    data = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 128)
    batch = {"ids": data, "labels": jnp.roll(data, -1, 1)}
    params, state = model.params, opt.state
    losses = []
    for i in range(6):
        params, state, m = step(params, state, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses
