"""HF checkpoint interop (reference ``convert_checkpoints.py``): build tiny
HF models with transformers (random init, no network), convert their state
dicts, and assert logits parity against the HF torch forward on the 8-device
CPU mesh — the strongest possible correctness check for layout algebra
(transposes, fused axes, NeoX per-head interleave, GQA ordering, RoPE
conventions all verified at once)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import neuronx_distributed_tpu as nxd  # noqa: E402
from neuronx_distributed_tpu.convert import (  # noqa: E402
    bert_params_from_hf,
    bert_params_to_hf,
    gpt_neox_params_from_hf,
    gpt_neox_params_to_hf,
    llama_params_from_hf,
    llama_params_to_hf,
    olmoe_params_from_hf,
    olmoe_params_to_hf,
)


def _assert_logits_close(ours, theirs, rtol=2e-4, atol=2e-4):
    np.testing.assert_allclose(np.asarray(ours, np.float32), theirs, rtol=rtol, atol=atol)


def _roundtrip(sd, to_fw, to_hf, cfg):
    back = to_hf(to_fw(sd, cfg), cfg)
    for k, v in sd.items():
        if k.endswith("rotary_emb.inv_freq") or "position_ids" in k:
            continue
        got = back.get(k)
        assert got is not None, f"missing {k} after roundtrip"
        np.testing.assert_array_equal(got, v.detach().numpy(), err_msg=k)


def test_llama_gqa_logits_parity(devices8):
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
        num_attention_heads=8, num_key_value_heads=2, max_position_embeddings=64,
        rms_norm_eps=1e-5, rope_theta=10000.0, tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval().float()
    ids = torch.randint(0, 128, (2, 16))
    with torch.no_grad():
        want = hf(ids).logits.numpy()

    nxd.initialize_model_parallel(tensor_parallel_size=2, kv_size_multiplier=2)
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=8, num_kv_heads=2, max_seq_len=64, rms_eps=1e-5,
        sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = jax.tree.map(jnp.asarray, llama_params_from_hf(hf.state_dict(), cfg))
    model = LlamaForCausalLM(cfg)
    # lm_head is vocab-sharded (gather_output=False) but with full logits
    # materialized on the replicated output it equals the dense head
    got = jax.jit(lambda p, i: model.apply(p, i))(params, jnp.asarray(ids.numpy()))
    _assert_logits_close(got, want)

    _roundtrip(hf.state_dict(), llama_params_from_hf, llama_params_to_hf, cfg)


def test_gpt_neox_logits_parity(devices8):
    from neuronx_distributed_tpu.models.gpt_neox import GPTNeoXConfig, GPTNeoXForCausalLM

    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256, num_hidden_layers=2,
        num_attention_heads=8, max_position_embeddings=64, rotary_pct=0.25,
        rotary_emb_base=10000, use_parallel_residual=True, layer_norm_eps=1e-5,
        hidden_act="gelu",
    )
    torch.manual_seed(1)
    hf = transformers.GPTNeoXForCausalLM(hf_cfg).eval().float()
    ids = torch.randint(0, 128, (2, 16))
    with torch.no_grad():
        want = hf(ids).logits.numpy()

    nxd.initialize_model_parallel(tensor_parallel_size=2)
    cfg = GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256, num_layers=2,
        num_heads=8, max_seq_len=64, rotary_pct=0.25, rope_theta=10000.0,
        use_parallel_residual=True, ln_eps=1e-5, sequence_parallel=False,
        remat="none", dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = jax.tree.map(jnp.asarray, gpt_neox_params_from_hf(hf.state_dict(), cfg))
    model = GPTNeoXForCausalLM(cfg)
    got = jax.jit(lambda p, i: model.apply(p, i))(params, jnp.asarray(ids.numpy()))
    _assert_logits_close(got, want)

    _roundtrip(hf.state_dict(), gpt_neox_params_from_hf, gpt_neox_params_to_hf, cfg)


def test_bert_pretraining_logits_parity(devices8):
    from neuronx_distributed_tpu.models.bert import BertConfig, BertForPreTraining

    hf_cfg = transformers.BertConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=8, max_position_embeddings=64, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        layer_norm_eps=1e-12, hidden_act="gelu",
    )
    torch.manual_seed(2)
    hf = transformers.BertForPreTraining(hf_cfg).eval().float()
    ids = torch.randint(0, 128, (2, 16))
    with torch.no_grad():
        hf_out = hf(ids)
        want_mlm = hf_out.prediction_logits.numpy()
        want_nsp = hf_out.seq_relationship_logits.numpy()

    nxd.initialize_model_parallel(tensor_parallel_size=2)
    cfg = BertConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=8, max_position_embeddings=64, type_vocab_size=2,
        hidden_dropout=0.0, ln_eps=1e-12, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = jax.tree.map(jnp.asarray, bert_params_from_hf(hf.state_dict(), cfg))
    model = BertForPreTraining(cfg)
    mlm, nsp = jax.jit(lambda p, i: model.apply(p, i))(params, jnp.asarray(ids.numpy()))
    _assert_logits_close(mlm, want_mlm)
    _assert_logits_close(nsp, want_nsp)

    _roundtrip(hf.state_dict(), bert_params_from_hf, bert_params_to_hf, cfg)


def test_padded_heads_preserve_function(devices8):
    """Converted HF weights + head padding (pad.py) keep logits identical —
    the converter composes with vocab/head padding for indivisible TP."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from neuronx_distributed_tpu.parallel.pad import pad_llama_params

    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=48, intermediate_size=96, num_hidden_layers=2,
        num_attention_heads=6, num_key_value_heads=3, max_position_embeddings=64,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
    )
    torch.manual_seed(3)
    hf = transformers.LlamaForCausalLM(hf_cfg).eval().float()
    ids = torch.randint(0, 128, (2, 12))
    with torch.no_grad():
        want = hf(ids).logits.numpy()

    # 6 q / 3 kv heads don't divide tp=4: pad to 8 q / 4 kv (group size 2)
    nxd.initialize_model_parallel(tensor_parallel_size=4)
    cfg6 = LlamaConfig(
        vocab_size=128, hidden_size=48, intermediate_size=96, num_layers=2,
        num_heads=6, num_kv_heads=3, head_dim=8, max_seq_len=64, rms_eps=1e-5,
        sequence_parallel=False, remat="none", dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = jax.tree.map(jnp.asarray, llama_params_from_hf(hf.state_dict(), cfg6))
    padded = pad_llama_params(params, old_heads=6, new_heads=8, head_dim=8,
                              old_kv_heads=3, new_kv_heads=4)
    cfg8 = LlamaConfig(
        vocab_size=128, hidden_size=48, intermediate_size=96, num_layers=2,
        num_heads=8, num_kv_heads=4, head_dim=8, max_seq_len=64, rms_eps=1e-5,
        sequence_parallel=False, remat="none", dtype=jnp.float32, param_dtype=jnp.float32,
    )
    model = LlamaForCausalLM(cfg8)
    got = jax.jit(lambda p, i: model.apply(p, i))(padded, jnp.asarray(ids.numpy()))
    _assert_logits_close(got, want)


def test_pipelined_llama_checkpoint_exports(devices8):
    """A PP-trained (uneven-cuts, padded-stack) Llama checkpoint converts to
    the standard tree — dense logits match the pipelined forward — and on
    through to HF keys."""
    from neuronx_distributed_tpu.convert import (
        llama_params_from_pipelined, llama_params_to_hf,
    )
    from neuronx_distributed_tpu.models.llama import (
        LlamaConfig, LlamaForCausalLM, build_pipelined_llama,
    )

    nxd.initialize_model_parallel(tensor_parallel_size=2, pipeline_parallel_size=2,
                                  devices=devices8)
    cfg = LlamaConfig.tiny(num_layers=6, sequence_parallel=False, remat="none",
                           dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=16)
    pmodel = build_pipelined_llama(cfg, num_microbatches=2, seed=9, pipeline_cuts=(4,))
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, cfg.vocab_size)

    flat = llama_params_from_pipelined(pmodel.params, pmodel.layer_rows)
    dense_logits = jax.jit(LlamaForCausalLM(cfg).apply)(flat, ids)
    # pipelined forward on the same batch (hidden -> head happens inside)
    pp_logits = jax.jit(pmodel.forward_fn)(pmodel.params, ids)
    np.testing.assert_allclose(np.asarray(pp_logits), np.asarray(dense_logits),
                               rtol=2e-4, atol=2e-4)

    sd = llama_params_to_hf(flat, cfg)
    assert "model.layers.5.self_attn.q_proj.weight" in sd
    assert sd["lm_head.weight"].shape == (cfg.vocab_size, cfg.hidden_size)


def test_pipelined_neox_checkpoint_exports(devices8):
    from neuronx_distributed_tpu.convert import (
        gpt_neox_params_from_pipelined, gpt_neox_params_to_hf,
    )
    from neuronx_distributed_tpu.models.gpt_neox import (
        GPTNeoXConfig, GPTNeoXForCausalLM, build_pipelined_gpt_neox,
    )

    nxd.initialize_model_parallel(tensor_parallel_size=2, pipeline_parallel_size=2,
                                  devices=devices8)
    cfg = GPTNeoXConfig.tiny(num_layers=4, sequence_parallel=False, remat="none",
                             dtype=jnp.float32, param_dtype=jnp.float32, max_seq_len=16)
    pmodel = build_pipelined_gpt_neox(cfg, num_microbatches=2, seed=9)
    ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, cfg.vocab_size)

    flat = gpt_neox_params_from_pipelined(pmodel.params, pmodel.layer_rows)
    dense_logits = jax.jit(GPTNeoXForCausalLM(cfg).apply)(flat, ids)
    pp_logits = jax.jit(pmodel.forward_fn)(pmodel.params, ids)
    np.testing.assert_allclose(np.asarray(pp_logits), np.asarray(dense_logits),
                               rtol=2e-4, atol=2e-4)
    sd = gpt_neox_params_to_hf(flat, cfg)
    assert any(k.startswith("gpt_neox.layers.3.") for k in sd)


def test_qwen2_logits_parity(devices8):
    """Qwen2 = Llama + QKV biases: HF Qwen2 logits parity through the same
    converter (qkv_bias drives the bias import/export), plus roundtrip."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=2,
        num_attention_heads=8, num_key_value_heads=2, max_position_embeddings=64,
        rms_norm_eps=1e-6, rope_theta=10000.0, tie_word_embeddings=False,
        attention_dropout=0.0, use_sliding_window=False,
    )
    torch.manual_seed(0)
    hf = transformers.Qwen2ForCausalLM(hf_cfg).eval().float()
    ids = torch.randint(0, 128, (2, 16))
    with torch.no_grad():
        want = hf(ids).logits.numpy()

    nxd.initialize_model_parallel(tensor_parallel_size=2, kv_size_multiplier=2)
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=8, num_kv_heads=2, max_seq_len=64, rms_eps=1e-6,
        qkv_bias=True, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    params = jax.tree.map(jnp.asarray, llama_params_from_hf(hf.state_dict(), cfg))
    model = LlamaForCausalLM(cfg)
    got = jax.jit(lambda p, i: model.apply(p, i))(params, jnp.asarray(ids.numpy()))
    _assert_logits_close(got, want)

    _roundtrip(hf.state_dict(), llama_params_from_hf, llama_params_to_hf, cfg)


def test_qwen2_preset_shapes():
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.qwen2_7b()
    assert cfg.qkv_bias and cfg.num_kv_heads == 4 and cfg.vocab_size == 152064


def test_qwen2_bias_checkpoint_requires_flag(devices8):
    """Converting a biased (Qwen2) checkpoint with qkv_bias=False must fail
    loudly, not silently zero the biases."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=1,
        num_attention_heads=8, num_key_value_heads=2, max_position_embeddings=64,
        tie_word_embeddings=False,
    )
    hf = transformers.Qwen2ForCausalLM(hf_cfg).eval().float()
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=96,
                      num_layers=1, num_heads=8, num_kv_heads=2, max_seq_len=64,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    with pytest.raises(ValueError, match="qkv_bias"):
        llama_params_from_hf(hf.state_dict(), cfg)


def test_olmoe_logits_parity_and_roundtrip(devices8):
    """OLMoE = the Llama layout + full-width q/k RMSNorm + a dropless
    top-k-of-E expert block with gates NOT renormalised: HF
    ``OlmoeForCausalLM`` logits through ``olmoe_params_from_hf``, and the
    state dict back bit for bit."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    hf_cfg = transformers.OlmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        num_experts=8, num_experts_per_tok=3, norm_topk_prob=False,
        tie_word_embeddings=False, attention_dropout=0.0, clip_qkv=None,
    )
    torch.manual_seed(0)
    hf = transformers.OlmoeForCausalLM(hf_cfg).eval().float()
    with torch.no_grad():  # norm weights of ones would hide a missing norm
        for name, w in hf.named_parameters():
            if name.endswith("norm.weight"):
                w.add_(0.3 * torch.randn_like(w))
    ids = torch.randint(0, 128, (2, 16))
    with torch.no_grad():
        want = hf(ids).logits.numpy()

    nxd.initialize_model_parallel(tensor_parallel_size=1)
    cfg = LlamaConfig.olmoe_1b_7b(
        vocab_size=128, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=4, max_seq_len=64, num_experts=8,
        moe_top_k=3, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32)
    params = jax.tree.map(jnp.asarray, olmoe_params_from_hf(hf.state_dict(), cfg))
    model = LlamaForCausalLM(cfg)
    got = jax.jit(lambda p, i: model.apply(p, i))(params, jnp.asarray(ids.numpy()))
    _assert_logits_close(got, want)

    _roundtrip(hf.state_dict(), olmoe_params_from_hf, olmoe_params_to_hf, cfg)
