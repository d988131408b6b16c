"""HuggingFace ↔ framework weight converters for the three model families.

The reference ships a script-level HF↔NxD checkpoint converter
(``examples/training/llama2/convert_checkpoints.py``); here conversion is a
library function over plain numpy state dicts, because the interesting work
is *layout algebra*, not IO:

- torch ``nn.Linear`` stores ``weight [out, in]``; flax kernels are
  ``[in, out]`` → transpose everywhere;
- fused projections: the framework's ``n_fused`` kernels carry an explicit
  fused axis ``[in, F, out/F]`` (``parallel/layers.py``), Llama's GQA module
  stores per-head kernels ``[in, n_heads, head_dim]`` (``parallel/qkv.py``);
- **GPT-NeoX's QKV is interleaved per head** (HF rows ordered
  ``[head0-q, head0-k, head0-v, head1-q, ...]``) while the framework uses a
  clean fused axis — the converter de-interleaves with a reshape/transpose;
- GQA q-head ordering: both HF Llama and the framework index q-head ``h``'s
  kv head as ``h // (NQ/NKV)``, so no head permutation is needed — the
  framework's "kv-major" property lives in the *sharding spec*
  (``Q_HEAD_AXES``), not the data layout;
- head/vocab padding for indivisible TP degrees is applied AFTER conversion
  via :func:`..parallel.pad.pad_llama_params` (zero-padded heads are
  function-preserving by construction).

All functions take/return flat ``{hf_key: np.ndarray}`` dicts on the HF side
(what ``model.state_dict()`` or a safetensors file yields) and nested flax
param trees (the ``{"params": ...}`` dict) on the framework side.  Arrays
are numpy on output — shard placement happens downstream via
``jax.device_put`` with the model's param shardings.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import numpy as np

from neuronx_distributed_tpu.obs import startup


def _np(x) -> np.ndarray:
    if hasattr(x, "detach"):  # torch tensor
        x = x.detach().cpu().numpy()
    return np.asarray(x)


# ---------------------------------------------------------------------------
# Llama
# ---------------------------------------------------------------------------


def llama_stack_layers(params: Mapping[str, Any], num_layers: int) -> Dict[str, Any]:
    """Per-layer tree (``model.layer_i...``) → scanned layout
    (``model.layers...`` with leading ``[L]`` axes) for
    ``LlamaConfig(scan_layers=True)`` models."""
    tree = params.get("params", params)
    model = dict(tree["model"])
    layers = [model.pop(f"layer_{i}") for i in range(num_layers)]
    model["layers"] = jax.tree.map(lambda *xs: np.stack([_np(x) for x in xs]), *layers)
    out = dict(tree)
    out["model"] = model
    return {"params": out} if "params" in params else out


def llama_unstack_layers(params: Mapping[str, Any], num_layers: int) -> Dict[str, Any]:
    """Inverse of :func:`llama_stack_layers`."""
    tree = params.get("params", params)
    model = dict(tree["model"])
    stacked = model.pop("layers")
    for i in range(num_layers):
        model[f"layer_{i}"] = jax.tree.map(lambda x, i=i: _np(x)[i], stacked)
    out = dict(tree)
    out["model"] = model
    return {"params": out} if "params" in params else out


def _decoder_layer_from_hf(sd: Mapping[str, np.ndarray], p: str, cfg,
                           norm_offset: float = 0.0) -> Dict[str, Any]:
    """One HF Llama-layout decoder layer (prefix ``p``) → the shared
    ``LlamaBlock`` param subtree.  ``norm_offset`` folds Gemma's ``(1+w)``
    RMSNorm convention into the stored weight."""
    H, D = cfg.hidden_size, cfg.head_dim_
    NQ, NKV = cfg.num_heads, cfg.num_kv_heads
    qkv = {
        "q_kernel": sd[p + "self_attn.q_proj.weight"].T.reshape(H, NQ, D),
        "k_kernel": sd[p + "self_attn.k_proj.weight"].T.reshape(H, NKV, D),
        "v_kernel": sd[p + "self_attn.v_proj.weight"].T.reshape(H, NKV, D),
    }
    if getattr(cfg, "qkv_bias", False):
        # Qwen2: biased q/k/v projections
        qkv["q_bias"] = sd[p + "self_attn.q_proj.bias"].reshape(NQ, D)
        qkv["k_bias"] = sd[p + "self_attn.k_proj.bias"].reshape(NKV, D)
        qkv["v_bias"] = sd[p + "self_attn.v_proj.bias"].reshape(NKV, D)
    elif p + "self_attn.q_proj.bias" in sd:
        raise ValueError(
            "HF checkpoint carries QKV biases (Qwen2-style) but the "
            "config has qkv_bias=False — converting would silently zero "
            "them; build the config with qkv_bias=True"
        )
    return {
        "attn": {
            "qkv": qkv,
            "o_proj": {"kernel": sd[p + "self_attn.o_proj.weight"].T},
        },
        "mlp": {
            "gate_up": {
                "kernel": np.stack(
                    [sd[p + "mlp.gate_proj.weight"].T, sd[p + "mlp.up_proj.weight"].T],
                    axis=1,
                )  # [H, 2, I]
            },
            "down": {"kernel": sd[p + "mlp.down_proj.weight"].T},
        },
        "input_norm": {"weight": sd[p + "input_layernorm.weight"] + norm_offset},
        "post_attn_norm": {"weight": sd[p + "post_attention_layernorm.weight"] + norm_offset},
    }


@startup.phased("weights")
def llama_params_from_hf(state_dict: Mapping[str, Any], cfg) -> Dict[str, Any]:
    """HF ``LlamaForCausalLM.state_dict()`` → framework param tree for
    :class:`~..models.llama.LlamaForCausalLM` with config ``cfg`` (scanned
    layout when ``cfg.scan_layers``)."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    model: Dict[str, Any] = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": {"weight": sd["model.norm.weight"]},
    }
    for i in range(cfg.num_layers):
        model[f"layer_{i}"] = _decoder_layer_from_hf(sd, f"model.layers.{i}.", cfg)
    lm_head = sd.get("lm_head.weight")
    if lm_head is None:  # tied-embedding HF checkpoints omit it
        lm_head = sd["model.embed_tokens.weight"]
    out = {"params": {"model": model, "lm_head": {"kernel": lm_head.T}}}
    if getattr(cfg, "scan_layers", False):
        out = llama_stack_layers(out, cfg.num_layers)
    return out


def llama_params_to_hf(params: Mapping[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`llama_params_from_hf` (framework → HF state dict)."""
    if getattr(cfg, "scan_layers", False):
        params = llama_unstack_layers(params, cfg.num_layers)
    tree = params.get("params", params)
    model, head = tree["model"], tree["lm_head"]
    H = cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": _np(model["embed"]["embedding"]),
        "model.norm.weight": _np(model["final_norm"]["weight"]),
        "lm_head.weight": _np(head["kernel"]).T,
    }
    for i in range(cfg.num_layers):
        lyr = model[f"layer_{i}"]
        p = f"model.layers.{i}."
        qkv = lyr["attn"]["qkv"]
        gu = _np(lyr["mlp"]["gate_up"]["kernel"])  # [H, 2, I]
        out.update({
            p + "self_attn.q_proj.weight": _np(qkv["q_kernel"]).reshape(H, -1).T,
            p + "self_attn.k_proj.weight": _np(qkv["k_kernel"]).reshape(H, -1).T,
            p + "self_attn.v_proj.weight": _np(qkv["v_kernel"]).reshape(H, -1).T,
            p + "self_attn.o_proj.weight": _np(lyr["attn"]["o_proj"]["kernel"]).T,
            p + "mlp.gate_proj.weight": gu[:, 0, :].T,
            p + "mlp.up_proj.weight": gu[:, 1, :].T,
            p + "mlp.down_proj.weight": _np(lyr["mlp"]["down"]["kernel"]).T,
            p + "input_layernorm.weight": _np(lyr["input_norm"]["weight"]),
            p + "post_attention_layernorm.weight": _np(lyr["post_attn_norm"]["weight"]),
        })
        if "q_bias" in qkv:  # Qwen2 biased projections
            out.update({
                p + "self_attn.q_proj.bias": _np(qkv["q_bias"]).reshape(-1),
                p + "self_attn.k_proj.bias": _np(qkv["k_bias"]).reshape(-1),
                p + "self_attn.v_proj.bias": _np(qkv["v_bias"]).reshape(-1),
            })
    return out


# ---------------------------------------------------------------------------
# OLMoE (Llama layout + q/k norms + a routed expert block per layer)
# ---------------------------------------------------------------------------


@startup.phased("weights")
def olmoe_params_from_hf(state_dict: Mapping[str, Any], cfg) -> Dict[str, Any]:
    """HF ``OlmoeForCausalLM.state_dict()`` -> the param tree of
    :class:`~..models.llama.LlamaForCausalLM` under an OLMoE config
    (``qk_norm``, ``num_experts`` > 1): ``mlp.gate.weight [E, H]`` becomes
    ``moe_mlp/router [H, E]``, the per-expert ``experts.{i}.{gate,up,
    down}_proj`` the stacked ``gate [E, H, I]``, ``up [E, H, I]`` (the
    dropless layout, ``parallel/moe.py``) and ``down [E, I, H]``,
    ``self_attn.{q,k}_norm`` the full-width norms."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    H, D, E = cfg.hidden_size, cfg.head_dim_, cfg.num_experts
    model: Dict[str, Any] = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": {"weight": sd["model.norm.weight"]},
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        ex = p + "mlp.experts."
        model[f"layer_{i}"] = {
            "attn": {
                "qkv": {
                    "q_kernel": sd[p + "self_attn.q_proj.weight"].T.reshape(
                        H, cfg.num_heads, D),
                    "k_kernel": sd[p + "self_attn.k_proj.weight"].T.reshape(
                        H, cfg.num_kv_heads, D),
                    "v_kernel": sd[p + "self_attn.v_proj.weight"].T.reshape(
                        H, cfg.num_kv_heads, D),
                },
                "q_norm": {"weight": sd[p + "self_attn.q_norm.weight"]},
                "k_norm": {"weight": sd[p + "self_attn.k_norm.weight"]},
                "o_proj": {"kernel": sd[p + "self_attn.o_proj.weight"].T},
            },
            "moe_mlp": {
                "router": sd[p + "mlp.gate.weight"].T,
                "gate": np.stack([sd[f"{ex}{e}.gate_proj.weight"].T
                                  for e in range(E)]),
                "up": np.stack([sd[f"{ex}{e}.up_proj.weight"].T
                                for e in range(E)]),
                "down": np.stack([sd[f"{ex}{e}.down_proj.weight"].T
                                  for e in range(E)]),
            },
            "input_norm": {"weight": sd[p + "input_layernorm.weight"]},
            "post_attn_norm": {
                "weight": sd[p + "post_attention_layernorm.weight"]},
        }
    return {"params": {"model": model,
                       "lm_head": {"kernel": sd["lm_head.weight"].T}}}


def olmoe_params_to_hf(params: Mapping[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`olmoe_params_from_hf`."""
    tree = params.get("params", params)
    model, H = tree["model"], cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": _np(model["embed"]["embedding"]),
        "model.norm.weight": _np(model["final_norm"]["weight"]),
        "lm_head.weight": _np(tree["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.num_layers):
        lyr, p = model[f"layer_{i}"], f"model.layers.{i}."
        attn, moe = lyr["attn"], lyr["moe_mlp"]
        gate, up, down = (_np(moe[k]) for k in ("gate", "up", "down"))
        out.update({
            p + "self_attn.q_proj.weight": _np(attn["qkv"]["q_kernel"]).reshape(H, -1).T,
            p + "self_attn.k_proj.weight": _np(attn["qkv"]["k_kernel"]).reshape(H, -1).T,
            p + "self_attn.v_proj.weight": _np(attn["qkv"]["v_kernel"]).reshape(H, -1).T,
            p + "self_attn.o_proj.weight": _np(attn["o_proj"]["kernel"]).T,
            p + "self_attn.q_norm.weight": _np(attn["q_norm"]["weight"]),
            p + "self_attn.k_norm.weight": _np(attn["k_norm"]["weight"]),
            p + "mlp.gate.weight": _np(moe["router"]).T,
            p + "input_layernorm.weight": _np(lyr["input_norm"]["weight"]),
            p + "post_attention_layernorm.weight": _np(lyr["post_attn_norm"]["weight"]),
        })
        for e in range(cfg.num_experts):
            ex = f"{p}mlp.experts.{e}."
            out[ex + "gate_proj.weight"] = gate[e].T
            out[ex + "up_proj.weight"] = up[e].T
            out[ex + "down_proj.weight"] = down[e].T
    return out


# ---------------------------------------------------------------------------
# MiniCPM-SALA (Llama layout + a layer list: per-head q/k norms, output
# gates, an output norm on the lightning layers, muP scalars)
# ---------------------------------------------------------------------------

# the attention sub-module's tensors beside q/k/v/o_proj, by the name this
# package gives them.  The published ``modeling_minicpm_sala.py`` could not
# be read where this was written: the right-hand names are the family's
# convention (MiniCPM4 / Qwen3 ``q_norm`` / ``k_norm``; an ``o_gate``
# projection; ``o_norm`` on the lightning layers) and are ASSUMED — a real
# checkpoint that names them otherwise changes this one table.
MINICPM_SALA_ATTN_NAMES = {
    ("q_norm", "weight"): "self_attn.q_norm.weight",
    ("k_norm", "weight"): "self_attn.k_norm.weight",
    ("gate", "kernel"): "self_attn.o_gate.weight",
    ("out_norm", "weight"): "self_attn.o_norm.weight",   # lightning-attn only
}


def minicpm_sala_config_from_hf(hf_config: Mapping[str, Any], **overrides):
    """The published ``config.json`` of a ``minicpm_sala`` model -> a
    :class:`~..models.llama.LlamaConfig`: ``mixer_types`` as the layer list,
    the muP scalars (``scale_emb`` -> ``embed_scale``, ``scale_depth /
    sqrt(num_hidden_layers)`` -> ``residual_scale``, ``dim_model_base /
    hidden_size`` -> ``logit_scale``), the lightning head geometry.  The
    ``sparse_config`` keys (``kernel_size``, ``kernel_stride``,
    ``block_size``, ``init_blocks``, ``window_size``, ``topk``,
    ``dense_len``) are read where the config has them and keep MiniCPM4's
    values where it does not."""
    import math

    from neuronx_distributed_tpu.models.llama import LlamaConfig

    c = dict(hf_config)
    layers = int(c["num_hidden_layers"])
    sparse = {f"sparse_{k}": int(v)
              for k, v in dict(c.get("sparse_config") or {}).items()
              if k in ("kernel_size", "kernel_stride", "block_size",
                       "init_blocks", "window_size", "topk", "dense_len")}
    return LlamaConfig(**{**dict(
        vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
        intermediate_size=int(c["intermediate_size"]), num_layers=layers,
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        max_seq_len=int(c["max_position_embeddings"]),
        mixer_types=tuple(c["mixer_types"]),
        embed_scale=float(c["scale_emb"]),
        residual_scale=float(c["scale_depth"]) / math.sqrt(layers),
        logit_scale=float(c["dim_model_base"]) / float(c["hidden_size"]),
        lightning_heads=int(c["lightning_nh"]),
        lightning_head_dim=int(c["lightning_head_dim"])),
        **sparse, **overrides})


def _sala_layer_dims(cfg, i):
    from neuronx_distributed_tpu.models.hybrid import lightning_dims

    if cfg.mixer(i) == "lightning-attn":
        nh, d = lightning_dims(cfg)
        return nh, nh, d
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_


@startup.phased("weights")
def minicpm_sala_params_from_hf(state_dict: Mapping[str, Any], cfg
                                ) -> Dict[str, Any]:
    """A ``minicpm_sala`` state dict -> the param tree of
    :class:`~..models.llama.LlamaForCausalLM` under a config with
    ``mixer_types`` (``models/hybrid.py``): the Llama layout, q/k/v shaped by
    each layer's own head geometry, and the tensors of
    :data:`MINICPM_SALA_ATTN_NAMES`."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    H = cfg.hidden_size
    model: Dict[str, Any] = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": {"weight": sd["model.norm.weight"]},
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        nq, nkv, d = _sala_layer_dims(cfg, i)
        attn: Dict[str, Any] = {
            "qkv": {
                "q_kernel": sd[p + "self_attn.q_proj.weight"].T.reshape(H, nq, d),
                "k_kernel": sd[p + "self_attn.k_proj.weight"].T.reshape(H, nkv, d),
                "v_kernel": sd[p + "self_attn.v_proj.weight"].T.reshape(H, nkv, d),
            },
            "o_proj": {"kernel": sd[p + "self_attn.o_proj.weight"].T},
        }
        for (mod, leaf), name in MINICPM_SALA_ATTN_NAMES.items():
            if mod == "out_norm" and cfg.mixer(i) != "lightning-attn":
                continue
            w = sd[p + name]
            attn.setdefault(mod, {})[leaf] = w.T if leaf == "kernel" else w
        model[f"layer_{i}"] = {
            "attn": attn,
            "mlp": {
                "gate_up": {"kernel": np.stack(
                    [sd[p + "mlp.gate_proj.weight"].T,
                     sd[p + "mlp.up_proj.weight"].T], axis=1)},
                "down": {"kernel": sd[p + "mlp.down_proj.weight"].T},
            },
            "input_norm": {"weight": sd[p + "input_layernorm.weight"]},
            "post_attn_norm": {
                "weight": sd[p + "post_attention_layernorm.weight"]},
        }
    return {"params": {"model": model,
                       "lm_head": {"kernel": sd["lm_head.weight"].T}}}


def minicpm_sala_params_to_hf(params: Mapping[str, Any], cfg
                              ) -> Dict[str, np.ndarray]:
    """Inverse of :func:`minicpm_sala_params_from_hf`."""
    tree = params.get("params", params)
    model, H = tree["model"], cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": _np(model["embed"]["embedding"]),
        "model.norm.weight": _np(model["final_norm"]["weight"]),
        "lm_head.weight": _np(tree["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.num_layers):
        lyr, p = model[f"layer_{i}"], f"model.layers.{i}."
        attn = lyr["attn"]
        gu = _np(lyr["mlp"]["gate_up"]["kernel"])
        out.update({
            p + "self_attn.q_proj.weight": _np(attn["qkv"]["q_kernel"]).reshape(H, -1).T,
            p + "self_attn.k_proj.weight": _np(attn["qkv"]["k_kernel"]).reshape(H, -1).T,
            p + "self_attn.v_proj.weight": _np(attn["qkv"]["v_kernel"]).reshape(H, -1).T,
            p + "self_attn.o_proj.weight": _np(attn["o_proj"]["kernel"]).T,
            p + "mlp.gate_proj.weight": gu[:, 0, :].T,
            p + "mlp.up_proj.weight": gu[:, 1, :].T,
            p + "mlp.down_proj.weight": _np(lyr["mlp"]["down"]["kernel"]).T,
            p + "input_layernorm.weight": _np(lyr["input_norm"]["weight"]),
            p + "post_attention_layernorm.weight": _np(lyr["post_attn_norm"]["weight"]),
        })
        for (mod, leaf), name in MINICPM_SALA_ATTN_NAMES.items():
            if mod in attn:
                w = _np(attn[mod][leaf])
                out[p + name] = w.T if leaf == "kernel" else w
    return out


# ---------------------------------------------------------------------------
# Nemotron-H (``nemotron_h``: Mamba-2 / attention / routed-expert layers,
# one sublayer each)
# ---------------------------------------------------------------------------

# The tensor names of ``modeling_nemotron_h.py`` as this file ASSUMES them
# (no checkpoint was read here): ``backbone.embeddings``, ``backbone.norm_f``,
# ``lm_head``; a layer ``backbone.layers.{i}`` holds ``norm`` and ``mixer``,
# whose tensors depend on the layer's character of
# ``hybrid_override_pattern``:
NEMOTRON_H_MIXER_NAMES = {
    "M": ("in_proj.weight", "conv1d.weight", "conv1d.bias", "dt_bias",
          "A_log", "D", "norm.weight", "out_proj.weight"),
    "*": ("q_proj.weight", "k_proj.weight", "v_proj.weight",
          "o_proj.weight"),
    "E": ("gate.weight", "gate.e_score_correction_bias",
          "experts.{e}.up_proj.weight", "experts.{e}.down_proj.weight",
          "shared_experts.up_proj.weight", "shared_experts.down_proj.weight"),
}
_NEMOTRON_H_KINDS = {"M": ("mamba2", "none"), "*": ("attention", "none"),
                     "E": ("none", "moe"), "-": ("none", "mlp")}


def nemotron_h_config_from_hf(hf_config: Mapping[str, Any], **overrides):
    """The published ``config.json`` of a ``nemotron_h`` model -> a
    :class:`~..models.llama.LlamaConfig`: ``hybrid_override_pattern`` as the
    two layer lists, the Mamba-2 geometry, the sigmoid router with its
    correction bias and scale, relu2 experts and the shared expert, attention
    without RoPE.  ``moe_experts_held=(first, count)`` (an override) makes it
    one expert-parallel rank's share."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    c = dict(hf_config)
    pattern = str(c["hybrid_override_pattern"])
    if len(pattern) != int(c["num_hidden_layers"]) or "-" in pattern:
        raise ValueError(
            "hybrid_override_pattern names M, E or * for each of the "
            f"num_hidden_layers layers (dense '-' layers are not carried), "
            f"got {pattern!r}")
    return LlamaConfig(**{**dict(
        vocab_size=int(c["vocab_size"]), hidden_size=int(c["hidden_size"]),
        intermediate_size=int(c["moe_intermediate_size"]),
        num_layers=len(pattern), num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["layer_norm_epsilon"]),
        max_seq_len=int(c["max_position_embeddings"]), attn_rope=False,
        mixer_types=tuple(_NEMOTRON_H_KINDS[k][0] for k in pattern),
        ffn_types=tuple(_NEMOTRON_H_KINDS[k][1] for k in pattern),
        ssm_heads=int(c["mamba_num_heads"]),
        ssm_head_dim=int(c["mamba_head_dim"]), ssm_groups=int(c["n_groups"]),
        ssm_state_size=int(c["ssm_state_size"]),
        ssm_conv_kernel=int(c["conv_kernel"]),
        ssm_chunk_rows=int(c["chunk_size"]),
        ssm_dt_min=float(c["time_step_min"]),
        ssm_dt_max=float(c["time_step_max"]),
        ssm_dt_floor=float(c["time_step_floor"]),
        num_experts=int(c["n_routed_experts"]),
        moe_top_k=int(c["num_experts_per_tok"]), moe_dispatch="dropless",
        moe_norm_topk_prob=bool(c["norm_topk_prob"]),
        moe_router_scores="sigmoid", moe_router_bias=True,
        moe_route_scale=float(c["routed_scaling_factor"]),
        mlp_activation=str(c["mlp_hidden_act"]),
        moe_shared_intermediate_size=int(
            c["moe_shared_expert_intermediate_size"]),
        moe_n_group=int(c.get("n_group", 1)),
        moe_topk_group=int(c.get("topk_group", 1))), **overrides})


def _nemotron_h_held(cfg):
    return cfg.moe_experts_held or (0, cfg.num_experts)


@startup.phased("weights")
def nemotron_h_params_from_hf(state_dict: Mapping[str, Any], cfg
                              ) -> Dict[str, Any]:
    """A ``nemotron_h`` state dict (:data:`NEMOTRON_H_MIXER_NAMES`) -> the
    param tree of :class:`~..models.llama.LlamaForCausalLM` under the layer
    lists of :func:`nemotron_h_config_from_hf`: a Mamba layer's convolution
    ``[C, 1, K]`` as ``conv_weight [K, C]``, a routed layer's held experts
    stacked (``up [held, F, H]`` as a Linear stores each, ``down [held, F,
    H]``), everything else transposed in-major."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    H = cfg.hidden_size
    first, count = _nemotron_h_held(cfg)
    model: Dict[str, Any] = {
        "embed": {"embedding": sd["backbone.embeddings.weight"]},
        "final_norm": {"weight": sd["backbone.norm_f.weight"]},
    }
    for i in range(cfg.num_layers):
        p = f"backbone.layers.{i}."
        m = p + "mixer."
        norm = {"weight": sd[p + "norm.weight"]}
        if cfg.mixer(i) == "mamba2":
            model[f"layer_{i}"] = {"input_norm": norm, "attn": {
                "in_proj": {"kernel": sd[m + "in_proj.weight"].T},
                "conv_weight": sd[m + "conv1d.weight"][:, 0, :].T,
                "conv_bias": sd[m + "conv1d.bias"],
                "dt_bias": sd[m + "dt_bias"], "A_log": sd[m + "A_log"],
                "D": sd[m + "D"], "norm_weight": sd[m + "norm.weight"],
                "out_proj": {"kernel": sd[m + "out_proj.weight"].T}}}
        elif cfg.mixer(i) == "attention":
            nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
            model[f"layer_{i}"] = {"input_norm": norm, "attn": {
                "qkv": {
                    "q_kernel": sd[m + "q_proj.weight"].T.reshape(H, nq, d),
                    "k_kernel": sd[m + "k_proj.weight"].T.reshape(H, nkv, d),
                    "v_kernel": sd[m + "v_proj.weight"].T.reshape(H, nkv, d)},
                "o_proj": {"kernel": sd[m + "o_proj.weight"].T}}}
        else:
            experts = range(first, first + count)
            model[f"layer_{i}"] = {"post_attn_norm": norm, "moe_mlp": {
                "router": sd[m + "gate.weight"].T,
                "router_bias": sd[m + "gate.e_score_correction_bias"],
                "up": np.stack([sd[m + f"experts.{e}.up_proj.weight"]
                                for e in experts]),
                "down": np.stack([sd[m + f"experts.{e}.down_proj.weight"].T
                                  for e in experts]),
                "shared_up": {
                    "kernel": sd[m + "shared_experts.up_proj.weight"].T},
                "shared_down": {
                    "kernel": sd[m + "shared_experts.down_proj.weight"].T}}}
    return {"params": {"model": model,
                       "lm_head": {"kernel": sd["lm_head.weight"].T}}}


def nemotron_h_params_to_hf(params: Mapping[str, Any], cfg
                            ) -> Dict[str, np.ndarray]:
    """Inverse of :func:`nemotron_h_params_from_hf` (the held experts under
    their own numbers among the routed ones)."""
    tree = params.get("params", params)
    model, H = tree["model"], cfg.hidden_size
    first, count = _nemotron_h_held(cfg)
    out: Dict[str, np.ndarray] = {
        "backbone.embeddings.weight": _np(model["embed"]["embedding"]),
        "backbone.norm_f.weight": _np(model["final_norm"]["weight"]),
        "lm_head.weight": _np(tree["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.num_layers):
        lyr, p = model[f"layer_{i}"], f"backbone.layers.{i}."
        m = p + "mixer."
        out[p + "norm.weight"] = _np(
            lyr.get("input_norm", lyr.get("post_attn_norm"))["weight"])
        if cfg.mixer(i) == "mamba2":
            a = lyr["attn"]
            out.update({
                m + "in_proj.weight": _np(a["in_proj"]["kernel"]).T,
                m + "conv1d.weight": _np(a["conv_weight"]).T[:, None, :],
                m + "conv1d.bias": _np(a["conv_bias"]),
                m + "dt_bias": _np(a["dt_bias"]), m + "A_log": _np(a["A_log"]),
                m + "D": _np(a["D"]), m + "norm.weight": _np(a["norm_weight"]),
                m + "out_proj.weight": _np(a["out_proj"]["kernel"]).T})
        elif cfg.mixer(i) == "attention":
            a = lyr["attn"]
            for name, key in (("q_proj", "q_kernel"), ("k_proj", "k_kernel"),
                              ("v_proj", "v_kernel")):
                out[m + name + ".weight"] = _np(a["qkv"][key]).reshape(H, -1).T
            out[m + "o_proj.weight"] = _np(a["o_proj"]["kernel"]).T
        else:
            moe = lyr["moe_mlp"]
            out[m + "gate.weight"] = _np(moe["router"]).T
            out[m + "gate.e_score_correction_bias"] = _np(moe["router_bias"])
            up, down = _np(moe["up"]), _np(moe["down"])
            for n in range(count):
                out[m + f"experts.{first + n}.up_proj.weight"] = up[n]
                out[m + f"experts.{first + n}.down_proj.weight"] = down[n].T
            out[m + "shared_experts.up_proj.weight"] = _np(
                moe["shared_up"]["kernel"]).T
            out[m + "shared_experts.down_proj.weight"] = _np(
                moe["shared_down"]["kernel"]).T
    return out


# ---------------------------------------------------------------------------
# GPT-NeoX
# ---------------------------------------------------------------------------


# -- Xing4.0 (``model_type`` ``xing4_0``) ----------------------------------------
#
# The source's tensor names are ASSUMED (no checkpoint index is at hand): the
# DeepSeek-V2/V3 names for latent attention and the routed block, which the
# config's keys follow letter for letter, and ``attn_hc`` / ``ffn_hc`` for the
# two sublayers' stream-mixing maps, which no published checkpoint names yet.
XING4_NAMES = {
    "attention": ("self_attn.q_a_proj.weight", "self_attn.q_a_layernorm.weight",
                  "self_attn.q_b_proj.weight",
                  "self_attn.kv_a_proj_with_mqa.weight",
                  "self_attn.kv_a_layernorm.weight",
                  "self_attn.kv_b_proj.weight", "self_attn.o_proj.weight"),
    "dense": ("mlp.gate_proj.weight", "mlp.up_proj.weight",
              "mlp.down_proj.weight"),
    "routed": ("mlp.gate.weight", "mlp.gate.e_score_correction_bias",
               "mlp.experts.{e}.gate_proj.weight",
               "mlp.experts.{e}.up_proj.weight",
               "mlp.experts.{e}.down_proj.weight",
               "mlp.shared_experts.gate_proj.weight",
               "mlp.shared_experts.up_proj.weight",
               "mlp.shared_experts.down_proj.weight"),
    "streams": ("{hc}.phi.weight", "{hc}.b", "{hc}.alpha_pre",
                "{hc}.alpha_post", "{hc}.alpha_res"),
    "norms": ("input_layernorm.weight", "post_attention_layernorm.weight"),
}


def xing4_config_from_hf(hf_config: Mapping[str, Any], **overrides):
    """A ``xing4_0`` ``config.json`` -> :class:`~..models.llama.LlamaConfig`:
    every layer ``"mla"``, the first ``first_k_dense_replace`` layers dense
    and the rest routed, ``hc_mult`` streams.  The next-token module
    (``num_nextn_predict_layers``) is not built."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    c = hf_config
    L, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    rs = c.get("rope_scaling") or {}
    if rs and rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(f"xing4_0 rope_scaling {rs}: only YaRN is read")
    return LlamaConfig(**{**dict(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        moe_intermediate_size=c["moe_intermediate_size"], num_layers=L,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_attention_heads"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
        mixer_types=("mla",) * L,
        ffn_types=("mlp",) * dense + ("moe",) * (L - dense),
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_yarn_factor=float(rs.get("factor", 1.0)),
        rope_yarn_original_max_seq=int(
            rs.get("original_max_position_embeddings", 4096)),
        rope_yarn_beta_fast=float(rs.get("beta_fast", 32)),
        rope_yarn_beta_slow=float(rs.get("beta_slow", 1)),
        rope_yarn_mscale=float(rs.get("mscale", 1)),
        rope_yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
        hc_mult=c["hc_mult"], hc_sinkhorn_iters=c["hc_sinkhorn_iters"],
        hc_eps=c["hc_eps"],
        hc_res_clamp=(c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]),
        num_experts=c["n_routed_experts"], moe_top_k=c["num_experts_per_tok"],
        moe_dispatch="dropless", moe_norm_topk_prob=c["norm_topk_prob"],
        moe_router_scores=c["scoring_func"], moe_router_bias=True,
        moe_route_scale=float(c["routed_scaling_factor"]),
        moe_shared_intermediate_size=(c["n_shared_experts"]
                                      * c["moe_intermediate_size"]),
        moe_n_group=c.get("n_group", 1), moe_topk_group=c.get("topk_group", 1),
    ), **overrides})


def _rope_halves(rope: int) -> np.ndarray:
    return np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])


def _permute_rope_columns(w, heads: int, lead: int, order) -> np.ndarray:
    w = w.reshape(w.shape[0], heads, lead + len(order))
    return np.concatenate([w[..., :lead], w[..., lead:][..., order]],
                          axis=-1).reshape(w.shape[0], -1)


def rope_half_from_interleaved(w: np.ndarray, heads: int, lead: int,
                               rope: int) -> np.ndarray:
    """The output columns of a projection ``w [in, heads * (lead + rope)]``
    whose last ``rope`` columns a head are RoPE pairs INTERLEAVED ``(x0, x1),
    (x2, x3), ...`` (the DeepSeek convention) -> the same with each head's
    pairs as halves ``(x0, x2, ... | x1, x3, ...)``, which this package's
    rotate-half RoPE turns.  ``q . k`` is unchanged when both sides are
    permuted alike."""
    return _permute_rope_columns(w, heads, lead, _rope_halves(rope))


def rope_interleaved_from_half(w: np.ndarray, heads: int, lead: int,
                               rope: int) -> np.ndarray:
    """The inverse of :func:`rope_half_from_interleaved`."""
    return _permute_rope_columns(w, heads, lead,
                                 np.argsort(_rope_halves(rope)))


def _xing4_hc_names(p: str, hc: str):
    return [p + n.format(hc=hc) for n in XING4_NAMES["streams"]]


@startup.phased("weights")
def _latent_params_from_hf(state_dict: Mapping[str, Any], cfg
                           ) -> Dict[str, Any]:
    """The state dict of a latent-attention decoder with one leading run of
    dense layers and routed ones after it (:data:`XING4_NAMES`;
    :data:`DEEPSEEK_V2_NAMES` are those without the streams' maps and the
    router bias) -> the param tree of
    :class:`~..models.llama.LlamaForCausalLM`: Linear weights transposed
    in-major, the RoPE columns of ``q_b`` and ``kv_a`` from interleaved pairs
    to halves, ``kv_b`` split a head ``[rank, NH, dn + dv]``, the experts
    HELD (``cfg.moe_experts_held``; all without) stacked, the router over
    every expert, its bias where ``cfg.moe_router_bias``, and under
    ``cfg.hc_mult > 1`` each sublayer's maps, ``phi [nC, n^2 + 2n]`` as ``[n,
    C, n^2 + 2n]``."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    NH, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    n, C = cfg.hc_mult, cfg.hidden_size
    first, count = cfg.moe_experts_held or (0, cfg.num_experts)
    model: Dict[str, Any] = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": {"weight": sd["model.norm.weight"]},
    }

    def hc(p, name):
        phi, b, *alphas = (sd[k] for k in _xing4_hc_names(p, name))
        return {"phi": phi.reshape(n, C, -1), "b": b,
                **dict(zip(("a_pre", "a_post", "a_res"), alphas))}

    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        qa, qan, qb, kva, kvan, kvb, wo = (
            sd[p + k] for k in XING4_NAMES["attention"])
        layer = {
            "input_norm": {"weight": sd[p + "input_layernorm.weight"]},
            "post_attn_norm": {
                "weight": sd[p + "post_attention_layernorm.weight"]},
            "attn": {
                "q_a": {"kernel": qa.T}, "q_a_norm": {"weight": qan},
                "q_b": {"kernel": rope_half_from_interleaved(
                    qb.T, NH, dn, dr)},
                "kv_a": {"kernel": rope_half_from_interleaved(
                    kva.T, 1, r, dr)},
                "kv_a_norm": {"weight": kvan},
                "kv_b": kvb.T.reshape(r, NH, dn + dv),
                "o_proj": {"kernel": wo.T}}}
        if n > 1:
            layer.update(attn_hc=hc(p, "attn_hc"), ffn_hc=hc(p, "ffn_hc"))
        if cfg.ffn(i) == "mlp":
            gate, up, down = (sd[p + k] for k in XING4_NAMES["dense"])
            layer["mlp"] = {
                "gate_up": {"kernel": np.stack([gate.T, up.T], axis=1)},
                "down": {"kernel": down.T}}
        else:
            m = p + "mlp."
            stack = lambda what: np.stack([  # noqa: E731
                sd[m + f"experts.{e}.{what}_proj.weight"].T
                for e in range(first, first + count)])
            layer["moe_mlp"] = {
                "router": sd[m + "gate.weight"].T,
                "gate": stack("gate"), "up": stack("up"),
                "down": stack("down"),
                **{f"shared_{w}": {
                    "kernel": sd[m + f"shared_experts.{w}_proj.weight"].T}
                   for w in ("gate", "up", "down")}}
            if cfg.moe_router_bias:
                layer["moe_mlp"]["router_bias"] = sd[
                    m + "gate.e_score_correction_bias"]
        model[f"layer_{i}"] = layer
    return {"params": {"model": model,
                       "lm_head": {"kernel": sd["lm_head.weight"].T}}}


def _latent_params_to_hf(params: Mapping[str, Any], cfg
                         ) -> Dict[str, np.ndarray]:
    """The inverse of :func:`_latent_params_from_hf`, bit for bit (of a held
    share: the held experts under their own numbers)."""
    p = params["params"] if "params" in params else params
    model = p["model"]
    NH, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    first, _ = cfg.moe_experts_held or (0, cfg.num_experts)
    sd: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": _np(model["embed"]["embedding"]),
        "model.norm.weight": _np(model["final_norm"]["weight"]),
        "lm_head.weight": _np(p["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.num_layers):
        lp, pre = model[f"layer_{i}"], f"model.layers.{i}."
        at = lp["attn"]
        sd[pre + "input_layernorm.weight"] = _np(lp["input_norm"]["weight"])
        sd[pre + "post_attention_layernorm.weight"] = _np(
            lp["post_attn_norm"]["weight"])
        for name in ("attn_hc", "ffn_hc") if cfg.hc_mult > 1 else ():
            h = lp[name]
            phi = _np(h["phi"])
            for key, val in zip(_xing4_hc_names(pre, name), (
                    phi.reshape(-1, phi.shape[-1]), _np(h["b"]),
                    _np(h["a_pre"]), _np(h["a_post"]), _np(h["a_res"]))):
                sd[key] = val
        values = (
            _np(at["q_a"]["kernel"]).T, _np(at["q_a_norm"]["weight"]),
            rope_interleaved_from_half(_np(at["q_b"]["kernel"]), NH, dn,
                                       dr).T,
            rope_interleaved_from_half(_np(at["kv_a"]["kernel"]), 1, r,
                                       dr).T,
            _np(at["kv_a_norm"]["weight"]),
            _np(at["kv_b"]).reshape(r, -1).T, _np(at["o_proj"]["kernel"]).T)
        for key, val in zip(XING4_NAMES["attention"], values):
            sd[pre + key] = val
        if "mlp" in lp:
            gu = _np(lp["mlp"]["gate_up"]["kernel"])
            for key, val in zip(XING4_NAMES["dense"], (
                    gu[:, 0].T, gu[:, 1].T,
                    _np(lp["mlp"]["down"]["kernel"]).T)):
                sd[pre + key] = val
        else:
            moe, m = lp["moe_mlp"], pre + "mlp."
            sd[m + "gate.weight"] = _np(moe["router"]).T
            if cfg.moe_router_bias:
                sd[m + "gate.e_score_correction_bias"] = _np(
                    moe["router_bias"])
            for w in ("gate", "up", "down"):
                for e, mat in enumerate(_np(moe[w])):
                    sd[m + f"experts.{first + e}.{w}_proj.weight"] = mat.T
                sd[m + f"shared_experts.{w}_proj.weight"] = _np(
                    moe[f"shared_{w}"]["kernel"]).T
    return sd


@startup.phased("weights")
def xing4_params_from_hf(state_dict: Mapping[str, Any], cfg
                         ) -> Dict[str, Any]:
    """A ``xing4_0`` state dict (:data:`XING4_NAMES`, assumed) -> the param
    tree of :class:`~..models.llama.LlamaForCausalLM` under
    :func:`xing4_config_from_hf`'s layer lists
    (:func:`_latent_params_from_hf`)."""
    return _latent_params_from_hf(state_dict, cfg)


def xing4_params_to_hf(params: Mapping[str, Any], cfg
                       ) -> Dict[str, np.ndarray]:
    """The inverse of :func:`xing4_params_from_hf`, bit for bit."""
    return _latent_params_to_hf(params, cfg)


# -- DeepSeek-V2 (``deepseek_v2``) ---------------------------------------------
#
# The published checkpoint's names: Xing4.0's (the same attention and
# feed-forward modules) without the streams' maps and without a router bias.

DEEPSEEK_V2_NAMES = {
    "attention": XING4_NAMES["attention"],
    "dense": XING4_NAMES["dense"],
    "routed": tuple(n for n in XING4_NAMES["routed"]
                    if n != "mlp.gate.e_score_correction_bias"),
    "norms": XING4_NAMES["norms"],
}


def deepseek_v2_config_from_hf(hf_config: Mapping[str, Any], **overrides):
    """A ``deepseek_v2`` ``config.json`` -> :class:`~..models.llama.LlamaConfig`:
    every layer ``"mla"``, the first ``first_k_dense_replace`` layers dense
    and the rest routed by softmax scores under the group limit (``n_group``,
    ``topk_group``; ``topk_method`` ``"greedy"`` is one group), the
    ``n_shared_experts`` shared experts as ONE gated MLP of their summed
    width (as the published code builds them).  ``moe_experts_held=(first,
    count)`` (an override) makes it one expert-parallel rank's share."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    c = hf_config
    L, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    rs = c.get("rope_scaling") or {}
    if rs and rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(f"deepseek_v2 rope_scaling {rs}: only YaRN is read")
    if c.get("scoring_func", "softmax") != "softmax" \
            or c.get("moe_layer_freq", 1) != 1 or not c.get("q_lora_rank"):
        raise ValueError(
            "deepseek_v2: softmax scores, a routed block every layer past "
            "the dense ones and a query bottleneck (q_lora_rank) are read")
    method = c.get("topk_method", "greedy")
    if method not in ("greedy", "group_limited_greedy"):
        raise ValueError(f"deepseek_v2 topk_method {method!r} is not read")
    grouped = method == "group_limited_greedy"
    return LlamaConfig(**{**dict(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        moe_intermediate_size=c["moe_intermediate_size"], num_layers=L,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_attention_heads"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
        mixer_types=("mla",) * L,
        ffn_types=("mlp",) * dense + ("moe",) * (L - dense),
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_yarn_factor=float(rs.get("factor", 1.0)),
        rope_yarn_original_max_seq=int(
            rs.get("original_max_position_embeddings", 4096)),
        rope_yarn_beta_fast=float(rs.get("beta_fast", 32)),
        rope_yarn_beta_slow=float(rs.get("beta_slow", 1)),
        rope_yarn_mscale=float(rs.get("mscale", 1)),
        rope_yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
        num_experts=c["n_routed_experts"], moe_top_k=c["num_experts_per_tok"],
        moe_dispatch="dropless", moe_norm_topk_prob=c["norm_topk_prob"],
        moe_router_scores="softmax",
        # the published code scales the gates only where it does not
        # renormalise them
        moe_route_scale=(1.0 if c["norm_topk_prob"]
                         else float(c["routed_scaling_factor"])),
        moe_shared_intermediate_size=(c["n_shared_experts"]
                                      * c["moe_intermediate_size"]),
        moe_n_group=c["n_group"] if grouped else 1,
        moe_topk_group=c["topk_group"] if grouped else 1,
    ), **overrides})


@startup.phased("weights")
def deepseek_v2_params_from_hf(state_dict: Mapping[str, Any], cfg
                               ) -> Dict[str, Any]:
    """A ``deepseek_v2`` state dict (:data:`DEEPSEEK_V2_NAMES`) -> the param
    tree of :class:`~..models.llama.LlamaForCausalLM` under
    :func:`deepseek_v2_config_from_hf`'s layer lists
    (:func:`_latent_params_from_hf`: no streams' maps, no router bias, the
    experts HELD under ``cfg.moe_experts_held``)."""
    return _latent_params_from_hf(state_dict, cfg)


def deepseek_v2_params_to_hf(params: Mapping[str, Any], cfg
                             ) -> Dict[str, np.ndarray]:
    """The inverse of :func:`deepseek_v2_params_from_hf`, bit for bit (of a
    held share: the held experts under their own numbers)."""
    return _latent_params_to_hf(params, cfg)


# -- LFM2 (``model_type`` ``lfm2_moe``) -----------------------------------------
#
# The source's tensor names are ASSUMED (no checkpoint index is at hand): the
# family's public modeling code's — ``operator_norm`` / ``ffn_norm``, the
# gated short convolution under ``conv``, attention under ``self_attn`` with
# ``q_layernorm`` / ``k_layernorm`` and ``out_proj``, a feed-forward part's
# ``w1`` (gate), ``w3`` (up), ``w2`` (down), a routed block's ``gate`` and
# ``expert_bias``, the final norm as ``embedding_norm`` — and no ``lm_head``
# (the head is the embedding table).


def lfm2_moe_config_from_hf(hf_config: Mapping[str, Any], **overrides):
    """An ``lfm2_moe`` ``config.json`` -> :class:`~..models.llama.LlamaConfig`:
    ``layer_types`` as ``mixer_types`` (``"conv"`` | ``"attention"``), the
    first ``num_dense_layers`` feed-forward parts dense and the rest routed
    by sigmoid scores with a correction bias, renormalised with 1e-6, no
    balance term in the loss; per-head q/k norm; tied embeddings.
    ``moe_experts_held=(first, count)`` (an override) makes it one
    expert-parallel rank's share."""
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    c = hf_config
    L, dense = c["num_hidden_layers"], c["num_dense_layers"]
    if c.get("conv_bias"):
        raise ValueError("lfm2_moe: a convolution bias is not read")
    return LlamaConfig(**{**dict(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        moe_intermediate_size=c["moe_intermediate_size"], num_layers=L,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), rms_eps=c["norm_eps"],
        conv_L_cache=c["conv_L_cache"],
        mixer_types=tuple("attention" if t == "full_attention" else t
                          for t in c["layer_types"]),
        ffn_types=("mlp",) * dense + ("moe",) * (L - dense),
        num_experts=c["num_experts"], moe_top_k=c["num_experts_per_tok"],
        moe_dispatch="dropless", moe_router_scores="sigmoid",
        moe_router_bias=bool(c.get("use_expert_bias", True)),
        moe_norm_topk_prob=c["norm_topk_prob"],
        moe_route_scale=float(c["routed_scaling_factor"]),
        moe_aux_loss=False, qk_norm_per_head=True, tie_word_embeddings=True,
    ), **overrides})


@startup.phased("weights")
def lfm2_moe_params_from_hf(state_dict: Mapping[str, Any], cfg
                            ) -> Dict[str, Any]:
    """An ``lfm2_moe`` state dict (names as assumed above) -> the param tree
    of :class:`~..models.llama.LlamaForCausalLM` under
    :func:`lfm2_moe_config_from_hf`'s layer lists: a convolution ``[C, 1,
    L]`` as ``conv_weight [L, C]``, a routed layer's HELD experts stacked
    (``cfg.moe_experts_held``), every Linear transposed in-major."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    H = cfg.hidden_size
    first, count = cfg.moe_experts_held or (0, cfg.num_experts)
    model: Dict[str, Any] = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": {"weight": sd["model.embedding_norm.weight"]},
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        lyr: Dict[str, Any] = {
            "input_norm": {"weight": sd[p + "operator_norm.weight"]},
            "post_attn_norm": {"weight": sd[p + "ffn_norm.weight"]}}
        if cfg.mixer(i) == "conv":
            lyr["attn"] = {
                "in_proj": {"kernel": sd[p + "conv.in_proj.weight"].T},
                "conv_weight": sd[p + "conv.conv.weight"][:, 0, :].T,
                "out_proj": {"kernel": sd[p + "conv.out_proj.weight"].T}}
        else:
            a = p + "self_attn."
            nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
            lyr["attn"] = {
                "qkv": {
                    "q_kernel": sd[a + "q_proj.weight"].T.reshape(H, nq, d),
                    "k_kernel": sd[a + "k_proj.weight"].T.reshape(H, nkv, d),
                    "v_kernel": sd[a + "v_proj.weight"].T.reshape(H, nkv, d)},
                "q_norm": {"weight": sd[a + "q_layernorm.weight"]},
                "k_norm": {"weight": sd[a + "k_layernorm.weight"]},
                "o_proj": {"kernel": sd[a + "out_proj.weight"].T}}
        f = p + "feed_forward."
        if cfg.ffn(i) == "mlp":
            lyr["mlp"] = {
                "gate_up": {"kernel": np.stack(
                    [sd[f + "w1.weight"].T, sd[f + "w3.weight"].T], axis=1)},
                "down": {"kernel": sd[f + "w2.weight"].T}}
        else:
            experts = range(first, first + count)
            lyr["moe_mlp"] = {
                "router": sd[f + "gate.weight"].T,
                "router_bias": sd[f + "expert_bias"],
                **{ours: np.stack([sd[f + f"experts.{e}.{theirs}.weight"].T
                                   for e in experts])
                   for ours, theirs in (("gate", "w1"), ("up", "w3"),
                                        ("down", "w2"))}}
        model[f"layer_{i}"] = lyr
    return {"params": {"model": model}}


def lfm2_moe_params_to_hf(params: Mapping[str, Any], cfg
                          ) -> Dict[str, np.ndarray]:
    """The inverse of :func:`lfm2_moe_params_from_hf` (of a held share: the
    held experts under their own numbers among the routed ones)."""
    model = params.get("params", params)["model"]
    H = cfg.hidden_size
    first, count = cfg.moe_experts_held or (0, cfg.num_experts)
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": _np(model["embed"]["embedding"]),
        "model.embedding_norm.weight": _np(model["final_norm"]["weight"]),
    }
    for i in range(cfg.num_layers):
        lyr, p = model[f"layer_{i}"], f"model.layers.{i}."
        out[p + "operator_norm.weight"] = _np(lyr["input_norm"]["weight"])
        out[p + "ffn_norm.weight"] = _np(lyr["post_attn_norm"]["weight"])
        a = lyr["attn"]
        if cfg.mixer(i) == "conv":
            out[p + "conv.in_proj.weight"] = _np(a["in_proj"]["kernel"]).T
            out[p + "conv.conv.weight"] = _np(a["conv_weight"]).T[:, None, :]
            out[p + "conv.out_proj.weight"] = _np(a["out_proj"]["kernel"]).T
        else:
            s = p + "self_attn."
            for name, key in (("q_proj", "q_kernel"), ("k_proj", "k_kernel"),
                              ("v_proj", "v_kernel")):
                out[s + name + ".weight"] = _np(a["qkv"][key]).reshape(H, -1).T
            out[s + "q_layernorm.weight"] = _np(a["q_norm"]["weight"])
            out[s + "k_layernorm.weight"] = _np(a["k_norm"]["weight"])
            out[s + "out_proj.weight"] = _np(a["o_proj"]["kernel"]).T
        f = p + "feed_forward."
        if cfg.ffn(i) == "mlp":
            gate_up = _np(lyr["mlp"]["gate_up"]["kernel"])
            out[f + "w1.weight"] = gate_up[:, 0, :].T
            out[f + "w3.weight"] = gate_up[:, 1, :].T
            out[f + "w2.weight"] = _np(lyr["mlp"]["down"]["kernel"]).T
        else:
            moe = lyr["moe_mlp"]
            out[f + "gate.weight"] = _np(moe["router"]).T
            out[f + "expert_bias"] = _np(moe["router_bias"])
            for ours, theirs in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
                w = _np(moe[ours])
                for n in range(count):
                    out[f + f"experts.{first + n}.{theirs}.weight"] = w[n].T
    return out


def _neox_deinterleave(w_qkv: np.ndarray, b_qkv: np.ndarray, num_heads: int, head_dim: int):
    """HF NeoX fused QKV rows are per-head interleaved ``[n,(q|k|v),d]``;
    the framework's fused axis wants ``[in, 3, n*d]``."""
    H_in = w_qkv.shape[1]
    w = w_qkv.T.reshape(H_in, num_heads, 3, head_dim)
    w = w.transpose(0, 2, 1, 3).reshape(H_in, 3, num_heads * head_dim)
    b = b_qkv.reshape(num_heads, 3, head_dim).transpose(1, 0, 2).reshape(3, -1)
    return w, b


def _neox_interleave(w: np.ndarray, b: np.ndarray, num_heads: int, head_dim: int):
    H_in = w.shape[0]
    wq = w.reshape(H_in, 3, num_heads, head_dim).transpose(0, 2, 1, 3)
    wq = wq.reshape(H_in, 3 * num_heads * head_dim).T
    bq = b.reshape(3, num_heads, head_dim).transpose(1, 0, 2).reshape(-1)
    return wq, bq


@startup.phased("weights")
def gpt_neox_params_from_hf(state_dict: Mapping[str, Any], cfg) -> Dict[str, Any]:
    """HF ``GPTNeoXForCausalLM.state_dict()`` → framework param tree."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    N, D = cfg.num_heads, cfg.head_dim

    tree: Dict[str, Any] = {
        "embed_in": {"embedding": sd["gpt_neox.embed_in.weight"]},
        "final_norm": {
            "weight": sd["gpt_neox.final_layer_norm.weight"],
            "bias": sd["gpt_neox.final_layer_norm.bias"],
        },
        "embed_out": {"kernel": sd["embed_out.weight"].T},
    }
    for i in range(cfg.num_layers):
        p = f"gpt_neox.layers.{i}."
        wq, bq = _neox_deinterleave(
            sd[p + "attention.query_key_value.weight"],
            sd[p + "attention.query_key_value.bias"], N, D,
        )
        tree[f"layer_{i}"] = {
            "ln_1": {
                "weight": sd[p + "input_layernorm.weight"],
                "bias": sd[p + "input_layernorm.bias"],
            },
            "ln_2": {
                "weight": sd[p + "post_attention_layernorm.weight"],
                "bias": sd[p + "post_attention_layernorm.bias"],
            },
            "attn": {
                "qkv": {"kernel": wq, "bias": bq},
                "dense": {
                    "kernel": sd[p + "attention.dense.weight"].T,
                    "bias": sd[p + "attention.dense.bias"],
                },
            },
            "mlp": {
                "dense_h_to_4h": {
                    "kernel": sd[p + "mlp.dense_h_to_4h.weight"].T,
                    "bias": sd[p + "mlp.dense_h_to_4h.bias"],
                },
                "dense_4h_to_h": {
                    "kernel": sd[p + "mlp.dense_4h_to_h.weight"].T,
                    "bias": sd[p + "mlp.dense_4h_to_h.bias"],
                },
            },
        }
    return {"params": tree}


def gpt_neox_params_to_hf(params: Mapping[str, Any], cfg) -> Dict[str, np.ndarray]:
    tree = params.get("params", params)
    N, D = cfg.num_heads, cfg.head_dim
    out: Dict[str, np.ndarray] = {
        "gpt_neox.embed_in.weight": _np(tree["embed_in"]["embedding"]),
        "gpt_neox.final_layer_norm.weight": _np(tree["final_norm"]["weight"]),
        "gpt_neox.final_layer_norm.bias": _np(tree["final_norm"]["bias"]),
        "embed_out.weight": _np(tree["embed_out"]["kernel"]).T,
    }
    for i in range(cfg.num_layers):
        lyr = tree[f"layer_{i}"]
        p = f"gpt_neox.layers.{i}."
        wq, bq = _neox_interleave(
            _np(lyr["attn"]["qkv"]["kernel"]), _np(lyr["attn"]["qkv"]["bias"]), N, D
        )
        out.update({
            p + "input_layernorm.weight": _np(lyr["ln_1"]["weight"]),
            p + "input_layernorm.bias": _np(lyr["ln_1"]["bias"]),
            p + "post_attention_layernorm.weight": _np(lyr["ln_2"]["weight"]),
            p + "post_attention_layernorm.bias": _np(lyr["ln_2"]["bias"]),
            p + "attention.query_key_value.weight": wq,
            p + "attention.query_key_value.bias": bq,
            p + "attention.dense.weight": _np(lyr["attn"]["dense"]["kernel"]).T,
            p + "attention.dense.bias": _np(lyr["attn"]["dense"]["bias"]),
            p + "mlp.dense_h_to_4h.weight": _np(lyr["mlp"]["dense_h_to_4h"]["kernel"]).T,
            p + "mlp.dense_h_to_4h.bias": _np(lyr["mlp"]["dense_h_to_4h"]["bias"]),
            p + "mlp.dense_4h_to_h.weight": _np(lyr["mlp"]["dense_4h_to_h"]["kernel"]).T,
            p + "mlp.dense_4h_to_h.bias": _np(lyr["mlp"]["dense_4h_to_h"]["bias"]),
        })
    return out


# ---------------------------------------------------------------------------
# BERT
# ---------------------------------------------------------------------------


@startup.phased("weights")
def bert_params_from_hf(state_dict: Mapping[str, Any], cfg) -> Dict[str, Any]:
    """HF ``BertForPreTraining.state_dict()`` → framework param tree for
    :class:`~..models.bert.BertForPreTraining` (separate HF q/k/v linears
    fuse onto the framework's ``n_fused=3`` kernel; the MLM decoder is tied
    to the word embedding on both sides, so only its bias transfers)."""
    sd = {k: _np(v) for k, v in state_dict.items()}

    bert: Dict[str, Any] = {
        "word_embeddings": {"embedding": sd["bert.embeddings.word_embeddings.weight"]},
        "position_embeddings": sd["bert.embeddings.position_embeddings.weight"],
        "token_type_embeddings": sd["bert.embeddings.token_type_embeddings.weight"],
        "embed_norm": {
            "weight": sd["bert.embeddings.LayerNorm.weight"],
            "bias": sd["bert.embeddings.LayerNorm.bias"],
        },
        "pooler": {
            "kernel": sd["bert.pooler.dense.weight"].T,
            "bias": sd["bert.pooler.dense.bias"],
        },
    }
    for i in range(cfg.num_layers):
        p = f"bert.encoder.layer.{i}."
        wq = np.stack(
            [sd[p + f"attention.self.{n}.weight"].T for n in ("query", "key", "value")],
            axis=1,
        )  # [H, 3, H]
        bq = np.stack(
            [sd[p + f"attention.self.{n}.bias"] for n in ("query", "key", "value")], axis=0
        )
        bert[f"layer_{i}"] = {
            "attention": {
                "qkv": {"kernel": wq, "bias": bq},
                "dense": {
                    "kernel": sd[p + "attention.output.dense.weight"].T,
                    "bias": sd[p + "attention.output.dense.bias"],
                },
            },
            "attention_norm": {
                "weight": sd[p + "attention.output.LayerNorm.weight"],
                "bias": sd[p + "attention.output.LayerNorm.bias"],
            },
            "intermediate": {
                "kernel": sd[p + "intermediate.dense.weight"].T,
                "bias": sd[p + "intermediate.dense.bias"],
            },
            "output": {
                "kernel": sd[p + "output.dense.weight"].T,
                "bias": sd[p + "output.dense.bias"],
            },
            "output_norm": {
                "weight": sd[p + "output.LayerNorm.weight"],
                "bias": sd[p + "output.LayerNorm.bias"],
            },
        }

    tree: Dict[str, Any] = {"bert": bert}
    if "cls.predictions.transform.dense.weight" in sd:
        tree["mlm_transform"] = {
            "kernel": sd["cls.predictions.transform.dense.weight"].T,
            "bias": sd["cls.predictions.transform.dense.bias"],
        }
        tree["mlm_norm"] = {
            "weight": sd["cls.predictions.transform.LayerNorm.weight"],
            "bias": sd["cls.predictions.transform.LayerNorm.bias"],
        }
        tree["mlm_bias"] = sd["cls.predictions.bias"]
        tree["nsp_classifier"] = {
            "kernel": sd["cls.seq_relationship.weight"].T,
            "bias": sd["cls.seq_relationship.bias"],
        }
    return {"params": tree}


def bert_params_to_hf(params: Mapping[str, Any], cfg) -> Dict[str, np.ndarray]:
    tree = params.get("params", params)
    bert = tree["bert"]
    out: Dict[str, np.ndarray] = {
        "bert.embeddings.word_embeddings.weight": _np(bert["word_embeddings"]["embedding"]),
        "bert.embeddings.position_embeddings.weight": _np(bert["position_embeddings"]),
        "bert.embeddings.token_type_embeddings.weight": _np(bert["token_type_embeddings"]),
        "bert.embeddings.LayerNorm.weight": _np(bert["embed_norm"]["weight"]),
        "bert.embeddings.LayerNorm.bias": _np(bert["embed_norm"]["bias"]),
        "bert.pooler.dense.weight": _np(bert["pooler"]["kernel"]).T,
        "bert.pooler.dense.bias": _np(bert["pooler"]["bias"]),
    }
    for i in range(cfg.num_layers):
        lyr = bert[f"layer_{i}"]
        p = f"bert.encoder.layer.{i}."
        wq = _np(lyr["attention"]["qkv"]["kernel"])  # [H, 3, H]
        bq = _np(lyr["attention"]["qkv"]["bias"])
        for j, n in enumerate(("query", "key", "value")):
            out[p + f"attention.self.{n}.weight"] = wq[:, j, :].T
            out[p + f"attention.self.{n}.bias"] = bq[j]
        out.update({
            p + "attention.output.dense.weight": _np(lyr["attention"]["dense"]["kernel"]).T,
            p + "attention.output.dense.bias": _np(lyr["attention"]["dense"]["bias"]),
            p + "attention.output.LayerNorm.weight": _np(lyr["attention_norm"]["weight"]),
            p + "attention.output.LayerNorm.bias": _np(lyr["attention_norm"]["bias"]),
            p + "intermediate.dense.weight": _np(lyr["intermediate"]["kernel"]).T,
            p + "intermediate.dense.bias": _np(lyr["intermediate"]["bias"]),
            p + "output.dense.weight": _np(lyr["output"]["kernel"]).T,
            p + "output.dense.bias": _np(lyr["output"]["bias"]),
            p + "output.LayerNorm.weight": _np(lyr["output_norm"]["weight"]),
            p + "output.LayerNorm.bias": _np(lyr["output_norm"]["bias"]),
        })
    if "mlm_transform" in tree:
        out.update({
            "cls.predictions.transform.dense.weight": _np(tree["mlm_transform"]["kernel"]).T,
            "cls.predictions.transform.dense.bias": _np(tree["mlm_transform"]["bias"]),
            "cls.predictions.transform.LayerNorm.weight": _np(tree["mlm_norm"]["weight"]),
            "cls.predictions.transform.LayerNorm.bias": _np(tree["mlm_norm"]["bias"]),
            "cls.predictions.bias": _np(tree["mlm_bias"]),
            # HF materializes the tied decoder as its own (shared) tensors
            "cls.predictions.decoder.weight": _np(bert["word_embeddings"]["embedding"]),
            "cls.predictions.decoder.bias": _np(tree["mlm_bias"]),
            "cls.seq_relationship.weight": _np(tree["nsp_classifier"]["kernel"]).T,
            "cls.seq_relationship.bias": _np(tree["nsp_classifier"]["bias"]),
        })
    return out


# ---------------------------------------------------------------------------
# Pipeline-engine checkpoints
# ---------------------------------------------------------------------------
#
# The PP engine's param tree is {"embed": ..., "layers": stacked [L', ...],
# "head": {...}} with layer_rows mapping real layer i to its stack row
# (padded rows from non-divisible counts / pipeline_cuts hold zeros and are
# dropped here).  These rebuild the standard per-layer module tree so the
# HF exporters above — and plain pp=1 serving — consume PP-trained
# checkpoints directly.


def llama_params_from_pipelined(pparams: Mapping[str, Any], layer_rows) -> Dict[str, Any]:
    """Pipelined-Llama engine tree → the ``LlamaForCausalLM`` param tree."""
    model: Dict[str, Any] = {"embed": jax.tree.map(_np, dict(pparams["embed"]))}
    head = dict(pparams["head"])
    model["final_norm"] = jax.tree.map(_np, head["final_norm"])
    # one device->host transfer of the stack; per-row numpy views after
    stacked = jax.tree.map(_np, pparams["layers"])
    for i, row in enumerate(layer_rows):
        model[f"layer_{i}"] = jax.tree.map(lambda x, r=row: x[r], stacked)
    return {"params": {"model": model,
                       "lm_head": jax.tree.map(_np, head["lm_head"])}}


def gpt_neox_params_from_pipelined(pparams: Mapping[str, Any], layer_rows) -> Dict[str, Any]:
    """Pipelined-GPT-NeoX engine tree → the ``GPTNeoXForCausalLM`` tree."""
    head = dict(pparams["head"])
    out: Dict[str, Any] = {
        "embed_in": jax.tree.map(_np, dict(pparams["embed"])),
        "final_norm": jax.tree.map(_np, head["final_norm"]),
        "embed_out": jax.tree.map(_np, head["embed_out"]),
    }
    stacked = jax.tree.map(_np, pparams["layers"])
    for i, row in enumerate(layer_rows):
        out[f"layer_{i}"] = jax.tree.map(lambda x, r=row: x[r], stacked)
    return {"params": out}


# ---------------------------------------------------------------------------
# Mistral: the HF layout is byte-identical to Llama's (same module names,
# same fused-projection shapes; the sliding window is config-only), so the
# Llama converters serve the Mistral family directly.
# ---------------------------------------------------------------------------

mistral_params_from_hf = llama_params_from_hf
mistral_params_to_hf = llama_params_to_hf


# ---------------------------------------------------------------------------
# Gemma: Llama-layout layers + tied embedding head + (1 + w) RMSNorm
# ---------------------------------------------------------------------------


@startup.phased("weights")
def gemma_params_from_hf(state_dict: Mapping[str, Any], cfg) -> Dict[str, Any]:
    """HF ``GemmaForCausalLM.state_dict()`` → framework param tree for
    :class:`~..models.gemma.GemmaForCausalLM`.

    HF Gemma's RMSNorm computes ``x * (1 + weight)``; the framework's
    computes ``x * weight`` — every norm weight gets ``+1`` folded in here
    (bit-equivalent in fp32: the sum is formed once, outside the graph).
    The LM head is the tied embedding table, so no head tensor exists in
    either layout."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    block_cfg = cfg.block_config()
    tree: Dict[str, Any] = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": {"weight": sd["model.norm.weight"] + 1.0},
    }
    for i in range(cfg.num_layers):
        tree[f"layer_{i}"] = _decoder_layer_from_hf(
            sd, f"model.layers.{i}.", block_cfg, norm_offset=1.0)
    return {"params": tree}


def gemma_params_to_hf(params: Mapping[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`gemma_params_from_hf` (framework → HF state dict,
    norm weights shifted back by ``-1``)."""
    tree = params.get("params", params)
    H = cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": _np(tree["embed"]["embedding"]),
        "model.norm.weight": _np(tree["final_norm"]["weight"]) - 1.0,
    }
    for i in range(cfg.num_layers):
        lyr = tree[f"layer_{i}"]
        p = f"model.layers.{i}."
        qkv = lyr["attn"]["qkv"]
        gu = _np(lyr["mlp"]["gate_up"]["kernel"])  # [H, 2, I]
        out.update({
            p + "self_attn.q_proj.weight": _np(qkv["q_kernel"]).reshape(H, -1).T,
            p + "self_attn.k_proj.weight": _np(qkv["k_kernel"]).reshape(H, -1).T,
            p + "self_attn.v_proj.weight": _np(qkv["v_kernel"]).reshape(H, -1).T,
            p + "self_attn.o_proj.weight": _np(lyr["attn"]["o_proj"]["kernel"]).T,
            p + "mlp.gate_proj.weight": gu[:, 0, :].T,
            p + "mlp.up_proj.weight": gu[:, 1, :].T,
            p + "mlp.down_proj.weight": _np(lyr["mlp"]["down"]["kernel"]).T,
            p + "input_layernorm.weight": _np(lyr["input_norm"]["weight"]) - 1.0,
            p + "post_attention_layernorm.weight": _np(lyr["post_attn_norm"]["weight"]) - 1.0,
        })
    return out


@startup.phased("weights")
def gemma2_params_from_hf(state_dict: Mapping[str, Any], cfg) -> Dict[str, Any]:
    """HF ``Gemma2ForCausalLM.state_dict()`` → framework param tree for
    :class:`~..models.gemma.Gemma2ForCausalLM` (tied head; every RMSNorm —
    including the two feedforward sandwich norms — gets the ``+1`` fold)."""
    sd = {k: _np(v) for k, v in state_dict.items()}
    block_cfg = cfg.block_config(sliding=False)  # layout-only use
    tree: Dict[str, Any] = {
        "embed": {"embedding": sd["model.embed_tokens.weight"]},
        "final_norm": {"weight": sd["model.norm.weight"] + 1.0},
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        lyr = _decoder_layer_from_hf(sd, p, block_cfg, norm_offset=1.0)
        lyr["pre_ffw_norm"] = {
            "weight": sd[p + "pre_feedforward_layernorm.weight"] + 1.0}
        lyr["post_ffw_norm"] = {
            "weight": sd[p + "post_feedforward_layernorm.weight"] + 1.0}
        # in Gemma-2 post_attention_layernorm is the post-attn sandwich norm
        # (same name the framework block uses), already mapped by the helper
        tree[f"layer_{i}"] = lyr
    return {"params": tree}


def gemma2_params_to_hf(params: Mapping[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Inverse of :func:`gemma2_params_from_hf`."""
    tree = params.get("params", params)
    H = cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": _np(tree["embed"]["embedding"]),
        "model.norm.weight": _np(tree["final_norm"]["weight"]) - 1.0,
    }
    for i in range(cfg.num_layers):
        lyr = tree[f"layer_{i}"]
        p = f"model.layers.{i}."
        qkv = lyr["attn"]["qkv"]
        gu = _np(lyr["mlp"]["gate_up"]["kernel"])  # [H, 2, I]
        out.update({
            p + "self_attn.q_proj.weight": _np(qkv["q_kernel"]).reshape(H, -1).T,
            p + "self_attn.k_proj.weight": _np(qkv["k_kernel"]).reshape(H, -1).T,
            p + "self_attn.v_proj.weight": _np(qkv["v_kernel"]).reshape(H, -1).T,
            p + "self_attn.o_proj.weight": _np(lyr["attn"]["o_proj"]["kernel"]).T,
            p + "mlp.gate_proj.weight": gu[:, 0, :].T,
            p + "mlp.up_proj.weight": gu[:, 1, :].T,
            p + "mlp.down_proj.weight": _np(lyr["mlp"]["down"]["kernel"]).T,
            p + "input_layernorm.weight": _np(lyr["input_norm"]["weight"]) - 1.0,
            p + "post_attention_layernorm.weight":
                _np(lyr["post_attn_norm"]["weight"]) - 1.0,
            p + "pre_feedforward_layernorm.weight":
                _np(lyr["pre_ffw_norm"]["weight"]) - 1.0,
            p + "post_feedforward_layernorm.weight":
                _np(lyr["post_ffw_norm"]["weight"]) - 1.0,
        })
    return out
