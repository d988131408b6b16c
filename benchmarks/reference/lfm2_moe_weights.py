"""lfm2_moe_weights.py — from ``models.llama.LlamaForCausalLM``'s parameter
tree (a ``LlamaConfig`` with ``mixer_types`` of ``"conv"`` and
``"attention"``, ``ffn_types`` of ``"mlp"`` and ``"moe"``, tied embeddings)
to the plain dict ``lfm2_moe_f32.py`` reads.

The one place the yardstick knows how the program lays these weights out.
``adapt`` reshapes and slices, nothing else, so a tree of GRADIENTS shaped
like the parameters goes through it too: the program's gradient comes out
in the reference's layout, leaf beside leaf with ``jax.grad`` of the
reference's loss.  ``GROUPS`` names the parameter groups a comparison
reports, by the reference's keys."""

from __future__ import annotations

# group -> the reference's keys (of a layer's dict unless marked)
GROUPS = {
    "embedding": ("embed",),
    "final_norm": ("final_norm",),
    "norms": ("norm1", "norm2"),
    "conv_in": ("conv_in",), "conv_taps": ("conv_w",),
    "conv_out": ("conv_out",),
    "attn_q": ("wq",), "attn_k": ("wk",), "attn_v": ("wv",),
    "attn_o": ("wo",), "qk_norms": ("q_norm", "k_norm"),
    "dense_mlp": ("w_gate", "w_up", "w_down"),
    "router": ("router",),
    "expert_gate": ("e_gate",), "expert_up": ("e_up",),
    "expert_down": ("e_down",),
}


def _value(x):
    return getattr(x, "value", x)  # unwrap flax Partitioned boxes


def adapt(params, num_layers: int) -> dict:
    p = params["params"] if "params" in params else params
    model = p["model"]

    def layer(i):
        lp = model[f"layer_{i}"]
        mix = lp["attn"]
        out = {"norm1": _value(lp["input_norm"]["weight"]),
               "norm2": _value(lp["post_attn_norm"]["weight"])}
        if "conv_weight" in mix:
            out.update(conv_in=_value(mix["in_proj"]["kernel"]),
                       conv_w=_value(mix["conv_weight"]),
                       conv_out=_value(mix["out_proj"]["kernel"]))
        else:
            qkv = mix["qkv"]
            H = _value(qkv["q_kernel"]).shape[0]
            out.update(
                {k: _value(qkv[n]).reshape(H, -1) for k, n in (
                    ("wq", "q_kernel"), ("wk", "k_kernel"),
                    ("wv", "v_kernel"))},
                q_norm=_value(mix["q_norm"]["weight"]),
                k_norm=_value(mix["k_norm"]["weight"]),
                wo=_value(mix["o_proj"]["kernel"]))
        if "moe_mlp" in lp:
            moe = lp["moe_mlp"]
            out.update(router=_value(moe["router"]),
                       router_bias=_value(moe["router_bias"]),
                       e_gate=_value(moe["gate"]), e_up=_value(moe["up"]),
                       e_down=_value(moe["down"]))
        else:
            gate_up = _value(lp["mlp"]["gate_up"]["kernel"])
            out.update(w_gate=gate_up[:, 0, :], w_up=gate_up[:, 1, :],
                       w_down=_value(lp["mlp"]["down"]["kernel"]))
        return out

    return {"embed": _value(model["embed"]["embedding"]),
            "final_norm": _value(model["final_norm"]["weight"]),
            "layers": [layer(i) for i in range(num_layers)]}


def by_group(tree: dict) -> dict:
    """``{group: [arrays]}`` of a tree in the reference's layout."""
    out = {}
    for group, keys in GROUPS.items():
        leaves = [tree[k] for k in keys if k in tree] + [
            lw[k] for lw in tree["layers"] for k in keys if k in lw]
        if leaves:
            out[group] = leaves
    return out
