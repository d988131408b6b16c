"""qwen3_next_weights.py — from ``models.llama.LlamaForCausalLM``'s parameter
tree, built with a ``mixer_types`` of ``"gated-delta"`` and ``"attention"``
layers (``attn_output_gate``, ``qk_norm_per_head``, ``norm_zero_centered``)
over routed blocks with a gated shared expert, to the plain dict
``qwen3_next_f32.py`` reads.

A configuration names its adapter as ``"reference": {"weights_from":
"qwen3_next"}``.  The one place the yardstick knows how the program lays its
weights out; arrays are passed as they are served (the experts' stacks
``[held, H, F]`` of the experts HELD among them: stack row ``i`` is expert
``first + i``, which the reference's ``Shape.held`` says), the reference
widens them a layer and an expert at a time, and layers are produced on
demand.  A layer's kind is read off its parameters."""

from __future__ import annotations


def _value(x):
    return getattr(x, "value", x)  # unwrap flax Partitioned boxes


class _Layers:
    """``for lw in layers`` builds each layer's dict when it is reached."""

    def __init__(self, n, make):
        self._n, self._make = n, make

    def __len__(self):
        return self._n

    def __iter__(self):
        return (self._make(i) for i in range(self._n))


def adapt(params, num_layers: int) -> dict:
    p = params["params"] if "params" in params else params
    model = p["model"]

    def layer(i):
        lp = model[f"layer_{i}"]
        at, moe = lp["attn"], lp["moe_mlp"]
        lw = {"norm": _value(lp["input_norm"]["weight"]),
              "ffn_norm": _value(lp["post_attn_norm"]["weight"]),
              "router": _value(moe["router"]),
              "w_gate": _value(moe["gate"]), "w_up": _value(moe["up"]),
              "w_down": _value(moe["down"]),
              "ws_gate": _value(moe["shared_gate"]["kernel"]),
              "ws_up": _value(moe["shared_up"]["kernel"]),
              "ws_down": _value(moe["shared_down"]["kernel"]),
              "w_sgate": _value(moe["shared_expert_gate"])}
        if "in_proj_qkvz" in at:
            lw.update(kind="D",
                      w_qkvz=_value(at["in_proj_qkvz"]["kernel"]),
                      w_ba=_value(at["in_proj_ba"]["kernel"]),
                      conv_w=_value(at["conv_weight"]),
                      dt_bias=_value(at["dt_bias"]),
                      A_log=_value(at["A_log"]),
                      norm_w=_value(at["norm_weight"]),
                      w_out=_value(at["o_proj"]["kernel"]))
            return lw
        qkv = at["qkv"]
        H = _value(qkv["q_kernel"]).shape[0]
        flat = lambda w: _value(w).reshape(H, -1)  # noqa: E731
        lw.update(kind="A", wq=flat(qkv["q_kernel"]),
                  wk=flat(qkv["k_kernel"]), wv=flat(qkv["v_kernel"]),
                  wgate=_value(at["gate"]["kernel"]),
                  q_norm=_value(at["q_norm"]["weight"]),
                  k_norm=_value(at["k_norm"]["weight"]),
                  wo=_value(at["o_proj"]["kernel"]))
        return lw

    return {"embed": _value(model["embed"]["embedding"]),
            "final_norm": _value(model["final_norm"]["weight"]),
            "head": _value(p["lm_head"]["kernel"]),
            "layers": _Layers(num_layers, layer)}
