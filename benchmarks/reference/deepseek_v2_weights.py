"""deepseek_v2_weights.py — from ``models.llama.LlamaForCausalLM``'s
parameter tree, built with ``mixer_types`` ``"mla"`` and an ``ffn_types`` of
one dense layer and routed ones (gated experts, a shared one, no router
bias), to the plain dict ``deepseek_v2_f32.py`` reads.

A configuration names its adapter as ``"reference": {"weights_from":
"deepseek_v2"}``.  The one place the yardstick knows how the program lays its
weights out; arrays are passed as they are served (the experts' stacks ``[Eh,
H, F]`` of the experts HELD among them: stack row ``i`` is expert ``first +
i``, which the reference's ``Shape.held`` says), the reference widens them a
layer and an expert at a time, and layers are produced on demand.  A layer's
kind is read off its parameters."""

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
        at = lp["attn"]
        lw = {"attn_norm": _value(lp["input_norm"]["weight"]),
              "ffn_norm": _value(lp["post_attn_norm"]["weight"]),
              "wq_a": _value(at["q_a"]["kernel"]),
              "q_a_norm": _value(at["q_a_norm"]["weight"]),
              "wq_b": _value(at["q_b"]["kernel"]),
              "wkv_a": _value(at["kv_a"]["kernel"]),
              "kv_a_norm": _value(at["kv_a_norm"]["weight"]),
              "wkv_b": _value(at["kv_b"]),
              "wo": _value(at["o_proj"]["kernel"])}
        if "moe_mlp" in lp:
            moe = lp["moe_mlp"]
            lw.update(router=_value(moe["router"]),
                      w_gate=_value(moe["gate"]), w_up=_value(moe["up"]),
                      w_down=_value(moe["down"]),
                      ws_gate=_value(moe["shared_gate"]["kernel"]),
                      ws_up=_value(moe["shared_up"]["kernel"]),
                      ws_down=_value(moe["shared_down"]["kernel"]))
        else:
            gu = _value(lp["mlp"]["gate_up"]["kernel"])      # [C, 2, F]
            lw.update(w_gate=gu[:, 0], w_up=gu[:, 1],
                      w_down=_value(lp["mlp"]["down"]["kernel"]))
        return lw

    return {"embed": _value(model["embed"]["embedding"]),
            "final_norm": _value(model["final_norm"]["weight"]),
            "head": _value(p["lm_head"]["kernel"]),
            "layers": _Layers(num_layers, layer)}
