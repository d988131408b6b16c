"""nemotron_h_weights.py — from ``models.llama.LlamaForCausalLM``'s
parameter tree, built with the one-sublayer layer lists (``mixer_types`` /
``ffn_types``: ``"mamba2"``, ``"attention"``, a routed block alone), to the
plain dict ``nemotron_h_f32.py`` reads.

A configuration names its adapter as ``"reference": {"weights_from":
"nemotron_h"}``.  The one place the yardstick knows how the program lays its
weights out; arrays are passed as they are served (the held experts' stacks
``[held, H, F]`` among them), the reference widens them a layer and an expert
at a time, and layers are produced on demand.  A layer's kind is read off its
parameters."""

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
        if "moe_mlp" in lp:
            moe = lp["moe_mlp"]
            return {"kind": "E",
                    "norm": _value(lp["post_attn_norm"]["weight"]),
                    "router": _value(moe["router"]),
                    "router_bias": _value(moe["router_bias"]),
                    # the program stores each expert's up matrix out-major
                    "w_up": _value(moe["up"]).swapaxes(1, 2),
                    "w_down": _value(moe["down"]),
                    "ws_up": _value(moe["shared_up"]["kernel"]),
                    "ws_down": _value(moe["shared_down"]["kernel"])}
        mixer = lp["attn"]
        norm = _value(lp["input_norm"]["weight"])
        if "in_proj" in mixer:
            return {"kind": "M", "norm": norm,
                    "w_in": _value(mixer["in_proj"]["kernel"]),
                    "conv_w": _value(mixer["conv_weight"]),
                    "conv_b": _value(mixer["conv_bias"]),
                    "dt_bias": _value(mixer["dt_bias"]),
                    "A_log": _value(mixer["A_log"]), "D": _value(mixer["D"]),
                    "norm_w": _value(mixer["norm_weight"]),
                    "w_out": _value(mixer["out_proj"]["kernel"])}
        qkv = mixer["qkv"]
        H = _value(qkv["q_kernel"]).shape[0]
        flat = lambda w: _value(w).reshape(H, -1)  # noqa: E731
        return {"kind": "*", "norm": norm, "wq": flat(qkv["q_kernel"]),
                "wk": flat(qkv["k_kernel"]), "wv": flat(qkv["v_kernel"]),
                "wo": _value(mixer["o_proj"]["kernel"])}

    return {"embed": _value(model["embed"]["embedding"]),
            "final_norm": _value(model["final_norm"]["weight"]),
            "head": _value(p["lm_head"]["kernel"]),
            "layers": _Layers(num_layers, layer)}
