"""granite_hybrid_weights.py — from ``models.llama.LlamaForCausalLM``'s
parameter tree, built with the layer lists of a Granite-4.0-H configuration
(``mixer_types`` of ``"mamba2"`` and ``"attention"``, ``ffn_types`` all
``"mlp"``, ``tie_word_embeddings``), to the plain dict
``granite_hybrid_f32.py`` reads.

A configuration names its adapter as ``"reference": {"weights_from":
"granite_hybrid"}``.  The one place the yardstick knows how the program lays
these weights out; arrays are passed as they are served, the reference
widens them a layer at a time, and layers are produced on demand (slicing
the fused gate/up kernel copies it).  A layer's kind is read off its
parameters; the head is the table (``"head": None``) unless the tree holds
an ``lm_head`` of its own."""

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
        mixer, mlp = lp["attn"], lp["mlp"]
        gate_up = _value(mlp["gate_up"]["kernel"])          # [H, 2, F]
        out = {"norm": _value(lp["input_norm"]["weight"]),
               "norm2": _value(lp["post_attn_norm"]["weight"]),
               "w_gate": gate_up[:, 0, :], "w_up": gate_up[:, 1, :],
               "w_down": _value(mlp["down"]["kernel"])}
        if "in_proj" in mixer:
            out.update(kind="mamba",
                       w_in=_value(mixer["in_proj"]["kernel"]),
                       conv_w=_value(mixer["conv_weight"]),
                       conv_b=_value(mixer["conv_bias"]),
                       dt_bias=_value(mixer["dt_bias"]),
                       A_log=_value(mixer["A_log"]), D=_value(mixer["D"]),
                       norm_w=_value(mixer["norm_weight"]),
                       w_out=_value(mixer["out_proj"]["kernel"]))
            return out
        qkv = mixer["qkv"]
        H = _value(qkv["q_kernel"]).shape[0]
        flat = lambda w: _value(w).reshape(H, -1)  # noqa: E731
        out.update(kind="attention", wq=flat(qkv["q_kernel"]),
                   wk=flat(qkv["k_kernel"]), wv=flat(qkv["v_kernel"]),
                   wo=_value(mixer["o_proj"]["kernel"]))
        return out

    return {"embed": _value(model["embed"]["embedding"]),
            "final_norm": _value(model["final_norm"]["weight"]),
            "head": (_value(p["lm_head"]["kernel"]) if "lm_head" in p
                     else None),
            "layers": _Layers(num_layers, layer)}
