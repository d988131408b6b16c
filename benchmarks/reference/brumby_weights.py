"""brumby_weights.py — from ``models.llama.LlamaForCausalLM``'s parameter
tree, built with ``mixer_types = ["power-retention"] * L``
(``models/hybrid.py``), to the plain dict ``brumby_f32.py`` reads.

A configuration names its adapter as ``"reference": {"weights_from":
"brumby"}``.  The one place the yardstick knows how the program lays its
weights out; arrays are passed as they are served, the reference widens them
where it multiplies, and layers are produced on demand (slicing the fused
gate/up kernel copies it)."""

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
    """q/k/v kernels ``[H, heads, D]`` -> ``[H, heads * D]`` (query heads
    kv-major, the Hugging Face order), fused gate/up ``[H, 2, F]`` apart."""
    p = params["params"] if "params" in params else params
    model = p["model"]

    def layer(i):
        lp = model[f"layer_{i}"]
        attn, mlp = lp["attn"], lp["mlp"]
        qkv = attn["qkv"]
        H = _value(qkv["q_kernel"]).shape[0]
        flat = lambda w: _value(w).reshape(H, -1)  # noqa: E731
        gate_up = _value(mlp["gate_up"]["kernel"])
        return {
            "norm1": _value(lp["input_norm"]["weight"]),
            "norm2": _value(lp["post_attn_norm"]["weight"]),
            "wq": flat(qkv["q_kernel"]), "wk": flat(qkv["k_kernel"]),
            "wv": flat(qkv["v_kernel"]),
            "q_norm": _value(attn["q_norm"]["weight"]),
            "k_norm": _value(attn["k_norm"]["weight"]),
            "w_decay": _value(attn["gate"]["kernel"]),
            "b_decay": _value(attn["gate_bias"]),
            "wo": _value(attn["o_proj"]["kernel"]),
            "w_gate": gate_up[:, 0, :], "w_up": gate_up[:, 1, :],
            "w_down": _value(mlp["down"]["kernel"]),
        }

    return {"embed": _value(model["embed"]["embedding"]),
            "final_norm": _value(model["final_norm"]["weight"]),
            "head": _value(p["lm_head"]["kernel"]),
            "layers": _Layers(num_layers, layer)}
