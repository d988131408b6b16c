"""llama_weights.py — from ``models.llama.LlamaForCausalLM``'s parameter tree
to the plain dict ``decoder_f32.py`` reads.

A configuration names its adapter as ``"reference": {"weights_from":
"llama"}`` -> ``benchmarks/reference/llama_weights.py`` -> ``adapt``.  This is
the one place the yardstick knows how the program lays its weights out;
arrays are passed as they are (device arrays, in the served dtype) — the
reference widens them, a layer at a time.  Layers are produced one by one,
on demand: slicing the fused gate/up kernel copies it, and sixteen layers of
such copies would not fit beside the served weights."""

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
    """q/k/v kernels ``[H, heads, D]`` (query heads kv-major, which is also
    the Hugging Face order), fused gate/up ``[H, 2, F]``."""
    p = params["params"] if "params" in params else params
    model = p["model"]

    def layer(i):
        lp = model[f"layer_{i}"]
        qkv, mlp = lp["attn"]["qkv"], lp["mlp"]
        H = _value(qkv["q_kernel"]).shape[0]
        flat = lambda w: _value(w).reshape(H, -1)  # noqa: E731
        bias = lambda n: (_value(qkv[n]).reshape(-1)  # noqa: E731
                          if n in qkv else None)
        gate_up = _value(mlp["gate_up"]["kernel"])
        return {
            "norm1": _value(lp["input_norm"]["weight"]),
            "norm2": _value(lp["post_attn_norm"]["weight"]),
            "wq": flat(qkv["q_kernel"]), "wk": flat(qkv["k_kernel"]),
            "wv": flat(qkv["v_kernel"]),
            "bq": bias("q_bias"), "bk": bias("k_bias"), "bv": bias("v_bias"),
            "wo": _value(lp["attn"]["o_proj"]["kernel"]),
            "w_gate": gate_up[:, 0, :], "w_up": gate_up[:, 1, :],
            "w_down": _value(mlp["down"]["kernel"]),
        }

    return {"embed": _value(model["embed"]["embedding"]),
            "final_norm": _value(model["final_norm"]["weight"]),
            "head": _value(p["lm_head"]["kernel"]),
            "layers": _Layers(num_layers, layer)}
