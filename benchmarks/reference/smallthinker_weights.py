"""smallthinker_weights.py — from ``models.llama.LlamaForCausalLM``'s
parameter tree (a SmallThinker configuration: a window and a RoPE switch a
layer, ReGLU experts routed on the attention's input) to the plain dict
``smallthinker_f32.py`` reads.

A configuration names its adapter as ``"reference": {"weights_from":
"smallthinker"}`` -> ``benchmarks/reference/smallthinker_weights.py`` ->
``adapt``.  Arrays
are passed as they are (device arrays, in the served dtype); the reference
widens them, a layer and an expert at a time.  Layers are produced one by
one, on demand, as ``llama_weights.py`` does: reshaping the q/k/v kernels
copies them."""

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
    """q/k/v kernels ``[H, heads, D]``, router ``[H, E]``, expert gate and
    up ``[E, H, F]`` each (the dropless layout), expert down ``[E, F, H]``."""
    p = params["params"] if "params" in params else params
    model = p["model"]

    def layer(i):
        lp = model[f"layer_{i}"]
        attn, qkv, moe = lp["attn"], lp["attn"]["qkv"], lp["moe_mlp"]
        H = _value(qkv["q_kernel"]).shape[0]
        flat = lambda w: _value(w).reshape(H, -1)  # noqa: E731
        return {
            "norm1": _value(lp["input_norm"]["weight"]),
            "norm2": _value(lp["post_attn_norm"]["weight"]),
            "wq": flat(qkv["q_kernel"]), "wk": flat(qkv["k_kernel"]),
            "wv": flat(qkv["v_kernel"]),
            "wo": _value(attn["o_proj"]["kernel"]),
            "router": _value(moe["router"]),
            "w_gate": _value(moe["gate"]), "w_up": _value(moe["up"]),
            "w_down": _value(moe["down"]),
        }

    return {"embed": _value(model["embed"]["embedding"]),
            "final_norm": _value(model["final_norm"]["weight"]),
            "head": _value(p["lm_head"]["kernel"]),
            "layers": _Layers(num_layers, layer)}
