"""Operations and bytes of ONE paged-attention call of a model whose layers
come in kinds — window layers beside global ones — and which kind a device
operation's layer is: the yardstick's own arithmetic for
``paged_full_roofline`` / ``paged_window_roofline`` and the two attention
shares.

A call's kind is its LAYER's: the operation's name stack holds
``model/layer_N/attn`` and the configuration's
``program.kwargs.sliding_window`` names each layer's window (``null``: the
layer attends everything).  Its keys come from the host span that launched
its program, by ``paged_roofline.py``'s arithmetic: a GLOBAL layer's from
``ctx_tokens`` (every key before the row), a WINDOW layer's from
``window_tokens`` (each row's keys capped at the window: the program writes
it beside ``ctx_tokens`` where the model has a window) — a decode reads and
multiplies its keys once a row; a chunk of ``width`` causal rows reads the
keys its rows span once and multiplies each row's own.
"""

from __future__ import annotations

import re
from typing import Optional

import numpy as np

from benchmarks.harness import flops, trace_scopes

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}
PAGED_GROUPS = ("paged_decode", "paged_chunk")
_LAYER = re.compile(r"layer_(\d+)$")


def layer_windows(cfg: dict) -> Optional[list]:
    """Each layer's window from the configuration's program arguments;
    None where the model has no window of a layer's own."""
    w = cfg["program"]["kwargs"].get("sliding_window")
    return list(w) if isinstance(w, (list, tuple)) else None


def kind_of(tf_op: str, windows) -> Optional[str]:
    """``"full"`` or ``"window"``: the kind of the layer an operation's name
    stack names; None where it names none (the embedding, the head) or the
    model has no kinds."""
    if not windows:
        return None
    for part in trace_scopes.components(tf_op):
        m = _LAYER.match(part)
        if m and int(m.group(1)) < len(windows):
            return "full" if windows[int(m.group(1))] is None else "window"
    return None


def keys_attr(kind: str) -> str:
    return "ctx_tokens" if kind == "full" else "window_tokens"


def call_flops_bytes(span, cfg: dict, kind: str):
    """``(operations, bytes)`` of one call of ``kind`` in the program the
    span launched."""
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    keys = float(span.attrs[keys_attr(kind)])
    if span.name.endswith("prefill_chunk"):
        width = int(span.attrs["width"])
        # the last row attends ``last`` keys, row r of the chunk (width - 1
        # - r) fewer; a window layer's rows are capped at the window
        last = float(span.attrs["ctx_tokens"])
        cap = (float("inf") if kind == "full"
               else min(w for w in layer_windows(cfg) if w is not None))
        rows = np.minimum(last - width + 1 + np.arange(width), cap)
        pairs = float(np.sum(np.maximum(rows, 0)))
    else:
        pairs = keys
    kv_bytes = DTYPE_BYTES[cfg["serving"]["kv_cache_dtype"]]
    return 2 * 2.0 * nq * d * pairs, 2.0 * keys * nkv * d * kv_bytes


def least_seconds(span, cfg: dict, peak: dict, kind: str):
    """The least time of ONE kernel call of ``kind``, and its bound."""
    return flops.roofline_seconds(*call_flops_bytes(span, cfg, kind), peak)


def roofline(r, kind: str) -> Optional[float]:
    """Over the traced window's paged calls of ``kind``: their least time
    summed over their measured time summed, in percent; None where no such
    call was launched by a span that names its keys."""
    sc = trace_scopes.of(r)
    if sc is None or r.peak is None or not sc.devices:
        return None
    cfg, dev = r.cell.config, sc.devices[0]
    windows = layer_windows(cfg)
    least = measured = 0.0
    bounds = {}
    for op in dev.ops:
        span = dev.programs[op.program].span if op.program >= 0 else None
        if op.group not in PAGED_GROUPS or span is None \
                or kind_of(op.tf_op, windows) != kind \
                or keys_attr(kind) not in span.attrs \
                or "ctx_tokens" not in span.attrs:
            continue
        t, bound = least_seconds(span, cfg, r.peak, kind)
        bounds[(op.group, bound)] = bounds.get((op.group, bound), 0) + 1
        least += t
        measured += op.end - op.start
    if not measured:
        return None
    print(f"[paged_{kind}_roofline] calls by kernel and bound {bounds}: "
          f"least {least * 1e3:.3f} ms over measured {measured * 1e3:.3f} ms",
          flush=True)
    return 100.0 * least / measured


def attention_share(r, kind: str) -> Optional[float]:
    """Self time of the paged calls and the attention projections (q/k/v and
    output projections, RoPE, layout changes around the kernel) of the
    layers of ``kind`` over the device's busy time, in percent."""
    sc = trace_scopes.of(r)
    if sc is None or not sc.devices or not sc.busy_s:
        return None
    windows = layer_windows(r.cell.config)
    got = sum(op.own for d in sc.devices for op in d.ops
              if op.group in PAGED_GROUPS + ("attn_proj",)
              and kind_of(op.tf_op, windows) == kind) / len(sc.devices)
    return 100.0 * got / sc.busy_s if got else None
