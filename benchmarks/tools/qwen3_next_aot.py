#!/usr/bin/env python
"""qwen3_next_aot.py — compile the paged decode and chunk-prefill programs of
a Qwen3-Next configuration (gated delta-rule layers beside gated attention
layers whose heads are 256 wide, a routed block that holds a share of its
experts in every layer) at REAL size for a described (not attached)
``v5e:2x2``, in the sandbox, at no chip time.

    JAX_PLATFORMS=cpu python benchmarks/tools/qwen3_next_aot.py --workload <cell> [--layers N]

``granite_aot.py``'s compile (the pool built as the program lays a K/V page
out, the state rows as the layer's record gives them).  Prints, beside
``memory_analysis()`` of each program: the pool's bytes by shape and as the
device lays them out, and both over the tokens the pool holds; whether the
text holds a copy shaped like a pool or a state array (none: both are
donated and updated in place); and the Mosaic calls by name (``gdn_chunk``,
``gdn_step``, ``paged_attention_*``, ``kv_pool_write``, the grouped matmuls).

``--layers N`` keeps the first N entries of the layer lists."""

import argparse
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

GIB = 2.0 ** 30


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args()

    from benchmarks.harness import manifest
    from benchmarks.tools.aot_compile import report
    from benchmarks.tools.granite_aot import (
        compile_serve_programs,
        laid_out_bytes,
    )
    from benchmarks.tools.sala_aot import pool_copies

    cell = manifest.Cell(args.workload)
    s = cell.config["serving"]
    programs, nbytes, pool_bytes, shapes, mcfg = compile_serve_programs(
        cell, args.layers)
    kinds = mcfg.mixer_types
    page, states = shapes[0], shapes[1:]
    n_attn, n_delta = kinds.count("attention"), kinds.count("gated-delta")
    tokens = (s["num_pages"] - 1) * s["page_size"]
    plain = 2 * n_attn * page.size * page.dtype.itemsize
    state_bytes = n_delta * sum(x.size * x.dtype.itemsize for x in states)
    print(f"[aot] {cell.name}: {mcfg.num_layers} layers ({n_delta} "
          f"gated-delta, {n_attn} attention); weights {nbytes / GIB:.3f} "
          f"GiB; K/V pages {plain / GIB:.3f} GiB by shape ({s['num_pages']} "
          f"pages of {s['page_size']}, arrays {list(page.shape)}), state "
          f"rows {state_bytes / GIB:.3f} GiB ({s['slots']} rows)")
    totals = []
    for name, compiled in programs:
        totals.append(report(name, compiled))
        text = compiled.as_text()
        laid, layout = laid_out_bytes(text, page)
        if laid is not None:
            laid *= 2 * n_attn
            print(f"[aot] {name}: a pool array {list(page.shape)} is laid "
                  f"out as {{{layout}}}: K/V pages {laid / GIB:.3f} GiB on "
                  f"the device, {laid / (s['num_pages'] * s['page_size']):.0f}"
                  f" bytes a token ({plain / (s['num_pages'] * s['page_size']):.0f}"
                  " by shape)")
        copies = pool_copies(text, shapes)
        print(f"[aot] {name}: {len(copies)} pool- or state-shaped copies"
              + "".join("\n      " + c for c in copies[:6]))
        calls = {}
        for call in re.findall(
                r"%(gdn_\w+?|paged_attention_\w+?|kv_pool_write|gmm)"
                r"[.\d]* = [^\n]*tpu_custom_call", text):
            calls[call] = calls.get(call, 0) + 1
        print(f"[aot] {name}: Mosaic calls by name {calls}", flush=True)
    print(f"[aot] resident weights + pool {(nbytes + pool_bytes) / GIB:.2f} "
          f"GiB by shape over {tokens} tokens of pages; largest program "
          f"total {max(totals) / GIB:.2f} GiB")


if __name__ == "__main__":
    main()
