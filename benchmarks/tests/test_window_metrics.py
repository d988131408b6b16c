"""``harness/window_flops.py`` by hand-counted cases, and the readers of a
model of window and global layers on the trace that
``tools/trace_probe_scopes.py`` recorded on the v5e (a two-layer toy through
the real ``ServingEngine``: 7 decodes and 2 prefill chunks, 2 layers each),
read with a configuration that calls layer 0 global and layer 1 windowed."""

import copy
import os
import types

import pytest

from benchmarks.harness import manifest, trace_reduce, trace_scopes, window_flops
from benchmarks.harness.trace_scopes import Span

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe_scopes.xplane.pb")
CFG = {"num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 4,
       "hidden_size": 32, "serving": {"kv_cache_dtype": "bfloat16"},
       "program": {"kwargs": {"sliding_window": [None, 6]}}}
PEAK = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
CELL = "smallthinker-21b-a3b.serve-longdocs"


def test_a_layers_kind_is_read_from_the_name_stack():
    w = window_flops.layer_windows(CFG)
    stack = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_%d/attn/%s"
    assert window_flops.kind_of(stack % (0, "qkv/dot_general:"), w) == "full"
    assert window_flops.kind_of(stack % (1, "pallas_call:"), w) == "window"
    assert window_flops.kind_of(stack % (7, "pallas_call:"), w) is None
    assert window_flops.kind_of("jit(f)/LlamaForCausalLM/lm_head/dot:", w) \
        is None
    # a model with ONE window has no kinds to tell apart
    one = {"program": {"kwargs": {"sliding_window": 4096}}}
    assert window_flops.layer_windows(one) is None
    assert window_flops.kind_of(stack % (0, "pallas_call:"), None) is None


def test_operations_and_bytes_of_one_call():
    decode = Span("nxd/serve/dispatch", 0, 1,
                  {"active": 2, "ctx_tokens": 30, "window_tokens": 11})
    # a decode multiplies and reads each key once: 2 matmuls x 2 x 8 x 4
    assert window_flops.call_flops_bytes(decode, CFG, "full") == (
        4 * 8 * 4 * 30, 2 * 30 * 2 * 4 * 2)
    assert window_flops.call_flops_bytes(decode, CFG, "window") == (
        4 * 8 * 4 * 11, 2 * 11 * 2 * 4 * 2)
    chunk = Span("nxd/serve/prefill_chunk", 0, 1,
                 {"width": 4, "ctx_tokens": 20, "window_tokens": 9})
    # rows attend 17, 18, 19, 20 keys — or 6 each under the window; the
    # kernel reads the keys its rows span once
    assert window_flops.call_flops_bytes(chunk, CFG, "full") == (
        4 * 8 * 4 * (17 + 18 + 19 + 20), 2 * 20 * 2 * 4 * 2)
    assert window_flops.call_flops_bytes(chunk, CFG, "window") == (
        4 * 8 * 4 * 24, 2 * 9 * 2 * 4 * 2)
    t, bound = window_flops.least_seconds(decode, CFG, PEAK, "full")
    assert (t, bound) == (pytest.approx(4 * 8 * 4 * 30 / 1e3), "compute")


@pytest.fixture(scope="module")
def recorded():
    trace = trace_reduce.load(PATH, chips=1)
    return trace, trace_scopes.build(trace_scopes.read_space(PATH), trace)


def reading(monkeypatch, sc, cfg=CFG, counters=None):
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    return types.SimpleNamespace(
        trace=object(), counters=counters or {}, peak=PEAK,
        cell=types.SimpleNamespace(config=cfg, name="x"))


def test_a_full_and_a_window_call_are_told_apart(recorded, monkeypatch):
    trace, sc = recorded
    cell = manifest.Cell(CELL)
    read = {n: cell.layer_metric(n).read for n in (
        "attn_full_time_share.served", "attn_window_time_share.served",
        "paged_full_roofline.served", "paged_window_roofline.served")}
    r = reading(monkeypatch, sc)
    full, window = (read["attn_full_time_share.served"](r),
                    read["attn_window_time_share.served"](r))
    # the toy's two layers are alike: each kind takes its layer's half of
    # the serving attention (the train steps' attention names no kind here:
    # their stack holds layer_N too, so they are in; what matters is that
    # the two shares split one total)
    both = sum(op.own for op in sc.devices[0].ops
               if op.group in window_flops.PAGED_GROUPS + ("attn_proj",)
               and window_flops.kind_of(op.tf_op, [None, 6]))
    assert full > 0 and window > 0
    assert full + window == pytest.approx(100.0 * both / sc.busy_s)
    assert 0.5 < full / window < 2.0
    # the recorded program wrote no window_tokens: the global layers' calls
    # have their keys, the window layers' have nothing to read
    assert read["paged_full_roofline.served"](r) > 0
    assert read["paged_window_roofline.served"](r) is None
    # ... and with the key the program of this PR writes, they read too
    with_keys = copy.deepcopy(sc)
    for s in with_keys.spans:
        if "ctx_tokens" in s.attrs:
            s.attrs["window_tokens"] = min(float(s.attrs["ctx_tokens"]), 6.0)
    for d in with_keys.devices:
        for p in d.programs:
            if p.span is not None and "ctx_tokens" in p.span.attrs:
                p.span.attrs["window_tokens"] = min(
                    float(p.span.attrs["ctx_tokens"]), 6.0)
    r = reading(monkeypatch, with_keys)
    got = read["paged_window_roofline.served"](r)
    assert got is not None and 0 < got <= read["paged_full_roofline.served"](r)
    # a configuration with one window for every layer has no kinds
    one = {**CFG, "program": {"kwargs": {"sliding_window": 6}}}
    r = reading(monkeypatch, sc, cfg=one)
    assert all(read[n](r) is None for n in read)
    # an untraced run reads nothing
    monkeypatch.setattr(trace_scopes, "of", lambda r: None)
    assert all(read[n](r) is None for n in read)


def test_window_pages_held_share_reads_the_two_counters():
    read = manifest.Cell(CELL).layer_metric("window_pages_held_share").read
    r = types.SimpleNamespace(counters={
        "kvcache/window_pages_held_total": 30.0,
        "kvcache/window_pages_unfreed_total": 120.0})
    assert read(r) == 25.0
    # the parent program has no such counters: nothing, and no error
    assert read(types.SimpleNamespace(counters={})) is None
    assert read(types.SimpleNamespace(
        counters={"kvcache/window_pages_unfreed_total": 0.0})) is None
