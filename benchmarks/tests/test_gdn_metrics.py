"""The readers the Qwen3-Next cell adds, on the trace that
``tools/trace_probe_scopes.py`` recorded on the v5e (a two-layer toy through
the real ``ServingEngine``: 7 decodes and 2 prefill chunks): the recorded
program has no delta-rule scope or counter — the readers find nothing and
return ``None``, as on a parent commit — and with the attention operations of
its serve programs renamed as a gated-delta layer names its core, they read.
And ``harness/gdn_flops.py`` at the published sizes."""

import copy
import os
import types

import pytest

from benchmarks.harness import gdn_flops, manifest, trace_reduce, trace_scopes
from benchmarks.layer_metrics import paged_roofline

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe_scopes.xplane.pb")
CELL = "qwen3-next-80b-a3b.serve-longdocs"
# the published sizes, three delta layers of four
CFG = {"linear_num_key_heads": 16, "linear_num_value_heads": 32,
       "linear_key_head_dim": 128, "linear_value_head_dim": 128,
       "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
       "num_hidden_layers": 4}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAMES = ("gdn_time_share.served", "gdn_chunk_roofline.served",
         "gdn_step_roofline.served", "gdn_tokens_step_share",
         "attn_gate_time_share.served", "paged_program_roofline.served")


def test_a_steps_and_a_chunks_work_at_the_published_sizes():
    state, taps = 32 * 128 * 128 * 4, 3 * 8192 * 2
    assert (state, taps) == (2097152, 49152)       # a layer's row: 2.05 MiB
    row = (8192 + 4096) * 2                        # q, k, v in; o out
    assert gdn_flops.core_bytes(1, 1, CFG) == 2 * (state + taps) + row
    assert gdn_flops.core_bytes(8, 8, CFG) == 8 * (2 * (state + taps) + row)
    assert gdn_flops.core_bytes(512, 1, CFG) == 2 * (state + taps) + 512 * row
    # a block a head: five [64, 64] products and three against the state
    block = 5 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128
    assert block == 11534336
    assert gdn_flops.chunk_flops(512, CFG) == 8 * 32 * block
    assert gdn_flops.chunk_flops(513, CFG) == 9 * 32 * block   # whole blocks
    t, bound = gdn_flops.chunk_least_seconds(512, CFG, PEAK)
    assert bound == "memory" and t == pytest.approx(
        gdn_flops.core_bytes(512, 1, CFG) / 819e9)
    assert gdn_flops.step_least_seconds(8, CFG, PEAK) == pytest.approx(
        8 * (2 * (state + taps) + row) / 819e9)
    assert gdn_flops.delta_layers(CFG) == 3
    assert gdn_flops.delta_layers({**CFG, "num_hidden_layers": 12}) == 9
    assert gdn_flops.delta_layers({"num_hidden_layers": 12}) == 0


def test_the_configuration_file_is_the_published_one_cut_as_it_says():
    cfg = manifest.Cell(CELL).config
    pub = cfg["published"]
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers"]
    assert all(cfg[k] == v for k, v in pub.items() if k not in cfg["reduced"])
    assert (cfg["num_hidden_layers"], pub["num_hidden_layers"]) == (12, 48)
    held = cfg["experts_held"]
    assert (held["first"], held["count"], held["of"]) == (0, 128, 512) \
        and cfg["num_experts"] == 128 and pub["num_experts"] == 512
    kw = cfg["program"]["kwargs"]
    assert kw["num_experts"] == 512 and kw["moe_experts_held"] == [0, 128]
    for ours, theirs in (("gdn_key_heads", "linear_num_key_heads"),
                         ("gdn_value_heads", "linear_num_value_heads"),
                         ("gdn_key_head_dim", "linear_key_head_dim"),
                         ("gdn_value_head_dim", "linear_value_head_dim"),
                         ("gdn_conv_kernel", "linear_conv_kernel_dim"),
                         ("head_dim", "head_dim"), ("num_heads",
                                                    "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("moe_top_k", "num_experts_per_tok"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("moe_shared_intermediate_size",
                          "shared_expert_intermediate_size"),
                         ("partial_rotary_factor", "partial_rotary_factor"),
                         ("rope_theta", "rope_theta"),
                         ("rms_eps", "rms_norm_eps"),
                         ("vocab_size", "vocab_size"),
                         ("hidden_size", "hidden_size")):
        assert kw[ours] == pub[theirs], ours
    every = pub["full_attention_interval"]
    assert kw["mixer_types"] == [
        "attention" if (i + 1) % every == 0 else "gated-delta"
        for i in range(12)]
    mix = manifest.Cell(CELL).traffic
    other = manifest._load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", "longdocs-32k-backlog.json"))
    # the numbers are the other routed long-document cell's; the runner's
    # name, the words and the lead-in (found by the phase probe) are its own
    own = ("kind", "what", "lead_in_s", "lead_in_why")
    assert {k: v for k, v in mix.items() if k not in own} \
        == {k: v for k, v in other.items() if k not in own}
    assert mix["lead_in_s"] >= other["lead_in_s"]


@pytest.fixture(scope="module")
def recorded():
    trace = trace_reduce.load(PATH, chips=1)
    return trace_scopes.build(trace_scopes.read_space(PATH), trace)


def reading(monkeypatch, sc, counters=None, cfg=CFG):
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    return types.SimpleNamespace(
        trace=object(), counters=counters or {}, peak=PEAK,
        cell=types.SimpleNamespace(config=cfg, name="x"))


def renamed(sc):
    """The recorded serve programs with their attention kernels' operations
    under the scopes a gated-delta layer gives its core (a decode's under
    ``gdn_step``, a chunk's under ``gdn_chunk``), the pool write's under
    ``state_write``, and the span key the engine adds."""
    sc = copy.deepcopy(sc)
    took = {"step": 0.0, "chunk": 0.0}
    programs = set()        # those that ran a core whole inside the window
    lo, hi = sc.window
    for d in sc.devices:
        for op in d.ops:
            prog = d.programs[op.program] if op.program >= 0 else None
            span = prog.span if prog is not None else None
            if span is None or op.group not in ("paged_decode", "paged_chunk",
                                                "kv_write"):
                continue
            if not span.name.endswith(("dispatch", "prefill_chunk")):
                continue        # a page copy under ``admit``: no core
            path = "step" if span.name.endswith("dispatch") else "chunk"
            scope = ("state_write" if op.group == "kv_write"
                     else "gdn_step" if path == "step" else "gdn_chunk")
            op.tf_op = f"jit(_paged_step_fn)/model/layer_0/attn/{scope}/x:"
            if prog.start >= lo and prog.end <= hi:
                took[path] += op.own
                programs.add(op.program)
        for p in d.programs:
            if p.span is not None and p.span.name.endswith("dispatch"):
                p.span.attrs["state_rows"] = p.span.attrs["active"]
    return sc, took, programs


def test_the_readers_on_a_recorded_trace(recorded, monkeypatch):
    cell = manifest.Cell(CELL)
    read = {n: cell.layer_metric(n).read for n in NAMES}
    # a program without the scopes and the counters (the parent's): nothing
    # to read, and no error — the D-256 walk's reader reads the recorded
    # walks themselves, but not of a configuration without the interval
    r = reading(monkeypatch, recorded, cfg={})
    assert all(read[n](r) is None for n in NAMES)
    r = reading(monkeypatch, recorded)
    assert all(read[n](r) is None for n in NAMES[:5])
    sc, took, ran = renamed(recorded)
    assert took["step"] > 0 and took["chunk"] > 0
    r = reading(monkeypatch, sc, counters={
        "serving/gdn_tokens_total/step": 300.0,
        "serving/gdn_tokens_total/chunk": 900.0})
    dev = sc.devices[0]
    whole = [dev.programs[i] for i in sorted(ran)]
    # the step: one read and one write of every stepped row's state and taps
    # a delta layer, over the HBM's bandwidth
    rows = [float(p.span.attrs["state_rows"]) for p in whole
            if p.span.name.endswith("dispatch")]
    assert rows
    least = sum(3 * gdn_flops.core_bytes(n, n, CFG) / 819e9 for n in rows)
    step = read["gdn_step_roofline.served"](r)
    assert step == pytest.approx(100.0 * least / took["step"]) and step > 0
    # the chunk: the larger of its matmuls and its bytes, a delta layer
    chunks = [min(float(p.span.attrs["width"]),
                  float(p.span.attrs["ctx_tokens"])) for p in whole
              if p.span.name.endswith("prefill_chunk")]
    assert chunks
    least = sum(3 * max(gdn_flops.chunk_flops(n, CFG) / 197e12,
                        gdn_flops.core_bytes(n, 1, CFG) / 819e9)
                for n in chunks)
    assert read["gdn_chunk_roofline.served"](r) == pytest.approx(
        100.0 * least / took["chunk"])
    assert read["gdn_tokens_step_share"](r) == pytest.approx(25.0)
    share = read["gdn_time_share.served"](r)
    assert 0 < share <= 100.0
    # the gate: nothing under its scope in the recorded program
    assert read["attn_gate_time_share.served"](r) is None
    # an untraced run reads no trace metric; the counters' need no trace
    monkeypatch.setattr(trace_scopes, "of", lambda r: None)
    assert read["gdn_step_roofline.served"](r) is None
    assert read["gdn_chunk_roofline.served"](r) is None
    assert read["gdn_time_share.served"](r) is None
    assert read["gdn_tokens_step_share"](r) == pytest.approx(25.0)
    # state_read / state_write alone are every recurrent kind's names: a
    # program whose only such operations are theirs reads nothing here
    sc2 = copy.deepcopy(sc)
    for d in sc2.devices:
        for op in d.ops:
            op.tf_op = op.tf_op.replace("gdn_step", "ssm_step").replace(
                "gdn_chunk", "ssm_scan_chunk")
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc2)
    assert read["gdn_time_share.served"](r) is None
    assert read["gdn_step_roofline.served"](r) is None


@pytest.mark.parametrize("kwargs", [
    {"mixer_types": ["gated-delta"] * 3 + ["attention"], "num_layers": 4},
    {"num_layers": 1}], ids=["a_layer_list", "no_layer_list"])
def test_the_walk_in_parts_is_counted_once_a_program(recorded, monkeypatch,
                                                     kwargs):
    """``paged_program_roofline`` books a program's least time once a layer
    that keeps pages (the ``"attention"`` entries of the program's layer
    list, every layer without one), whatever the calls the walk is cut
    into; on the recorded trace (one call a layer, one such layer) it reads
    what ``paged_roofline`` reads of the programs that ran whole in the
    window."""
    cell = manifest.Cell(CELL)
    cfg = {"num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
           "hidden_size": 128, "program": {"kwargs": kwargs},
           "serving": {"kv_cache_dtype": "bfloat16"}}
    r = reading(monkeypatch, recorded, cfg=cfg)
    got = cell.layer_metric("paged_program_roofline.served").read(r)
    dev = recorded.devices[0]
    lo, hi = recorded.window
    least = measured = 0.0
    for op in dev.ops:
        if op.group not in ("paged_decode", "paged_chunk") or op.program < 0:
            continue
        prog = dev.programs[op.program]
        if prog.span is None or prog.start < lo or prog.end > hi \
                or "ctx_tokens" not in prog.span.attrs:
            continue
        measured += op.end - op.start
    seen = set()
    for op in dev.ops:
        if op.group in ("paged_decode", "paged_chunk") and op.program >= 0 \
                and op.program not in seen:
            prog = dev.programs[op.program]
            if prog.span is not None and prog.start >= lo \
                    and prog.end <= hi and "ctx_tokens" in prog.span.attrs:
                seen.add(op.program)
                least += paged_roofline.least_seconds(prog.span, cfg, PEAK)[0]
    assert got == pytest.approx(100.0 * least / measured) and got > 0
