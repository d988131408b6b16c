"""The four readers the Granite-4.0-H cell adds, on the trace that
``tools/trace_probe_scopes.py`` recorded on the v5e (a two-layer toy through
the real ``ServingEngine``: 7 decodes and 2 prefill chunks): the recorded
program has no Mamba-2 scope, counter or gauge — the readers find nothing and
return ``None``, as on a parent commit — and with the attention operations of
its serve programs renamed as a Mamba-2 layer names its core, they read."""

import copy
import os
import types

import pytest

from benchmarks.harness import manifest, ssm_flops, trace_reduce, trace_scopes

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe_scopes.xplane.pb")
CELL = "granite-4.0-h-micro.serve-sessions"
# the harness's spellings of the published sizes, two scan layers of three
CFG = {"mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 1,
       "ssm_state_size": 128, "conv_kernel": 4,
       "hybrid_override_pattern": "M*M"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAMES = ("ssm_step_roofline.served", "ssm_chunk_roofline.served",
         "ssm_tokens_step_share", "kv_page_bytes_per_token")


def test_a_steps_and_a_chunks_bytes_at_the_published_sizes():
    state, taps = 64 * 64 * 128 * 4, 3 * (4096 + 256) * 2
    assert (state, taps) == (2097152, 26112)       # a layer's row: 2.02 MiB
    row = (4096 + 256 + 4096) * 2                  # x, B, C in; y out
    assert ssm_flops.scan_bytes(1, 1, CFG) == 2 * (state + taps) + row
    assert ssm_flops.scan_bytes(32, 32, CFG) == 32 * (2 * (state + taps) + row)
    # a chunk of one sequence: its state once, its rows each
    assert ssm_flops.scan_bytes(512, 1, CFG) == 2 * (state + taps) + 512 * row
    assert ssm_flops.scan_flops(512, CFG) == 512 * 64 * 4 * 64 * 128


def test_the_configuration_file_carries_the_harness_spellings():
    cfg = manifest.Cell(CELL).config
    pub = cfg["published"]
    for ours, theirs in (("mamba_num_heads", "mamba_n_heads"),
                         ("mamba_head_dim", "mamba_d_head"),
                         ("n_groups", "mamba_n_groups"),
                         ("ssm_state_size", "mamba_d_state"),
                         ("conv_kernel", "mamba_d_conv"),
                         ("chunk_size", "mamba_chunk_size")):
        assert cfg[ours] == pub[theirs] == cfg[theirs]
    pattern = cfg["hybrid_override_pattern"]
    assert [{"M": "mamba", "*": "attention"}[c] for c in pattern] \
        == pub["layer_types"] and len(pattern) == 40
    assert cfg["reduced"] == {} and all(cfg[k] == v for k, v in pub.items())
    assert cfg["head_dim"] == pub["hidden_size"] // pub["num_attention_heads"]


@pytest.fixture(scope="module")
def recorded():
    trace = trace_reduce.load(PATH, chips=1)
    return trace_scopes.build(trace_scopes.read_space(PATH), trace)


def reading(monkeypatch, sc, counters=None):
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    return types.SimpleNamespace(
        trace=object(), counters=counters or {}, peak=PEAK,
        cell=types.SimpleNamespace(config=CFG, name="x"))


def renamed(sc):
    """The recorded serve programs with their attention kernels' operations
    under the scopes a Mamba-2 layer gives its core (a decode's under
    ``ssm_step``, a chunk's under ``ssm_scan_chunk``), the pool write's
    under ``state_write``, and the span key the engine adds."""
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
                     else "ssm_step" if path == "step" else "ssm_scan_chunk")
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
    # a program without the scopes, the counters and the gauge (the
    # parent's): nothing to read, and no error
    r = reading(monkeypatch, recorded)
    assert all(read[n](r) is None for n in NAMES)
    sc, took, ran = renamed(recorded)
    assert took["step"] > 0 and took["chunk"] > 0
    r = reading(monkeypatch, sc, counters={
        "serving/ssm_tokens_total/step": 300.0,
        "serving/ssm_tokens_total/chunk": 900.0,
        "kvcache/page_bytes_per_token": 8192.0})
    dev = sc.devices[0]
    whole = [dev.programs[i] for i in sorted(ran)]
    # the step: one read and one write of every stepped row's state and taps
    # a scan layer, over the HBM's bandwidth
    rows = [float(p.span.attrs["state_rows"]) for p in whole
            if p.span.name.endswith("dispatch")]
    assert rows
    least = sum(2 * ssm_flops.scan_bytes(n, n, CFG) / 819e9 for n in rows)
    step = read["ssm_step_roofline.served"](r)
    assert step == pytest.approx(100.0 * least / took["step"]) and step > 0
    # the chunk: the larger of its operations and its bytes, a scan layer
    chunks = [min(float(p.span.attrs["width"]),
                  float(p.span.attrs["ctx_tokens"])) for p in whole
              if p.span.name.endswith("prefill_chunk")]
    assert chunks
    least = sum(2 * max(ssm_flops.scan_flops(n, CFG) / 197e12,
                        ssm_flops.scan_bytes(n, 1, CFG) / 819e9)
                for n in chunks)
    assert read["ssm_chunk_roofline.served"](r) == pytest.approx(
        100.0 * least / took["chunk"])
    assert read["ssm_tokens_step_share"](r) == pytest.approx(25.0)
    assert read["kv_page_bytes_per_token"](r) == 8192.0
    # an untraced run reads no trace metric; the counters' need no trace
    monkeypatch.setattr(trace_scopes, "of", lambda r: None)
    assert read["ssm_step_roofline.served"](r) is None
    assert read["ssm_chunk_roofline.served"](r) is None
    assert read["ssm_tokens_step_share"](r) == pytest.approx(25.0)
    # a configuration without scan layers reads no roofline
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    r.cell.config = {**CFG, "hybrid_override_pattern": "**"}
    assert read["ssm_step_roofline.served"](r) is None
