"""``harness/retention_flops.py`` by hand-counted cases, and the four readers
of the power-retention cell on the trace that ``tools/trace_probe_scopes.py``
recorded on the v5e (a two-layer toy through the real ``ServingEngine``: 7
decodes and 2 prefill chunks): the recorded program has no retention scope —
the readers find nothing and return ``None``, as on a parent commit — and
with the attention operations of its serve programs renamed as this PR's
program names its cores, they read."""

import copy
import os
import types

import pytest

from benchmarks.harness import manifest, retention_flops, trace_reduce, trace_scopes

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe_scopes.xplane.pb")
CELL = "brumby-14b.serve-continuations"
CFG = {"num_attention_heads": 40, "num_key_value_heads": 8, "head_dim": 128,
       "num_hidden_layers": 2}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NAMES = ("retention_time_share.served", "retention_chunk_roofline.served",
         "retention_step_roofline.served", "state_bytes_share")


def test_operations_of_a_token_are_the_cheaper_exact_form():
    # the minimal symmetric square of 128 channels
    assert retention_flops.phi_dim_min(128) == 8256
    state = 2 * 8256 * 128 * (40 + 8)              # read a q head, update a kv
    assert state == 101449728
    # below the crossover a token counts the quadratic form, 4 n d a q head
    assert retention_flops.token_flops(1, CFG) == 4 * 128 * 40
    assert retention_flops.token_flops(300, CFG) == 4 * 300 * 128 * 40
    assert retention_flops.token_flops(4953, CFG) == 4 * 4953 * 128 * 40
    # ... above it the state form, whatever the position
    assert retention_flops.token_flops(4954, CFG) == state
    assert retention_flops.token_flops(16000, CFG) == state
    assert retention_flops.crossover(CFG) == pytest.approx(4953.6)


@pytest.mark.parametrize("first,tokens", [(1, 512), (4700, 512), (9000, 512),
                                          (4953, 2), (2049, 300)])
def test_a_chunk_is_the_sum_of_its_tokens(first, tokens):
    by_token = sum(retention_flops.token_flops(n, CFG)
                   for n in range(first, first + tokens))
    assert retention_flops.chunk_flops(first, tokens, CFG) == \
        pytest.approx(by_token, rel=1e-12)


def test_a_step_reads_each_rows_state_once():
    # 8 kv heads x (8256 x 128 + 8256) float32 a row a layer
    assert retention_flops.step_bytes(1, CFG) == 8 * 8256 * 129 * 4
    assert retention_flops.step_bytes(16, CFG) == 16 * 8 * 8256 * 129 * 4


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
    under the scopes this PR's program gives its cores, and the two span
    keys it adds."""
    sc = copy.deepcopy(sc)
    took = {"retention_step": 0.0, "retention_chunk": 0.0}
    programs = set()        # those that ran a core whole inside the window
    for d in sc.devices:
        for op in d.ops:
            prog = d.programs[op.program] if op.program >= 0 else None
            span = prog.span if prog is not None else None
            if span is None or op.group not in ("paged_decode", "paged_chunk"):
                continue
            scope = ("retention_step" if span.name.endswith("dispatch")
                     else "retention_chunk")
            op.tf_op = f"jit(_paged_step_fn)/model/layer_0/attn/{scope}/x:"
            lo, hi = sc.window
            if prog.start >= lo and prog.end <= hi:
                took[scope] += op.own
                programs.add(op.program)
        for p in d.programs:
            if p.span is not None and p.span.name.endswith("dispatch"):
                p.span.attrs["state_rows"] = p.span.attrs["active"]
            if p.span is not None and p.span.name.endswith("prefill_chunk"):
                p.span.attrs["chunk_tokens"] = min(
                    float(p.span.attrs["width"]),
                    float(p.span.attrs["ctx_tokens"]))
    return sc, took, programs


def test_the_readers_on_a_recorded_trace(recorded, monkeypatch):
    cell = manifest.Cell(CELL)
    read = {n: cell.layer_metric(n).read for n in NAMES}
    # a program without the scopes and the gauge (the parent's): nothing to
    # read, and no error
    r = reading(monkeypatch, recorded)
    assert all(read[n](r) is None for n in NAMES)
    sc, took, ran = renamed(recorded)
    assert took["retention_step"] > 0 and took["retention_chunk"] > 0
    r = reading(monkeypatch, sc, counters={
        "kvcache/state_bytes": 4.5 * 2 ** 30, "bytes_in_use": 12.5 * 2 ** 30})
    share = read["retention_time_share.served"](r)
    both = sum(op.own for op in sc.devices[0].ops
               if "retention_" in op.tf_op)
    assert share == pytest.approx(100.0 * both / sc.busy_s) and share > 0
    # the step: one read of every stepped row's state a layer over the HBM's
    # bandwidth, over what the renamed operations took
    dev = sc.devices[0]
    whole = [dev.programs[i] for i in sorted(ran)]
    rows = sum(float(p.span.attrs["state_rows"]) for p in whole
               if p.span.name.endswith("dispatch"))
    assert rows > 0
    step = read["retention_step_roofline.served"](r)
    assert step == pytest.approx(
        100.0 * 2 * retention_flops.step_bytes(rows, CFG) / 819e9
        / took["retention_step"])
    # the chunk: each token the cheaper exact form at its position
    least = sum(2 * retention_flops.chunk_flops(
        int(float(p.span.attrs["ctx_tokens"]))
        - int(p.span.attrs["chunk_tokens"]) + 1,
        int(p.span.attrs["chunk_tokens"]), CFG) / 197e12
        for p in whole if p.span.name.endswith("prefill_chunk"))
    assert least > 0
    assert read["retention_chunk_roofline.served"](r) == pytest.approx(
        100.0 * least / took["retention_chunk"])
    assert read["state_bytes_share"](r) == pytest.approx(36.0)
    # an untraced run reads no trace metric; the counter's needs no trace
    monkeypatch.setattr(trace_scopes, "of", lambda r: None)
    assert all(read[n](r) is None for n in NAMES[:3])
    assert read["state_bytes_share"](r) == pytest.approx(36.0)
