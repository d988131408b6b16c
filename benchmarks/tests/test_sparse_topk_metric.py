"""``sparse_topk_time_share`` (PR 40): the choosing among scored blocks, the
part of ``sparse_select_time_share`` under the program's scope
``sparse_topk`` — on a synthetic reading (no stored trace holds a block-sparse
layer): operations by their name stacks, nothing of the program imported."""

import types

import pytest

from benchmarks.harness import manifest, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Program, Scopes

CELL = "minicpm-sala.serve-longdocs"
L0 = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_0/attn/"


def op(start, dur, tf_op, text="fusion"):
    return Op(text, start, start + dur, dur, tf_op,
              trace_scopes.group_of(text, tf_op), 0)


def reading_of(monkeypatch, ops, busy=10.0):
    sc = Scopes([DeviceScopes(0, ops, [Program("jit__paged", 0.0, 9.0, 1)])],
                [], (0.0, 9.0), busy)
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    return types.SimpleNamespace(trace=object(), counters={},
                                 cell=types.SimpleNamespace(name="x"))


def reader(name):
    return manifest.Cell(CELL).layer_metric(name)


def test_the_cell_lists_the_entry():
    assert "sparse_topk_time_share.served" in [
        m["name"] for m in manifest.Cell(CELL).per_layer]
    assert reader("sparse_topk_time_share.served").SCOPES == ("sparse_topk",)


@pytest.mark.parametrize("stack, counted", [
    (L0 + "sparse_topk/reduce_sum", True),          # a pass of the threshold
    (L0 + "sparse_topk/cumsum", True),              # the table's rank
    (L0 + "sparse_topk/jit(argsort)/sort", True),   # a sort, were one left
    (L0 + "sparse_score/dot_general", False),
    (L0 + "sparse_compress/gather", False),
    (L0 + "jit(argsort)/sort", False),              # the parent's table
    (L0 + "qkv/dot_general", False),
])
def test_only_the_scope_sparse_topk_counts(monkeypatch, stack, counted):
    r = reading_of(monkeypatch, [op(0.0, 0.5, stack),
                                 op(1.0, 2.0, L0 + "o_proj/dot_general")])
    got = reader("sparse_topk_time_share.served").read(r)
    assert got == (pytest.approx(5.0) if counted else None)


def test_it_is_a_part_of_the_selections_share(monkeypatch):
    r = reading_of(monkeypatch, [
        op(0.0, 0.3, L0 + "sparse_compress/gather"),
        op(0.3, 0.5, L0 + "sparse_score/dot_general"),
        op(0.8, 0.2, L0 + "sparse_topk/reduce_sum"),
        op(1.0, 0.1, L0 + "sparse_topk/cumsum")])
    assert reader("sparse_topk_time_share.served").read(r) == \
        pytest.approx(3.0)
    assert reader("sparse_select_time_share.served").read(r) == \
        pytest.approx(11.0)


def test_no_trace_and_no_device_give_nothing(monkeypatch):
    r = reading_of(monkeypatch, [])
    assert reader("sparse_topk_time_share.served").read(r) is None
    monkeypatch.setattr(trace_scopes, "of", lambda r: None)
    assert reader("sparse_topk_time_share.served").read(r) is None
