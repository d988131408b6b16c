"""The head's two readers (PR 37): ``head_time_share`` reads the group ``head``
of ``trace_scopes``' table, whichever way a program reaches its head (whole
in ``__call__``, or as its own ``apply`` under a conditional), and
``head_rows_per_prefill_chunk`` the program's counters.  Both give nothing,
and do not raise, where the program has nothing for them (the parent commit
counts no head rows)."""

import types

import pytest

from benchmarks.harness import manifest, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Program, Scopes, Span

CELL = "xing4.0-29b-a4b.serve-longdocs"
STEP = "jit(_paged_step_fn)/"


def op(start, dur, tf_op, program=0):
    return Op("fusion", start, start + dur, dur, tf_op,
              trace_scopes.group_of("fusion", tf_op), program)


def reader(name):
    return manifest.Cell(CELL).layer_metric(name)


@pytest.fixture
def reading(monkeypatch):
    spans = [Span("nxd/serve/dispatch", 0.0, 0.1, {"active": 2}),
             Span("nxd/serve/prefill_chunk", 1.0, 1.1, {"width": 8})]
    programs = [Program("jit__paged", 0.0, 1.0, 1, 0.0, spans[0]),
                Program("jit__paged", 1.0, 2.0, 2, 1.0, spans[1])]
    ops = [op(0.0, 0.6, STEP + "LlamaForCausalLM/model/layer_0/mlp/down/dot"),
           # a decode: the module applied whole
           op(0.6, 0.4, STEP + "LlamaForCausalLM/lm_head/dot_general"),
           op(1.0, 0.7, STEP + "LlamaForCausalLM/model/layer_0/mlp/down/dot",
              program=1),
           # a prompt's last chunk: the head's own apply, under the conditional
           op(1.7, 0.1, STEP + "cond/branch_1_fun/LlamaForCausalLM.head/"
              "lm_head/dot_general", program=1)]
    sc = Scopes([DeviceScopes(0, ops, programs)], spans, (0.0, 2.0), 2.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    return types.SimpleNamespace(
        trace={}, cell=types.SimpleNamespace(name="x"),
        counters={"serving/head_rows_total/prefill_chunk_pages": 3.0,
                  "serving/prefill_chunks_total": 120.0})


@pytest.mark.parametrize("tag", ["served", "tpot"])
def test_head_time_share_is_the_group_head(reading, tag):
    assert reader("head_time_share." + tag).read(reading) == \
        pytest.approx(100 * (0.4 + 0.1) / 2.0)


@pytest.mark.parametrize("name", ["head_rows_per_prefill_chunk",
                                  "head_rows_per_prefill_chunk.tpot"])
def test_head_rows_a_chunk_is_the_counters_ratio(reading, name):
    assert reader(name).read(reading) == 3.0 / 120.0
    reading.counters["serving/head_rows_total/prefill_chunk_pages"] = 0.0
    assert reader(name).read(reading) == 0.0        # counted, and none


def test_a_program_that_counts_no_head_rows_gives_nothing(reading,
                                                          monkeypatch):
    """The parent commit: no such counter, and (here) a trace with no head
    operation or no trace at all — ``None``, never an exception."""
    reading.counters = {"serving/prefill_chunks_total": 120.0}
    assert reader("head_rows_per_prefill_chunk").read(reading) is None
    reading.counters = {"serving/head_rows_total/prefill_chunk_pages": 1.0}
    assert reader("head_rows_per_prefill_chunk").read(reading) is None
    bare = Scopes([DeviceScopes(0, [op(0.0, 1.0, STEP + "model/embed/gather")],
                                [])], [], (0.0, 1.0), 1.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: bare)
    assert reader("head_time_share.served").read(reading) is None
    monkeypatch.setattr(trace_scopes, "of", lambda r: None)
    assert reader("head_time_share.served").read(reading) is None
