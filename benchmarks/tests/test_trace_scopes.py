"""``harness/trace_scopes.py`` against the trace that
``tools/trace_probe_scopes.py`` recorded on the v5e: a two-layer toy through
the real ``ServingEngine`` (7 steps, a prompt of two chunks arriving in the
second) and 3 real train steps."""

import os

import pytest

from benchmarks.harness import trace_reduce, trace_scopes

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe_scopes.xplane.pb")
SERVE = trace_scopes.SERVE


@pytest.fixture(scope="module")
def sc():
    assert os.path.getsize(PATH) < 300 * 1024
    trace = trace_reduce.load(PATH, chips=1)
    return trace, trace_scopes.build(trace_scopes.read_space(PATH), trace)


def test_every_group_of_the_table_is_there_and_they_add_up(sc):
    trace, scopes = sc
    by = scopes.group_seconds()
    for group in trace_scopes.GROUPS:
        # one chip; and the toy's pool of 50 pages goes in as it lies
        if group not in ("collective", "pool_copy"):
            assert by.get(group, 0.0) > 0, group
    assert sum(by.values()) == pytest.approx(trace.busy_s(), rel=5e-3)
    assert scopes.share("other") < 25      # a toy: eager glue between steps
    assert scopes.share("flash_fwd", "flash_bwd") == pytest.approx(
        100 * sum(by[g] for g in ("flash_fwd", "flash_bwd")) / trace.busy_s())
    assert scopes.share("collective") is None


def test_kernels_run_as_often_as_the_programs_say(sc):
    _, scopes = sc
    count = lambda g, name: sum(                            # noqa: E731
        trace_reduce.hlo_name(op.text).startswith(name)
        for op in scopes.ops_of(g))
    # 3 train steps x 2 layers, selective remat: the forward runs once
    assert count("flash_fwd", "flash_fwd") == 6
    assert count("flash_bwd", "flash_dq") == 6
    assert count("flash_bwd", "flash_dkv") == 6
    # 7 decode steps and 2 prefill chunks, 2 layers each
    assert count("paged_decode", "paged_attention_decode") == 14
    assert count("paged_chunk", "paged_attention_chunk") == 4
    # the pool write is told from the projections around it
    writes = scopes.ops_of("kv_write")
    assert any("attn" in trace_scopes.components(op.tf_op)
               and "kv_write" in trace_scopes.components(op.tf_op)
               for op in writes)
    assert any("kv_valid" in trace_scopes.components(op.tf_op)
               for op in writes)


def test_every_serve_span_is_there_with_its_arguments(sc):
    _, scopes = sc
    steps = scopes.named(SERVE + "step")
    assert [s.attrs["step"] for s in steps] == list(range(14, 21))
    assert all({"active", "queued"} <= set(s.attrs) for s in steps)
    assert [s.attrs["granted"] for s in scopes.named(SERVE + "admit")] \
        == [0, 1, 0, 0, 0, 0, 0]
    chunks = scopes.named(SERVE + "prefill_chunk")
    assert [(c.attrs["request_id"], c.attrs["tok_start"], c.attrs["width"],
             c.attrs["ctx_tokens"]) for c in chunks] \
        == [(2, 16, 64, 52), (2, 80, 48, 100)]
    dispatch = scopes.named(SERVE + "dispatch")
    assert [d.attrs["active"] for d in dispatch] == [2, 2, 3, 3, 3, 3, 3]
    # two slots a token further each step; the third joins in the step of
    # its last chunk with the 100 keys of its prompt
    assert [d.attrs["ctx_tokens"] for d in dispatch] \
        == [119, 121, 123 + 100, 226, 229, 232, 235]
    assert [f.attrs["tokens"] for f in scopes.named(SERVE + "finish")] \
        == [2, 2, 2, 3, 3, 3, 3]
    assert len(scopes.named(SERVE + "collect")) == 7
    assert len(scopes.named(SERVE + "fetch")) == 8   # + one first token
    for step in scopes.steps():
        assert step.fetch is not None and step.dispatch is not None
        assert 0 < step.host_s < step.span.dur


def test_programs_are_set_against_the_spans_that_launched_them(sc):
    _, scopes = sc
    progs = scopes.devices[0].programs
    under = {}
    for p in progs:
        if p.span is not None:
            under.setdefault(p.span.name[len(SERVE):], []).append(p.name)
    # a decode step: the paged program, the sampler, the token pack
    assert sorted(under["dispatch"]) == sorted(
        ["jit__unknown", "jit__sample_rows", "jit__pack_tokens"] * 7)
    assert under["prefill_chunk"].count("jit__unknown") == 2
    assert "jit__insert_valid_fn" in under["admit"]
    assert under["fetch"] == ["jit__pack_tokens"]     # the first token's
    chunk_s = scopes.seconds_launched_under(SERVE + "prefill_chunk")
    assert 0 < chunk_s < scopes.busy_s
    # no program starts on the device before the host asked for it, once
    # the clocks' offset is taken out
    least, most = scopes.clock_offset_bounds()
    assert 0 < least < most < 3e-3
    assert all(p.start + most >= p.launched for p in progs
               if p.launched is not None)


def test_the_relaunch_gap_is_found_at_the_named_boundary(sc):
    _, scopes = sc
    gaps = scopes.relaunch_gaps()
    assert len(gaps) == 6                   # 7 steps: 6 have a step before
    for gap, lag in gaps:
        assert 0 < gap < 0.02 and 0 < lag < 0.02
    # a toy on a fast chip: the device waits for the host every step, and
    # the gap is most of the step
    assert 1e-3 < sorted(g for g, _ in gaps)[len(gaps) // 2] < 4e-3
    idle = scopes.idle_by_span()
    assert idle[SERVE + "dispatch"] == max(idle.values())
    lines = trace_scopes.report(scopes)
    assert sum(line.startswith("[scopes]") for line in lines) == 2
    assert sum(line.startswith("[phases]") for line in lines) == 2


def test_a_trace_without_the_spans_gives_nothing_and_does_not_raise():
    """The parent program's traces have no ``nxd/`` span and unnamed flash
    kernels: the readers must return ``None``, not raise."""
    path = os.path.join(os.path.dirname(PATH), "probe.xplane.pb")
    trace = trace_reduce.load(path)
    scopes = trace_scopes.build(trace_scopes.read_space(path), trace)
    assert scopes.steps() == [] and scopes.relaunch_gaps() == []
    assert scopes.idle_by_span() == {}
    assert scopes.share("flash_fwd") is None
    assert scopes.share("optimizer") is None
    assert scopes.seconds_launched_under(SERVE + "prefill_chunk") == 0.0
    assert not any("[phases]" in line for line in trace_scopes.report(scopes))
