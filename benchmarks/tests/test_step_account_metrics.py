"""The five readers of the serve loop's own account of its step: the three
that read the step account's counters on hand-built readings, the two that
read the spans ``nxd/serve/tail`` and ``nxd/serve/first_token`` on a
synthetic ``Scopes`` with hand-counted idle stretches and on the trace
recorded on the v5e before those spans were (nothing to read there: nothing,
and no error)."""

import os
import types

import pytest

from benchmarks.harness import manifest, trace_reduce, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Scopes, Span

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe_scopes.xplane.pb")
SERVE = trace_scopes.SERVE
COUNTER_READERS = ("engine_stall_share", "engine_step_offcpu_share",
                   "engine_step_ms_max")
TRACE_READERS = ("step_tail_idle_share", "first_token_idle_share")


def reader(name, cell="nemotron-3-nano.serve-agents"):
    return manifest.Cell(cell).layer_metric(name)


def counters(**kw):
    return types.SimpleNamespace(
        trace=None, counters={"serving/" + k.replace("__", "/"): float(v)
                              for k, v in kw.items()})


def reading(monkeypatch, sc):
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    return types.SimpleNamespace(trace=object(), counters={})


def busy(start, end):
    return Op("fusion", start, end, end - start, "jit(f)/mlp/dot", "mlp", -1)


# -- the counters ------------------------------------------------------------------

def test_a_clean_run_reads_zero_not_nothing(capsys):
    r = counters(step_ms_total=40000, step_cpu_ms_total=9000,
                 step_blocked_ms_total=30000, step_ms_max=61.5,
                 stalls_total=0, stall_ms_total=0,
                 host_ms_total__fetch=30000, host_ms_total__tail=900,
                 stall_ms_total__fetch=0, stall_ms_total__between=0)
    assert reader("engine_stall_share.served").read(r) == 0.0
    line = capsys.readouterr().out
    assert line.startswith("[steps] 40000.0 ms of stepping")
    assert "fetch 30000.0, tail 900.0" in line and "none in any phase" in line
    assert reader("engine_step_offcpu_share.served").read(r) \
        == pytest.approx(100 * 1000 / 40000)
    assert reader("engine_step_ms_max.served").read(r) == 61.5


def test_a_hole_in_a_step_is_its_share_a_hole_between_steps_is_not(capsys):
    # a 5.2 s step against a median of 17 ms, inside ``fetch``; 3 s more
    # between two steps while the profiler started
    r = counters(step_ms_total=45000, step_cpu_ms_total=9000,
                 step_blocked_ms_total=35183, step_ms_max=5200,
                 stalls_total=1, stall_ms_total=5183,
                 stall_ms_total__fetch=5183, stall_ms_total__between=3000,
                 stall_ms_total__admit=0)
    assert reader("engine_stall_share.tpot", "qwen2-7b.serve-chat").read(r) \
        == pytest.approx(100 * 5183 / 45000)
    assert "stalls 1, ms over the median 5183.0, between 3000.0, fetch " \
        "5183.0" in capsys.readouterr().out
    assert reader("engine_step_ms_max.tpot", "qwen2-7b.serve-chat").read(r) \
        == 5200
    # the thread ran or waited for the device all the time: floored at 0
    r.counters["serving/step_cpu_ms_total"] = 12000.0
    assert reader("engine_step_offcpu_share.served").read(r) == 0.0


def test_a_program_without_the_account_reads_nothing():
    r = types.SimpleNamespace(trace=None, counters={
        "serving/last_step_ms": 17.0, "compiles_in_window": 0})
    for name in COUNTER_READERS:
        assert reader(name + ".served").read(r) is None
    # declared, and nothing stepped since: 0, not a division
    r = counters(step_ms_total=0, step_ms_max=0)
    assert reader("engine_stall_share.served").read(r) == 0.0
    assert reader("engine_step_offcpu_share.served").read(r) == 0.0


# -- the spans ---------------------------------------------------------------------

def steps(first_token_in=()):
    """Three steps of 1 s: ``finish`` to 0.8, ``tail`` from 0.8 to 1.0; in
    the steps named, a first-token tail from 0.2 to 0.5 with its blocking
    fetch from 0.25 to 0.45 inside it."""
    out = []
    for i in range(3):
        out += [Span(SERVE + "step", float(i), i + 1.0, {"step": i}),
                Span(SERVE + "admit", float(i), i + 0.1, {"granted": 0}),
                Span(SERVE + "dispatch", i + 0.55, i + 0.6, {}),
                Span(SERVE + "finish", i + 0.7, i + 0.8, {}),
                Span(SERVE + "tail", i + 0.8, i + 1.0, {})]
        if i in first_token_in:
            out += [Span(SERVE + "first_token", i + 0.2, i + 0.5, {}),
                    Span(SERVE + "fetch", i + 0.25, i + 0.45, {})]
    return out


def test_idle_under_the_tail_and_inside_the_first_token_tails(monkeypatch,
                                                              capsys):
    # step 0: busy throughout.  step 1: the device waits from 1.3 to 1.6 —
    # 0.05 under ``first_token`` itself, 0.15 under the ``fetch`` inside it,
    # 0.05 after it, before the dispatch — and from 1.9 to 2.0 under the
    # tail.  step 2: it waits through the whole tail.
    ops = [busy(0.0, 1.3), busy(1.6, 1.9), busy(2.0, 2.8)]
    sc = Scopes([DeviceScopes(0, ops, [])], steps(first_token_in=(1,)),
                (0.0, 4.0), 2.4)
    r = reading(monkeypatch, sc)
    by = sc.idle_by_span()
    assert by[SERVE + "tail"] == pytest.approx(0.3)
    assert by[SERVE + "first_token"] == pytest.approx(0.05)   # innermost only
    assert by[SERVE + "fetch"] == pytest.approx(0.15)
    assert reader("step_tail_idle_share.served").read(r) == pytest.approx(
        100 * 0.3 / 4.0)
    # the reader clips the idle to the span itself: its fetch's share too
    assert reader("first_token_idle_share.served").read(r) == pytest.approx(
        100 * 0.2 / 4.0)
    assert "[first_token] 1 first-token tails in the window, 300.00 ms " \
        "long together, the device idle inside them 200.00 ms" \
        in capsys.readouterr().out
    # a window without a first token, of a program that has the span: 0
    sc.spans = steps()
    assert reader("first_token_idle_share.served").read(r) == 0.0
    # the device never waited: 0, not nothing
    sc.devices = [DeviceScopes(0, [busy(0.0, 4.0)], [])]
    sc.spans = steps(first_token_in=(0, 2))
    for name in TRACE_READERS:
        assert reader(name + ".served").read(r) == 0.0


def test_no_span_no_trace_nothing(monkeypatch):
    old = [s for s in steps(first_token_in=(1,))
           if s.name not in (SERVE + "tail", SERVE + "first_token")]
    older = Scopes([DeviceScopes(0, [busy(0.0, 1.0)], [])], old, (0.0, 3.0),
                   1.0)
    bare = Scopes([DeviceScopes(0, [busy(0.0, 1.0)], [])], [], (0.0, 1.0), 1.0)
    for sc in (older, bare, None):
        r = reading(monkeypatch, sc)
        for name in TRACE_READERS:
            assert reader(name + ".served").read(r) is None


def test_on_the_trace_recorded_before_the_spans_were(monkeypatch):
    trace = trace_reduce.load(PATH, chips=1)
    sc = trace_scopes.build(trace_scopes.read_space(PATH), trace)
    assert sc.named(SERVE + "finish") and not sc.named(SERVE + "tail")
    r = reading(monkeypatch, sc)
    for name in TRACE_READERS:
        assert reader(name + ".served").read(r) is None
    # the same trace with what follows each ``finish`` named, as the program
    # names it now: the step's end less the finish's is the tail
    by_step = [(s, sc.inside(s, SERVE + "finish")[0])
               for s in sc.named(SERVE + "step")
               if sc.inside(s, SERVE + "finish")]
    sc.spans = sc.spans + [Span(SERVE + "tail", f.end, s.end, {})
                           for s, f in by_step]
    share = reader("step_tail_idle_share.served").read(r)
    lo, hi = sc.window
    assert share == pytest.approx(
        100 * sc.idle_by_span()[SERVE + "tail"] / (hi - lo))
    assert 0 < share < 100 * (1 - trace.busy_s() / (hi - lo))
    assert reader("first_token_idle_share.served").read(r) == 0.0
