"""The two readers of the ``nxd/serve/admit`` span: on a synthetic ``Scopes``
with hand-counted idle stretches, and on the trace that
``tools/trace_probe_scopes.py`` recorded on the v5e."""

import os
import types

import pytest

from benchmarks.harness import manifest, trace_reduce, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Scopes, Span

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "probe_scopes.xplane.pb")
SERVE = trace_scopes.SERVE


def reader(name):
    return manifest.Cell("mistral-7b.serve-docs").layer_metric(name)


def reading(monkeypatch, sc, counters=None):
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    return types.SimpleNamespace(trace=object(), counters=counters or {})


def busy(start, end):
    return Op("fusion", start, end, end - start, "jit(f)/mlp/dot", "mlp", -1)


def spans(granted):
    # three steps of 1 s; each admits for its first 0.1 / 0.4 / 0.3 s
    out = []
    for i, (dur, g) in enumerate(zip((0.1, 0.4, 0.3), granted)):
        attrs = {} if g is None else {"granted": g}
        out += [Span(SERVE + "step", float(i), i + 1.0, {"step": i}),
                Span(SERVE + "admit", float(i), i + dur, attrs),
                Span(SERVE + "dispatch", i + 0.5, i + 0.6, {})]
    return out


def test_idle_under_admit_over_the_window(monkeypatch):
    # the device works through the first admission, waits through the whole
    # of the second (0.4 s) and half of the third (0.15 s): 0.55 s of 4
    ops = [busy(0.0, 1.0), busy(1.4, 2.0), busy(2.15, 3.0)]
    sc = Scopes([DeviceScopes(0, ops, [])], spans((0, 2, 1)), (0.0, 4.0), 2.45)
    r = reading(monkeypatch, sc)
    assert reader("admit_idle_share.served").read(r) == pytest.approx(
        100 * 0.55 / 4.0)
    assert sc.idle_by_span()[SERVE + "admit"] == pytest.approx(0.55)


def test_the_longest_admission_that_granted(monkeypatch, capsys):
    sc = Scopes([DeviceScopes(0, [busy(0.0, 3.0)], [])], spans((0, 2, 1)),
                (0.0, 4.0), 3.0)
    r = reading(monkeypatch, sc, {"kvcache/evictions_total": 300.0,
                                  "kvcache/evict_scanned_total": 4700.0})
    assert reader("admit_host_ms_max.served").read(r) == pytest.approx(400.0)
    line = capsys.readouterr().out
    assert line.startswith("[admit] 3 admit spans in the window, 2 of them")
    assert "ms 300.00 400.00; sum 700.00" in line
    assert "(15.7 a page)" in line
    # the device never waited: a share of 0, not nothing
    assert reader("admit_idle_share.served").read(r) == 0.0
    # the longest span granted nothing: it is not an admission
    sc.spans = spans((0, 0, 1))
    assert reader("admit_host_ms_max.served").read(r) == pytest.approx(300.0)
    # no span granted anything in the window (few, long requests): the
    # longest of all, so the cell's line always carries the metric
    sc.spans = spans((0, 0, 0))
    assert reader("admit_host_ms_max.served").read(r) == pytest.approx(400.0)
    # a trace whose spans do not say what they granted: the longest of all
    sc.spans = spans((None, None, None))
    r.counters = {}
    capsys.readouterr()
    assert reader("admit_host_ms_max.served").read(r) == pytest.approx(400.0)
    assert "a page" not in capsys.readouterr().out


def test_no_span_no_trace_nothing(monkeypatch):
    bare = Scopes([DeviceScopes(0, [busy(0.0, 1.0)], [])], [], (0.0, 1.0), 1.0)
    for sc in (bare, None):
        r = reading(monkeypatch, sc)
        for name in ("admit_idle_share.served", "admit_host_ms_max.served"):
            assert reader(name).read(r) is None


def test_on_the_recorded_trace(monkeypatch):
    trace = trace_reduce.load(PATH, chips=1)
    sc = trace_scopes.build(trace_scopes.read_space(PATH), trace)
    r = reading(monkeypatch, sc)
    # 7 steps, the second admits one request
    [granting] = [s for s in sc.named(SERVE + "admit")
                  if s.attrs["granted"] >= 1]
    assert reader("admit_host_ms_max.served").read(r) == pytest.approx(
        granting.dur * 1e3)
    share = reader("admit_idle_share.served").read(r)
    lo, hi = sc.window
    assert share == pytest.approx(
        100 * sc.idle_by_span()[SERVE + "admit"] / (hi - lo))
    assert 0 < share < 100 * (1 - trace.busy_s() / (hi - lo))
