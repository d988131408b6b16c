"""The reduction from a profiler trace to numbers: interval arithmetic and
name matching on hand-made cases, then the whole reduction on a small trace
recorded on a TPU v5e (``data/probe.xplane.pb``, written by
``benchmarks/tools/trace_probe.py`` in PR 22: three iterations of a flash
forward+backward with a matmul, a 5 ms host sleep, a paged decode call)."""

import os

import numpy as np
import pytest

from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.trace_reduce import Device, Event, Trace

PROBE = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")

FUSION = ("%fusion.7 = bf16[1024,1024]{1,0:T(8,128)(2,1)} fusion(bf16[1024,"
          "1024]{0,1:T(8,128)(2,1)S(1)} %bitcast.45), kind=kOutput")
FLASH = ('%branch_0_fun.3 = (bf16[1,8,1024,128]{3,2,1,0:T(8,128)(2,1)S(1)}, '
         'f32[1,8,1024,128]{3,2,1,0:T(8,128)S(1)}) custom-call(bf16[1,8,1024,'
         '128]{3,2,1,0} %x), custom_call_target="tpu_custom_call"')
PAGED = ('%paged_attention.1 = (f32[4,2,1,4,128]{4,3,2,1,0:T(4,128)S(1)}) '
         'custom-call(s32[4,32]{1,0} %copy-done), '
         'custom_call_target="tpu_custom_call"')
ALL_GATHER = ("%all-gather.12 = bf16[2,8192,4096]{2,1,0:T(8,128)(2,1)} "
              "all-gather(bf16[2,2048,4096]{2,1,0} %p), dimensions={1}")
AG_START = ("%all-gather-start.3 = (bf16[8,16]{1,0}, bf16[32,16]{1,0}) "
            "all-gather-start(bf16[8,16]{1,0} %p), dimensions={0}")
PERMUTE_DONE = ("%collective-permute-done.4 = bf16[8,16]{1,0} "
                "collective-permute-done((bf16[8,16]{1,0}, u32[]) %s)")
ASYNC_FUSED = ("%async-collective-start.2 = (bf16[2,2048,4096]{2,1,0}, "
               "bf16[2,8192,4096]{2,1,0}, s32[2]{0:S(4)}) fusion(bf16[2,2048,"
               "4096]{2,1,0} %fusion.463), kind=kCustom")
# an operand NAMED after a collective must not make a fusion one
FUSION_OF_AG = ("%fusion.9 = bf16[8,16]{1,0} fusion(bf16[8,16]{1,0} "
                "%all-gather.12), kind=kLoop")


def test_union_complement_subtract():
    iv = np.array([[0.0, 2.0], [1.0, 3.0], [5.0, 6.0], [6.0, 6.0]])
    assert tr.union(iv).tolist() == [[0.0, 3.0], [5.0, 6.0]]
    assert tr.total(tr.union(iv)) == 4.0
    assert tr.complement(iv, -1.0, 7.0).tolist() == [
        [-1.0, 0.0], [3.0, 5.0], [6.0, 7.0]]
    assert tr.subtract(np.array([[0.0, 10.0]]),
                       np.array([[2.0, 3.0], [9.0, 12.0]])).tolist() == [
        [0.0, 2.0], [3.0, 9.0]]
    assert tr.total(tr.subtract(np.zeros((0, 2)), iv)) == 0.0


def test_self_times_subtract_nested_children():
    evs = [Event("while", 0.0, 10.0), Event("body.1", 1.0, 4.0),
           Event("inner", 2.0, 3.0), Event("body.2", 5.0, 9.0),
           Event("after", 10.0, 11.0)]
    assert tr.self_times(evs) == [3.0, 2.0, 1.0, 4.0, 1.0]


def test_names_and_opcodes():
    assert tr.hlo_name(FUSION) == "fusion.7"
    assert tr.op_group(FUSION) == "fusion"
    assert tr.op_group(FLASH) == "branch_0_fun (mosaic)"
    assert tr.op_group(PAGED) == "paged_attention (mosaic)"
    assert tr.opcode(FUSION) == "fusion"
    assert tr.opcode(FLASH) == "custom-call"
    assert tr.opcode(ALL_GATHER) == "all-gather"
    assert tr.opcode(AG_START) == "all-gather-start"
    assert tr.opcode(PERMUTE_DONE) == "collective-permute-done"
    assert tr.is_mosaic(FLASH) and tr.is_mosaic(PAGED)
    assert not tr.is_mosaic(FUSION)
    assert tr.opcode(ASYNC_FUSED) == "fusion"
    for text in (ALL_GATHER, AG_START, PERMUTE_DONE, ASYNC_FUSED,
                 ASYNC_FUSED.replace("start.2", "done")):
        assert tr.is_collective(text), text
    for text in (FUSION, FLASH, FUSION_OF_AG, "jit_step(123)"):
        assert not tr.is_collective(text), text


def hand_made():
    """Two chips, window [0, 10].  Chip 0: a matmul 0-4, an all-gather 3-6
    (1 s hidden under the matmul, 2 s exposed), a matmul 7-9.  Chip 1: an
    all-gather 0-2 alone, a matmul 2-10."""
    d0 = Device(0, [Event(FUSION, 0.0, 4.0), Event(ALL_GATHER, 3.0, 6.0),
                    Event(FUSION, 7.0, 9.0)],
                [Event("jit_step(1)", 0.0, 6.0), Event("jit_step(1)", 7.0, 9.0)])
    d1 = Device(1, [Event(ALL_GATHER, 0.0, 2.0), Event(FUSION, 2.0, 10.0)], [])
    ann = [Event("bench/step", 0.0, 10.0), Event("bench/sleep", 6.0, 6.8)]
    return Trace([d0, d1], ann, (0.0, 10.0))


def test_busy_idle_on_hand_made_trace():
    t = hand_made()
    assert t.window_s == 10.0
    # chip 0 busy 0-6 and 7-9 = 8 s, chip 1 busy 0-10 = 10 s
    assert t.busy_s() == pytest.approx(9.0)
    assert t.idle_share() == pytest.approx(0.1)


def test_span_gaps_are_the_longest_wait_inside_each_step():
    """Three engine steps.  The first covers two gaps between programs
    (0.5-0.52 and 0.9-1.0: the longer one counts), the second covers the
    middle of none, the third the gap 2.0-2.3, whose middle it covers
    although the gap began before the span did."""
    d0 = Device(0, [Event(FUSION, 0.1, 0.9), Event(FUSION, 2.3, 2.8)],
                [Event("jit_chunk(1)", 0.1, 0.5), Event("jit_decode(2)", 0.52, 0.9),
                 Event("jit_pack(3)", 1.0, 2.0), Event("jit_decode(2)", 2.3, 2.8)])
    ann = [Event("bench/engine_step", 0.0, 1.0), Event("bench/submit", 1.0, 1.1),
           Event("bench/engine_step", 1.1, 1.2),
           Event("bench/engine_step", 2.1, 3.0)]
    t = Trace([d0], ann, (0.0, 3.0))
    assert t.span_gaps("engine_step") == [pytest.approx(0.1),
                                          pytest.approx(0.3)]
    assert t.span_gaps("submit") == []


def test_collective_exposed_vs_hidden():
    t = hand_made()
    # collective time: 3 s on chip 0, 2 s on chip 1
    assert t.time_of(tr.is_collective) == pytest.approx(2.5)
    # exposed: 2 s of chip 0's (4-6), all 2 s of chip 1's
    assert t.exposed_time_of(tr.is_collective) == pytest.approx(2.0)


def test_idle_gaps_go_to_the_innermost_annotation():
    t = hand_made()
    # chip 0 is idle 6-7 and 9-10: 0.8 s under bench/sleep, 1.2 under step
    gaps = dict(t.idle_gaps())
    assert gaps["bench/sleep"] == pytest.approx(0.8)
    assert gaps["bench/step"] == pytest.approx(1.2)


def test_top_ops_add_up_to_busy_when_nothing_overlaps():
    t = Trace([Device(0, [Event(FUSION, 0.0, 1.0), Event(FLASH, 1.0, 3.0),
                          Event(FUSION.replace("fusion.7", "fusion.8"),
                                3.5, 4.0)], [])], [], (0.0, 4.0))
    assert t.top_ops() == [["branch_0_fun (mosaic)", pytest.approx(2.0)],
                           ["fusion", pytest.approx(1.5)]]


# -- the recorded trace ----------------------------------------------------------


@pytest.fixture(scope="module")
def probe():
    return tr.load(PROBE)


def test_recorded_trace_structure(probe):
    assert len(probe.devices) == 1
    assert probe.devices[0].index == 0
    names = sorted({e.name for e in probe.annotations})
    assert names == ["bench/decode", "bench/sleep", "bench/step",
                     "bench/train"]
    assert len(probe.annotations) == 12          # 3 iterations x 4 spans
    assert 0.02 < probe.window_s < 0.03          # three ~8 ms iterations


def test_recorded_trace_busy_and_idle(probe):
    # tiny kernels between 5 ms host sleeps: the device is idle ~98%
    assert probe.busy_s() == pytest.approx(5.406e-4, rel=1e-3)
    assert probe.idle_share() == pytest.approx(0.977, abs=1e-3)
    gaps = probe.idle_gaps()
    assert gaps[0][0] == "bench/sleep"           # the sleep owns most of it
    assert gaps[0][1] == pytest.approx(3 * 5.4e-3, rel=0.1)


def test_recorded_trace_kernels_by_pattern(probe):
    def paged(text):
        return tr.is_mosaic(text) and tr.hlo_name(text).startswith(
            "paged_attention")

    def flash(text):
        return tr.is_mosaic(text) and not paged(text)

    dev = probe.devices[0]
    lo, hi = probe.window
    inside = [e for e in dev.ops if e.start >= lo and e.end <= hi]
    # per iteration: flash forward, dq, dkv (anonymous) and one paged call;
    # the first iteration's train program ran before the first annotation
    assert sum(1 for e in inside if paged(e.name)) == 3
    assert sum(1 for e in inside if flash(e.name)) == 6
    assert probe.time_of(paged) == pytest.approx(1.395e-4, rel=1e-2)
    assert probe.time_of(flash) == pytest.approx(2.722e-4, rel=1e-2)
    top = probe.top_ops()
    assert top[0][0] == "branch_0_fun (mosaic)"
    assert top[1][0] == "paged_attention (mosaic)"
    assert probe.time_of(tr.is_collective) == 0.0
    # self times of operations that do not nest add up to the busy time
    assert sum(v for _, v in probe.top_ops(1000)) == pytest.approx(
        probe.busy_s(), rel=1e-6)
