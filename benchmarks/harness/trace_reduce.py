"""From a profiler trace (``.xplane.pb``) to numbers.

Written against what the v5e's profiler emits (PR 22, recorded in
``benchmarks/tests/data/probe.xplane.pb``):

- one plane per chip, named ``/device:TPU:<n>``; its line ``XLA Ops`` holds
  one event per executed HLO operation, named by the operation's full HLO
  text (``%fusion.7 = bf16[...] fusion(...)``); ``XLA Modules`` holds one
  event per executed program (``jit_step(123...)``);
- a Mosaic (Pallas) kernel is an event whose text holds
  ``custom_call_target="tpu_custom_call"``; its HLO name is the kernel's
  ``name=`` when it has one (``%paged_attention.1``) and an anonymous
  ``%branch_0_fun.N`` when it has none (the flash kernels, today);
- host threads are lines of the plane ``/host:CPU``; the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans are events there whose names start
  with ``bench/``.  Host and device events share one clock (nanoseconds from
  the start of the profile).

Times are seconds as floats.  Interval arithmetic is on sorted, merged
``[n, 2]`` numpy arrays.  Nothing here imports the program under test.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench/"
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
# HLO opcodes that move data between chips (sync, or the start/done halves
# of the async form); matched against the opcode, not the operands
COLLECTIVE_OPCODES = ("all-gather", "all-reduce", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "collective-broadcast")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


# -- interval arithmetic --------------------------------------------------------


def as_intervals(events: Sequence[Event]) -> np.ndarray:
    if not events:
        return np.zeros((0, 2))
    return np.asarray([(e.start, e.end) for e in events], dtype=float)


def union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted intervals covering the same points."""
    iv = np.asarray(iv, dtype=float).reshape(-1, 2)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for lo, hi in iv[1:]:
        if lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return np.asarray(out)


def total(iv: np.ndarray) -> float:
    iv = np.asarray(iv).reshape(-1, 2)
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.asarray(iv, dtype=float).reshape(-1, 2)
    if len(iv) == 0:
        return iv
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], axis=1)
    return c[c[:, 1] > c[:, 0]]


def complement(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The parts of ``[lo, hi]`` that merged intervals ``iv`` do not cover."""
    iv = clip(union(iv), lo, hi)
    edges = np.concatenate([[lo], iv.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """The parts of merged ``a`` that ``b`` does not cover."""
    a = union(a)
    if len(a) == 0:
        return a
    free = complement(b, float(a[0, 0]), float(a[-1, 1]))
    out = [clip(free, lo, hi) for lo, hi in a]
    return union(np.concatenate(out)) if out else np.zeros((0, 2))


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration minus what its nested children cover (a
    ``while`` spans the operations of its body): times that add up."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    own = [e.dur for e in events]
    stack: List[int] = []
    for i in order:
        while stack and events[stack[-1]].end <= events[i].start:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(events[i].end, events[stack[-1]].end) \
                - events[i].start
        stack.append(i)
    return [max(x, 0.0) for x in own]


# -- names ------------------------------------------------------------------------


def hlo_name(text: str) -> str:
    """``%fusion.7 = ...`` -> ``fusion.7``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def op_group(text: str) -> str:
    """A name that adds up over layers: the HLO name without its numeric
    suffix, with ``(mosaic)`` on a Pallas kernel."""
    base = re.sub(r"[.\d]+$", "", hlo_name(text)) or hlo_name(text)
    return base + " (mosaic)" if MOSAIC_MARK in text else base


def opcode(text: str) -> str:
    """The HLO opcode of an event's text (``fusion``, ``all-gather-start``,
    ``custom-call``, ...); empty when the text is not HLO."""
    head = text.split(" = ", 1)
    if len(head) != 2:
        return ""
    # the opcode is the first lower-case word followed by "(" after the
    # result shape, which may itself hold parentheses (tuples, tilings)
    depth, i, rest = 0, 0, head[1]
    while i < len(rest):
        ch = rest[i]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            m = re.match(r"([a-z][a-z\-]*)\(", rest[i + 1:])
            if m:
                return m.group(1)
        i += 1
    m = re.match(r"([a-z][a-z\-]*)\(", rest)
    return m.group(1) if m else ""


def is_collective(text: str) -> bool:
    """An operation that moves data between chips: a collective opcode in
    its sync, ``-start`` or ``-done`` form, or one of the TPU compiler's
    fused async collectives, which are custom fusions NAMED
    ``%async-collective-start`` / ``%async-collective-done`` (seen on the
    2x2 host in PR 22: the sequence-parallel all-gathers)."""
    if hlo_name(text).startswith("async-collective-"):
        return True
    op = opcode(text)
    return any(op == c or op == c + "-start" or op == c + "-done"
               for c in COLLECTIVE_OPCODES)


def is_mosaic(text: str) -> bool:
    return MOSAIC_MARK in text


# -- the trace ----------------------------------------------------------------------


@dataclasses.dataclass
class Device:
    index: int
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    annotations: List[Event]      # the benchmark's own host spans
    window: Tuple[float, float]   # first annotation start .. last end

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    # ---- device time

    def _ops_in_window(self, d: Device, pred: Optional[Callable] = None,
                       window: Optional[Tuple[float, float]] = None):
        lo, hi = window or self.window
        evs = [e for e in d.ops if e.end > lo and e.start < hi
               and (pred is None or pred(e.name))]
        return clip(as_intervals(evs), lo, hi)

    def busy(self, d: Device) -> np.ndarray:
        return union(self._ops_in_window(d))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return float(np.mean([total(self.busy(d)) for d in self.devices]))

    def idle_share(self) -> float:
        if not self.window_s:
            return 1.0
        return 1.0 - self.busy_s() / self.window_s

    def time_of(self, pred: Callable[[str], bool],
                window: Optional[Tuple[float, float]] = None) -> float:
        """Seconds (union of intervals) of the operations ``pred`` picks,
        averaged over the chips; inside ``window`` where one is given."""
        if not self.devices:
            return 0.0
        return float(np.mean([
            total(union(self._ops_in_window(d, pred, window)))
            for d in self.devices]))

    def dominant_runs(self) -> List[Event]:
        """The executions, whole inside the window, of the program that
        took most device time on the first chip (the train step of a
        training trace), in order."""
        lo, hi = self.window
        if not self.devices:
            return []
        by: Dict[str, List[Event]] = {}
        for e in self.devices[0].modules:
            if e.start >= lo and e.end <= hi:
                by.setdefault(e.name.split("(")[0], []).append(e)
        if not by:
            return []
        name = max(by, key=lambda k: sum(e.dur for e in by[k]))
        return sorted(by[name], key=lambda e: e.start)

    def exposed_time_of(self, pred: Callable[[str], bool]) -> float:
        """The part of those seconds during which no OTHER operation ran on
        the same chip."""
        if not self.devices:
            return 0.0
        out = []
        for d in self.devices:
            mine = union(self._ops_in_window(d, pred))
            others = union(self._ops_in_window(d, lambda n: not pred(n)))
            out.append(total(subtract(mine, others)))
        return float(np.mean(out))

    def top_ops(self, n: int = 10) -> List[List]:
        """``[[group, seconds], ...]``: self time by operation group inside
        the window, averaged over the chips, largest first."""
        lo, hi = self.window
        acc: Dict[str, float] = {}
        for d in self.devices:
            evs = [e for e in d.ops if e.end > lo and e.start < hi]
            for e, own in zip(evs, self_times(evs)):
                g = op_group(e.name)
                acc[g] = acc.get(g, 0.0) + own / len(self.devices)
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def span_gaps(self, span: str) -> List[float]:
        """For each host span named ``span`` (the benchmark's
        ``engine_step``), the longest time the first chip ran no program
        between two consecutive programs, counted to the span that covers
        the middle of it.  Where a loop makes the device wait once a span
        for the host, that wait is the span's longest gap, wherever in the
        span it falls (the v5e's serve loop, PR 22: 3-4 ms after the
        program that packs a step's tokens, near the END of the span that
        fetched them, because the same ``step()`` call launches the next
        decode; every other gap is microseconds).  Spans that cover no gap
        are skipped."""
        lo, hi = self.window
        if not self.devices:
            return []
        mods = sorted((e for e in self.devices[0].modules
                       if e.start >= lo and e.end <= hi),
                      key=lambda e: e.start)
        gaps = [(0.5 * (a.end + b.start), max(b.start - a.end, 0.0))
                for a, b in zip(mods, mods[1:])]
        mids = np.asarray([g[0] for g in gaps])
        out = []
        for s in sorted((e for e in self.annotations
                         if e.name == ANNOTATION_PREFIX + span),
                        key=lambda e: e.start):
            i, j = np.searchsorted(mids, (s.start, s.end))
            if j > i:
                out.append(max(g[1] for g in gaps[i:j]))
        return out

    # ---- idle time by what the host was doing

    def idle_gaps(self, n: int = 10) -> List[List]:
        """``[[annotation, seconds], ...]``: the first chip's idle time
        inside the window, by the innermost benchmark annotation that
        covers each stretch (``(none)`` where no annotation does)."""
        lo, hi = self.window
        if not self.devices:
            return []
        gaps = complement(self.busy(self.devices[0]), lo, hi)
        # innermost first: later-starting, shorter spans win; each span
        # takes what it covers of ALL the remaining gaps in one pass
        rest = gaps
        acc: Dict[str, float] = {}
        for s in sorted(self.annotations, key=lambda e: (-e.start, e.dur)):
            if len(rest) == 0:
                break
            got = total(clip(rest, s.start, s.end))
            if got:
                acc[s.name] = acc.get(s.name, 0.0) + got
                rest = np.concatenate([clip(rest, -np.inf, s.start),
                                       clip(rest, s.end, np.inf)])
        if total(rest):
            acc["(none)"] = total(rest)
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, chips: Optional[int] = None) -> Trace:
    """Read an ``.xplane.pb`` with nothing but JAX."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: List[Device] = []
    annotations: List[Event] = []

    def events(line):
        return [Event(e.name, e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events]

    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            devices.append(Device(
                int(m.group(1)), events(lines[OPS_LINE]),
                events(lines[MODULES_LINE]) if MODULES_LINE in lines else []))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                annotations += [e for e in events(ln)
                                if e.name.startswith(ANNOTATION_PREFIX)]
    devices.sort(key=lambda d: d.index)
    devices = [d for d in devices if d.ops]
    if chips is not None:
        devices = devices[:chips]
    if annotations:
        window = (min(e.start for e in annotations),
                  max(e.end for e in annotations))
    else:
        every = [e for d in devices for e in d.ops]
        window = ((min(e.start for e in every), max(e.end for e in every))
                  if every else (0.0, 0.0))
    return Trace(devices, annotations, window)
