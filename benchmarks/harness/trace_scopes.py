"""What the raw trace says beyond ``trace_reduce``: who each device
operation belongs to, and what the serve loop was doing.

``jax.profiler.ProfileData`` (what ``trace_reduce.load`` reads) shows each
event's own stats only.  The raw ``.xplane.pb`` holds more, and this module
reads it with the generated ``xplane_pb2`` of an installed package:

- every ``XLA Ops`` event's *metadata* carries ``tf_op``, the JAX name stack
  of the operation (``jit(_step)/transpose(jvp(LlamaForCausalLM.hidden))/
  model/checkpoint/layer_1/mlp/down/dot_general``): flax scopes every
  module, the program adds ``optimizer``, ``loss_head``, ``sample``,
  ``pack_tokens``, ``kv_write``, ``kv_valid`` where no module is, and a
  Pallas kernel's ``name=`` is a component too;
- every ``XLA Modules`` event (one executed program) carries a ``run_id``,
  which the host plane's ``DoEnqueueProgram`` events carry as well; the flow
  ids of the runtime's own events (``_p`` on ``tpu::System::Execute``,
  ``_c`` on ``...=>IssueSequencedEvent``) lead from there to the moment the
  calling thread asked for the program, and so to the host span that
  launched it;
- host ``TraceAnnotation`` spans keep their keyword arguments as event
  stats: the program's ``nxd/serve/*`` phases (``obs.tracing.phase``) and the
  benchmark's own ``bench/*``.

Times are seconds on the clock ``trace_reduce`` uses (a line's
``timestamp_ns`` plus the event's ``offset_ps``), so its window and its
devices apply unchanged.  Self times are taken on events clipped to the
window, so a cell's groups add up to its busy time.  A trace without a
device plane (a rehearsal on the CPU) or without ``nxd/`` spans (a program
older than they are) gives empty lists, and the readers built on this
return ``None``.  Nothing here imports the program under test.
"""

from __future__ import annotations

import bisect
import dataclasses
import importlib.util
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.harness import stats, trace_reduce
from benchmarks.harness.trace_reduce import Event

SPAN_PREFIXES = ("nxd/", trace_reduce.ANNOTATION_PREFIX)
SERVE = "nxd/serve/"

# -- the table: a name stack -> a group ------------------------------------------
#
# A Mosaic call is named by its kernel; everything else by the first rule a
# component of its ``tf_op`` meets, outermost concern first: a pool write
# inside the attention module is cache traffic, the head inside the chunked
# loss is the loss.  Components are what is left of the stack when the
# transform wrappers (``jit(..)``, ``jvp(..)``, ``transpose(..)``,
# ``checkpoint``, ``cond/branch_N_fun``, ``while/body``) are cut open.
KERNEL_GROUPS = (("flash_fwd", "flash_fwd"), ("flash_dq", "flash_bwd"),
                 ("flash_dkv", "flash_bwd"),
                 ("paged_attention_decode", "paged_decode"),
                 ("paged_attention", "paged_chunk"))
SCOPE_GROUPS = (
    ("optimizer", ("optimizer",)),
    ("loss_head", ("loss_head",)),
    ("sample", ("sample", "pack_tokens", "_sample_rows", "_propose_rows",
                "_spec_accept", "_pack_tokens")),
    ("kv_write", ("kv_write", "kv_valid", "_insert_valid_fn")),
    ("pool_copy", ()),      # by argument name, see group_of
    ("attn_proj", ("attn", "self_attn", "attention")),
    ("mlp", ("mlp",)),
    ("norm", ("input_norm", "post_attn_norm", "final_norm", "norm")),
    ("embed", ("embed", "embed_tokens")),
    ("head", ("lm_head", "LlamaForCausalLM.head")),
)
GROUPS = tuple(dict.fromkeys(
    [g for _, g in KERNEL_GROUPS] + [g for g, _ in SCOPE_GROUPS]
    + ["collective", "other"]))
_SPLIT = re.compile(r"[/()]+")
_BY_COMPONENT = {c: g for g, cs in reversed(SCOPE_GROUPS) for c in cs}
_RANK = {g: i for i, (g, _) in enumerate(SCOPE_GROUPS)}


def components(tf_op: str) -> List[str]:
    """``jit(f)/transpose(jvp(a/b))/c/mul:`` -> ``[jit, f, transpose, jvp,
    a, b, c, mul]``."""
    return [c for c in _SPLIT.split(tf_op.rstrip(":")) if c]


def group_of(text: str, tf_op: str, program: str = "") -> str:
    """The group of one device operation: ``text`` is its HLO text (the
    event's name), ``tf_op`` its name stack, ``program`` the name of the
    program it ran in.  An operation the compiler made up has no stack, or
    the name of the ARGUMENT it copies: a copy of ``caches[3][1]`` is the
    page pool relaid out on its way into a program (``pool_copy``; the v5e's
    serve programs do it to every layer's pool every step, PR 23), and an
    operation with no stack at all is its program's (the sampler's)."""
    if trace_reduce.is_mosaic(text):
        name = trace_reduce.hlo_name(text)
        for prefix, group in KERNEL_GROUPS:
            if name.startswith(prefix):
                return group
    if trace_reduce.is_collective(text):
        return "collective"
    if tf_op.startswith("caches["):
        return "pool_copy"
    parts = components(tf_op) or [program[len("jit_"):]]
    found = [_BY_COMPONENT[c] for c in parts if c in _BY_COMPONENT]
    return min(found, key=_RANK.__getitem__) if found else "other"


# -- the raw file --------------------------------------------------------------------

_PB2 = None
# packages that ship the generated module, and where in them
_PB2_HOMES = (("tensorflow", "tsl/profiler/protobuf/xplane_pb2.py"),
              ("tsl", "profiler/protobuf/xplane_pb2.py"),
              ("xprof", "protobuf/xplane_pb2.py"),
              ("tensorboard_plugin_profile", "protobuf/xplane_pb2.py"))


def xplane_pb2():
    """The generated ``xplane_pb2`` of whichever installed package has it,
    loaded from its FILE: importing ``tensorflow`` itself costs seconds and
    a second accelerator runtime in a process that holds the chip."""
    global _PB2
    if _PB2 is None:
        for package, rel in _PB2_HOMES:
            spec = importlib.util.find_spec(package)
            where = spec and (spec.submodule_search_locations or [None])[0]
            path = where and os.path.join(where, rel)
            if path and os.path.exists(path):
                mod_spec = importlib.util.spec_from_file_location(
                    "benchmarks_xplane_pb2", path)
                _PB2 = importlib.util.module_from_spec(mod_spec)
                mod_spec.loader.exec_module(_PB2)
                break
        else:
            raise ImportError("no installed package holds xplane_pb2 (looked "
                              f"in {[p for p, _ in _PB2_HOMES]})")
    return _PB2


def read_space(path: str):
    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stat_value(stat, names: Dict[int, str]):
    kind = stat.WhichOneof("value")
    value = getattr(stat, kind)
    return names.get(value, "") if kind == "ref_value" else value


def _stats(holder, names: Dict[int, str]) -> dict:
    return {names.get(s.metadata_id, str(s.metadata_id)):
            _stat_value(s, names) for s in holder.stats}


@dataclasses.dataclass
class Op:
    """One executed device operation, clipped to the window."""

    text: str          # the HLO text (what trace_reduce calls the name)
    start: float
    end: float
    own: float         # self time: its children's time taken out
    tf_op: str
    group: str
    program: int       # index into the device's programs, -1: none covers it


@dataclasses.dataclass
class Program:
    """One executed program (an ``XLA Modules`` event)."""

    name: str                        # ``jit__step`` (the id cut off)
    start: float
    end: float
    run_id: Optional[int]
    launched: Optional[float] = None     # when the host asked for it
    span: Optional["Span"] = None        # the innermost serve span then


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    attrs: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


def _intervals(ops: Sequence[Op]) -> np.ndarray:
    return np.asarray([(op.start, op.end) for op in ops],
                      dtype=float).reshape(-1, 2)


@dataclasses.dataclass
class DeviceScopes:
    index: int
    ops: List[Op]
    programs: List[Program]


@dataclasses.dataclass
class Scopes:
    devices: List[DeviceScopes]
    spans: List[Span]
    window: Tuple[float, float]
    busy_s: float

    # ---- groups

    def group_seconds(self) -> Dict[str, float]:
        """Self time by group, averaged over the chips."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for op in d.ops:
                acc[op.group] = acc.get(op.group, 0.0) \
                    + op.own / len(self.devices)
        return acc

    def share(self, *groups: str) -> Optional[float]:
        """Percent of the busy time that the groups' self time is; ``None``
        where nothing ran under them (a program that lacks the scope)."""
        by = self.group_seconds()
        got = sum(by.get(g, 0.0) for g in groups)
        return 100.0 * got / self.busy_s if got and self.busy_s else None

    def by_group_and_program(self) -> List[Tuple[str, str, float]]:
        acc: Dict[Tuple[str, str], float] = {}
        for d in self.devices:
            for op in d.ops:
                prog = d.programs[op.program].name if op.program >= 0 else "-"
                key = (op.group, prog)
                acc[key] = acc.get(key, 0.0) + op.own / len(self.devices)
        return sorted(((g, p, s) for (g, p), s in acc.items()),
                      key=lambda x: -x[2])

    def ops_of(self, group: str, window=None) -> List[Op]:
        """The first chip's operations of a group, whole inside ``window``."""
        if not self.devices:
            return []
        lo, hi = window or self.window
        return [op for op in self.devices[0].ops
                if op.group == group and op.start >= lo and op.end <= hi]

    # ---- host spans

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def inside(self, outer: Span, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name
                and s.start >= outer.start and s.end <= outer.end]

    def seconds_launched_under(self, span_name: str) -> float:
        """Self time of the operations of every program that the host
        launched from inside a span of that name, averaged over the chips."""
        acc = 0.0
        for d in self.devices:
            under = {i for i, p in enumerate(d.programs)
                     if p.span is not None and p.span.name == span_name}
            acc += sum(op.own for op in d.ops if op.program in under)
        return acc / max(len(self.devices), 1)

    def steps(self) -> List["Step"]:
        """The serve loop's steps whose phases the trace holds, in order."""
        out = []
        for s in sorted(self.named(SERVE + "step"), key=lambda s: s.start):
            collect = self.inside(s, SERVE + "collect")
            fetch = (self.inside(collect[0], SERVE + "fetch")
                     if collect else [])
            dispatch = self.inside(s, SERVE + "dispatch")
            out.append(Step(s, fetch[0] if fetch else None,
                            dispatch[0] if dispatch else None,
                            self.inside(s, SERVE + "fetch")))
        return out

    def relaunch_gaps(self) -> List[Tuple[float, float]]:
        """For each step that both fetched and dispatched: ``(gap, lag)``.
        ``gap`` is the first chip's idle time between the end of the
        program the step's ``fetch`` waited for (the last one the step
        before launched from its ``dispatch``) and the start of the first
        program its own ``dispatch`` launched; ``lag`` is how long after
        that program's end on the device's clock the fetch returned on the
        host's: clock skew plus the transfer."""
        if not self.devices:
            return []
        d = self.devices[0]
        by_span: Dict[int, List[Program]] = {}
        for p in d.programs:
            if p.span is not None:
                by_span.setdefault(id(p.span), []).append(p)
        busy = trace_reduce.union(_intervals(d.ops))
        out = []
        steps = self.steps()
        for before, step in zip(steps, steps[1:]):
            if (before.dispatch is None or step.dispatch is None
                    or step.fetch is None):
                continue
            waited = by_span.get(id(before.dispatch))
            mine = by_span.get(id(step.dispatch))
            if not waited or not mine:
                continue
            t0 = max(p.end for p in waited)
            t1 = min(p.start for p in mine)
            if t1 < t0:
                continue
            between = trace_reduce.total(trace_reduce.clip(busy, t0, t1))
            out.append((t1 - t0 - between, step.fetch.end - t0))
        return out

    def clock_offset_bounds(self) -> Tuple[Optional[float], Optional[float]]:
        """``(least, most)`` seconds to add to a time on the device's clock
        to get the host's.  A program starts after the host asked for it,
        so no ``launched - start`` can pass the offset; a blocking fetch
        returns after the program it waited for has ended, so the offset
        cannot pass any ``lag`` of :meth:`relaunch_gaps`.  Their distance is
        the launch and transfer latency: how finely an idle stretch can be
        set against a host span."""
        progs = self.devices[0].programs if self.devices else []
        least = [p.launched - p.start for p in progs
                 if p.launched is not None]
        most = [lag for _, lag in self.relaunch_gaps()]
        return (max(least) if least else None, min(most) if most else None)

    def idle_by_span(self, prefix: str = SERVE) -> Dict[str, float]:
        """The first chip's idle seconds inside the window by the innermost
        span under ``prefix`` covering each stretch, the device's times
        moved onto the host's clock by the offset's lower bound: a program
        launched into an idle device starts within the launch latency (tens
        of microseconds), so that bound is nearly met wherever the device
        waited, while the upper one also holds a device-to-host transfer."""
        if not self.devices:
            return {}
        lo, hi = self.window
        d = self.devices[0]
        shift = self.clock_offset_bounds()[0] or 0.0
        rest = trace_reduce.complement(_intervals(d.ops), lo, hi) + shift
        acc: Dict[str, float] = {}
        for s in sorted((s for s in self.spans if s.name.startswith(prefix)),
                        key=lambda s: (-s.start, s.dur)):
            got = trace_reduce.total(trace_reduce.clip(rest, s.start, s.end))
            if got:
                acc[s.name] = acc.get(s.name, 0.0) + got
                rest = np.concatenate([
                    trace_reduce.clip(rest, -np.inf, s.start),
                    trace_reduce.clip(rest, s.end, np.inf)])
        return acc


@dataclasses.dataclass
class Step:
    span: Span
    fetch: Optional[Span]        # the blocking fetch inside ``collect``
    dispatch: Optional[Span]
    fetches: List[Span]          # every blocking fetch of the step

    @property
    def host_s(self) -> float:
        """The step's own host time: its length less its blocking fetches."""
        return self.span.dur - sum(f.dur for f in self.fetches)


# -- building it ---------------------------------------------------------------------


def _line_events(plane, line):
    """``(metadata, event, start, end)`` of a line's events; no line, none."""
    if line is None:
        return
    base = line.timestamp_ns * 1e-9
    for e in line.events:
        meta = plane.event_metadata[e.metadata_id]
        start = base + e.offset_ps * 1e-12
        yield meta, e, start, start + e.duration_ps * 1e-12


def _host(space) -> Tuple[List[Span], Dict[int, float]]:
    """The annotation spans, and for each ``run_id`` the time the calling
    thread asked the runtime to execute that program."""
    spans: List[Span] = []
    launched: Dict[int, float] = {}
    for plane in space.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        asked: Dict[int, float] = {}      # flow id -> Execute start
        issued: List[Tuple[int, float, float, int]] = []   # line, lo, hi, flow
        enqueued: List[Tuple[int, float, int]] = []        # line, at, run_id
        for li, line in enumerate(plane.lines):
            for meta, e, start, end in _line_events(plane, line):
                if meta.name.startswith(SPAN_PREFIXES):
                    spans.append(Span(meta.name, start, end,
                                      _stats(e, names)))
                elif meta.name == "tpu::System::Execute":
                    flow = _stats(e, names).get("_p")
                    if flow is not None:
                        asked[flow] = start
                elif meta.name == "tpu::System::Execute=>IssueSequencedEvent":
                    flow = _stats(e, names).get("_c")
                    if flow is not None:
                        issued.append((li, start, end, flow))
                elif meta.name == "DoEnqueueProgram":
                    run = _stats(e, names).get("run_id")
                    if run is not None:
                        enqueued.append((li, start, int(run)))
        for li, at, run in enqueued:
            flows = [f for (lj, lo, hi, f) in issued
                     if lj == li and lo <= at <= hi]
            launched[run] = asked.get(flows[-1], at) if flows else at
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans, launched


def _innermost(spans: Sequence[Span], starts: List[float],
               at: float) -> Optional[Span]:
    """The innermost of ``spans`` (sorted by start) covering time ``at``."""
    best = None
    for s in spans[:bisect.bisect_right(starts, at)]:
        if s.end >= at and (best is None or s.start >= best.start):
            best = s
    return best


def build(space, trace: trace_reduce.Trace) -> Scopes:
    """The scopes of a parsed file, for the window and the chips ``trace``
    (the same file through ``trace_reduce.load``) kept."""
    lo, hi = trace.window
    spans, launched = _host(space)
    spans = [s for s in spans if s.end > lo and s.start < hi]
    serve = [s for s in spans if s.name.startswith(SERVE)
             and s.name != SERVE + "step"]
    serve_starts = [s.start for s in serve]
    keep = {d.index for d in trace.devices}
    devices: List[DeviceScopes] = []
    for plane in space.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) not in keep:
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        lines = {ln.name: ln for ln in plane.lines}
        programs: List[Program] = []
        for meta, e, start, end in _line_events(
                plane, lines.get(trace_reduce.MODULES_LINE)):
            if end <= lo or start >= hi:
                continue
            run = _stats(e, names).get("run_id")
            p = Program(meta.name.split("(")[0], start, end,
                        None if run is None else int(run))
            p.launched = launched.get(p.run_id)
            if p.launched is not None:
                p.span = _innermost(serve, serve_starts, p.launched)
            programs.append(p)
        programs.sort(key=lambda p: p.start)
        prog_starts = [p.start for p in programs]
        tf_ops: Dict[int, str] = {}
        raw: List[Tuple[str, int, float, float]] = []
        for meta, e, start, end in _line_events(
                plane, lines.get(trace_reduce.OPS_LINE)):
            if end <= lo or start >= hi:
                continue
            if meta.id not in tf_ops:
                tf_ops[meta.id] = str(_stats(meta, names).get("tf_op", ""))
            raw.append((meta.name, meta.id, max(start, lo), min(end, hi)))
        own = trace_reduce.self_times([Event(t, a, b) for t, _, a, b in raw])
        ops = []
        for (text, mid, a, b), o in zip(raw, own):
            i = bisect.bisect_right(prog_starts, a) - 1
            if i >= 0 and programs[i].end < a:
                i = -1
            ops.append(Op(text, a, b, o, tf_ops[mid], group_of(
                text, tf_ops[mid], programs[i].name if i >= 0 else ""), i))
        devices.append(DeviceScopes(int(m.group(1)), ops, programs))
    devices.sort(key=lambda d: d.index)
    return Scopes(devices, spans, (lo, hi), trace.busy_s())


_CACHE: Dict[str, Scopes] = {}


def of(r) -> Optional[Scopes]:
    """The scopes of a reading's traced run: the raw file is parsed once a
    process, and the ``[scopes]`` / ``[phases]`` lines are printed then.
    ``None`` where the run was not traced."""
    if r.trace is None:
        return None
    from benchmarks.harness import common

    path = trace_reduce.find_xplane(os.path.join(common.TRACE_DIR,
                                                 r.cell.name))
    if path not in _CACHE:
        _CACHE[path] = build(read_space(path), r.trace)
        for line in report(_CACHE[path]):
            print(line, flush=True)
    return _CACHE[path]


# -- the lines -----------------------------------------------------------------------


def report(sc: Scopes) -> List[str]:
    out = []
    if sc.busy_s:
        cells = sc.by_group_and_program()
        top, total = cells[:12], sum(s for _, _, s in cells)
        out.append("[scopes] self time by group x program, s (share of "
                   f"{sc.busy_s:.3f} s busy; groups add up to {total:.3f}): "
                   + "; ".join(f"{g} x {p} {s:.4f} ({100 * s / sc.busy_s:.1f}%)"
                               for g, p, s in top))
        by = sc.group_seconds()
        out.append("[scopes] by group: " + ", ".join(
            f"{g} {100 * by[g] / sc.busy_s:.2f}%"
            for g in sorted(by, key=lambda g: -by[g])))
    names = sorted({s.name for s in sc.spans if s.name.startswith(SERVE)})
    if names:
        idle = sc.idle_by_span()
        parts = []
        for n in names:
            ds = [s.dur * 1e3 for s in sc.named(n)]
            parts.append(f"{n[len(SERVE):]} n={len(ds)} p50 "
                         f"{stats.median(ds):.3f} ms, device idle under it "
                         f"{idle.get(n, 0.0) * 1e3:.2f} ms")
        out.append("[phases] " + "; ".join(parts))
        gaps = sc.relaunch_gaps()
        least, most = sc.clock_offset_bounds()
        if gaps and least is not None:
            out.append(
                f"[phases] relaunch gap over {len(gaps)} steps: p50 "
                f"{stats.median([g for g, _ in gaps]) * 1e3:.3f} ms; host "
                f"clock - device clock between {least * 1e3:.3f} ms (no "
                "program starts before it is launched) and "
                f"{most * 1e3:.3f} ms (the least of fetch end - end of the "
                "program it waited for, p50 "
                f"{stats.median([g[1] for g in gaps]) * 1e3:.3f}): an idle "
                f"stretch is placed to {(most - least) * 1e3:.3f} ms")
    return out
