"""The program's own spans in a profiler trace, and what is read from them.

Every `repro.obs.Tracker.span` is also a profiler annotation named
`repro.<name>` (the training loop's `repro.step`, `repro.data_next`,
`repro.train_step`, `repro.dispatch`, `repro.device_wait`,
`repro.readback`, `repro.callbacks`), so a `.xplane.pb` holds them on the
host threads' lines, on the same clock as the device's operations. `load`
reads them beside `trace.load`'s reduction, which it leaves as it is; the
functions below turn them into per-step numbers and put the device's idle
time down to the span the host was in. The step's named scopes (`perturb`,
`descent`, `ascent`, `update`, `cross_entropy`) are not in a TPU trace's
operation events: `scope_ms` reads them from the compiled program's HLO
as the profiler keeps it (`op_names_from_xspace`), matched to the events
by instruction name.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable, Optional

from bench import trace as tr

PREFIX = "repro."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str             # "repro.<name>"
    start: float          # ns
    end: float            # ns
    thread: int           # index of the host line (one per OS thread)
    args: dict            # the annotation's stats

    @property
    def ns(self) -> float:
        return self.end - self.start


def load(path: str) -> tuple[tr.Trace, list]:
    """`trace.load(path)` and the program's spans, sorted by start."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    start = float(e.start_ns)
                    spans.append(Span(e.name, start,
                                      start + float(e.duration_ns), thread,
                                      dict(e.stats)))
    spans.sort(key=lambda s: (s.start, -s.end))
    return tr.load(path), spans


def in_window(trace: tr.Trace, spans: Iterable[Span],
              name: Optional[str] = None) -> list:
    """The spans (named `name`, if given) that lie inside the window."""
    lo, hi = trace.window()
    return [s for s in spans if s.start >= lo and s.end <= hi
            and (name is None or s.name == name)]


def steps(trace: tr.Trace, spans: Iterable[Span]) -> int:
    """Training steps in the window: its `repro.train_step` spans."""
    return len(in_window(trace, spans, "repro.train_step"))


def _per_step_ms(ns: float, n: int) -> Optional[float]:
    return ns * 1e-6 / n if n else None


def span_ms(trace: tr.Trace, spans: list, name: str) -> Optional[float]:
    """Time per step inside spans named `name` (`repro.readback`,
    `repro.dispatch`, `repro.data_next`, ...); None where the trace has no
    training step or no such span."""
    mine = in_window(trace, spans, name)
    if not mine:
        return None
    return _per_step_ms(sum(s.ns for s in mine), steps(trace, spans))


def loop_self_ms(trace: tr.Trace, spans: list) -> Optional[float]:
    """Time per step in `repro.step` outside what its children on the same
    thread, `repro.data_next` and `repro.train_step`, cover: the loop's
    own work between drawing a batch and stepping."""
    loops = in_window(trace, spans, "repro.step")
    if not loops:
        return None
    kids = [s for s in in_window(trace, spans)
            if s.name in ("repro.data_next", "repro.train_step")]
    self_ns = 0.0
    for loop in loops:
        covered = sum(k.ns for k in kids if k.thread == loop.thread
                      and k.start >= loop.start and k.end <= loop.end)
        self_ns += loop.ns - covered
    return _per_step_ms(self_ns, steps(trace, spans))


# --- the device's idle time, by the span the host was in ---------------------

def idle_intervals(trace: tr.Trace) -> list:
    """[(start, end)] ns: the gaps between operations on the idlest device,
    inside the window (the intervals `trace.idle_gaps` ranks)."""
    if not trace.devices:
        return []
    lo, hi = trace.window()
    dev = max(trace.devices,
              key=lambda d: -tr.union_ns(trace.devices[d], lo, hi))
    gaps, edge = [], lo
    for o in trace.devices[dev]:
        if o.end <= lo or o.start >= hi:
            continue
        if o.start > edge:
            gaps.append((edge, o.start))
        edge = max(edge, o.end)
    if hi > edge:
        gaps.append((edge, hi))
    return gaps


def innermost(spans: list) -> list:
    """[(start, end, name)]: the timeline cut where any span starts or ends,
    each piece named for the innermost span over it (the latest started of
    those open); pieces no span covers are left out."""
    cuts = sorted({t for s in spans for t in (s.start, s.end)})
    by_start = sorted(spans, key=lambda s: (s.start, -s.end))
    out, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i].start <= a:
            open_.append(by_start[i])
            i += 1
        open_ = [s for s in open_ if s.end > a]
        if open_:
            inner = max(open_, key=lambda s: (s.start, -s.end))
            out.append((a, b, inner.name))
    return out


def idle_by_span(trace: tr.Trace, spans: list,
                 gaps: Optional[list] = None) -> dict:
    """Seconds of the idlest device's idle time under each innermost
    program span, None for time no span covers."""
    pieces = innermost(spans)
    out: dict = {}
    j = 0
    for g0, g1 in sorted(gaps if gaps is not None else idle_intervals(trace)):
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        covered = 0.0
        k = j
        while k < len(pieces) and pieces[k][0] < g1:
            a, b, name = pieces[k]
            ns = min(b, g1) - max(a, g0)
            if ns > 0:
                out[name] = out.get(name, 0.0) + ns * tr.NS
                covered += ns
            k += 1
        if g1 - g0 > covered:
            out[None] = out.get(None, 0.0) + (g1 - g0 - covered) * tr.NS
    return out


def longest_gaps(trace: tr.Trace, spans: list, n: int = 10) -> list:
    """[seconds, {span: seconds}]: the `n` longest idle gaps, each split by
    the innermost program span over it."""
    gaps = sorted(idle_intervals(trace), key=lambda g: g[0] - g[1])[:n]
    return [[(g1 - g0) * tr.NS, idle_by_span(trace, spans, [(g0, g1)])]
            for g0, g1 in gaps]


# --- named scopes ------------------------------------------------------------

def op_names_from_xspace(path: str, module: str = "jit_step") -> dict:
    """{instruction: op_name} of the largest program named `module` whose
    HLO the profiler keeps in the trace's "/host:metadata" plane. A TPU
    trace's operation events carry no name stack of their own; the
    compiled program's metadata does. An instruction without one that calls
    a computation (a fusion) takes the op_name of that computation's root.
    Reads the XSpace and HLO protobuf classes that tensorflow ships."""
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    best = b""
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        ids = {k for k, m in plane.stat_metadata.items()
               if m.name == "Hlo Proto"}
        for md in plane.event_metadata.values():
            if md.name.split("(")[0] != module:
                continue
            for st in md.stats:
                if st.metadata_id in ids and len(st.bytes_value) > len(best):
                    best = st.bytes_value
    proto = hlo_pb2.HloProto()
    proto.ParseFromString(best)
    names, calls, roots = {}, {}, {}
    for comp in proto.hlo_module.computations:
        for i in comp.instructions:
            if i.id == comp.root_id:
                roots[comp.id] = i.name
            if i.metadata.op_name:
                names[i.name] = i.metadata.op_name
            if i.called_computation_ids:
                calls[i.name] = i.called_computation_ids[0]
    for instr, comp_id in calls.items():
        if instr not in names and roots.get(comp_id) in names:
            names[instr] = names[roots[comp_id]]
    return names


def has_scope(op_name: str, scope: str) -> bool:
    """Whether a name stack holds `scope` as a component, also inside a
    transformation (`transpose(jvp(ascent))`) or under remat."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)",
                     op_name) is not None


_NAME = re.compile(r"^%?([^\s=]+)")


def scope_ms(trace: tr.Trace, spans: list, scope: str,
             op_names: Optional[dict] = None) -> Optional[float]:
    """Device time per step, averaged over devices, of the operations in the
    window whose name stack holds `scope` (`ascent_ms`: "ascent"). The name
    stack is looked up by instruction name in `op_names` where given, else
    searched in the operation's text."""
    lo, hi = trace.window()
    total = 0.0
    for ops in trace.devices.values():
        for o in ops:
            if o.start < lo or o.end > hi:
                continue
            if op_names is None:
                text = o.text
            else:
                m = _NAME.match(o.name)
                text = op_names.get(m.group(1), "") if m else ""
            if has_scope(text, scope):
                total += o.end - o.start
    if not total:
        return None
    return _per_step_ms(total / max(1, len(trace.devices)),
                        steps(trace, spans))
