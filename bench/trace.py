"""Reduction of a profiler trace to device busy time, kernel time and gaps.

`load` reads the `.xplane.pb` that `jax.profiler` writes: per device, the
operations that ran on it (a TPU's "XLA Ops" line; on the CPU, the events
that carry an `hlo_op`), and the benchmark's own host annotations (events
named "bench.*"). Every function below works on that and nothing else, so a
recorded trace checks them.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from typing import Iterable, Optional

NS = 1e-9


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float          # ns
    end: float            # ns
    text: str             # the event's name and its string stats, joined


@dataclasses.dataclass
class Trace:
    devices: dict          # device id -> list[Op], sorted by start
    host: list             # list[Op]: the benchmark's "bench.*" annotations

    def window(self) -> tuple[float, float]:
        """The traced window: the "bench.window" annotation, else the span
        of every device operation."""
        spans = [h for h in self.host if h.name == "bench.window"]
        if spans:
            return spans[0].start, spans[0].end
        ops = [o for v in self.devices.values() for o in v]
        return min(o.start for o in ops), max(o.end for o in ops)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _op(event) -> Op:
    stats = dict(event.stats)
    text = " ".join([event.name] + [str(v) for v in stats.values()
                                    if isinstance(v, str)])
    start = float(event.start_ns)
    return Op(event.name, start, start + float(event.duration_ns), text)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.setdefault(int(m.group(1)), []).extend(
                        _op(e) for e in line.events)
            continue
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    host.append(_op(e))
                    continue
                stats = dict(e.stats)
                if "hlo_op" in stats:   # the CPU backend's operations
                    devices.setdefault(int(stats.get("device_ordinal", 0)),
                                       []).append(_op(e))
    host.sort(key=lambda o: o.start)
    return Trace({d: _leaves(ops) for d, ops in devices.items()}, host)


def _leaves(ops: list) -> list:
    """The operations that enclose no other, sorted by start. A TPU trace
    lists a `while` loop as one event over all of its body's operations,
    which would count their time twice."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    parents, stack = set(), []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            parents.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(ops) if i not in parents]


def union_ns(ops: Iterable[Op], lo: float, hi: float) -> float:
    """Length of the union of the operations' intervals inside [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for o in sorted(ops, key=lambda o: o.start):
        s, e = max(o.start, lo), min(o.end, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: Trace) -> dict:
    """Seconds in which some operation ran, per device, inside the window."""
    lo, hi = trace.window()
    return {d: union_ns(ops, lo, hi) * NS for d, ops in trace.devices.items()}


def idle_share(trace: Trace) -> float:
    """1 - busy / window on the idlest device."""
    lo, hi = trace.window()
    return max(1.0 - b / ((hi - lo) * NS) for b in busy_s(trace).values())


def summed_s(ops: Iterable[Op]) -> float:
    return sum(o.end - o.start for o in ops) * NS


_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8}
_ARRAY = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64)"
                    r"\[([\d,]*)\]")


def _arrays(text: str) -> list:
    """(dtype, shape) of each array type written in an HLO fragment."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _ARRAY.findall(text)]


@dataclasses.dataclass(frozen=True)
class CustomCall:
    """A `tpu_custom_call` (a Pallas kernel) and its array types."""
    op: Op
    results: list          # [(dtype, shape)]
    operands: list

    @property
    def nbytes(self) -> int:
        """Bytes of every operand and result: what a kernel that streams
        each once moves through HBM."""
        total = 0
        for dt, shape in self.results + self.operands:
            n = _ITEMSIZE[dt]
            for x in shape:
                n *= x
            total += n
        return total


_CALL = re.compile(r"^%\S+ = (.*?) custom-call\((.*?)\), "
                   r"custom_call_target=\"tpu_custom_call\"")


def custom_calls(ops: Iterable[Op]) -> list:
    """The Pallas kernels among `ops`, with the array types of their HLO.

    A TPU trace names an operation by its HLO instruction, and these kernels
    carry no name of their own there, so a reader tells them apart by the
    shapes of what they take and give.
    """
    out = []
    for o in ops:
        types = _call_types(o.name)
        if types:
            out.append(CustomCall(o, *types))
    return out


@functools.lru_cache(maxsize=2**16)
def _call_types(name: str) -> Optional[tuple]:
    """(results, operands) of a Pallas kernel's instruction, else None;
    one parse per instruction, which a trace repeats every step."""
    m = "tpu_custom_call" in name and _CALL.match(name)
    if not m:
        return None
    return _arrays(m.group(1)), _arrays(re.sub(r"\{[^}]*\}", "", m.group(2)))


@functools.lru_cache(maxsize=2**16)
def label(name: str) -> str:
    """A short name for an operation: its HLO instruction's name without
    the numeric suffix, and its result type ("fusion f32[2,2048]")."""
    m = re.match(r"%([A-Za-z_\-]+)[.\d]* = (\(?[a-z0-9]+\[[\d,]*\])", name)
    if not m:
        return name[:100]
    return f"{m.group(1)} {re.sub(r'^[(]', '', m.group(2))}"


def top_ops(trace: Trace, n: int = 10) -> list:
    """[label, seconds]: the operations, grouped by `label`, that took most
    device time in the window, averaged over devices."""
    lo, hi = trace.window()
    tot: dict = {}
    for ops in trace.devices.values():
        for o in ops:
            if o.start >= lo and o.end <= hi:
                k = label(o.name)
                tot[k] = tot.get(k, 0.0) + (o.end - o.start) * NS
    k = max(1, len(trace.devices))
    return sorted(([a, b / k] for a, b in tot.items()),
                  key=lambda x: -x[1])[:n]


def _host_label(trace: Trace, t: float) -> str:
    """The innermost "bench.*" annotation that covers time t; "engine"
    (the loop's own code in `Engine.fit`) where none does."""
    best: Optional[Op] = None
    for h in trace.host:
        if h.start <= t <= h.end and h.name != "bench.window" and (
                best is None or h.start >= best.start):
            best = h
    return best.name if best else "engine"


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[what the host was doing, seconds]: the longest gaps between
    operations on the idlest device, inside the window."""
    lo, hi = trace.window()
    dev = max(trace.devices, key=lambda d: -union_ns(trace.devices[d], lo, hi))
    gaps, edge = [], lo
    for o in trace.devices[dev]:
        if o.end <= lo or o.start >= hi:
            continue
        if o.start > edge:
            gaps.append((o.start - edge, edge, o.start))
        edge = max(edge, o.end)
    if hi > edge:
        gaps.append((hi - edge, edge, hi))
    gaps.sort(reverse=True)
    return [[_host_label(trace, (s + e) / 2), g * NS] for g, s, e in gaps[:n]]
