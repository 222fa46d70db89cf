"""Reduce a `jax.profiler` trace to the numbers the benchmark reports.

The trace is the ``*.xplane.pb`` that `jax.profiler.start_trace` writes,
read with `jax.profiler.ProfileData` (nothing but JAX). Its planes named
``/device:<platform>:<n>`` are the devices; on each, the line ``XLA Ops``
holds one event per operation that ran, and ``XLA Modules`` one per
program launch. On the host plane, the Python thread's line holds the
`jax.profiler.TraceAnnotation` spans it opened and JAX's dispatch spans
(``PjitFunction(update)``).

Everything is clipped to one window, given as a host span's name (the
benchmark opens such a span around the traced steps):

* busy: the union of the operation intervals of a device, averaged over
  the devices that ran anything;
* device time per program (``jit_update``) and per operation
  (``fusion.8``; a Pallas kernel is the custom call named after its
  function, ``topk_wire``);
* idle gaps: the stretches of the window in which a device ran nothing,
  each named by the innermost host span open at its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float
    custom: bool = False  # an operation that is a custom call (a kernel)

    @property
    def dur(self) -> float:
        return self.end - self.start


def op_name(text: str) -> str:
    """An operation event's name is its HLO instruction, ``%fusion.8 =
    f32[...] fusion(...)``; keep the instruction's name, ``fusion.8``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def is_custom_call(text: str) -> bool:
    return " custom-call(" in text


def program_name(text: str) -> str:
    """A launch event is ``jit_update(16620479568959515328)``: keep
    ``jit_update``."""
    return text.split("(", 1)[0]


@dataclasses.dataclass
class Trace:
    """The parts of one trace the reduction reads."""

    device_ops: Dict[str, List[Event]]  # device plane -> operations
    device_modules: Dict[str, List[Event]]  # device plane -> launches
    host_spans: List[Event]  # events with a duration on Python threads


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    mods: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        Event(op_name(e.name), e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9,
                              is_custom_call(e.name))
                        for e in line.events]
                else:
                    mods[plane.name] = [
                        Event(program_name(e.name), e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue  # runtime threads; the spans are the caller's
                host.extend(Event(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.duration_ns > 0)
    return Trace(ops, mods, host)


def host_window(trace: Trace, span_name: str) -> Tuple[float, float]:
    """(start, end) of the one host span called ``span_name``."""
    hits = [e for e in trace.host_spans if e.name == span_name]
    if len(hits) != 1:
        raise ValueError(f"{len(hits)} host spans named {span_name!r} in "
                         "the trace; expected one")
    return hits[0].start, hits[0].end


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(dataclasses.replace(e, start=s, end=t))
    return out


def _union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    spans = sorted((e.start, e.end) for e in events)
    merged: List[List[float]] = []
    for s, t in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over the devices that ran anything
    ops: List[Event]  # every device's operations, clipped to the window
    modules: List[Event]
    gaps: List[Tuple[float, float]]  # idle stretches of the first device
    devices: int

    def module_seconds(self, program: str) -> float:
        """Device time of the launches of the program called ``program``
        (``jit_update``), summed over devices."""
        return sum(e.dur for e in self.modules if e.name == program)

    def kernel_seconds(self, kernel: str) -> float:
        """Device time of the custom calls (Pallas kernels) whose
        instruction is named ``kernel`` (``topk_wire``, ``topk_wire.3``),
        summed over devices."""
        return sum(e.dur for e in self.ops
                   if e.custom and e.name.split(".")[0] == kernel)

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations with most device time, each named
        ``<program>/<instruction>`` by the launch it ran in."""
        starts = [m.start for m in self.modules]
        order = sorted(range(len(self.modules)), key=starts.__getitem__)
        starts = [starts[i] for i in order]
        by_name: Dict[str, float] = defaultdict(float)
        for e in self.ops:
            i = bisect.bisect_right(starts, e.start) - 1
            mod = self.modules[order[i]] if i >= 0 else None
            prog = mod.name if mod is not None and \
                mod.start <= e.start <= mod.end else "?"
            by_name[f"{prog}/{e.name}"] += e.dur
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]


def reduce(trace: Trace, window: Tuple[float, float]) -> Reduction:
    lo, hi = window
    busy, devices, gaps = [], 0, []
    all_ops: List[Event] = []
    for plane in sorted(trace.device_ops):
        ops = _clip(trace.device_ops[plane], lo, hi)
        if not ops:
            continue
        devices += 1
        all_ops.extend(ops)
        union = _union(ops)
        busy.append(sum(t - s for s, t in union))
        if devices == 1:
            edges = [lo] + [x for st in union for x in st] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    modules = [e for evs in trace.device_modules.values()
               for e in _clip(evs, lo, hi)]
    return Reduction(window_s=hi - lo,
                     busy_s=(sum(busy) / len(busy)) if busy else 0.0,
                     ops=all_ops, modules=modules, gaps=gaps,
                     devices=devices)


def name_gaps(gaps: Sequence[Tuple[float, float]], spans: Sequence[Event],
              n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps, each named by the innermost (shortest)
    host span that is open at the gap's middle."""
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + t)
        open_ = [e for e in spans if e.start <= mid <= e.end]
        name = min(open_, key=lambda e: e.dur).name if open_ \
            else "(no host span)"
        out.append([name, t - s])
    return out


def breakdown(red: Reduction, spans: Sequence[Event],
              n: int = 10) -> Dict[str, List[List]]:
    return {"device_ops": red.top_ops(n),
            "idle_gaps": name_gaps(red.gaps, spans, n)}
