"""The program's host spans and the chunk program's executions, read from a
profiler trace (`.xplane.pb`).

A recording `repro.obs.Tracer` mirrors each span into the profiler as an
annotation, so its spans sit on the lines of the `/host:CPU` plane, one
line per thread, on the same nanosecond clock as the `/device:TPU:<n>`
planes. `host_lanes` returns every line of that plane with its events;
lanes are told apart by the spans they hold (the driver's holds `chunk`,
the prefetch worker's the kicked `chunk_prep`), never by thread names.
A lane also holds the runtime's own events (`PjitFunction(...)`, ...):
`innermost` looks at the program's spans (`PROGRAM_SPANS`) alone.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Tuple

import devtrace

Interval = Tuple[int, int]
# the span names repro.obs.Tracer records on the host (docs/observability.md)
PROGRAM_SPANS = frozenset((
    "chunk", "dispatch", "metrics_flush", "hooks_boundary", "prep_stall",
    "prefetch_degraded", "chunk_prep", "ctl_build", "batch_stage",
    "batch_gen", "prep_device_wait", "h2d_put", "ckpt_snapshot",
    "ckpt_write", "retry", "channel_realize", "schedule_solve",
    "params_init"))
MODULES_LINE = "XLA Modules"


@dataclass
class Event:
    name: str
    start: int
    end: int


@dataclass
class Lane:
    """One line of the host plane: its events in start order."""
    name: str
    events: List[Event]

    def holds(self, span: str) -> bool:
        return any(e.name == span for e in self.events)

    def spans(self, name: str) -> List[Event]:
        return [e for e in self.events if e.name == name]


def host_lanes(path: str) -> List[Lane]:
    """The `/host:CPU` plane's lines, with their events."""
    from jax.profiler import ProfileData
    lanes = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [Event(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                      for e in line.events]
            events.sort(key=lambda e: (e.start, -e.end))
            lanes.append(Lane(line.name, events))
    return lanes


def driver_lane(lanes: Iterable[Lane]) -> Optional[Lane]:
    """The lane that holds the driver's `chunk` spans."""
    return next((ln for ln in lanes if ln.holds("chunk")), None)


def worker_lane(lanes: Iterable[Lane]) -> Optional[Lane]:
    """The prefetch worker's lane: it prepares chunks (`chunk_prep`) and
    holds no `chunk`."""
    return next((ln for ln in lanes
                 if ln.holds("chunk_prep") and not ln.holds("chunk")), None)


def program_lanes(lanes: Iterable[Lane]) -> List[Lane]:
    """The lanes that hold any of the program's spans."""
    return [ln for ln in lanes
            if any(e.name in PROGRAM_SPANS for e in ln.events)]


def innermost(lane: Lane, window: Interval) -> List[Tuple[str, int, int]]:
    """(span, start, end) pieces of the window, each named after the
    innermost of the program's spans open on the lane then; time in no
    span is left out. Spans on a lane nest (one thread's annotations)."""
    spans = [e for e in lane.events if e.name in PROGRAM_SPANS
             and e.end > window[0] and e.start < window[1]]
    bounds = sorted({window[0], window[1]}
                    | {max(window[0], min(window[1], t))
                       for e in spans for t in (e.start, e.end)})
    out: List[Tuple[str, int, int]] = []
    for a, b in zip(bounds, bounds[1:]):
        mid = (a + b) / 2
        open_ = [e for e in spans if e.start <= mid < e.end]
        if not open_:
            continue
        name = min(open_, key=lambda e: e.end - e.start).name
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two unions of intervals."""
    a, b = devtrace._union(a), devtrace._union(b)
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def length(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def module_runs(path: str, match=re.compile(r"^jit_chunk\b").search
                ) -> Dict[int, List[Interval]]:
    """{chip: [(start, end)]} of the executions of the modules whose name
    matches (the scan engine's chunk program, `jit_chunk(...)`), from each
    device plane's `XLA Modules` line."""
    from jax.profiler import ProfileData
    runs: Dict[int, List[Interval]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                runs[int(m.group(1))] = sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if match(e.name))
    return runs


def instruction(op: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` → `fusion.12`, the name the
    compiled module's text gives the instruction."""
    return op.split(" = ", 1)[0].lstrip("%")
