"""Reduction of a profiler trace (`.xplane.pb`) to device busy/idle time,
per-op device time, collective exposure and idle gaps.

The TPU planes are `/device:TPU:<n>`; the operations that ran on a chip
are the events of its `XLA Ops` line, on the same nanosecond clock as the
host plane's events (both relative to the profile's start). An event's name
is its HLO instruction's text, `%<op>.<n> = <shape> <opcode>(...)`; `kind`
reduces it to `<op>`. A Pallas kernel's op is named after the jitted
wrapper around its `pallas_call` (`seeded_axpy_pallas`,
`flash_attention_pallas`). Control-flow ops (`while`, `conditional`, `call`)
span the ops of their bodies, which appear on the same line, so op time is
counted as self time: an event's duration less that of the events nested in
it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"send|recv", re.IGNORECASE)

Interval = Tuple[int, int]            # [start_ns, end_ns)


@dataclass
class DeviceOps:
    """One chip's op events: (name, start_ns, end_ns)."""
    device: int
    ops: List[Tuple[str, int, int]] = field(default_factory=list)


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _length(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def _subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (unioned) intervals `a` not covered by unioned `b`."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def load(path: str) -> Tuple[List[DeviceOps], List[Tuple[str, int, int]]]:
    """(per-chip op events, host events) from an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: List[DeviceOps] = []
    host: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceOps(int(m.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events)
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events)
    return sorted(devices, key=lambda d: d.device), host


def busy(dev: DeviceOps) -> List[Interval]:
    return _union((a, b) for _, a, b in dev.ops)


def busy_s(dev: DeviceOps, window: Optional[Interval] = None) -> float:
    """Seconds in which an op ran on this chip (inside `window`, if given)."""
    intervals = busy(dev)
    if window is not None:
        w0, w1 = window
        intervals = [(max(a, w0), min(b, w1)) for a, b in intervals
                     if min(b, w1) > max(a, w0)]
    return _length(intervals) * 1e-9


def kind(name: str) -> str:
    """`%seeded_axpy_pallas.12 = f32[...] custom-call(...)` → the op's
    name without its instance number: `seeded_axpy_pallas`."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+(\.clone)*$", "", head)


def self_times(dev: DeviceOps) -> List[Tuple[str, int]]:
    """(name, self ns) of each op: its duration less its nested ops'."""
    out: List[Tuple[str, int]] = []
    stack: List[List] = []            # [name, end, self]
    for name, a, b in sorted(dev.ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    out.extend((n, s) for n, _, s in stack)
    return out


def op_seconds(devices: List[DeviceOps],
               match: Callable[[str], bool]) -> float:
    """Self time of the ops whose kind matches, summed over all chips."""
    return sum(s for d in devices for n, s in self_times(d)
               if match(kind(n))) * 1e-9


def op_count(devices: List[DeviceOps], match: Callable[[str], bool]) -> int:
    return sum(1 for d in devices for n, _, _ in d.ops if match(kind(n)))


def exposed_collective_s(dev: DeviceOps) -> float:
    """Time in which a collective runs on this chip and nothing else does."""
    coll = _union((a, b) for n, a, b in dev.ops if COLLECTIVE.search(n))
    other = _union((a, b) for n, a, b in dev.ops
                   if not COLLECTIVE.search(n))
    return _length(_subtract(coll, other)) * 1e-9


def top_ops(devices: List[DeviceOps], n: int = 10
            ) -> List[Tuple[str, float]]:
    """The op kinds with the most self time, summed over chips."""
    tot: Dict[str, int] = {}
    for d in devices:
        for name, s in self_times(d):
            key = kind(name)
            tot[key] = tot.get(key, 0) + s
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v * 1e-9) for k, v in ranked]


def idle_gaps(dev: DeviceOps, window: Interval) -> List[Interval]:
    """Intervals of the window in which this chip ran no op."""
    return _subtract([window], busy(dev))


def describe(path: str, n: int = 25) -> Dict:
    """A by-hand look at a trace: planes, lines, event counts, top names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            tot: Dict[str, float] = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
            sample = [{"name": e.name, "start_ns": e.start_ns,
                       "dur_ns": e.duration_ns,
                       "stats": [(str(k), str(v)[:200]) for k, v in
                                 list(e.stats)[:12]]} for e in evs[:3]]
            lines.append({"line": line.name, "events": len(evs),
                          "top_ns": top, "sample": sample})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


def window_of(devices: List[DeviceOps]) -> Optional[Interval]:
    """First op start to last op end over all chips."""
    starts = [a for d in devices for _, a, _ in d.ops]
    ends = [b for d in devices for _, _, b in d.ops]
    if not starts:
        return None
    return min(starts), max(ends)
