"""From a profiler trace to the few event lists the metrics read.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps, for each TPU device, the events of its module and op lines, and
from the host the benchmark's own spans (``jax.profiler.TraceAnnotation``
names in ``HOST_SPANS``), all on the trace's one clock.  ``Trace`` can be
written to and read from JSON, so a small recorded trace can sit beside
the tests.  The reductions (busy time as a union of intervals, idle gaps
and the host span each falls in) work on those lists alone.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start ns, duration ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"               # one event per program run
OP_LINE = "XLA Ops"                       # one event per operation run
# the jitted chunk step of repro.fleet.jaxexec, as its module is named
CHUNK_PROGRAM = re.compile(r"^jit_chunk\b")
# the spans the benchmark opens around the calls into the program
HOST_SPANS = ("window", "pass", "state-reset", "chunk-step", "poll",
              "bail-replay", "resident-replay", "kernel-interpret")


@dataclass
class Trace:
    # device id -> {"modules": [...], "ops": [...]}
    devices: Dict[int, Dict[str, List[Event]]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def to_json(self) -> str:
        return json.dumps({"devices": {str(k): v for k, v in
                                       self.devices.items()},
                           "host": self.host})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(devices={int(k): {n: [tuple(e) for e in evs]
                                     for n, evs in v.items()}
                            for k, v in d["devices"].items()},
                   host=[tuple(e) for e in d["host"]])

    def window(self) -> Optional[Tuple[float, float]]:
        """The traced window: the benchmark's ``window`` span."""
        spans = [e for e in self.host if e[0] == "window"]
        if not spans:
            return None
        _, start, dur = spans[0]
        return start, start + dur


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    trace = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            trace.devices[int(m.group(1))] = {
                key: ([(_short(e.name), float(e.start_ns),
                        float(e.duration_ns))
                       for e in lines[name].events] if name in lines else [])
                for key, name in (("modules", MODULE_LINE), ("ops", OP_LINE))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                trace.host.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name in HOST_SPANS)
    trace.host.sort(key=lambda e: e[1])
    return trace


def _short(name: str) -> str:
    """An op event is named by its whole HLO instruction; keep the name
    before ``=`` (``%fusion.62``)."""
    return name.split(" = ", 1)[0]


def clip(events: List[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The events' intervals cut to [lo, hi]."""
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one of the events ran."""
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def gaps(events: List[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi]: where none of the events ran."""
    out, t = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(host: List[Event], t: float) -> str:
    """The innermost benchmark span open at time t (the latest to open)."""
    name = "outside any span"
    for n, s, d in host:
        if s > t:
            break
        if n != "window" and s <= t <= s + d:
            name = n
    return name


def device_events(trace: Trace, device: int) -> List[Event]:
    """What ran on a device: its ops, or its modules where no op line was
    recorded."""
    d = trace.devices[device]
    return d["ops"] or d["modules"]


def self_times(events: List[Event]) -> List[Event]:
    """Each event with its own time: its duration less that of the events
    nested in it (a ``while`` op holds the ops of its body)."""
    out: List[list] = []
    open_: List[list] = []
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ and open_[-1][1] + open_[-1][2] <= s:
            open_.pop()
        ev = [name, s, d, d]
        if open_:
            open_[-1][3] -= d
        open_.append(ev)
        out.append(ev)
    return [(name, s, own) for name, s, _, own in out]


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10):
    """The n op names that took most device time of their own in
    [lo, hi], averaged over devices."""
    total: Dict[str, float] = {}
    for dev in trace.devices:
        inside = [e for e in device_events(trace, dev)
                  if lo <= e[1] and e[1] + e[2] <= hi]
        for name, _, own in self_times(inside):
            total[name] = total.get(name, 0.0) + own
    k = max(len(trace.devices), 1)
    return sorted(([name, ns / k / 1e9] for name, ns in total.items()),
                  key=lambda x: -x[1])[:n]


def longest_gaps(trace: Trace, lo: float, hi: float, n: int = 10):
    """The n longest idle gaps over all devices, each named by the host
    span open at its middle."""
    found = []
    for dev in trace.devices:
        for a, b in gaps(device_events(trace, dev), lo, hi):
            found.append((b - a, span_at(trace.host, (a + b) / 2)))
    found.sort(key=lambda x: -x[0])
    return [[name, ns / 1e9] for ns, name in found[:n]]


def chunk_program_ns(trace: Trace, device: int, lo: float, hi: float,
                     runs: int) -> Optional[float]:
    """Device time of the chunk program (``CHUNK_PROGRAM``) on one device
    within [lo, hi], or None unless it ran there exactly ``runs`` times."""
    found = [d for name, s, d in trace.devices[device]["modules"]
             if CHUNK_PROGRAM.match(name) and lo <= s and s + d <= hi]
    if len(found) != runs:
        return None
    return sum(found)
