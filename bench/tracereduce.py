"""Reduce a profiler trace to device busy time, op totals, collective
exposure and idle gaps named by what the host was doing.

Input is a ``.xplane.pb`` as ``jax.profiler.trace`` writes it, read with
``jax.profiler.ProfileData``. Device planes are ``/device:<KIND>:<i>``;
their ops are the events of the ``XLA Ops`` line, each named by its HLO
instruction (``%fusion.73 = u8[4104,1024]{...} fusion(...), kind=kCustom``),
which is cut here to ``fusion.73 u8[4104,1024] fusion kCustom``. Host planes
(``/host:...``) carry the benchmark's ``TraceAnnotation`` spans and the
runtime's own host events on the same clock.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|collective-permute|"
                        r"reduce-scatter|all-to-all|collective-broadcast|"
                        r"send|recv)\b")
_KIND = re.compile(r"kind=(\w+)")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float   # ns
    end: float     # ns


@dataclasses.dataclass
class TraceData:
    devices: Dict[str, List[Event]]   # plane name -> op events
    host: List[Event]                 # every host event, all threads

    def annotation(self, name: str) -> Optional[Interval]:
        """The first host event called ``name``, as (start, end) ns."""
        for ev in self.host:
            if ev.name == name:
                return ev.start, ev.end
        return None


def op_label(hlo: str) -> str:
    """``name type opcode [kind]`` of an HLO instruction's text (a tuple
    type reads ``tuple``); other names pass unchanged."""
    if " = " not in hlo:
        return hlo
    name, rhs = hlo.split(" = ", 1)
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        typ, rest = "tuple", rhs[i + 1:].lstrip()
    else:
        typ, _, rest = rhs.partition(" ")
        typ = typ.split("{")[0]
    kind = _KIND.search(rest)
    return " ".join([name.lstrip("%"), typ, rest.split("(")[0]]
                    + ([kind[1]] if kind else []))


def _events(line, labels: Optional[Dict[str, str]] = None) -> List[Event]:
    out = []
    for e in line.events:
        name = e.name
        if labels is not None:
            label = labels.get(name)
            if label is None:
                label = labels[name] = op_label(name)
            name = label
        out.append(Event(name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns)))
    return out


def from_profile(pd) -> TraceData:
    """Split a ``jax.profiler.ProfileData`` into device ops and host events."""
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    labels: Dict[str, str] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [ev for line in plane.lines if line.name == OP_LINE
                   for ev in _events(line, labels)]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return TraceData(devices=devices, host=host)


def load(path: str) -> TraceData:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def is_collective(label: str) -> bool:
    """An op that moves data between chips: by its opcode where the label
    has one, else by its name."""
    parts = label.split(" ")
    return bool(COLLECTIVE.match(parts[2] if len(parts) >= 3 else parts[0]))


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """ns per op name, each event's duration less that of the events nested
    in it on the same line (a loop op does not count its body twice)."""
    totals: Dict[str, float] = defaultdict(float)
    stack: List[List] = []   # [event, child time]
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end < ev.end:
            done, child = stack.pop()
            totals[done.name] += (done.end - done.start) - child
        if stack:
            stack[-1][1] += ev.end - ev.start
        stack.append([ev, 0.0])
    for done, child in stack:
        totals[done.name] += (done.end - done.start) - child
    return dict(totals)


class Reduction:
    """Metrics of one traced window ``[t0, t1]`` (ns, trace clock); by
    default the span from the first device op to the last."""

    def __init__(self, data: TraceData, window: Optional[Interval] = None):
        self.data = data
        if window is None:
            ends = [(e.start, e.end) for ops in data.devices.values()
                    for e in ops]
            window = (min(s for s, _ in ends), max(e for _, e in ends))
        self.t0, self.t1 = window
        self._busy = {name: merge(clip([(e.start, e.end) for e in ops],
                                       self.t0, self.t1))
                      for name, ops in data.devices.items()}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_s(self, t0: Optional[float] = None,
               t1: Optional[float] = None) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self._busy:
            return 0.0
        t0 = self.t0 if t0 is None else t0
        t1 = self.t1 if t1 is None else t1
        total = sum(length(clip(b, t0, t1)) for b in self._busy.values())
        return total / len(self._busy) * 1e-9

    def idle_share(self) -> Optional[float]:
        if not self._busy or self.t1 <= self.t0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def collective_exposed_share(self) -> Optional[float]:
        """Share of the window in which a collective runs on a device and no
        other op does, averaged over the devices; None without collectives."""
        shares, seen = [], False
        for name, ops in self.data.devices.items():
            coll, comp = [], []
            for e in ops:
                (coll if is_collective(e.name) else comp).append(
                    (e.start, e.end))
            seen = seen or bool(coll)
            exposed = subtract(merge(clip(coll, self.t0, self.t1)),
                               merge(clip(comp, self.t0, self.t1)))
            shares.append(length(exposed) / (self.t1 - self.t0))
        if not seen:
            return None
        return sum(shares) / len(shares)

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """The ops that took most device time (self seconds per device)."""
        totals: Dict[str, float] = defaultdict(float)
        for ops in self.data.devices.values():
            inside = [e for e in ops if e.end > self.t0 and e.start < self.t1]
            for name, ns in self_times(inside).items():
                totals[name] += ns
        n = max(len(self.data.devices), 1)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        return [(name, ns / n * 1e-9) for name, ns in ranked]

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest stretches in which no device ran an op, each named by
        the innermost host event under its midpoint (with the outermost
        benchmark annotation around it)."""
        busy = merge([iv for b in self._busy.values() for iv in b])
        gaps = subtract([(self.t0, self.t1)], busy)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return [(self._host_name((s + e) / 2), (e - s) * 1e-9)
                for s, e in gaps]

    def _host_name(self, t: float) -> str:
        under = [ev for ev in self.data.host if ev.start <= t < ev.end]
        if not under:
            return "no host event"
        inner = min(under, key=lambda ev: ev.end - ev.start)
        outer = [ev for ev in under if ev.name.startswith("bench.")]
        if not outer:
            return inner.name
        top = max(outer, key=lambda ev: ev.end - ev.start)
        if top is inner:
            return f"{top.name} > host code outside runtime calls"
        return f"{top.name} > {inner.name}"
