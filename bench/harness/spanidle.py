"""Device-idle time under the program's own spans.

An enabled ``repro.obs.Tracer`` opens a ``jax.profiler.TraceAnnotation``
named ``repro:<span>`` for the life of each span, so a profiler trace holds
the program's span tree on the device trace's clock.  This reduction extends
``devtrace.reduce_events`` with those annotations; the numbers that one
gives (busy time, module time, the top device operations) are taken from it
unchanged.

* ``idle_within[name]``: the device-idle time under the union of that
  annotation name's host intervals, averaged over the devices that ran
  anything, as busy time is.  A span nested in another counts toward both.
* ``idle_gaps``: the longest idle gaps between device operations, each named
  by the innermost ``repro:`` or ``bench:`` annotation open at its middle.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Tuple

from . import devtrace
from .devtrace import DEVICE_PLANE, Event, _union

HOST_PREFIXES = (devtrace.HOST_PREFIX, "repro:")

Intervals = List[Tuple[float, float]]


@dataclasses.dataclass
class SpanReduced(devtrace.Reduced):
    idle_within: Dict[str, float]    # annotation name -> device-idle seconds under it


def events_from_xspace(path: str) -> List[Event]:
    """Device events, the benchmark's host annotations and the program's
    span annotations of one trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for e in line.events:
                if dev or e.name.startswith(HOST_PREFIXES):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def busy_intervals(events: Iterable[Event]) -> List[Intervals]:
    """Per device that ran anything, the union of its operation intervals
    (its module intervals where it has no operation line), as
    ``devtrace.reduce_events`` counts busy time."""
    by_plane: Dict[str, Dict[str, List[Event]]] = {}
    for e in events:
        if DEVICE_PLANE.match(e.plane):
            by_plane.setdefault(e.plane, {}).setdefault(e.line, []).append(e)
    out = []
    for lines in by_plane.values():
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        if op_line:
            out.append(_union([(e.start_ns, e.end_ns) for e in op_line]))
    return out


def _overlap_ns(a: Intervals, b: Intervals) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_under(host: Intervals, busy: List[Intervals]) -> float:
    """Seconds of the host intervals ``host`` in which the device ran
    nothing, averaged over the devices in ``busy``."""
    if not busy:
        return 0.0
    u = _union(host)
    length = sum(e - s for s, e in u)
    return sum(length - _overlap_ns(u, b) for b in busy) / len(busy) * 1e-9


def reduce_events(events: Iterable[Event], top: int = 10) -> SpanReduced:
    events = list(events)
    base = devtrace.reduce_events(events, top)
    busy = busy_intervals(events)
    host = sorted((e for e in events if not DEVICE_PLANE.match(e.plane)
                   and e.name.startswith(HOST_PREFIXES)), key=lambda e: e.start_ns)
    by_name: Dict[str, Intervals] = {}
    for e in host:
        by_name.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    idle_within = {n: idle_under(iv, busy) for n, iv in sorted(by_name.items())}

    def innermost(t: float) -> str:
        name = "host:none"
        for e in host:
            if e.start_ns > t:
                break
            if e.end_ns >= t:
                name = e.name
        return name

    gaps = [(iv[j][1], iv[j + 1][0]) for iv in busy for j in range(len(iv) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(innermost(0.5 * (s + e)), (e - s) * 1e-9) for s, e in gaps[:top]]
    return SpanReduced(base.busy_s, base.n_devices, base.module_s, base.device_ops,
                       idle, idle_within)
