"""Reduction of a JAX profiler trace to device busy time, module time and the
idle gaps, with what the host was doing in each.

``events_from_xspace`` flattens the ``.xplane.pb`` the profiler writes into
plain ``Event`` records; everything else works on those records, so a small
recorded fixture checks the reduction without a chip.

* Device planes are ``/device:<ACCEL>:<n>``.  On each, the ``XLA Modules``
  line holds one event per program execution and ``XLA Ops`` one per
  operation.  Busy time is the union of the operation intervals (the module
  intervals where a plane has no operation line), averaged over the
  devices that ran anything.
* Module time sums the ``XLA Modules`` events by program name, the name with
  ``jit_`` and any ``(<id>)`` or ``.<n>`` suffix removed.
* Host annotations (``bench:serve``, ``bench:wait``; the batcher writes them
  with ``jax.profiler.TraceAnnotation``) name the idle gaps.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PREFIX = "bench:"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def module_name(name: str) -> str:
    """``jit_masked_l2_topk(1234)`` -> ``masked_l2_topk``."""
    n = re.sub(r"\(.*\)$", "", name.strip())
    n = re.sub(r"\.\d+$", "", n)
    return n[4:] if n.startswith("jit_") else n


def op_label(name: str, width: int = 96) -> str:
    """An HLO op's text without its layouts, cut to ``width`` characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def find_xspace(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def events_from_xspace(path: str) -> List[Event]:
    """Device events and the benchmark's host annotations of one trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for e in line.events:
                if dev or e.name.startswith(HOST_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(iv):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Reduced:
    busy_s: float                    # mean over devices that ran anything
    n_devices: int
    module_s: Dict[str, float]       # program name -> device seconds (all devices)
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def reduce_events(events: Iterable[Event], top: int = 10) -> Reduced:
    events = list(events)
    by_plane: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for e in events:
        if DEVICE_PLANE.match(e.plane):
            by_plane.setdefault(e.plane, {}).setdefault(e.line, []).append(e)
        elif e.name.startswith(HOST_PREFIX):
            host.append(e)
    busy, module_s, ops = [], {}, {}
    gaps: List[Tuple[float, float]] = []
    for lines in by_plane.values():
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        if not op_line:
            continue
        iv = _union([(e.start_ns, e.end_ns) for e in op_line])
        busy.append(sum(e - s for s, e in iv) * 1e-9)
        gaps += [(iv[j][1], iv[j + 1][0]) for j in range(len(iv) - 1)]
        for e in lines.get("XLA Modules", []):
            m = module_name(e.name)
            module_s[m] = module_s.get(m, 0.0) + e.dur_ns * 1e-9
        for e in lines.get("XLA Ops", []):
            op = op_label(e.name)
            ops[op] = ops.get(op, 0.0) + e.dur_ns * 1e-9
    host.sort(key=lambda e: e.start_ns)

    def doing(t: float) -> str:
        name = "host:none"
        for e in host:
            if e.start_ns > t:
                break
            if e.end_ns >= t:
                name = e.name
        return name

    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [(doing(0.5 * (s + e)), (e - s) * 1e-9) for s, e in gaps[:top]]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(sum(busy) / len(busy) if busy else 0.0, len(busy), module_s,
                   top_ops, idle)
