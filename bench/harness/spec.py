"""Loader of ``BENCHMARK.json`` and of the data files it names.

Each configuration, traffic mix, cell and per-layer metric sits in a file of
its own, found by its name:

* ``configs[].file``                        the configuration (sizes, schema)
* ``<bench>/traffic/<traffic>.json``        the traffic mix's parameters
* ``<bench>/cells/<workload>.json``         the cell's offered rate
* ``<bench>/metrics/<metric>.py``           a metric's reader, end-to-end or per-layer

so a new cell, mix or metric is added by new files and new entries alone.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"device_trace", "host_clock"}


class SpecError(ValueError):
    """BENCHMARK.json or a file it names breaks the benchmark's rules."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: Optional[tuple]       # None: every cell that reports `moves`
    bound: Optional[float] = None    # end-to-end metrics only
    layer: str = ""                  # per-layer metrics only
    moves: str = ""                  # per-layer metrics only


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    rate_qps: float
    end_to_end: List[Metric]
    per_layer: List[Metric]


@dataclasses.dataclass
class Spec:
    root: Path
    bench_dir: Path
    raw: dict
    configs: Dict[str, dict]
    workloads: Dict[str, dict]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise SpecError(f"unknown workload {name!r}; known: {sorted(self.workloads)}")
        w = self.workloads[name]
        traffic = _load_json(self.bench_dir / "traffic" / f"{w['traffic']}.json")
        cell_file = _load_json(self.bench_dir / "cells" / f"{name}.json")
        rate = float(cell_file["rate_qps"])
        if not rate > 0:
            raise SpecError(f"cell {name}: rate_qps must be > 0")
        e2e = [m for m in self.end_to_end if m.workloads is None or name in m.workloads]
        e2e_names = {m.name for m in e2e}
        layer = [m for m in self.per_layer
                 if (name in m.workloads if m.workloads is not None
                     else m.moves in e2e_names)]
        return Cell(name, w["config"], w["traffic"], int(w["chips"]),
                    self.configs[w["config"]], traffic, rate, e2e, layer)

    def reader(self, metric: str) -> Callable:
        """The metric's reader, ``bench/metrics/<metric>.py:read``."""
        path = self.bench_dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise SpecError(f"no reader for metric {metric!r} at {path}")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + re.sub(r"\W", "_", metric), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _name(x, what: str) -> str:
    if not isinstance(x, str) or not NAME_RE.match(x):
        raise SpecError(f"bad {what} name {x!r}")
    return x


def _metric(m: dict, end_to_end: bool) -> Metric:
    name = _name(m.get("name"), "metric")
    if not isinstance(m.get("unit"), str) or not UNIT_RE.match(m["unit"]):
        raise SpecError(f"metric {name}: bad unit {m.get('unit')!r}")
    if m.get("better") not in ("lower", "higher"):
        raise SpecError(f"metric {name}: better must be lower or higher")
    src = m.get("source")
    if src not in (E2E_SOURCES if end_to_end else SOURCES):
        raise SpecError(f"metric {name}: source {src!r} not allowed")
    wl = m.get("workloads")
    if wl is not None:
        wl = tuple(_name(w, "workload") for w in wl)
    if end_to_end:
        b = m.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            raise SpecError(f"metric {name}: bound {b!r} outside [0.01, 0.25]")
        return Metric(name, m["unit"], m["better"], src, wl, bound=float(b))
    return Metric(name, m["unit"], m["better"], src, wl,
                  layer=str(m.get("layer", "")), moves=_name(m.get("moves"), "moves"))


def load_spec(root, bench_dir: Optional[Path] = None) -> Spec:
    """Read and check ``<root>/BENCHMARK.json``; the data files it names are
    read lazily, per cell (``Spec.cell``) and per metric (``Spec.reader``)."""
    root = Path(root)
    raw = _load_json(root / "BENCHMARK.json")
    bench_dir = Path(bench_dir) if bench_dir is not None else root / raw["paths"][0]
    configs = {}
    for c in raw["configs"]:
        configs[_name(c["name"], "config")] = _load_json(root / c["file"])
        for key in c.get("reduced", []):
            _name(key, "reduced key")
    workloads = {}
    for w in raw["workloads"]:
        n = _name(w["name"], "workload")
        if w["config"] not in configs:
            raise SpecError(f"workload {n}: unknown config {w['config']!r}")
        _name(w["traffic"], "traffic")
        if w.get("chips") not in (1, 4):
            raise SpecError(f"workload {n}: chips must be 1 or 4")
        if n in workloads:
            raise SpecError(f"duplicate workload {n}")
        workloads[n] = w
    e2e = [_metric(m, True) for m in raw["end_to_end"]]
    layer = [_metric(m, False) for m in raw["per_layer"]]
    names = [m.name for m in e2e + layer]
    if len(set(names)) != len(names):
        raise SpecError("duplicate metric names")
    e2e_names = {m.name for m in e2e}
    for m in layer:
        if m.moves not in e2e_names:
            raise SpecError(f"metric {m.name}: moves unknown metric {m.moves!r}")
    return Spec(root, bench_dir, raw, configs, workloads, e2e, layer)
