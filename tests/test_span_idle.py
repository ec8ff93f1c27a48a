"""The chip benchmark's reduction of device-idle time under the program's
``repro:`` span annotations (``bench/harness/spanidle.py``).

It must leave ``devtrace``'s own numbers as they are (busy time, module time,
idle share on the recorded fixture), attribute idle time to every open span
name, and name each idle gap by the innermost annotation open in it.
"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.append(str(ROOT / "bench"))

from harness import devtrace, readers, spanidle  # noqa: E402

FIXTURE = ROOT / "bench" / "tests" / "fixtures" / "trace_events.json"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start, end):
    return devtrace.Event(plane, line, name, float(start), float(end - start))


def _synthetic(devices=(DEV,)):
    ev = []
    for p in devices:
        ev += [_ev(p, "XLA Modules", "jit_masked_l2_topk(3)", 0, 100),
               _ev(p, "XLA Ops", "fusion.1", 0, 100),
               _ev(p, "XLA Modules", "jit__take_rows", 300, 400),
               _ev(p, "XLA Ops", "gather", 300, 400),
               _ev(p, "XLA Ops", "custom-call", 1000, 1100)]
    ev += [_ev(HOST, "python3", "bench:serve", 0, 1200),
           _ev(HOST, "python3", "repro:batch_query", 50, 1150),
           _ev(HOST, "python3", "repro:group", 100, 300),
           _ev(HOST, "python3", "repro:mask", 120, 250),
           _ev(HOST, "python3", "repro:ivf_round", 400, 1000),
           _ev(HOST, "python3", "unrelated", 0, 5000)]
    return ev


@pytest.mark.parametrize("devices", [(DEV,), (DEV, "/device:TPU:1")])
def test_idle_within_counts_every_open_span(devices):
    red = spanidle.reduce_events(_synthetic(devices))
    assert red.n_devices == len(devices)
    assert red.idle_within == {
        "bench:serve": pytest.approx(900e-9),        # 1200 less 300 busy
        "repro:batch_query": pytest.approx(850e-9),  # 1100 less 250 busy
        "repro:group": pytest.approx(200e-9),        # the whole gap 100..300
        "repro:mask": pytest.approx(130e-9),         # nested: counts for group too
        "repro:ivf_round": pytest.approx(600e-9),
    }
    # the longest gap first, each device's own, each named by the innermost
    # open annotation
    n = len(devices)
    assert red.idle_gaps == [("repro:ivf_round", pytest.approx(600e-9))] * n \
        + [("repro:mask", pytest.approx(200e-9))] * n
    base = devtrace.reduce_events(_synthetic(devices))
    assert (red.busy_s, red.module_s, red.device_ops) \
        == (base.busy_s, base.module_s, base.device_ops)
    assert red.idle_within["repro:ivf_round"] <= 1200e-9 - red.busy_s


def test_idle_within_a_span_the_device_fills_is_zero():
    ev = [_ev(DEV, "XLA Ops", "fusion.1", 0, 500),
          _ev(DEV, "XLA Ops", "fusion.2", 400, 900),
          _ev(HOST, "python3", "repro:scan_wait", 100, 800)]
    red = spanidle.reduce_events(ev)
    assert red.idle_within == {"repro:scan_wait": 0.0}
    assert red.idle_gaps == []


class _View:
    def __init__(self, reduced, window_s):
        self.reduced, self.window_s = reduced, window_s


def test_recorded_fixture_reads_as_before():
    with open(FIXTURE) as f:
        raw = json.load(f)
    events = [devtrace.Event(**e) for e in raw["events"]]
    base = devtrace.reduce_events(events)
    red = spanidle.reduce_events(events)
    expect = raw["expect"]
    assert red.busy_s == base.busy_s == pytest.approx(expect["busy_s"], rel=1e-9)
    assert red.module_s == base.module_s
    for name, s in expect["module_s"].items():
        assert red.module_s[name] == pytest.approx(s, rel=1e-9)
    assert red.device_ops == base.device_ops
    window_s = 3.0
    assert readers.idle_share(_View(red, window_s)) == readers.idle_share(_View(base, window_s))
    # only `bench:` annotations were recorded: the gaps keep their names
    assert red.idle_gaps == base.idle_gaps
    assert set(red.idle_within) <= {"bench:serve", "bench:wait"}


def test_events_from_xspace_keeps_program_and_benchmark_annotations(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench:serve"), TraceAnnotation("repro:batch_query"):
            with TraceAnnotation("repro:mask"), TraceAnnotation("other"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = spanidle.events_from_xspace(devtrace.find_xspace(str(tmp_path)))
    names = sorted(e.name for e in events if not devtrace.DEVICE_PLANE.match(e.plane))
    assert names == ["bench:serve", "repro:batch_query", "repro:mask"]
