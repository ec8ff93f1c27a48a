"""Find a cell's knee: the highest offered rate whose completions keep up
with its arrivals, with no backlog growing through the window.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds 8 \\
        --rates 100,200,400,800 [--events out.json]

One process, one set-up (the cell's own), then one window per rate, lowest
first; it stops after the first rate that leaves a backlog of more than a
fifth of its window's requests.  One JSON line per rate: offered and
completed q/s, the backlog at the close, latency percentiles of the
completed requests and the mean batch.  ``--events`` also traces a short
window at the first rate and writes its device events (at most 4000) and
their reduction to a file, the raw material of the trace fixture.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True, help="comma-separated q/s, ascending")
    ap.add_argument("--events", default=None)
    args = ap.parse_args(argv)

    from harness import devtrace
    from harness.cell import NoDevice, _trace_start, check_device, log, prepare
    from harness.spec import load_spec
    from run import use_compile_cache

    spec = load_spec(ROOT)
    cell = spec.cell(args.workload)
    try:
        check_device(cell.chips)
    except NoDevice as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    use_compile_cache(ROOT)
    prep = prepare(cell, args.seed)
    log(f"setup: {time.perf_counter() - T_PROCESS:.3f} s")
    rates = [float(r) for r in args.rates.split(",")]
    for j, rate in enumerate(rates):
        sched = prep.schedule(rate, args.seconds, args.seed, "schedule")
        n0 = prep.meter.n
        served = prep.serve(sched, drain=np.zeros(0, np.int64))
        done = served.done <= sched.seconds
        due = sched.due <= sched.seconds
        lat = (served.done - sched.due)[done] * 1e3
        row = {"rate": rate, "offered": len(sched), "completed_qps": float(done.sum()) / sched.seconds,
               "backlog_at_close": int(due.sum() - done.sum()),
               "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
               "p99_ms": float(np.percentile(lat, 99)) if lat.size else None,
               "mean_batch": float(np.mean([b.rows.size for b in served.batches])),
               "batches": len(served.batches), "compiles": prep.meter.n - n0,
               "post_clause_share": float(served.n_post_clauses[done].sum())
               / max(1, int(served.n_clauses[done].sum()))}
        print(json.dumps(row), flush=True)
        if row["backlog_at_close"] > 0.2 * len(sched):
            break
    if args.events:
        from jax.profiler import TraceAnnotation
        from repro.obs import Tracer

        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        sched = prep.schedule(rates[0], 3.0, args.seed, "prewindow")
        tracer = Tracer()
        prep.eng.set_tracer(tracer)
        _trace_start(log_dir)
        t = time.perf_counter()
        prep.serve(sched, annotate=TraceAnnotation)
        window_s = time.perf_counter() - t
        import jax

        jax.profiler.stop_trace()
        events = devtrace.events_from_xspace(devtrace.find_xspace(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        red = devtrace.reduce_events(events)
        groups = [dict(s.attrs, wall_s=s.wall_s) for s in tracer.spans() if s.name == "group"]
        lines = sorted({(e.plane, e.line) for e in events})
        Path(args.events).parent.mkdir(parents=True, exist_ok=True)
        with open(args.events, "w") as f:
            json.dump({"window_s": window_s, "lines": lines,
                       "reduced": dataclasses.asdict(red), "groups": groups[:200],
                       "events": [dataclasses.asdict(e) for e in events[:4000]]}, f)
        log(f"events: {len(events)} in {len(lines)} lines; busy {red.busy_s:.4f} s "
            f"of {window_s:.4f} s; modules {sorted(red.module_s.items(), key=lambda kv: -kv[1])[:8]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
