"""Run one cell of the filtered-ANN chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's corpus on the device from the seed, builds and fits
the engine, and warms every shape the cell's traffic uses; then requests
arrive open-loop on the wall clock for ``--seconds`` and are served through
``FilteredANNEngine.batch_query``.  A seeded sample of the window's answers
is compared with the benchmark's own reference.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared and its limit, also the last lines of
standard error).  With ``--trace 0`` the metrics are the cell's end-to-end
ones, with ``--trace 1`` its per-layer ones, read from a profiled window.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))


def use_compile_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program in it, so only a cell's first run compiles."""
    import jax

    path = root / ".jax_cache"
    path.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def finite(x):
    """JSON has no infinity: non-finite floats print as null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.cell import NoDevice, check_device, log, run_cell
    from harness.spec import load_spec

    spec = load_spec(ROOT)
    try:
        check_device(spec.cell(args.workload).chips)
    except NoDevice as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2
    log(f"compile cache: {use_compile_cache(ROOT)}")
    out = run_cell(spec, args.workload, args.seed, args.seconds, bool(args.trace),
                   T_PROCESS)
    log(f"total: {time.perf_counter() - T_PROCESS:.3f} s")
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
