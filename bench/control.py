"""The lower-precision control: the reference put in the program's place.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

For each seed it draws the cell's corpus and pool (from the configuration's
data seed), window schedule and check sample through the functions a run of
``bench/run.py`` draws them with, answers every sampled request with the
reference's scan in bfloat16 (no refinement), and scores those answers
against the float32 reference with the run's own comparison, all of them as
exact answers.  One JSON line per seed with the numbers
compared.  The control has to come out not correct: its ``exact_gap`` is
the upper reading from which the configuration's limit is set.  The
benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))


def control_readings(cell, seed: int, seconds: float):
    """(control readings, reference-against-itself readings) of one seed."""
    from harness import reference
    from harness.cell import compare, deployment, draw_window, reference_answers

    dep = deployment(cell)
    sched, sample = draw_window(dep, cell, seed, seconds)
    dep.x = None
    ref = reference_answers(dep, sched, sample)
    ctl_ids, _ = reference.topk(ref.x_dev, ref.masks, ref.pred_of, ref.q, dep.k, low=True)
    every = np.ones(sample.size, bool)
    return compare(ref, ctl_ids, every, every), compare(ref, ref.ids, every, every)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from harness import reference
    from harness.spec import load_spec
    from run import use_compile_cache

    spec = load_spec(ROOT)
    cell = spec.cell(args.workload)
    use_compile_cache(ROOT)
    limits = cell.config["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctl, ref = control_readings(cell, seed, args.seconds)
        checks = reference.checks(ctl, limits)
        print(json.dumps({
            "seed": seed, "control_correct": all(ok for *_, ok in checks),
            "control": {n: v for n, v, _, _ in checks},
            "reference_vs_itself": {"exact_gap": ref.exact_gap, "wrong": ref.wrong},
            "n": ctl.n_exact, "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
