"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = mean end-to-end
query latency where applicable; derived = the headline derived metric).

    PYTHONPATH=src python -m benchmarks.run              # full suite
    REPRO_BENCH_SCALE=small python -m benchmarks.run     # (default)
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    t_start = time.time()
    print("name,us_per_call,derived")

    from . import table1_datasets
    for r in table1_datasets.run():
        print(f"table1_{r['dataset']},0,dim={r['dim']};n={r['bench_size']}")

    from . import table2_construction
    for r in table2_construction.run():
        print(
            f"table2_{r['dataset']},{int(r['learned_planner_s']*1e6)},"
            f"speedup_vs_acorn={r['speedup']}x"
        )

    from . import fig2_latency_recall
    for r in fig2_latency_recall.run():
        print(
            f"fig2_{r['dataset']}_sel{r['avg_selectivity']},"
            f"{int(r['planner_s']*1e6)},"
            f"planner_recall={r['planner_recall']};post_recall={r['post_recall']};"
            f"acorn_recall={r['acorn_recall']};acorn_us={int(r['acorn_s']*1e6)}"
        )

    from . import selectivity_accuracy
    for r in selectivity_accuracy.run():
        print(f"selectivity_{r['dataset']}_{r['kind']},0,mae={r['mae']}")

    from . import planner_accuracy
    for r in planner_accuracy.run():
        print(
            f"planner_{r['dataset']},0,auc={r['auc']};acc={r['accuracy']};"
            f"util_vs_oracle={r['utility_vs_oracle']}"
        )

    from . import ablation_gbm
    for r in ablation_gbm.run():
        print(
            f"ablation_gbm_{r['dataset']},0,"
            f"mae_gbm={r['mae_with_gbm']};mae_indep={r['mae_independence']}"
        )

    from . import kernel_bench
    for r in kernel_bench.run():
        print(f"kernel_{r['kernel']},{r['vmem_bytes']},")

    from . import filter_bench
    for r in filter_bench.run():
        print(
            f"filter_{r['tier']},{r['cached_us']},"
            f"speedup_cached={r['speedup_cached']}x;speedup_cold={r['speedup_cold']}x"
        )

    from . import backend_bench
    for r in backend_bench.run():
        print(f"backend_{r['config']},{r['mean_us']},recall={r['recall']}")

    from . import runtime_bench
    for r in runtime_bench.run():
        print(
            f"runtime_{r['name']},{r['p99_us']},"
            f"speedup={r['speedup']};deadline_hit={r['deadline_hit_rate']}"
        )

    from . import mutation_bench
    for r in mutation_bench.run():
        print(
            f"mutation_{r['name']},{r['mean_us']},"
            f"latency_ratio={r['ratio']};recall={r['recall']}"
        )

    from . import fleet_bench
    for r in fleet_bench.run():
        print(
            f"fleet_{r['name']},{r['wall_qps']},"
            f"quiet_slo={r['quiet_slo']};rejected={r['rejected']}"
        )

    print(f"# total bench wall time {time.time()-t_start:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
