# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated bench names (default: all)")
    ap.add_argument("--sf", type=float, default=None,
                    help="override every suite's TPC-H scale factor "
                         "(CI bench-smoke runs --sf 0.005)")
    ap.add_argument("--seed", type=int, default=None,
                    help="dbgen seed (default 1); threaded through so "
                         "emitted numbers are reproducible run-to-run")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache

    from . import common
    from .common import emit

    setup_compile_cache()

    if args.sf is not None:
        common.set_scale(args.sf)
    if args.seed is not None:
        common.set_seed(args.seed)

    from .explain_bench import bench_explain
    from .incremental_bench import bench_incremental
    from .kernels_bench import bench_kernels
    from .oocore_bench import bench_oocore
    from .paper_tables import (
        bench_coverage, bench_fpr, bench_inter_opt, bench_no_inter,
        bench_overhead, bench_query_scaling, bench_query_time,
    )
    from .partition_bench import bench_partition
    from .pipelines import bench_pipelines
    from .roofline_bench import bench_roofline
    from .scan_bench import bench_scan_engine
    from .serve_bench import bench_serve
    from .store_bench import bench_store
    from .udf_bench import bench_udf

    benches = {
        "coverage": bench_coverage,       # paper Table 4
        "overhead": bench_overhead,       # paper Figures 5-8
        "query_time": bench_query_time,   # paper Figures 9-10
        "query_scaling": bench_query_scaling,  # 98x-claim scaling evidence
        "inter_opt": bench_inter_opt,     # paper Table 5
        "fpr": bench_fpr,                 # paper Table 6
        "no_inter": bench_no_inter,       # paper Figure 11
        "pipelines": bench_pipelines,     # paper Figure 12 / Table 7
        "kernels": bench_kernels,         # kernel-path scans
        "scan_engine": bench_scan_engine, # batched vs single-row query latency
        "store": bench_store,             # compressed store + budget planner
        "oocore": bench_oocore,           # out-of-core disk tier
        "partition": bench_partition,     # zone-map pruning + parallel scans
        "serve": bench_serve,             # concurrent service vs serial query()
        "udf": bench_udf,                 # annotation-driven UDF pushdown
        "explain": bench_explain,         # cost-model estimate accuracy
        "incremental": bench_incremental, # delta-append vs cold full re-run
        "roofline": bench_roofline,       # §Roofline (reads dry-run artifacts)
    }
    selected = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        t0 = time.time()
        try:
            rows = benches[name]()
            emit(rows)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name}.FAILED,0,exception")
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
