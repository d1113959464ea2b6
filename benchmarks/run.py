"""One function per paper table. Prints ``name,us_per_call,derived`` CSV.

``--smoke`` shrinks every benchmark's problem size so the full sweep
finishes quickly (CI smoke: ``make bench-smoke``).
``--only substr`` runs just the benchmarks whose name contains substr.
``--json PATH`` additionally writes the rows as JSON — ``make ci`` uses
this to record the per-PR perf trajectory (BENCH_<n>.json).
"""
import argparse
import json
import os
import sys

# allow `python benchmarks/run.py` from the repo root (or anywhere):
# the repo root for the `benchmarks` package, `src` for `repro` itself
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true", help="small sizes, fast sweep")
    parser.add_argument("--only", default="", help="run only benchmarks whose name contains this")
    parser.add_argument("--json", default="", help="also write rows as JSON to this path")
    args = parser.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks.bench_merge import (
        bench_batched_merge,
        bench_load_balance,
        bench_merge_throughput,
        bench_moe_dispatch,
        bench_partition_cost,
        bench_ragged_merge,
        bench_segmented_vs_regular,
        bench_sort,
    )
    from benchmarks.bench_distributed import bench_distributed
    from benchmarks.bench_serving import bench_serving
    from benchmarks.bench_tile_engine import bench_tile_engine
    from benchmarks._timing import stopwatch

    rows = []
    with stopwatch() as sw:
        for bench in (
            bench_merge_throughput,
            bench_tile_engine,
            bench_distributed,
            bench_batched_merge,
            bench_ragged_merge,
            bench_partition_cost,
            bench_load_balance,
            bench_segmented_vs_regular,
            bench_sort,
            bench_moe_dispatch,
            bench_serving,
        ):
            if args.only and args.only not in bench.__name__:
                continue
            print(f"# running {bench.__name__} ...", file=sys.stderr, flush=True)
            bench(rows, smoke=args.smoke)
    total_s = sw.seconds
    print(f"# total {total_s:.1f}s", file=sys.stderr)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},\"{r['derived']}\"")

    # Guarded-dispatch health: a benchmark run that silently degraded
    # (e.g. every pallas launch fell back to core) would report numbers
    # for the wrong code path — surface the counters and fail loudly.
    from repro.runtime import faults as _faults
    from repro.runtime import resilience as _res

    health = _res.health_summary()
    totals = health["totals"]
    print(
        f"# health: calls={totals['calls']} fallbacks={totals['fallbacks']} "
        f"preflight_rejects={totals['precondition_rejects']} "
        f"launch_failures={totals['launch_failures']} "
        f"verify_failures={totals['verify_failures']} "
        f"exhausted={totals['exhausted']}",
        file=sys.stderr,
    )
    for op, rec in sorted(health.items()):
        if op != "totals" and rec["fallbacks"]:
            print(f"# health[{op}]: fallback_edges={rec['fallback_edges']}", file=sys.stderr)
    if totals["fallbacks"] and not _faults.active():
        print(
            f"# health: FAIL — {totals['fallbacks']} fallback(s) taken with no "
            f"fault plan active; benchmark numbers describe degraded paths",
            file=sys.stderr,
        )
        sys.exit(1)

    if args.json:
        from repro.telemetry import get_telemetry, summary as telemetry_summary

        payload = {
            "smoke": bool(args.smoke),
            "only": args.only,
            "total_seconds": round(total_s, 1),
            "health": health,
            "telemetry": telemetry_summary(get_telemetry()),
            "rows": rows,
        }
        # record the perf-gate anchor rows explicitly so a snapshot is
        # self-describing (tools/bench_diff.py diffs these across PRs)
        from tools.bench_diff import anchor_values

        payload["anchors"] = {
            name: {"metric": metric, "value": value}
            for name, (metric, value) in sorted(anchor_values(payload).items())
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
