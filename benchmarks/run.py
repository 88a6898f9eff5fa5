"""Benchmark orchestrator — one benchmark per paper table + the tiering
study. Prints paper-style tables and a ``name,us_per_call,derived`` CSV
summary; JSON artifacts land in results/.

  PYTHONPATH=src python -m benchmarks.run           # full paper suite
  PYTHONPATH=src python -m benchmarks.run --fast    # CI-sized corpora
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="small corpora for CI")
    ap.add_argument("--engine", default="ref", choices=["ref", "pallas"],
                    help="unified-query engine for the latency table")
    args = ap.parse_args()

    from repro.runtime import configure_compile_cache
    configure_compile_cache()
    from benchmarks import (bench_complexity, bench_freshness, bench_isolation,
                            bench_latency, bench_tiering)

    iters = 50 if args.fast else 200
    n_docs = 10_000 if args.fast else 50_000
    n_queries = 200 if args.fast else 1000

    print("=" * 72)
    print("Table 1 — query latency (4 complexity levels x Stack A/B)")
    print("=" * 72)
    lat = bench_latency.run(iters=iters, engine=args.engine, n_docs=n_docs)

    print()
    print("=" * 72)
    print("Table 2 — freshness / inconsistency window")
    print("=" * 72)
    fresh = bench_freshness.run(n_writes=iters)

    print()
    print("=" * 72)
    print("Table 3 — tenant isolation (leakage simulation)")
    print("=" * 72)
    iso = bench_isolation.run(n_queries=n_queries)

    print()
    print("=" * 72)
    print("Table 4 — engineering complexity (sync LOC, this repo)")
    print("=" * 72)
    cx = bench_complexity.run()

    print()
    print("=" * 72)
    print("Section 7.3 — three-tier hybrid routing")
    print("=" * 72)
    tier = bench_tiering.run(n_docs=min(n_docs, 20_000), iters=max(iters // 2, 20))

    # CSV summary: name,us_per_call,derived
    print()
    print("name,us_per_call,derived")
    for qt, row in lat["table"].items():
        print(f"latency.{qt}.stack_a,{row['stack_a']['p50']*1e3:.1f},p50")
        print(f"latency.{qt}.stack_b,{row['stack_b']['p50']*1e3:.1f},p50")
    print(f"freshness.window.stack_a,"
          f"{fresh['stack_a']['inconsistency_window']['mean']*1e3:.1f},mean")
    print("freshness.window.stack_b,0.0,by-construction")
    print(f"isolation.leak_rate.stack_a,{iso['stack_a']['leak_rate']*1e6:.1f},ppm")
    print(f"isolation.leak_rate.stack_b,{iso['stack_b']['leak_rate']*1e6:.1f},ppm")
    print(f"complexity.sync_loc.stack_a,{cx['stack_a']['sync_loc']},loc")
    print(f"complexity.sync_loc.stack_b,{cx['stack_b']['sync_loc']},loc")
    print(f"tiering.hot_p50,{tier['hot_query_ms']['p50']*1e3:.1f},us")


if __name__ == "__main__":
    main()
