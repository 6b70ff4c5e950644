"""Benchmark harness entry point: one benchmark per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig14a --quick
  PYTHONPATH=src python -m benchmarks.run --engine   # substrate bench -> BENCH_engine.json
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

from repro.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None, help="substring filter, e.g. fig12")
    p.add_argument("--quick", action="store_true",
                   help="smaller graphs/budgets (CI mode)")
    p.add_argument("--engine", action="store_true",
                   help="run the old-vs-new substrate benchmark and emit "
                        "BENCH_engine.json (skips the paper figures)")
    p.add_argument("--dynamic", action="store_true",
                   help="run the structural-churn benchmark (patch vs "
                        "recompile, §3.3) and emit BENCH_dynamic.json")
    p.add_argument("--construct", action="store_true",
                   help="run the overlay-construction scale benchmark "
                        "(12k/120k/1M graphs, per-phase breakdown) and emit "
                        "BENCH_construct.json")
    p.add_argument("--sharded", action="store_true",
                   help="run the stacked shard_map vs per-shard host loop "
                        "benchmark at 2/4/8 shards (forces 8 host devices) "
                        "and emit BENCH_sharded.json")
    p.add_argument("--streaming", action="store_true",
                   help="run the streaming-ingest benchmark (legacy vs "
                        "vectorized vs pipelined write path, reads under "
                        "write, per-backend rows) and emit "
                        "BENCH_streaming.json")
    p.add_argument("--alerts", action="store_true",
                   help="run the standing-alert benchmark (push-based "
                        "device predicates vs the poll-everything oracle, "
                        "fired-fraction sweep, detection latency under "
                        "ingest) and emit BENCH_alerts.json")
    p.add_argument("--checkpoint", action="store_true",
                   help="run the durable-session benchmark (cold build vs "
                        "save/restore time-to-first-answer, restore with "
                        "resharding) and emit BENCH_checkpoint.json")
    p.add_argument("--check", action="store_true",
                   help="with --dynamic/--sharded/--streaming/--checkpoint: "
                        "exit nonzero if the measured path regresses below "
                        "its floor")
    args = p.parse_args(argv)
    enable_compile_cache(Path(__file__).resolve().parent.parent / ".jax_cache")

    if args.engine:
        from benchmarks.engine_bench import run_engine_bench
        run_engine_bench(quick=args.quick)
        return
    if args.dynamic:
        from benchmarks.dynamic_bench import run_dynamic_bench
        run_dynamic_bench(quick=args.quick, check=args.check)
        return
    if args.construct:
        from benchmarks.construct_bench import run_construct_bench
        run_construct_bench(quick=args.quick, check=args.check)
        return
    if args.sharded:
        from benchmarks.sharded_bench import run_sharded_bench
        run_sharded_bench(quick=args.quick, check=args.check)
        return
    if args.streaming:
        from benchmarks.streaming_bench import run_streaming_bench
        run_streaming_bench(quick=args.quick, check=args.check)
        return
    if args.alerts:
        from benchmarks.alerts_bench import run_alerts_bench
        run_alerts_bench(quick=args.quick, check=args.check)
        return
    if args.checkpoint:
        from benchmarks.checkpoint_bench import run_checkpoint_bench
        run_checkpoint_bench(quick=args.quick, check=args.check)
        return

    import benchmarks.paper_figures as F

    if args.quick:
        F.GRAPH = dict(n_nodes=4_000, n_edges=24_000)
        F.SMALL = dict(n_nodes=2_000, n_edges=12_000)

    rows = []
    failed = []

    def out(res):
        rows.append(res)
        print(res.row(), flush=True)

    t0 = time.time()
    for fn in F.ALL:
        if args.only and args.only not in fn.__name__:
            continue
        print(f"== {fn.__name__}", flush=True)
        try:
            fn(out)
        except Exception as e:  # keep the harness going; fail at the end
            print(f"{fn.__name__},FAILED,{type(e).__name__}: {e}", flush=True)
            failed.append(fn.__name__)
    print(f"\n{len(rows)} rows in {time.time()-t0:.0f}s")
    if failed:
        sys.exit(f"failed figures: {', '.join(failed)}")


if __name__ == "__main__":
    main()
