"""Benchmark harness (redisbench-admin style): small composable modules the
per-topic benches share instead of each growing its own copy.

  run_local   environment fingerprint, phase timers, latency percentiles,
              duration-based sustained loops
  compare     committed-baseline gates (tolerance bands + absolute floors)
              over dotted metric paths — the generalized `--check`
  export      per-PR trajectory export (BENCH_trajectory.jsonl, one line per
              run, keyed by git sha) for cross-PR throughput tracking
  watchdog    wall-clock budget guard
"""
from benchmarks.harness.compare import check_gates, load_baselines
from benchmarks.harness.export import export_trajectory
from benchmarks.harness.run_local import (
    Phases,
    env_fingerprint,
    frontier_summary,
    percentiles,
    sustained,
)
from benchmarks.harness.watchdog import Watchdog

__all__ = [
    "check_gates",
    "load_baselines",
    "export_trajectory",
    "Phases",
    "env_fingerprint",
    "frontier_summary",
    "percentiles",
    "sustained",
    "Watchdog",
]
