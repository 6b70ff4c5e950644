"""Streaming-ingest benchmark -> BENCH_streaming.json.

Measures sustained steady-state ingest (events/s) on one graph under three
write paths sharing one engine:

  legacy_sync      in-bench replica of the pre-PR-7 path: per-event Python
                   routing (dict lookups + keep-list) and one dense device
                   step per arrival batch (the legacy system predates the
                   frontier index) — the synchronous baseline the ISSUE
                   gates against
  vectorized_sync  ``write_batch`` (one BaseRoutes table lookup per batch),
                   still one device step per arrival batch
  pipeline         :class:`IngestPipeline` — vectorized routing plus ring
                   double-buffering and coalescing of arrival batches into
                   ``device_batch``-sized device steps

plus a ``sparse_vs_dense`` phase (PR 8): median write-step latency under the
frontier-sparse path vs the dense sweep at batch/overlay ratios of 0.01% /
0.1% / 1%, with the per-step frontier-size distribution; p50/p99/p99.9 read
latency sampled *during* the pipelined write load (reads-under-write); and a
per-backend (pallas / xla / xla_unrolled) ingest+read throughput section on
a small graph (ROADMAP carry-over).

Full mode runs the paper-scale 1M-node / 10M-edge power-law graph; quick mode
a 20k/120k R-MAT (CI). ``--check`` gates the pipeline-vs-legacy speedup
(absolute floor 1.5x), sustained pipeline events/s and the p99
read-under-write latency against ``BENCH_baselines.json``.

Run:  PYTHONPATH=src python -m benchmarks.run --streaming [--quick] [--check]
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.harness import (
    Phases,
    Watchdog,
    check_gates,
    env_fingerprint,
    export_trajectory,
    load_baselines,
    percentiles,
    sustained,
)
from repro.core import dataflow as D
from repro.core.aggregates import make_aggregate
from repro.core.bipartite import build_bipartite
from repro.core.engine import EagrEngine, bucket_batch
from repro.core.vnm import construct_vnm
from repro.core.window import WindowSpec
from repro.graphs.generators import powerlaw_graph, rmat_graph
from repro.streams.ingest import IngestPipeline
from repro.streams.traces import zipf_frequencies

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_streaming.json")

QUICK = dict(gen="rmat", n_nodes=20_000, n_edges=120_000,
             arrival=1_024, device_batch=8_192, duration_s=1.5,
             read_every=5, budget_s=900)
FULL = dict(gen="powerlaw", n_nodes=1_000_000, n_edges=10_000_000,
            arrival=2_048, device_batch=16_384, duration_s=10.0,
            read_every=5, budget_s=3_600)

WINDOW = 8
READ_BATCH = 256
N_ARRIVAL_BATCHES = 32


# ------------------------------------------------------------------- fixture
def _build(cfg: dict):
    """Graph -> bipartite -> overlay -> all-push engine (the continuous-query
    configuration: every result always fresh, no mincut at 1M nodes)."""
    if cfg["gen"] == "rmat":
        g = rmat_graph(cfg["n_nodes"], cfg["n_edges"], seed=0)
    else:
        g = powerlaw_graph(cfg["n_nodes"], cfg["n_edges"], sharing=0.5, seed=0)
    bp = build_bipartite(g)
    ov, _ = construct_vnm(bp, variant="vnm_a", max_iterations=3, seed=0)
    dec = np.full(ov.n_nodes, D.PUSH, np.int64)
    eng = EagrEngine(ov, dec, make_aggregate("sum"),
                     WindowSpec("tuple", WINDOW))
    return eng, g, ov


def _arrival_batches(eng: EagrEngine, arrival: int, *, n_batches: int,
                     seed: int = 1) -> list:
    """Pre-generated Zipfian write batches (ids, scalar values) so the timed
    loops replay arrays instead of paying RNG cost per step."""
    writer_bases = np.flatnonzero(eng.plan.routes.writer_row >= 0)
    freqs = zipf_frequencies(len(writer_bases), seed=seed)
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        ids = rng.choice(writer_bases, size=arrival, p=freqs)
        vals = rng.integers(0, 64, arrival).astype(np.float32)
        out.append((ids.astype(np.int64), vals))
    return out


def _read_ids(eng: EagrEngine, *, seed: int = 2) -> np.ndarray:
    readers = np.flatnonzero(eng.plan.routes.reader_node >= 0)
    rng = np.random.default_rng(seed)
    take = min(READ_BATCH, len(readers))
    return rng.choice(readers, size=take, replace=False).astype(np.int64)


def _reset(eng: EagrEngine) -> None:
    """Fresh windows/PAOs/clock between modes, same compiled plan."""
    import jax

    jax.block_until_ready(eng.state.now)
    eng.state = eng.init_state()
    eng._now_host = 0.0
    eng._last_eval_now = 0.0
    eng._expiry = []


# --------------------------------------------------------------- write modes
def _legacy_writer(eng: EagrEngine, arrival: int):
    """The pre-PR-7 write path, reconstructed: keep-list comprehension over
    ``writer_row_of_base`` dict lookups (per-event Python), then one padded
    device step per arrival batch — pinned to the dense sweep
    (``active=None``), because the legacy system it replicates predates the
    frontier index; letting it ride the auto-sparse path would compare the
    pipeline against something that never existed."""
    wrb = dict(eng.plan.writer_row_of_base)

    def step(ids: np.ndarray, vals: np.ndarray) -> int:
        keep = [(wrb[b], v) for b, v in zip(ids.tolist(), vals.tolist())
                if b in wrb]
        n = len(keep)
        rows = np.zeros(arrival, np.int32)
        vmat = np.zeros(arrival, np.float32)
        mask = np.zeros(arrival, bool)
        if n:
            rows[:n] = [r for r, _ in keep]
            vmat[:n] = [v for _, v in keep]
            mask[:n] = True
        eng.write_rows(rows, vmat, mask, n_live=n, active=None)
        return len(ids)

    return step


def _run_mode(name: str, eng, batches, step_fn, *, duration_s: float,
              barrier, warmup: int) -> dict:
    import jax

    for i in range(warmup):  # compile + first dispatches, outside the clock
        ids, vals = batches[i % len(batches)]
        step_fn(ids, vals)
    barrier()
    jax.block_until_ready(eng.state.now)
    _reset(eng)
    res = sustained(
        lambda i: step_fn(*batches[i % len(batches)]),
        duration_s=duration_s, barrier=barrier)
    print(f"streaming/{name}: {res['events_per_s']:,.0f} ev/s "
          f"({res['events']} events, {res['steps']} steps, "
          f"{res['elapsed_s']}s)", flush=True)
    return res


def _reads_under_write(eng, batches, read_ids, *, depth, device_batch,
                       duration_s: float, every: int) -> dict:
    """p50/p99/p99.9 read latency while the pipeline sustains write load —
    the 'read under concurrent write' number the ISSUE asks for. Reads drain
    the partial slot first (session semantics: a read observes every
    submitted event) and block on the device answer."""
    pipe = IngestPipeline([eng], depth=depth, device_batch=device_batch)
    rb = bucket_batch(len(read_ids))
    eng.read_batch(read_ids, batch_size=rb)  # compile outside the clock
    samples: list[float] = []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < duration_s:
        pipe.submit(*batches[i % len(batches)])
        if i % every == 0:
            r0 = time.perf_counter()
            pipe.drain()
            eng.read_batch(read_ids, batch_size=rb)
            samples.append(time.perf_counter() - r0)
        i += 1
    pipe.flush()
    out = percentiles(samples)
    out["every"] = every
    out["read_batch"] = int(len(read_ids))
    out["write_events_per_s"] = round(
        pipe.stats.events_in / (time.perf_counter() - t0), 1)
    return out


# ------------------------------------------------------------ sparse writes
SPARSE_RATIOS = (0.0001, 0.001, 0.01)  # batch size as a fraction of n_nodes


def _sparse_vs_dense(eng: EagrEngine, cfg: dict, *, quick: bool) -> dict:
    """Median write-step latency, dense sweep (EAGR_SPARSE_WRITE=0) vs
    frontier-sparse (=1), at batch sizes that are a fixed fraction of the
    graph — the regime the block-reachability index exists for: the sparser
    the batch relative to the overlay, the larger the win. JSON keys are
    dot-free (``ratio_0_001``) because the gate engine splits paths on '.'"""
    import jax

    from benchmarks.harness import frontier_summary
    from repro.core import frontier as F

    writer_bases = np.flatnonzero(eng.plan.routes.writer_row >= 0)
    rng = np.random.default_rng(9)
    reps = 12 if quick else 8
    out: dict = {}
    if eng.plan.frontier is None:  # charge the one-off index build visibly
        t0 = time.perf_counter()
        eng.plan.frontier = F.FrontierIndex.build(eng.plan)
        out["index_build_s"] = round(time.perf_counter() - t0, 3)
        print(f"streaming/sparse: frontier index built in "
              f"{out['index_build_s']}s", flush=True)
    prev = os.environ.get("EAGR_SPARSE_WRITE")
    try:
        for ratio in SPARSE_RATIOS:
            n = min(max(16, int(ratio * cfg["n_nodes"])), len(writer_bases))
            bs = bucket_batch(n)
            batches = [(rng.choice(writer_bases, size=n).astype(np.int64),
                        rng.integers(0, 64, n).astype(np.float32))
                       for _ in range(min(reps, 8))]
            key = "ratio_" + f"{ratio:g}".replace("0.", "0_")
            row: dict = {"batch": int(n)}
            for mode, label in (("0", "dense"), ("1", "sparse")):
                os.environ["EAGR_SPARSE_WRITE"] = mode
                _reset(eng)
                log0 = len(eng.frontier_log)
                for ids, vals in batches[:2]:  # compile outside the clock
                    eng.write_batch(ids, vals, batch_size=bs)
                jax.block_until_ready(eng.state.now)
                samples = []
                for i in range(reps):
                    ids, vals = batches[i % len(batches)]
                    t0 = time.perf_counter()
                    eng.write_batch(ids, vals, batch_size=bs)
                    jax.block_until_ready(eng.state.now)
                    samples.append(time.perf_counter() - t0)
                row[f"{label}_ms"] = round(
                    sorted(samples)[len(samples) // 2] * 1e3, 3)
                if mode == "1":
                    row["frontier"] = frontier_summary(eng.frontier_log[log0:])
            row["speedup"] = round(row["dense_ms"] / row["sparse_ms"], 2)
            out[key] = row
            print(f"streaming/sparse[{key}]: batch {n} dense "
                  f"{row['dense_ms']}ms sparse {row['sparse_ms']}ms = "
                  f"{row['speedup']}x {row['frontier']}", flush=True)
    finally:
        if prev is None:
            os.environ.pop("EAGR_SPARSE_WRITE", None)
        else:
            os.environ["EAGR_SPARSE_WRITE"] = prev
    return out


# ----------------------------------------------------------------- backends
def _backend_rows(quick: bool) -> dict:
    """Per-backend ingest/read throughput on a small shared fixture (the
    carried ROADMAP item): same overlay, three engine substrates."""
    from benchmarks.common import make_system

    rows: dict[str, dict] = {}
    dur = 0.6 if quick else 1.5
    for backend in ("pallas", "xla", "xla_unrolled"):
        try:
            eng, bp, _, _ = make_system(
                n_nodes=2_000, n_edges=12_000, decisions="all_push",
                backend=backend)
            batches = _arrival_batches(eng, 512, n_batches=8, seed=3)
            pipe = IngestPipeline([eng], depth=2, device_batch=2_048)
            ing = _run_mode(f"backend[{backend}]/ingest", eng, batches,
                            lambda ids, vals: (pipe.submit(ids, vals),
                                               len(ids))[1],
                            duration_s=dur, barrier=pipe.flush, warmup=8)
            read_ids = _read_ids(eng, seed=4)
            rb = bucket_batch(len(read_ids))
            rd = sustained(lambda i: len(
                eng.read_batch(read_ids, batch_size=rb)), duration_s=dur / 2)
            rows[backend] = {
                "ingest_events_per_s": ing["events_per_s"],
                "read_events_per_s": rd["events_per_s"],
            }
        except Exception as e:  # noqa: BLE001 — record, fail at the end
            rows[backend] = {"error": f"{type(e).__name__}: {e}"}
        print(f"streaming/backends[{backend}]: {rows[backend]}", flush=True)
    return rows


# --------------------------------------------------------------------- main
def run_streaming_bench(quick: bool = False, check: bool = False,
                        out_path: str = OUT_PATH) -> dict:
    cfg = QUICK if quick else FULL
    phases = Phases()
    report: dict = {
        "bench": "streaming",
        "quick": quick,
        "fingerprint": env_fingerprint(),
        "graph": {k: cfg[k] for k in ("gen", "n_nodes", "n_edges")},
        "window": WINDOW,
        "arrival_batch": cfg["arrival"],
        "device_batch": cfg["device_batch"],
        "depth": 2,
        "modes": {},
    }
    with Watchdog(cfg["budget_s"], label="streaming_bench"):
        with phases.phase("build"):
            eng, g, ov = _build(cfg)
        report["graph"]["overlay_nodes"] = int(ov.n_nodes)
        report["graph"]["overlay_edges"] = int(ov.n_edges)
        print(f"streaming/build: {cfg['n_nodes']} nodes -> "
              f"{ov.n_nodes} overlay nodes", flush=True)
        batches = _arrival_batches(eng, cfg["arrival"],
                                   n_batches=N_ARRIVAL_BATCHES)

        import jax

        barrier = lambda: jax.block_until_ready(eng.state.now)  # noqa: E731
        with phases.phase("legacy_sync"):
            report["modes"]["legacy_sync"] = _run_mode(
                "legacy_sync", eng, batches, _legacy_writer(eng,
                                                            cfg["arrival"]),
                duration_s=cfg["duration_s"], barrier=barrier, warmup=2)
        with phases.phase("vectorized_sync"):
            report["modes"]["vectorized_sync"] = _run_mode(
                "vectorized_sync", eng, batches,
                lambda ids, vals: (eng.write_batch(
                    ids, vals, batch_size=cfg["arrival"]), len(ids))[1],
                duration_s=cfg["duration_s"], barrier=barrier, warmup=2)
        with phases.phase("pipeline"):
            pipe = IngestPipeline([eng], depth=2,
                                  device_batch=cfg["device_batch"])
            res = _run_mode(
                "pipeline", eng, batches,
                lambda ids, vals: (pipe.submit(ids, vals), len(ids))[1],
                duration_s=cfg["duration_s"], barrier=pipe.flush,
                warmup=2 * cfg["device_batch"] // cfg["arrival"])
            res["ingest_stats"] = pipe.stats.as_dict()
            report["modes"]["pipeline"] = res

        legacy = report["modes"]["legacy_sync"]["events_per_s"]
        vect = report["modes"]["vectorized_sync"]["events_per_s"]
        pl = report["modes"]["pipeline"]["events_per_s"]
        report["speedup_pipeline_vs_legacy"] = round(pl / legacy, 2)
        report["speedup_pipeline_vs_vectorized"] = round(pl / vect, 2)
        print(f"streaming/speedup: pipeline {pl:,.0f} ev/s = "
              f"{report['speedup_pipeline_vs_legacy']}x legacy, "
              f"{report['speedup_pipeline_vs_vectorized']}x vectorized-sync",
              flush=True)

        with phases.phase("sparse_vs_dense"):
            _reset(eng)
            report["sparse_vs_dense"] = _sparse_vs_dense(eng, cfg,
                                                         quick=quick)

        with phases.phase("reads_under_write"):
            _reset(eng)
            report["reads_under_write"] = _reads_under_write(
                eng, batches, _read_ids(eng), depth=2,
                device_batch=cfg["device_batch"],
                duration_s=cfg["duration_s"], every=cfg["read_every"])
        print(f"streaming/reads_under_write: {report['reads_under_write']}",
              flush=True)

        with phases.phase("backends"):
            report["backends"] = _backend_rows(quick)

    report["phase_seconds"] = phases.seconds
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {os.path.abspath(out_path)}", flush=True)

    export_trajectory("streaming", {
        "quick": quick,
        "pipeline_events_per_s": pl,
        "legacy_events_per_s": legacy,
        "speedup_pipeline_vs_legacy":
            report["speedup_pipeline_vs_legacy"],
        "p99_read_under_write_ms":
            report["reads_under_write"].get("p99_ms"),
        "sparse_speedup_ratio_0_0001":
            report["sparse_vs_dense"]["ratio_0_0001"]["speedup"],
        "sparse_speedup_ratio_0_001":
            report["sparse_vs_dense"]["ratio_0_001"]["speedup"],
    })

    if check:
        all_b = load_baselines()
        view = {"tolerance": all_b.get("tolerance", 0.30),
                "streaming": all_b.get("streaming", {}).get(
                    "quick" if quick else "full", {})}
        check_gates(report, [
            {"path": "speedup_pipeline_vs_legacy", "floor": 1.5,
             "baseline": "speedup_pipeline_vs_legacy"},
            {"path": "modes.pipeline.events_per_s",
             "baseline": "pipeline_events_per_s"},
            {"path": "reads_under_write.p99_ms", "direction": "lower",
             "baseline": "p99_read_under_write_ms"},
            # ISSUE PR 8: sparse must beat dense >= 5x at the 0.1% ratio on
            # the full graph; the quick floors are conservative (small
            # graph — the dense sweep is already cheap there). The committed
            # baseline band sits on the sparsest ratio, where the win is
            # biggest and least noisy.
            {"path": "sparse_vs_dense.ratio_0_0001.speedup",
             "floor": 1.3 if quick else 5.0,
             "baseline": "sparse_speedup_ratio_0_0001"},
            {"path": "sparse_vs_dense.ratio_0_001.speedup",
             "floor": 1.3 if quick else 5.0},
        ], baselines=view, section="streaming", label="streaming")
    failed = sorted(b for b, row in report["backends"].items()
                    if "error" in row)
    if failed:
        raise SystemExit(f"streaming: backend rows failed: {failed}")
    return report


if __name__ == "__main__":
    import sys

    run_streaming_bench(quick="--quick" in sys.argv,
                        check="--check" in sys.argv)
