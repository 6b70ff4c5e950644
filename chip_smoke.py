#!/usr/bin/env python3
"""Bring-up smoke: the EAGr session end to end on one TPU chip.

Drives the main path once through the public entry points — ``EagrSession``
-> ``EagrEngine`` -> the Pallas ``segment_agg`` kernel — on power-law graphs
generated from ``--seed``, and checks every answer against host references:

  * a continuous ``count`` query over a 32-write tuple window carrying a
    standing ``above`` alert (the anomaly-detection deployment: all push, no
    min-cut), on 1M nodes / 10M edges;
  * a windowed ``sum`` query under the default min-cut push/pull decisions,
    in a second session on the 120k-node / 720k-edge graph (the min-cut host
    build at 1M would crowd the smoke's time limit);
  * per session: pipelined ingest (``ingest_depth=2``) that runs both the
    dense and the frontier-sparse write step, sampled reads, a churn burst
    of ``add_edge``/``delete_edge`` + ``flush``, writes and reads again, and
    the drained fired set.

Every sampled read must equal ``EagrEngine.oracle_read`` over the reader's
current inputs (count reads must also equal a host replica of the window
counts), and the fired set must equal a host replay of the alert state
machine over those counts. Phase wall times are bring-up diagnostics, not
speed claims.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # shards=4 vs shards=None, 4 chips

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_NODES, N_EDGES = 1_000_000, 10_000_000  # BENCH_construct/streaming scale
MINCUT_NODES, MINCUT_EDGES = 120_000, 720_000  # BENCH_construct "120k" graph
COUNT_WINDOW = 32      # README "Standing alerts" deployment
SUM_WINDOW = 8
ALERT_ABOVE, ALERT_HYSTERESIS, ALERT_DEBOUNCE = 32.0, 2.0, 8.0
# Full ring slots take the dense step (more live writers than the 5%
# EAGR_SPARSE_ROWFRAC at 1M writers); the small batch drained by the next
# read takes the frontier-sparse step.
INGEST_BATCH = 65_536
DENSE_STEPS = 3
N_SAMPLE = 128         # sampled readers per group per read phase
N_CHURN = 32           # edges added and edges deleted in the churn burst
VALUE_RANGE = 8        # integer write values: every sum below 2**24 is exact
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """Counts XLA backend compiles (one per jitted program built or loaded
    from the persistent cache) through ``jax.monitoring``."""

    def __init__(self):
        self.events: list[tuple[str, float]] = []
        self.total = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.events.append((str(kw.get("fun_name", "?")), duration))
            self.total += 1

    def take(self) -> list:
        out, self.events = self.events, []
        return out

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class Phase:
    """``with Phase("name", clog) as ph:`` prints the phase's wall seconds,
    its compiles and whatever ``ph.info`` collected."""

    def __init__(self, name: str, clog: CompileLog):
        self.name, self.clog, self.info = name, clog, {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            log(phase=self.name, seconds=time.perf_counter() - self.t0,
                compiles=self.clog.take(), **self.info)
        return False


class CountReplica:
    """Host replica of the count query: per-writer write counts capped at
    the tuple window, summed over each reader's current in-neighbors, and
    the alert state machine of ``streams/alerts.py`` replayed on top."""

    def __init__(self, graph):
        rev = graph.reverse()
        deg = np.diff(rev.indptr)
        self.n = graph.n_nodes
        self.edge_reader = np.repeat(np.arange(self.n), deg)
        self.edge_writer = np.asarray(rev.indices, np.int64)
        self.alerted = deg > 0  # every reader; churn adds none
        self.writes = np.zeros(self.n, np.int64)
        self.override: dict[int, set[int]] = {}  # churned neighborhoods
        self.armed = np.ones(self.n, bool)
        self.last_fire = np.full(self.n, -np.inf)
        self.last_m = np.zeros(self.n)
        self.fired: set[tuple[int, int, float]] = set()
        self.now = 0

    def measure(self) -> np.ndarray:
        capped = np.minimum(self.writes, COUNT_WINDOW).astype(np.float64)
        m = np.bincount(self.edge_reader, weights=capped[self.edge_writer],
                        minlength=self.n)
        for r, ins in self.override.items():
            m[r] = float(sum(min(self.writes[w], COUNT_WINDOW) for w in ins))
        return m

    def step(self, ids: np.ndarray) -> None:
        """One device batch: apply its writes, then evaluate the alert."""
        np.add.at(self.writes, ids, 1)
        m = self.measure()
        changed = self.alerted & (m != self.last_m)
        fired = (changed & self.armed & (m > ALERT_ABOVE)
                 & (self.now - self.last_fire >= ALERT_DEBOUNCE))
        inside = m <= ALERT_ABOVE - ALERT_HYSTERESIS
        self.armed = np.where(fired, False, self.armed | (changed & inside))
        self.last_fire = np.where(fired, self.now, self.last_fire)
        self.last_m = np.where(changed, m, self.last_m)
        self.fired |= {(self.now, int(r), float(m[r]))
                       for r in np.flatnonzero(fired)}
        self.now += 1


def register_count(session):
    """The continuous count query and its standing alert."""
    from repro import Query, WindowSpec

    handle = session.register(Query(
        agg="count", window=WindowSpec("tuple", COUNT_WINDOW),
        continuous=True))
    alert = handle.on_threshold(above=ALERT_ABOVE, hysteresis=ALERT_HYSTERESIS,
                                debounce=ALERT_DEBOUNCE)
    return handle, alert


def register_sum(session):
    """The windowed sum query under min-cut push/pull decisions."""
    from repro import Query, WindowSpec

    return session.register(Query(agg="sum",
                                  window=WindowSpec("tuple", SUM_WINDOW)))


def plan_backend(engine) -> tuple[str, bool]:
    """``(backend, interpret)`` of a single or stacked engine's plan."""
    meta = engine.meta if hasattr(engine, "meta") else engine.plan.meta
    return meta.backend, meta.interpret


def check_reads(session, handle, ids, replica=None) -> None:
    """Session reads against the engine's host oracle (and the count
    replica); integer-valued data makes equality exact."""
    got = np.asarray(session.read(handle, ids), np.float64).reshape(len(ids))
    eng = handle.group.engine
    inputs = {int(r): session.neighborhood(int(r)) for r in ids}
    want = np.array([np.asarray(eng.oracle_read(int(r), inputs)).item()
                     for r in ids])
    bad = np.flatnonzero(got != want)
    check(len(bad) == 0, f"{handle.agg.name} reads differ from oracle_read "
          f"at readers {ids[bad[:8]].tolist()}: got {got[bad[:8]].tolist()} "
          f"want {want[bad[:8]].tolist()}")
    if replica is not None:
        rep = replica.measure()[ids]
        bad = np.flatnonzero(got != rep)
        check(len(bad) == 0, f"count reads differ from the host replica at "
              f"readers {ids[bad[:8]].tolist()}")


class Deployment:
    """One session over its own generated graph, driven phase by phase."""

    def __init__(self, name: str, n_nodes: int, n_edges: int, *, seed: int,
                 ingest_batch: int, clog: CompileLog, on_chip: bool,
                 shards=None):
        from repro import EagrSession
        from repro.graphs.generators import powerlaw_graph

        self.name, self.clog, self.ingest_batch = name, clog, ingest_batch
        self.on_chip = on_chip
        self.rng = np.random.default_rng(seed + 1)
        with Phase(f"{name}/graph", clog) as ph:
            self.graph = powerlaw_graph(n_nodes, n_edges, sharing=0.5,
                                        seed=seed)
            ph.info.update(nodes=self.graph.n_nodes,
                           edges=self.graph.n_edges)
        with Phase(f"{name}/construction", clog) as ph:
            self.session = EagrSession(self.graph, shards=shards,
                                       ingest_depth=2,
                                       ingest_batch=ingest_batch, seed=seed)
            ph.info.update(
                phase_seconds=self.session.overlay_stats.phase_seconds)
        self.writers = np.asarray(self.session.writers, np.int64)
        self.readers = np.asarray(self.session.readers, np.int64)
        self.in_degree = np.diff(self.graph.reverse().indptr)
        self.handles: dict = {}
        self.replica = None

    def register(self, count: bool, total: bool) -> None:
        with Phase(f"{self.name}/register", self.clog) as ph:
            if count:
                self.handles["count"], self.alert = register_count(
                    self.session)
                self.replica = CountReplica(self.graph)
            if total:
                self.handles["sum"] = register_sum(self.session)
            for name, h in self.handles.items():
                eng = h.group.engine
                backend, interpret = plan_backend(eng)
                # no fallback may hide the device on the chip
                check(not self.on_chip or (backend == "pallas"
                                           and not interpret),
                      f"{self.name}/{name} runs backend={backend} "
                      f"interpret={interpret}, not the Pallas kernel")
                if hasattr(eng, "plan"):
                    meta = eng.plan.meta
                    ph.info[name] = dict(
                        build_seconds=h.group.build_seconds,
                        overlay_nodes=eng.overlay.n_nodes,
                        overlay_edges=int(sum(len(e) for e in
                                              eng.overlay.in_edges)),
                        backend=meta.backend, interpret=meta.interpret,
                        n_nodes=meta.n_nodes, n_writers=meta.n_writers,
                        n_levels=meta.n_levels,
                        push_blocks=int(eng.plan.arrays.push
                                        .tile_of_block.shape[1]),
                        pull_blocks=int(eng.plan.arrays.pull
                                        .tile_of_block.shape[1]))

    def ingest(self, phase: str) -> None:
        """Full ring slots (dense steps), then a small batch left pending
        for the next read to drain as one frontier-sparse step."""
        with Phase(f"{self.name}/{phase}", self.clog) as ph:
            sizes = [self.ingest_batch] * DENSE_STEPS \
                + [max(4, len(self.writers) // 1000)]
            for n in sizes:
                ids = self.rng.choice(self.writers, n)
                self.session.update(ids, self.rng.integers(
                    0, VALUE_RANGE, n).astype(np.float32))
                if self.replica is not None:
                    self.replica.step(ids)  # one device step per batch
            for h in self.handles.values():
                h.group.engine.state.pao.block_until_ready()
            ph.info.update(events=sum(sizes))

    def read(self, phase: str, extra=()) -> None:
        """Sampled readers, the eight highest in-degree hubs and ``extra``."""
        with Phase(f"{self.name}/{phase}", self.clog) as ph:
            hubs = self.readers[np.argsort(self.in_degree[self.readers])[-8:]]
            ids = np.unique(np.concatenate([
                self.rng.choice(self.readers, N_SAMPLE), hubs,
                np.asarray(extra, np.int64)]))
            for name, h in self.handles.items():
                check_reads(self.session, h, ids,
                            self.replica if name == "count" else None)
            ph.info.update(readers=len(ids))

    def churn(self) -> list[int]:
        """``N_CHURN`` new in-edges and ``N_CHURN`` dropped ones, on readers
        that keep at least one input; returns the touched readers."""
        with Phase(f"{self.name}/churn", self.clog) as ph:
            s, rng = self.session, self.rng
            cand = rng.permutation(
                self.readers[self.in_degree[self.readers] >= 2])[:2 * N_CHURN]
            touched = []
            for v in map(int, cand[:N_CHURN]):
                u = int(rng.choice(self.writers))
                if u != v and u not in s.neighborhood(v):
                    s.add_edge(u, v)
                    touched.append(v)
            for v in map(int, cand[N_CHURN:]):
                s.delete_edge(min(s.neighborhood(v)), v)
                touched.append(v)
            report = s.flush()
            if self.replica is not None:
                for v in touched:
                    self.replica.override[v] = s.neighborhood(v)
            ph.info.update(mutations=len(touched), patched=report.patched,
                           relayout=report.relayout,
                           recompiled=report.recompiled)
        return touched

    def check_fired(self) -> None:
        with Phase(f"{self.name}/drain_fired", self.clog) as ph:
            fired = {(int(b.now), int(r), float(v))
                     for b in self.session.drain_fired(self.alert)
                     for r, v in zip(b.base_ids, b.values)}
            rep = self.replica.fired
            check(fired == rep, f"fired set differs from the host replay: "
                  f"{len(fired - rep)} unexpected, {len(rep - fired)} missing")
            ph.info.update(fired=len(fired))

    def check_write_paths(self) -> None:
        for name, h in self.handles.items():
            log_ = h.group.engine.frontier_log
            check(-1 in log_ and any(k > 0 for k in log_),
                  f"{self.name}/{name} ran dense={log_.count(-1)} "
                  f"sparse={sum(k > 0 for k in log_)} write steps; both must")

def run_single(n_nodes: int = N_NODES, n_edges: int = N_EDGES, *,
               mincut_nodes: int = MINCUT_NODES,
               mincut_edges: int = MINCUT_EDGES, seed: int = 0,
               ingest_batch: int = INGEST_BATCH, on_chip: bool = True) -> None:
    """The one-chip phase; ``on_chip`` requires every group to run the
    compiled Pallas kernel."""
    clog = CompileLog()
    try:
        live = Deployment("live", n_nodes, n_edges, seed=seed,
                          ingest_batch=ingest_batch, clog=clog,
                          on_chip=on_chip)
        live.register(count=True, total=False)
        log(phase="cut", what="the min-cut sum group runs in its own "
            f"session on powerlaw_graph({mincut_nodes}, {mincut_edges}) "
            f"instead of ({n_nodes}, {n_edges}): at 1M/10M its host build "
            "(min-cut, plan compile, patch bookkeeping) would crowd the "
            "smoke's 1200 s limit")
        mincut = Deployment("mincut", mincut_nodes, mincut_edges, seed=seed,
                            ingest_batch=ingest_batch, clog=clog,
                            on_chip=on_chip)
        mincut.register(count=False, total=True)
        deps = (live, mincut)
        for d in deps:
            d.ingest("ingest")
            d.read("read")
        touched = [d.churn() for d in deps]
        for d, t in zip(deps, touched):
            d.ingest("ingest_after_churn")
            d.read("read_after_churn", extra=t)
        live.check_fired()
        for d in deps:
            d.check_write_paths()
        stats = jax.devices()[0].memory_stats() or {}
        log(phase="summary", jitted_programs_compiled=clog.total,
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            frontier_log={f"{d.name}/{n}": h.group.engine.frontier_log
                          for d in deps for n, h in d.handles.items()})
    finally:
        clog.close()


def run_four_chips(n_nodes: int = MINCUT_NODES, n_edges: int = MINCUT_EDGES,
                   *, seed: int = 0, ingest_batch: int = INGEST_BATCH,
                   n_shards: int = 4, on_chip: bool = True) -> None:
    """``shards=4`` against ``shards=None`` on the same graph, seed and
    traffic: sampled reads and fired sets must match bit for bit, and the
    stacked engine must run on a mesh of ``n_shards`` distinct devices."""
    clog = CompileLog()
    try:
        log(phase="cut", what=f"four-chip comparison on powerlaw_graph("
            f"{n_nodes}, {n_edges}): four chips bill four times per second")
        deps = [Deployment(name, n_nodes, n_edges, seed=seed,
                           ingest_batch=ingest_batch, clog=clog,
                           on_chip=on_chip, shards=sh)
                for name, sh in (("single", None), ("sharded", n_shards))]
        for d in deps:
            d.register(count=True, total=True)
        for h in deps[1].handles.values():
            mesh = h.group.engine.mesh
            check(mesh is not None, "stacked engine fell back to vmap: "
                  "fewer devices than shards")
            devs = {d.id for d in mesh.devices.flat}
            check(len(devs) == n_shards,
                  f"mesh spans {len(devs)} devices, want {n_shards}")
        with Phase("compare", clog) as ph:
            rng = np.random.default_rng(seed + 2)
            for _ in range(DENSE_STEPS):
                ids = rng.choice(deps[0].writers, ingest_batch)
                vals = rng.integers(0, VALUE_RANGE, len(ids)) \
                    .astype(np.float32)
                for d in deps:
                    d.session.update(ids, vals)
            sample = np.unique(rng.choice(deps[0].readers, N_SAMPLE))
            for name in ("count", "sum"):
                got = [np.asarray(d.handles[name].read(sample)) for d in deps]
                check(np.array_equal(got[0], got[1]),
                      f"{name}: shards={n_shards} reads differ from "
                      f"shards=None")
                check_reads(deps[0].session, deps[0].handles[name], sample)
            fired = [[(b.now, b.base_ids.tolist(), b.values.tolist())
                      for b in d.session.drain_fired()] for d in deps]
            check(fired[0] == fired[1],
                  "fired sets differ between sharded and single")
            ph.info.update(readers=len(sample),
                           fired=sum(len(b[1]) for b in fired[0]))
    finally:
        clog.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--four-chips", action="store_true",
                   help="run only the shards=4 vs shards=None comparison")
    args = p.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    log(phase="start", compile_cache=enable_compile_cache(ROOT / ".jax_cache"),
        device_kind=dev.device_kind, devices=len(devices), seed=args.seed)
    run = run_four_chips if args.four_chips else run_single
    try:
        run(seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
