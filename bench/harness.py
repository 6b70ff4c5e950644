"""The benchmark run: set-up, the measured window, the comparison, the result.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<cell>.json``). Per-layer metrics are readers of their own
(``bench/metrics/<metric>.py``), found by name. Adding a cell or a metric adds
files; nothing here names one.

Set-up. The first run of a configuration in a checkout builds the session
cold (construction, min-cut, plan compile, query and alert registration) and
saves it, before any traffic, into ``bench/.cache/sessions/<key>/``; the key
hashes the configuration, the program's sources and its ``EAGR_*``
environment, so a stale checkpoint is never restored. Every later run
restores that checkpoint (``EagrSession.restore``: plans, windows, PAOs and
clocks adopted verbatim), collects Python's garbage once, runs the stream's
first ``warmup_cycles`` cycles, which use every program shape the window
uses, and ends set-up when the last warm-up read has returned: ``setup_s``
is the time from process start to that first fresh answer, the time a
deployment takes to restart.

The window is closed loop: the caller issues the stream's next operation as
soon as the last one returned, in whole cycles of the traffic file, until
``seconds`` have passed at the end of a cycle, then a barrier
(``EagrSession.flush``) and a last ``drain_fired``. ``ops_per_s`` counts the
events submitted and the reader answers returned over the window's whole
length; ``read_p95_ms`` and ``alert_p95_ms`` are tails over every read and
every firing of the window. A program compiled inside the window makes the
run not correct: its numbers would not be those of the cell's steady state.

The comparison. Once the window has closed, the device peak read and the
session freed, ``reference.Reference`` recomputes from the edge list and the
submitted stream every fired set (all of them) and the answers of the reads
(all of them, or a sample drawn from the seed), and every number compared is
printed beside its limit. A traced run also wraps the engine's
``write_rows`` to keep which blocks each write step reduced, for the
kernel's roofline; an untraced run leaves the program as it is.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

import numpy as np

from bench import gen
from bench import reference as ref_mod
from bench import trace_reduce as tr

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
SPANS = ("window", "update", "read", "drain", "generate", "barrier")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, its configuration and
    traffic files, and the metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / confs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{name}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, int(w["chips"]), config, traffic,
                mine(spec["end_to_end"]), mine(spec["per_layer"]))


# ---------------------------------------------------------------- compiles
class CompileLog:
    """Counts the XLA programs built or loaded from the persistent cache,
    through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.total = 0
        self.seconds = 0.0
        self.names: list[str] = []
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.total += 1
            self.seconds += duration
            self.names.append(str(kw.get("fun_name", "?")))

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory inside the checkout. Every program is
    cached, however fast it compiled, so only a cell's first run compiles."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    path = env or os.fspath(CACHE / "jax")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ------------------------------------------------------------------ set-up
def session_key(config: dict) -> str:
    """What a saved session depends on: the configuration, the program's
    sources, its ``EAGR_*`` environment, the dataset generator and the JAX
    backend the plan was compiled for."""
    import jax

    h = hashlib.sha256()
    h.update(json.dumps(config, sort_keys=True).encode())
    for p in sorted((ROOT / "src").rglob("*.py")):
        h.update(p.relative_to(ROOT).as_posix().encode())
        h.update(p.read_bytes())
    h.update((BENCH / "gen.py").read_bytes())
    for k in sorted(os.environ):
        if k.startswith("EAGR_"):
            h.update(f"{k}={os.environ[k]}".encode())
    h.update(f"{jax.__version__} {jax.default_backend()}".encode())
    return h.hexdigest()[:16]


def load_dataset(config: dict, where: Path) -> gen.Dataset:
    """The configuration's dataset, cached beside its saved session."""
    path = where / "dataset.npz"
    if path.exists():
        z = np.load(path)
        return gen.Dataset(int(z["n_nodes"]), z["src"].astype(np.int64),
                           z["dst"].astype(np.int64), z["writers"],
                           z["readers"], z["write_freq"], z["read_freq"])
    ds = gen.make_dataset(config["graph"], float(config["popularity"]["alpha"]))
    where.mkdir(parents=True, exist_ok=True)
    tmp = where / "dataset.tmp.npz"
    np.savez(tmp, n_nodes=ds.n_nodes, src=ds.src.astype(np.int32),
             dst=ds.dst.astype(np.int32), writers=ds.writers,
             readers=ds.readers, write_freq=ds.write_freq,
             read_freq=ds.read_freq)
    os.replace(tmp, path)
    return ds


def build_session(config: dict, ds: gen.Dataset):
    """Cold build: the session over the dataset, with the configuration's
    query (and standing alert) registered. Returns ``(session, phases)``."""
    from repro import EagrSession, Query, WindowSpec
    from repro.graphs.csr import CSRGraph

    q, s = config["query"], config["session"]
    phases: dict = {}
    kw = dict(ingest_depth=int(s["ingest_depth"]),
              ingest_batch=int(s["ingest_batch"]), seed=int(s["seed"]))
    if q.get("decisions") == "mincut":
        kw.update(write_freq=ds.write_freq, read_freq=ds.read_freq)
    t0 = time.perf_counter()
    session = EagrSession(CSRGraph.from_edges(ds.src, ds.dst, ds.n_nodes),
                          **kw)
    phases["construction_s"] = time.perf_counter() - t0
    st = session.overlay_stats
    phases["construction_phase_s"] = dict(
        getattr(st, "phase_seconds", {}) or {})
    phases["sharing_index"] = (getattr(st, "si_per_iteration", None)
                               or [None])[-1]
    t0 = time.perf_counter()
    handle = session.register(Query(
        agg=q["agg"], window=WindowSpec("tuple", int(q["window"])),
        continuous=bool(q["continuous"])))
    a = config.get("alert")
    if a:
        handle.on_threshold(above=float(a["above"]),
                            hysteresis=float(a["hysteresis"]),
                            debounce=float(a["debounce"]))
    phases["register_s"] = time.perf_counter() - t0
    phases["group_build_s"] = dict(handle.group.build_seconds)
    return session, phases


def open_session(config: dict, log):
    """Restore the configuration's saved session, or build it cold and save
    it first. Returns ``(session, dataset, cold)``."""
    from repro import EagrSession

    where = CACHE / "sessions" / f"{config['name']}-{session_key(config)}"
    ckpt = where / "session"
    ds = load_dataset(config, where)
    if (where / "READY").exists():
        t0 = time.perf_counter()
        session = EagrSession.restore(os.fspath(ckpt))
        log(phase="restore", seconds=time.perf_counter() - t0)
        return session, ds, False
    shutil.rmtree(ckpt, ignore_errors=True)
    session, phases = build_session(config, ds)
    t0 = time.perf_counter()
    session.save(os.fspath(ckpt), blocking=True)
    session.wait_for_checkpoint()
    (where / "READY").write_text("saved before any traffic\n")
    phases["save_s"] = time.perf_counter() - t0
    log(phase="cold_build", **phases)
    return session, ds, True


# ------------------------------------------------------------------ window
@dataclasses.dataclass
class OpRecord:
    kind: str
    n: int            # events (update) or readers (read)
    t0: float
    t1: float
    stall_s: float = 0.0


class Caller:
    """Issues the stream's operations on the session and keeps what each
    returned, with its host-clock span."""

    def __init__(self, session, handle, alert, batch: int):
        import jax

        self.session, self.handle, self.alert = session, handle, alert
        self.batch = int(batch)  # ring slot: events per full device batch
        self._annotate = jax.profiler.TraceAnnotation
        self.ingest = None  # the session's IngestStats, once it has one
        self.ops: list[OpRecord] = []
        self.ids: list[np.ndarray] = []
        self.values: list[np.ndarray] = []
        self.n_events = 0
        self.reads: list[tuple[int, np.ndarray, np.ndarray, int]] = []
        self.fired: list[tuple[float, np.ndarray, np.ndarray, int]] = []

    def do(self, op: gen.Op) -> None:
        i = len(self.ops)
        with self._annotate(op.kind):
            t0 = time.perf_counter()
            stall0 = self.ingest.stall_s if self.ingest is not None else 0.0
            if op.kind == "update":
                self.session.update(op.ids, op.values)
                self.ids.append(op.ids)
                self.values.append(op.values)
                self.n_events += len(op.ids)
                n = len(op.ids)
            elif op.kind == "read":
                ans = self.session.read(self.handle, op.ids)
                self.reads.append((i, op.ids, np.asarray(ans).reshape(-1),
                                   self.n_events))
                n = len(op.ids)
            else:
                got = self.session.drain_fired(self.alert) \
                    if self.alert is not None else []
                for b in got:
                    self.fired.append((float(b.now), b.base_ids, b.values, i))
                n = 0
            t1 = time.perf_counter()
        stall = (self.ingest.stall_s - stall0) if self.ingest is not None \
            else 0.0
        self.ops.append(OpRecord(op.kind, n, t0, t1, stall))

    def barrier(self) -> None:
        """Every submitted event sent and its step done; the fired sets of
        every step collected."""
        i = len(self.ops)
        with self._annotate("barrier"):
            t0 = time.perf_counter()
            self.session.flush()
            if self.alert is not None:
                for b in self.session.drain_fired(self.alert):
                    self.fired.append((float(b.now), b.base_ids, b.values, i))
            t1 = time.perf_counter()
        self.ops.append(OpRecord("barrier", 0, t0, t1))


class GcLog:
    """Python's garbage collections while it is installed: how many of each
    generation, their total and longest pause."""

    def __init__(self):
        self.collections = [0, 0, 0]
        self.total_s = self.longest_s = 0.0
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            self.collections[info["generation"]] += 1
            self.total_s += d
            self.longest_s = max(self.longest_s, d)

    def summary(self) -> dict:
        return {"collections": self.collections, "total_s": self.total_s,
                "longest_s": self.longest_s}


def percentile(samples, p: float) -> float | None:
    """Nearest-rank percentile (``p`` in per cent)."""
    xs = sorted(samples)
    if not xs:
        return None
    return xs[min(len(xs) - 1, max(0, round(p / 100.0 * (len(xs) - 1))))]


# -------------------------------------------------------------- comparison
def compare(cell: Cell, ds: gen.Dataset, drv: Caller, seed: int,
            stand_in: str | None = None) -> dict:
    """Every number compared, as ``{name: (value, limit)}``. With
    ``stand_in`` (a lower precision, ``"bfloat16"``) the reference held in
    that precision stands in the program's place: the control."""
    q, a = cell.config["query"], cell.config.get("alert")
    refs = [ref_mod.Reference(ds.n_nodes, ds.src, ds.dst, agg=q["agg"],
                              size=int(q["window"]), store=store)
            for store in ("float64",) + ((stand_in,) if stand_in else ())]
    ids = np.concatenate(drv.ids) if drv.ids else np.zeros(0, np.int64)
    vals = np.concatenate(drv.values) if drv.values \
        else np.zeros(0, np.float32)
    for ref in refs:
        ref.set_stream(ids, vals)
    ref = refs[0]
    checks: dict[str, tuple[float, float]] = {}

    # reads: all of them, or a sample drawn from the seed (the first and the
    # last read always among them)
    reads = drv.reads
    cap = int(cell.traffic.get("compare_reads", len(reads)))
    if len(reads) > cap:
        rng = np.random.default_rng(gen.seed_words(seed) + [7])
        pick = set(rng.choice(len(reads), cap - 2, replace=False).tolist())
        pick |= {0, len(reads) - 1}
        reads = [reads[k] for k in sorted(pick)]
    asked = [(r[1], r[3]) for r in reads]
    want = ref.many_answers(asked)
    got = refs[-1].many_answers(asked) if stand_in else [r[2] for r in reads]
    err = [np.abs(g.astype(np.float64) - w) for g, w in zip(got, want)]
    flat = np.concatenate(err) if err else np.zeros(0)
    checks["read_mismatches"] = (float(np.count_nonzero(flat)), 0.0)
    checks["read_max_abs_err"] = (float(flat.max()) if flat.size else 0.0,
                                  0.0)

    cutoffs, _ = ref_mod.device_batches(
        [(o.kind, o.n if o.kind == "update" else 0) for o in drv.ops],
        drv.batch)
    if drv.ingest is not None:
        checks["batch_count_gap"] = (
            float(abs(drv.ingest.batches - len(cutoffs))), 0.0)
    if a:
        kw = dict(above=float(a["above"]), hysteresis=float(a["hysteresis"]),
                  debounce=float(a["debounce"]))
        want_f = ref.fired(cutoffs, **kw)
        got_f = refs[-1].fired(cutoffs, **kw) if stand_in else {
            (int(now), int(r), float(v)) for now, rs, vs, _ in drv.fired
            for r, v in zip(rs, vs)}
        checks["fired_mismatches"] = (float(len(got_f ^ want_f)), 0.0)
    return {"checks": checks, "reads_compared": len(reads),
            "reads_total": len(drv.reads),
            "fired_compared": sum(len(f[1]) for f in drv.fired),
            "max_reader_batch_delta": ref.max_batch_delta}


# ------------------------------------------------------------------ tracing
def needed_bytes(plan, steps, width: int) -> int | None:
    """Bytes the write steps of ``steps`` need of the segment reduction: the
    real edges of the blocks each one reduces and the rows it writes
    (``trace_reduce.reduce_bytes``). ``steps`` holds each write step's
    active tuple, ``None`` for every block of every level. Returns ``None``
    when the plan's tables cannot be read."""
    import jax
    import jax.numpy as jnp

    arrays = getattr(plan, "arrays", None)
    if arrays is None:
        return None

    @jax.jit
    def per_block(seg, tob):
        nb = tob.shape[1]
        s = seg.reshape(seg.shape[0], nb, -1)
        real = s >= 0
        first = jnp.concatenate(
            [real[..., :1], real[..., 1:] & (s[..., 1:] != s[..., :-1])], -1)
        return real.sum(-1), first.sum(-1)

    e, r = jax.device_get(per_block(arrays.push.seg,
                                    arrays.push.tile_of_block))
    e, r = np.asarray(e, np.int64), np.asarray(r, np.int64)
    total = 0
    for active in steps:
        if active is None:
            total += tr.reduce_bytes(e.sum(), r.sum(), width)
            continue
        for lvl, blocks in enumerate(active):
            b = np.asarray(blocks, np.int64)
            b = b[b < e.shape[1]]
            total += tr.reduce_bytes(e[lvl, b].sum(), r[lvl, b].sum(), width)
    return total


@contextlib.contextmanager
def record_steps(engine, steps: list):
    """Keep, for the traced window, which blocks each write step reduced:
    the active tuple the engine chose (``EagrEngine.frontier_active``)."""
    orig = engine.write_rows

    def write_rows(rows, vals, mask, *, n_live=None, active="auto"):
        if isinstance(active, str):
            active = engine.frontier_active(rows, mask, n_live=n_live)
        steps.append(active)
        return orig(rows, vals, mask, n_live=n_live, active=active)

    engine.write_rows = write_rows
    try:
        yield
    finally:
        del engine.write_rows


def load_metric(name: str):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read of one traced run."""

    cell: Cell
    ops: list                 # OpRecord of the window, barrier last
    window_s: float
    ingest: dict              # IngestStats deltas over the window
    frontier: list            # frontier_log entries written in the window
    trace: "tr.Trace | None"
    window: "tr.Interval | None"
    need_bytes: "int | None"  # bytes the window's reductions need
    peaks: dict


# ---------------------------------------------------------------------- run
def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, control: str | None = None, out=print,
        err=None) -> dict:
    """One run of ``cell``; returns the result object (also printed as the
    last line of ``out``). ``control`` (a precision, ``"bfloat16"``) also
    reads the numbers compared with the reference in that precision standing
    in the program's place (``bench/control.py``)."""
    import jax

    err = err or (lambda s: print(s, file=sys.stderr, flush=True))

    def log(**fields):
        out(json.dumps(fields, default=float))

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"needs {cell.chips} chips, JAX found {len(devices)}")
    cache_dir = enable_compile_cache()
    clog = CompileLog()
    try:
        return _run(cell, seed, seconds, trace, t_start, dev, len(devices),
                    cache_dir, clog, log, out, err, control)
    finally:
        clog.close()


def _run(cell, seed, seconds, trace, t_start, dev, n_dev, cache_dir, clog,
         log, out, err, control):
    import jax

    t_dev = time.perf_counter()
    session, ds, cold = open_session(cell.config, log)
    # the restored session's millions of host objects, collected once here
    # and not by a full collection that falls into the window at random
    gc.collect()
    t_open = time.perf_counter()
    handle = session.queries[0]
    alert = session.alerts[0] if session.alerts else None
    stream = gen.Stream(ds, cell.traffic, cell.config["values"], seed)
    block = int(cell.traffic.get("block_cycles", 1))
    warm = stream.cycles(int(cell.traffic["warmup_cycles"]))
    pool = stream.cycles(int(cell.traffic.get("pregenerate_cycles", 0)))
    t_gen = time.perf_counter()
    drv = Caller(session, handle, alert,
                 int(cell.config["session"]["ingest_batch"]))
    failed, failure = 0, None
    try:
        for op in warm:
            drv.do(op)
            if drv.ingest is None and op.kind == "update":
                drv.ingest = session.stats().ingest
    except Exception:  # the run goes on to report the failure
        failed, failure = 1, traceback.format_exc()
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    setup_compiles = clog.total
    log(phase="setup", setup_s=setup_s, cold=cold, compiles=setup_compiles,
        compile_s=clog.seconds, warmup_ops=len(drv.ops),
        to_device_s=t_dev - t_start, open_session_s=t_open - t_dev,
        generate_s=t_gen - t_open, warmup_s=t_warm - t_gen,
        compile_cache=cache_dir)

    eng = handle.group.engine
    n_warm = len(drv.ops)
    ing0 = dataclasses.asdict(drv.ingest) if drv.ingest is not None else {}
    flog0 = len(eng.frontier_log)
    steps: list = []
    trace_dir = CACHE / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(os.fspath(trace_dir), profiler_options=opts)
    gen_s = 0.0
    per_cycle = len(stream.cycle)
    gc_log = GcLog()
    gc.callbacks.append(gc_log)
    with record_steps(eng, steps) if trace else contextlib.nullcontext():
        t_w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            deadline = t_w0 + seconds
            todo = iter(pool)
            issued = 0
            while failure is None and (issued % per_cycle
                                       or time.perf_counter() < deadline):
                op = next(todo, None)
                if op is None:
                    g0 = time.perf_counter()
                    with jax.profiler.TraceAnnotation("generate"):
                        todo = iter(stream.cycles(block))
                    gen_s += time.perf_counter() - g0
                    continue
                try:
                    drv.do(op)
                except Exception:
                    failed, failure = failed + 1, traceback.format_exc()
                issued += 1
            if failure is None:
                try:
                    drv.barrier()
                except Exception:
                    failed, failure = failed + 1, traceback.format_exc()
        t_w1 = time.perf_counter()
    gc.callbacks.remove(gc_log)
    if trace:
        jax.profiler.stop_trace()
    window_s = t_w1 - t_w0
    window_compiles = clog.total - setup_compiles
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0)) or None
    wops = drv.ops[n_warm:]
    ing1 = dataclasses.asdict(drv.ingest) if drv.ingest is not None else {}
    ingest = {k: ing1[k] - ing0.get(k, 0) for k in ing1}
    frontier = list(eng.frontier_log[flog0:])
    plan = eng.plan if trace else None
    width = int(getattr(handle.agg, "pao_dim", 1))
    log(phase="window", window_s=window_s, ops=len(wops),
        compiles=window_compiles, compiled=clog.names[setup_compiles:][:20],
        generate_s=gen_s, ingest=ingest, steps=step_counts(frontier),
        calls=call_times(wops), gc=gc_log.summary())

    need = None
    if trace and failure is None:
        try:
            need = needed_bytes(plan, steps, width)
        except Exception:
            err("needed bytes: " + traceback.format_exc())
    del eng, plan, handle, alert, session
    drv.session = drv.handle = drv.alert = None
    gc.collect()

    t_c = time.perf_counter()
    cmp_ = compare(cell, ds, drv, seed)
    run_checks = {"ops_failed": (float(failed), 0.0),
                  "window_compiles": (float(window_compiles), 0.0)}
    checks = {**cmp_["checks"], **run_checks}
    log(phase="compare", seconds=time.perf_counter() - t_c,
        reads_compared=cmp_["reads_compared"],
        reads_total=cmp_["reads_total"],
        fired_compared=cmp_["fired_compared"],
        max_reader_batch_delta=cmp_["max_reader_batch_delta"])

    def verdict(checks):
        return failure is None and all(v <= lim for v, lim in checks.values())

    if control:
        # the control's verdict is the run's: the program's own is printed
        # on this line
        log(phase="program", correct=verdict(checks),
            checks={k: {"value": v, "limit": lim}
                    for k, (v, lim) in checks.items()})
        checks = {**compare(cell, ds, drv, seed, stand_in=control)["checks"],
                  **run_checks}
    if failure:
        err(failure)
    correct = verdict(checks)

    attempted = sum(1 for o in wops if o.kind != "barrier") + failed
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev, "memory_peak_bytes": peak}
    result: dict = {"correct": bool(correct), "attempted": attempted,
                    "failed": failed}
    if not trace:
        metrics = end_to_end(cell, drv, n_warm, window_s, setup_s)
    else:
        tdata = twin = None
        if failure is None:
            tdata = tr.load(trace_dir, SPANS)
            twin = tdata.window()
            device["busy_s"] = tr.busy_seconds(tdata, twin)
            device["window_s"] = twin.dur_ns * 1e-9
            result["breakdown"] = {
                "device_ops": tr.top_ops(tdata, twin),
                "idle_gaps": tr.idle_gaps(tdata, twin)}
        ctx = Context(cell, wops, window_s, ingest, frontier, tdata, twin,
                      need, tr.peaks(dev.device_kind) if dev.platform == "tpu"
                      else {})
        metrics = per_layer(cell, ctx) if failure is None else {}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        err(f"check {k} {v!r} limit {lim!r}")
    out(json.dumps(result))
    return result


def end_to_end(cell: Cell, drv: Caller, first_op: int, window_s: float,
               setup_s: float) -> dict:
    """The cell's end-to-end metrics, from the window's host-clock spans."""
    wops = drv.ops[first_op:]
    rd = [o for o in wops if o.kind == "read"]
    values = {
        "setup_s": setup_s,
        "ops_per_s": sum(o.n for o in wops if o.kind in ("update", "read"))
        / window_s,
        "read_p95_ms": _ms(percentile([o.t1 - o.t0 for o in rd], 95)),
        "alert_p95_ms": _ms(percentile(alert_latencies(drv, first_op), 95)),
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if values.get(m["name"]) is not None}


def per_layer(cell: Cell, ctx: Context) -> dict:
    """The cell's per-layer metrics, each from its reader; a reader that
    finds nothing to read leaves its metric out."""
    out = {}
    for m in cell.per_layer:
        v = load_metric(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def step_counts(frontier) -> dict:
    """Write steps of the window, dense and frontier-sparse, from the
    engine's ``frontier_log`` (-1 marks a dense step)."""
    dense = sum(1 for k in frontier if k < 0)
    return {"write_dense": dense, "write_sparse": len(frontier) - dense}


def call_times(ops) -> dict:
    """Host seconds per kind of call: count, total, median and largest."""
    out: dict = {}
    for kind in ("update", "read", "drain", "barrier"):
        d = sorted(o.t1 - o.t0 for o in ops if o.kind == kind)
        if d:
            out[kind] = {"n": len(d), "total_s": sum(d),
                         "p50_ms": 1e3 * d[len(d) // 2],
                         "max_ms": 1e3 * d[-1]}
    return out


def _ms(s):
    return None if s is None else s * 1e3


def alert_latencies(drv: Caller, first_op: int) -> list[float]:
    """Per firing: from the issue of the call that sent its device batch to
    the return of the drain that handed it back, for batches sent in the
    window."""
    _, op_of = ref_mod.device_batches(
        [(o.kind, o.n if o.kind == "update" else 0) for o in drv.ops],
        drv.batch)
    out = []
    for now, rs, _, got_at in drv.fired:
        k = int(now)
        if k >= len(op_of) or op_of[k] < first_op:
            continue
        out.extend([drv.ops[got_at].t1 - drv.ops[op_of[k]].t0] * len(rs))
    return out
