"""The session's own instrumentation: stable ``eagr.*`` device scopes in the
op metadata of every step program, host spans on the update/read/drain path
with their nesting and batch ticks, and the fired-set readback counters."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import dataflow as D
from repro.core import engine as E
from repro.core.aggregates import make_aggregate
from repro.core.bipartite import build_bipartite
from repro.core.vnm import construct_vnm
from repro.core.window import WindowSpec
from repro.graphs.generators import rmat_graph
from repro.kernels.segment_agg.ops import E_BLK, segment_agg_level
from repro.session import EagrSession, Query
from repro.streams import alerts as A

WRITE_SCOPES = ("eagr.apply_writes", "eagr.level_gather",
                "eagr.level_combine")


@pytest.fixture(scope="module")
def overlay():
    g = rmat_graph(120, 700, seed=3)
    ov, _ = construct_vnm(build_bipartite(g), variant="vnm_a",
                          max_iterations=2, seed=0)
    return ov


def _engine(overlay, agg="sum", window=WindowSpec("tuple", 4)):
    dec = np.full(overlay.n_nodes, D.PUSH, np.int64)
    return E.EagrEngine(overlay, dec, make_aggregate(agg), window,
                        backend="xla")


def _hlo(fn, *args) -> str:
    """The lowered program's text with each operation's source scope."""
    return fn.lower(*args).as_text(debug_info=True)


def _batch(eng, n=16):
    rows = np.arange(n, dtype=np.int32) % eng.plan.meta.n_writers
    return rows, np.ones(n, np.float32), np.ones(n, bool)


def _blocks(eng):
    """A per-level active-block tuple: the first two blocks of each level."""
    nb = eng.plan.arrays.push.tile_of_block.shape[1]
    return tuple(np.arange(min(2, nb), dtype=np.int32)
                 for _ in range(eng.plan.meta.n_levels))


@pytest.mark.parametrize("agg", ["sum", "max"])
@pytest.mark.parametrize("sparse", [False, True])
def test_write_steps_carry_their_scopes(overlay, agg, sparse):
    eng = _engine(overlay, agg)
    meta, a, spec, arrays = eng.plan.meta, eng.agg, eng.spec, eng.plan.arrays
    rows, vals, mask = _batch(eng)
    extra = () if agg == "sum" else (np.float32(0.0),)
    if sparse:
        extra += (_blocks(eng),)
    body = {(False, "sum"): E._write_body_sum,
            (False, "max"): E._write_body_extremal,
            (True, "sum"): E._write_body_sum_sparse,
            (True, "max"): E._write_body_extremal_sparse}[sparse, agg]
    text = _hlo(body, meta, a, spec, arrays, eng.state, rows, vals, mask,
                *extra)
    for scope in WRITE_SCOPES:
        assert scope in text, scope
    assert "eagr.read_step" not in text


@pytest.mark.parametrize("sparse", [False, True])
def test_read_steps_carry_their_scope(overlay, sparse):
    eng = _engine(overlay)
    nodes = np.flatnonzero(eng.plan.routes.reader_node >= 0)[:8]
    nodes = eng.plan.routes.reader_node[nodes].astype(np.int32)
    mask = np.ones(len(nodes), bool)
    if sparse:
        act = _blocks(eng)
        text = _hlo(E._read_body_sparse, eng.plan.meta, eng.agg,
                    eng.plan.arrays, eng.state, nodes, mask, act, act)
    else:
        text = _hlo(E._read_body, eng.plan.meta, eng.agg, eng.plan.arrays,
                    eng.state, nodes, mask)
    assert "eagr.read_step" in text


def test_fused_alert_step_carries_alert_eval(overlay):
    eng = _engine(overlay)
    al = A.AlertSet(cap=8)
    bases = np.flatnonzero(eng.plan.routes.reader_node >= 0)
    al.register(0, A.AlertSpec(above=2.0), bases.tolist(), dynamic=False)
    eng.attach_alerts(al)
    rows, vals, mask = _batch(eng)
    text = _hlo(A._alert_write, E.write_step_sum, eng.plan.meta, eng.agg,
                eng.spec, al.cap, eng.plan.arrays, eng.state, al.state,
                rows, vals, mask)
    for scope in WRITE_SCOPES + ("eagr.alert_eval",):
        assert scope in text, scope
    eng.alerts = None


def test_segment_reduction_carries_pad_and_kernel_scopes():
    nb, n_rows = 4, 256
    seg = np.repeat(np.arange(nb * E_BLK) // 8 % n_rows, 1).astype(np.int32)
    tob = (np.arange(nb) * 0).astype(np.int32)
    fot = np.array([1, 0, 0, 0], np.int32)
    x = np.ones((nb * E_BLK, 1), np.float32)
    f = jax.jit(lambda x, s, t, o: segment_agg_level(
        x, s, t, o, n_rows=n_rows, n_row_tiles=2, op="sum", interpret=True))
    text = _hlo(f, x, seg, tob, fot)
    assert "eagr.lane_pad" in text and "eagr.segment_agg" in text


# ---------------------------------------------------------------- host spans
def _session(cap=None):
    g = rmat_graph(200, 1200, seed=1)
    s = EagrSession(g, ingest_batch=16, ingest_depth=2)
    h = s.register(Query(agg="count", window=WindowSpec("tuple", 4),
                         continuous=True))
    h.on_threshold(above=1.0)
    return s, h


def _spans(log_dir) -> list:
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("eagr."):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return out


def _inside(outer, spans, name):
    return [s for s in spans if s[0] == name
            and outer[1] <= s[1] and s[2] <= outer[2]]


def test_session_spans_nest_and_carry_batch_ticks(tmp_path):
    s, h = _session()
    W, R = np.asarray(s.writers), np.asarray(s.readers)
    eng = h.group.engine
    s.update(W[:40])
    s.read(h, R[:8])
    s.drain_fired()
    s.flush()
    tick0 = int(eng._now_host)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(3):
            s.update(W[k * 16: k * 16 + 32])
            s.read(h, R[:8])
            s.drain_fired()
        s.flush()
    finally:
        jax.profiler.stop_trace()
    spans = _spans(str(tmp_path))
    names = [x[0] for x in spans]

    updates = [x for x in spans if x[0] == "eagr.update"]
    assert len(updates) == 3
    for u in updates:
        ds = _inside(u, spans, "eagr.dispatch")
        assert len(ds) == 2
        for d in ds:
            for child in ("eagr.route", "eagr.expand", "eagr.put"):
                assert len(_inside(d, spans, child)) == 1, child
            assert d[3]["batch"] == _inside(d, spans, "eagr.put")[0][3][
                "batch"]
        assert _inside(u, spans, "eagr.ring_wait")
    reads = [x for x in spans if x[0] == "eagr.read"]
    assert len(reads) == 3
    for r in reads:
        for child in ("eagr.read_route", "eagr.put", "eagr.read_wait",
                      "eagr.read_bookkeeping"):
            assert len(_inside(r, spans, child)) == 1, child
    drains = [x for x in spans if x[0] == "eagr.drain"]
    assert len(drains) == 3
    assert any(_inside(d, spans, "eagr.fired_readback") for d in drains)
    flush = [x for x in spans if x[0] == "eagr.flush"]
    assert len(flush) == 1 and _inside(flush[0], spans, "eagr.ring_wait")

    # every device batch dispatched is one tick of the engine, and each is
    # read back exactly once under its own tick
    ticks = [x[3]["batch"] for x in spans if x[0] == "eagr.dispatch"]
    assert ticks == list(range(tick0, int(eng._now_host)))
    back = sorted(x[3]["batch"] for x in spans
                  if x[0] == "eagr.fired_readback")
    assert back == ticks
    assert all(x[3]["overflow"] in (0, 1) for x in spans
               if x[0] == "eagr.fired_readback")
    # span arguments are host integers only
    assert all(isinstance(v, int) for x in spans for v in x[3].values())
    assert "eagr.update" in names and "eagr.read" in names


def test_tracing_changes_no_answer_and_no_program(tmp_path):
    """The same stream, traced and not: equal answers and fired sets, and
    the traced run compiles no program the untraced one did not."""
    from repro.streams.alerts import _alert_write

    got = []
    for traced in (False, True):
        s, h = _session()
        W, R = np.asarray(s.writers), np.asarray(s.readers)
        s.update(W[:48])
        s.read(h, R[:8])
        n_progs = (_alert_write._cache_size(), E._read_body._cache_size())
        if traced:
            jax.profiler.start_trace(str(tmp_path))
        try:
            out = [s.read(h, R[:8])]
            for k in range(3):
                s.update(W[k * 16: k * 16 + 32])
                out.append(s.read(h, R[:8]))
            fired = s.drain_fired()
        finally:
            if traced:
                jax.profiler.stop_trace()
        assert (_alert_write._cache_size(),
                E._read_body._cache_size()) == n_progs
        got.append(([np.asarray(o).tobytes() for o in out],
                    [(b.now, b.base_ids.tolist(), b.values.tobytes())
                     for b in fired]))
    assert got[0] == got[1]


@pytest.mark.parametrize("mode", ["0", "1"])
def test_dense_puts_carry_the_sweeps_extent(tmp_path, monkeypatch, mode):
    """Each dense write step's ``eagr.put`` (inside ``eagr.dispatch``)
    carries the blocks its bounded sweep visits and the plan's padded
    blocks, from the host copy of the extents; sparse steps carry neither."""
    monkeypatch.setenv("EAGR_SPARSE_WRITE", mode)
    s, h = _session()
    W = np.asarray(s.writers)
    eng = h.group.engine
    s.update(W[:32])
    s.flush()
    jax.profiler.start_trace(str(tmp_path))
    try:
        s.update(W[16:48])
        s.flush()
    finally:
        jax.profiler.stop_trace()
    spans = _spans(str(tmp_path))
    puts = [p for d in spans if d[0] == "eagr.dispatch"
            for p in _inside(d, spans, "eagr.put")]
    assert len(puts) == 2
    live, padded = eng.plan.dense_sweep_blocks(eng.agg.pao_dim)
    L, nb = eng.plan.arrays.push.tile_of_block.shape
    for p in puts:
        if mode == "0":
            assert (p[3]["live_blocks"], p[3]["padded_blocks"]) == \
                (live, padded)
            assert 0 < live < padded == L * nb
        else:
            assert "live_blocks" not in p[3] and "padded_blocks" not in p[3]


# ------------------------------------------------------------------ counters
@pytest.mark.parametrize("cap,overflow", [(2, True), (4096, False)])
def test_alert_stats_count_dense_fallback_readbacks(monkeypatch, cap,
                                                     overflow):
    monkeypatch.setenv("EAGR_ALERT_CAP", str(cap))
    s, h = _session()
    W = np.asarray(s.writers)
    for k in range(4):
        s.update(W[(k * 16) % len(W):][:16])
    fired = s.drain_fired()
    st = s.stats().alerts
    assert isinstance(st, A.AlertStats)
    assert st.batches_collected == h.group.engine.alerts.seq == 4
    assert max(len(b) for b in fired) > 2
    if overflow:
        assert 0 < st.overflow_batches <= st.batches_collected
        assert any(b.overflow for b in fired)
    else:
        assert st.overflow_batches == 0


def test_session_without_alerts_has_no_alert_stats():
    s = EagrSession(rmat_graph(60, 260, seed=1))
    s.register(Query(agg="sum"))
    assert s.stats().alerts is None
