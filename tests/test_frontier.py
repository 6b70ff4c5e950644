"""Frontier-sparse write/read steps (PR 8): the sparse paths must be
BIT-identical to the dense sweeps — across aggregates, payload shapes,
window kinds, backends, and structural churn — because the block index
promises a *superset* of every batch's reachable frontier. Plus the trace /
transfer discipline the substrate guarantees everywhere else: power-of-two
K bucketing keeps a bounded jit cache, and steady-state sparse ingest makes
zero implicit host->device transfers. The bf16 edge-value flag
(EAGR_SEGAGG_BF16) is checked against fp32 within rounding tolerance.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import dataflow as D
from repro.core import frontier as F
from repro.core.aggregates import make_aggregate
from repro.core.bipartite import build_bipartite
from repro.core.dynamic import DynamicOverlay
from repro.core.engine import EagrEngine
from repro.core.vnm import construct_vnm
from repro.core.window import WindowSpec
from repro.graphs.generators import rmat_graph
from repro.session import EagrSession, Query


# ---------------------------------------------------------------- fixtures
def _basis(seed=3, n=150, e=900):
    g = rmat_graph(n, e, seed=seed)
    bp = build_bipartite(g)
    ov, _ = construct_vnm(bp, variant="vnm_a", max_iterations=3, seed=0)
    dyn = DynamicOverlay.from_overlay(ov, bp.reader_input_sets())
    return dyn.to_overlay(prune=False)


@pytest.fixture(scope="module")
def basis():
    return _basis()


def _engine(basis, *, agg="sum", spec=None, all_push=False, backend=None,
            **agg_kwargs):
    if all_push:
        dec = np.full(basis.n_nodes, D.PUSH, np.int64)
    else:
        n = max((o for o in basis.origin if o >= 0), default=0) + 1
        wf = np.ones(n)
        dec, _ = D.decide_mincut(basis, wf, wf.copy(),
                                 D.cost_model_for("sum", window=4), window=4)
    return EagrEngine(basis, dec, make_aggregate(agg, **agg_kwargs),
                      spec or WindowSpec("tuple", 4), headroom=2.0,
                      backend=backend)


def _drive(eng, mode, monkeypatch, *, n_batches=6, arrival=16, value_dim=1,
           seed=7):
    monkeypatch.setenv("EAGR_SPARSE_WRITE", mode)
    writers = np.flatnonzero(eng.plan.routes.writer_row >= 0)
    rng = np.random.default_rng(seed)
    for _ in range(n_batches):
        ids = rng.choice(writers, size=arrival).astype(np.int64)
        shape = (arrival,) if value_dim == 1 else (arrival, value_dim)
        vals = rng.integers(0, 8, shape).astype(np.float32)
        eng.write_batch(ids, vals)


def _state_tuple(eng):
    s = eng.state
    return tuple(np.asarray(jax.device_get(x)) for x in
                 (s.windows.values, s.windows.stamps, s.windows.head,
                  s.windows.count, s.pao, s.now))


def _assert_states_equal(a, b):
    for x, y in zip(_state_tuple(a), _state_tuple(b)):
        np.testing.assert_array_equal(x, y)


# ------------------------------------------------------------- bit parity
CASES = [
    ("sum_scalar_tuple", dict(), 1),
    ("sum_vector_tuple", dict(agg="sum", value_dim=3,
                              spec=WindowSpec("tuple", 4, value_dim=3)), 3),
    ("sum_scalar_time", dict(agg="sum",
                             spec=WindowSpec("time", 4, capacity=8)), 1),
    ("max_scalar_tuple", dict(agg="max", all_push=True), 1),
    ("max_scalar_time", dict(agg="max", all_push=True,
                             spec=WindowSpec("time", 4, capacity=8)), 1),
    ("min_scalar_tuple", dict(agg="min", all_push=True), 1),
    ("min_vector_time", dict(agg="min", all_push=True, value_dim=2,
                             spec=WindowSpec("time", 4, capacity=8,
                                             value_dim=2)), 2),
]


@pytest.mark.parametrize("name,kw,vdim", CASES,
                         ids=[c[0] for c in CASES])
def test_sparse_write_bit_identical_to_dense(basis, monkeypatch, name, kw,
                                             vdim):
    dense, sparse = _engine(basis, **kw), _engine(basis, **kw)
    _drive(dense, "0", monkeypatch, value_dim=vdim)
    _drive(sparse, "1", monkeypatch, value_dim=vdim)
    _assert_states_equal(dense, sparse)
    assert any(k >= 0 for k in sparse.frontier_log), \
        "forced sparse mode never took the sparse path"
    assert all(k == -1 for k in dense.frontier_log)


def test_sparse_write_bit_identical_pallas(basis, monkeypatch):
    dense = _engine(basis, backend="pallas")
    sparse = _engine(basis, backend="pallas")
    _drive(dense, "0", monkeypatch)
    _drive(sparse, "1", monkeypatch)
    _assert_states_equal(dense, sparse)


@settings(deadline=None, max_examples=12)
@given(seed=st.integers(0, 2**16), arrival=st.integers(1, 48),
       agg=st.sampled_from(["sum", "max", "min"]),
       time_window=st.booleans())
def test_sparse_write_parity_hypothesis(seed, arrival, agg, time_window):
    """Property sweep: any batch mix, aggregate and window kind — sparse
    (forced) and dense states stay bit-identical."""
    import os
    basis = _basis(seed=4, n=120, e=700)
    spec = WindowSpec("time", 4, capacity=8) if time_window \
        else WindowSpec("tuple", 4)
    kw = dict(agg=agg, all_push=agg != "sum", spec=spec)
    dense, sparse = _engine(basis, **kw), _engine(basis, **kw)
    old = os.environ.get("EAGR_SPARSE_WRITE")
    try:
        writers = np.flatnonzero(dense.plan.routes.writer_row >= 0)
        rng = np.random.default_rng(seed)
        batches = [(rng.choice(writers, arrival).astype(np.int64),
                    rng.integers(0, 8, arrival).astype(np.float32))
                   for _ in range(4)]
        os.environ["EAGR_SPARSE_WRITE"] = "0"
        for ids, vals in batches:
            dense.write_batch(ids, vals)
        os.environ["EAGR_SPARSE_WRITE"] = "1"
        for ids, vals in batches:
            sparse.write_batch(ids, vals)
    finally:
        if old is None:
            os.environ.pop("EAGR_SPARSE_WRITE", None)
        else:
            os.environ["EAGR_SPARSE_WRITE"] = old
    _assert_states_equal(dense, sparse)


def test_sparse_parity_across_churn(monkeypatch):
    """Patch the plan, then write through both paths: the incrementally
    maintained index (exact per-writer overrides from the host graph walk)
    must keep sparse bit-identical, with the EAGR_PATCH_PARITY superset
    oracle active."""
    monkeypatch.setenv("EAGR_PATCH_PARITY", "1")

    def run(mode):
        monkeypatch.setenv("EAGR_SPARSE_WRITE", mode)
        g = rmat_graph(120, 700, seed=5)
        sess = EagrSession(g)
        h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
        rng = np.random.default_rng(11)
        writers = np.array(sorted(sess.writers))
        readers = np.array(sorted(sess.readers))
        reads = []
        for step in range(10):
            ids = rng.choice(writers, size=32).astype(np.int64)
            vals = rng.integers(0, 8, 32).astype(np.float32)
            sess.update(ids, vals)
            if step % 3 == 0:
                r = int(readers[step % len(readers)])
                nbrs = sess.neighborhood(r)
                if step % 2 and nbrs:
                    sess.delete_edge(min(nbrs), r)
                else:
                    w = int(writers[(step * 7) % len(writers)])
                    if w not in nbrs and w != r:
                        sess.add_edge(w, r)
                sess.flush()
            reads.append(sess.read(h, rng.choice(readers, 8, replace=False)))
        return reads, h.group.engine

    reads_d, eng_d = run("0")
    reads_s, eng_s = run("1")
    for a, b in zip(reads_d, reads_s):
        np.testing.assert_array_equal(a, b)
    _assert_states_equal(eng_d, eng_s)
    assert eng_s.plan.patches_applied > 0
    assert eng_s.plan.frontier is not None and eng_s.plan.frontier.overrides


def test_sparse_read_bit_identical_to_dense(basis, monkeypatch):
    """Mincut decisions so pull nodes exist: the demand-chunk + pull-block
    sparse read must match the dense read exactly."""
    eng = _engine(basis)  # mincut -> pull sweep is real
    _drive(eng, "0", monkeypatch)
    readers = np.flatnonzero(eng.plan.routes.reader_node >= 0)[:24]
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "0")
    dense = eng.read_batch(readers)
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "1")
    sparse = eng.read_batch(readers)
    np.testing.assert_array_equal(dense, sparse)
    assert eng.plan.reader_frontier is not None


def test_sparse_read_after_slot_patch(monkeypatch):
    """A slot patch lands a destination's new edge in any free slot of its
    row tile, so patched levels are no longer sorted by destination: the
    rebuilt reader index must still cover every block holding a slot of
    each demanded pull destination (pull readers of a min-cut group)."""
    from repro.graphs.generators import powerlaw_graph

    g = powerlaw_graph(1000, 10_000, sharing=0.5, seed=0)
    sess = EagrSession(g)
    h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 8)))
    rng = np.random.default_rng(1)
    writers = np.asarray(sess.writers)
    readers = np.asarray(sess.readers)
    for _ in range(3):
        ids = rng.choice(writers, 1024)
        sess.update(ids, rng.integers(0, 8, len(ids)).astype(np.float32))
    churned = []
    for v in rng.permutation(readers)[:32]:
        u = int(rng.choice(writers))
        if u != v and u not in sess.neighborhood(int(v)):
            sess.add_edge(u, int(v))
            churned.append(int(v))
    report = sess.flush()
    assert report.patched == 1 and report.relayout == 0
    eng = h.group.engine
    churned = np.asarray(churned)
    assert (eng.plan.decision[eng.plan.routes.reader_node[churned]]
            == D.PULL).any()
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "1")
    sparse = np.asarray(sess.read(h, churned)).reshape(-1)
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "0")
    dense = np.asarray(sess.read(h, churned)).reshape(-1)
    inputs = {int(r): sess.neighborhood(int(r)) for r in churned}
    want = [np.asarray(eng.oracle_read(int(r), inputs)).item()
            for r in churned]
    np.testing.assert_array_equal(dense, want)
    np.testing.assert_array_equal(sparse, want)


def test_block_spans_cover_unsorted_slots():
    """Per-destination spans bound every slot, in any slot order."""
    d_s = np.array([5, 7, 5, 9, 7, 5])
    b_s = np.array([0, 1, 1, 1, 3, 2])
    uniq, lo, hi = F._block_spans(d_s, b_s, nb=4)
    np.testing.assert_array_equal(uniq, [5, 7, 9])
    np.testing.assert_array_equal(lo, [0, 1, 1])
    np.testing.assert_array_equal(hi, [3, 4, 2])


# -------------------------------------------------- trace/transfer discipline
def test_sparse_k_bucketing_bounds_trace_count(basis, monkeypatch):
    """Varying batches whose frontiers land in one (batch bucket, per-level
    K-bucket tuple) pair must reuse one compiled sparse program."""
    from repro.core.engine import _write_body_sum_sparse

    assert [F.bucket_active(k) for k in (0, 1, 7, 8, 9, 64, 65)] == \
        [0, 8, 8, 8, 16, 64, 128]
    eng = _engine(basis)
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "1")
    writers = np.flatnonzero(eng.plan.routes.writer_row >= 0)
    rng = np.random.default_rng(3)

    def ktuple(ids):  # the trace-cache shape key of a batch's frontier
        rows, mask = eng.plan.routes.writer_rows(ids)
        act = eng.frontier_active(rows, mask)
        assert act is not None
        return tuple(a.shape[0] for a in act)

    warm = rng.choice(writers, 32).astype(np.int64)
    eng.write_batch(warm, np.ones(32, np.float32))  # warm (32, Ks) once
    c0 = _write_body_sum_sparse._cache_size()
    ks = {ktuple(warm)}
    for n in (17, 21, 31, 32):
        ids = rng.choice(writers, n).astype(np.int64)
        ks.add(ktuple(ids))
        eng.write_batch(ids, np.ones(n, np.float32))
    if len(ks) == 1:  # same K-tuple bucket throughout -> zero new traces
        assert _write_body_sum_sparse._cache_size() == c0
    assert _write_body_sum_sparse._cache_size() <= c0 + (len(ks) - 1)


def test_sparse_steady_state_no_implicit_transfers(basis, monkeypatch):
    """Sparse dispatch adds exactly one more explicit device_put (the active
    array) — after warmup the step must run with zero implicit h2d."""
    eng = _engine(basis)
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "1")
    writers = np.flatnonzero(eng.plan.routes.writer_row >= 0)
    rng = np.random.default_rng(5)
    batches = [(rng.choice(writers, 32).astype(np.int64),
                rng.integers(0, 8, 32).astype(np.float32))
               for _ in range(8)]
    for ids, vals in batches[:4]:
        eng.write_batch(ids, vals)
    with jax.transfer_guard_host_to_device("disallow"):
        for ids, vals in batches[4:]:
            eng.write_batch(ids, vals)
    assert sum(1 for k in eng.frontier_log if k >= 0) == 8


# --------------------------------------------------------------- bf16 flag
def test_segment_agg_bf16_parity_within_tolerance(basis, monkeypatch):
    ref = _engine(basis, all_push=True)
    assert ref.plan.meta.bf16 is False
    monkeypatch.setenv("EAGR_SEGAGG_BF16", "1")
    lo = _engine(basis, all_push=True)
    assert lo.plan.meta.bf16 is True
    monkeypatch.delenv("EAGR_SEGAGG_BF16")
    writers = np.flatnonzero(ref.plan.routes.writer_row >= 0)
    rng = np.random.default_rng(7)
    for _ in range(5):
        ids = rng.choice(writers, 16).astype(np.int64)
        vals = (rng.random(16) * 8).astype(np.float32)
        ref.write_batch(ids, vals)
        lo.write_batch(ids, vals)
    pr = np.asarray(jax.device_get(ref.state.pao))
    pl = np.asarray(jax.device_get(lo.state.pao))
    assert not np.array_equal(pr, pl) or np.abs(pr).max() == 0.0
    np.testing.assert_allclose(pl, pr, rtol=0.05, atol=0.5)


def test_bf16_sparse_matches_bf16_dense(basis, monkeypatch):
    """bf16 rounding must commute with the sparse gather: sparse bf16 ==
    dense bf16 bit-for-bit."""
    monkeypatch.setenv("EAGR_SEGAGG_BF16", "1")
    dense, sparse = _engine(basis), _engine(basis)
    monkeypatch.delenv("EAGR_SEGAGG_BF16")
    _drive(dense, "0", monkeypatch)
    _drive(sparse, "1", monkeypatch)
    _assert_states_equal(dense, sparse)


# ------------------------------------------------------------- index units
def test_frontier_blocks_cover_closures(basis):
    """Both index flavors must be supersets of their flavor-matched closure
    walk (the invariant `verify` enforces after churn, checked here at
    build), and the source-exact flavor must never exceed the span flavor."""
    from repro.core.plan_patch import PlanHost

    eng = _engine(basis, all_push=True)
    plan = eng.plan
    if plan.host is None:
        plan.host = PlanHost.from_plan(plan, eng.overlay)
    fi = F.FrontierIndex.build(plan)              # destination spans
    fi.verify(plan, plan.host)  # raises on any under-coverage
    fx = F.FrontierIndex.build(plan, exact=True)  # source-exact (sum)
    fx.verify(plan, plan.host)
    for node, row in fx.row_of_node.items():
        spans = fi.blocks_of(fi.row_of_node[node])
        for l, blks in fx.blocks_of(row).items():
            assert blks <= spans.get(l, set())


def test_frontier_density_fallback_and_unknown_rows(basis):
    eng = _engine(basis, all_push=True)
    fi = F.FrontierIndex.build(eng.plan)
    rows = np.arange(fi.n_base_rows)
    assert fi.expand(rows, density=0.0) is None       # too dense -> fallback
    act = fi.expand(rows[:2], density=None)           # forced sparse
    assert act is not None and len(act) == eng.plan.meta.n_levels
    nb = fi.n_blocks
    # within each level: int32, ascending actives, pads (== nb) at the end;
    # an empty level packs to shape (0,)
    for lvl in act:
        assert lvl.dtype == np.int32
        assert lvl.size == 0 or lvl.max() <= nb
        real = lvl[lvl < nb]
        assert (np.diff(real) > 0).all()
        assert (lvl[len(real):] == nb).all()
    assert fi.expand(np.array([fi.n_base_rows + 99]), density=None) is None


def test_stacked_sparse_bit_identical_to_dense(monkeypatch):
    """The stacked shard_map write must dispatch the same sparse bodies per
    shard (per-level widths shared across the stack) and stay bit-identical
    to dense."""
    from repro.distributed.eagr_shard import partition_overlay
    from repro.distributed.stacked import StackedShardedEngine

    def run(mode):
        monkeypatch.setenv("EAGR_SPARSE_WRITE", mode)
        g = rmat_graph(200, 1200, seed=9)
        bp = build_bipartite(g)
        ov, _ = construct_vnm(bp, variant="vnm_a", max_iterations=3, seed=0)
        rng0 = np.random.default_rng(9)
        wf, rf = rng0.random(g.n_nodes) + 0.1, rng0.random(g.n_nodes) + 0.1
        dec, _ = D.decide_mincut(ov, wf, rf, D.cost_model_for("sum"))
        sharded = partition_overlay(ov, dec, n_shards=4, seed=0)
        eng = StackedShardedEngine(sharded, make_aggregate("sum"),
                                   WindowSpec("tuple", 4))
        rng = np.random.default_rng(10)
        for _ in range(5):
            ids = rng.choice(bp.writers, 24)
            eng.write_batch(ids, rng.normal(size=24).astype(np.float32),
                            batch_size=24)
        return [np.asarray(jax.device_get(x)) for x in
                jax.tree_util.tree_leaves(
                    (eng.state.windows.values, eng.state.windows.stamps,
                     eng.state.pao, eng.state.now))]

    for x, y in zip(run("0"), run("1")):
        np.testing.assert_array_equal(x, y)


def test_frontier_auto_mode_gates(basis, monkeypatch):
    """auto: a batch touching most writers skips expansion entirely (dense);
    EAGR_SPARSE_WRITE=0 forces dense even for tiny batches."""
    eng = _engine(basis, all_push=True)
    writers = np.flatnonzero(eng.plan.routes.writer_row >= 0)
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "auto")
    monkeypatch.setenv("EAGR_SPARSE_ROWFRAC", "0.05")
    big = np.resize(writers, max(64, len(writers)))
    rows, mask = eng.plan.routes.writer_rows(big)
    assert eng.frontier_active(rows, mask) is None
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "0")
    rows, mask = eng.plan.routes.writer_rows(writers[:4])
    assert eng.frontier_active(rows, mask) is None
