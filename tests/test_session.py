"""``EagrSession`` public-API parity: the declarative front door must be
BIT-identical to the hand-assembled low-level tier it wraps, across all three
deployment shapes (single engine, sharded-stacked, dynamic churn), and it
must inherit the substrate's trace/transfer discipline — session-driven
in-capacity churn stays on one ``apply_patch_step`` trace with zero implicit
host->device transfers (the harness from ``tests/test_device_patch.py``).

Plus the register-time validation surface: ``make_aggregate`` names the valid
aggregate set, ``Query.resolve`` rejects incompatible window/aggregate specs
before anything compiles, and engine groups are shared exactly when specs
are compatible.
"""
import jax
import numpy as np
import pytest

from conftest import make_freqs
from repro.core import dataflow as D
from repro.core.aggregates import make_aggregate
from repro.core.bipartite import build_bipartite
from repro.core.dynamic import DynamicOverlay
from repro.core.engine import EagrEngine
from repro.core.plan_patch import apply_patch_step
from repro.core.vnm import construct_vnm
from repro.core.window import WindowSpec
from repro.graphs.generators import rmat_graph
from repro.session import EagrSession, Query, bucket_batch


def _hand_basis(g, *, max_iterations=3):
    """The session's internal construction, hand-assembled: adopt the
    constructed overlay into a ``DynamicOverlay`` and compile over the
    unpruned export (the §3.3-patchable id space)."""
    bp = build_bipartite(g)
    ov, _ = construct_vnm(bp, variant="vnm_a", max_iterations=max_iterations,
                          seed=0)
    dyn = DynamicOverlay.from_overlay(ov, bp.reader_input_sets())
    return bp, dyn, dyn.to_overlay(prune=False)


def _ones_decisions(basis, window=4, agg="sum"):
    n = max((o for o in basis.origin if o >= 0), default=0) + 1
    wf = np.ones(n)
    dec, _ = D.decide_mincut(basis, wf, wf.copy(),
                             D.cost_model_for(agg, window=window),
                             window=window)
    return dec


# ------------------------------------------------------------ single engine
def test_single_bit_identical_to_hand_assembled_engine():
    from repro.core.engine import _read_body, _write_body_sum

    g = rmat_graph(150, 900, seed=3)
    spec = WindowSpec("tuple", 4)

    bp, _, basis = _hand_basis(g)
    dec = _ones_decisions(basis)
    hand = EagrEngine(basis, dec, make_aggregate("sum"), spec, headroom=2.0)

    sess = EagrSession(g)
    h = sess.register(Query(agg="sum", window=spec))

    rng = np.random.default_rng(0)
    readers = np.asarray(sess.readers)
    caches = None
    for i in range(4):
        ids = rng.choice(bp.writers, 33)
        vals = rng.normal(size=33).astype(np.float32)
        hand.write_batch(ids, vals, batch_size=bucket_batch(33))
        sess.update(ids, vals)
        q = rng.choice(readers, 9)
        want = hand.read_batch(q, batch_size=bucket_batch(9))
        got = sess.read(h, q)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        if i == 0:
            # session and hand engine compile to the SAME plan shapes: once
            # the first round traced both bodies, neither side ever adds a
            # cache entry — the facade shares the hand path's programs
            caches = (_write_body_sum._cache_size(), _read_body._cache_size())
    assert caches == (_write_body_sum._cache_size(),
                      _read_body._cache_size()), \
        "session execution must reuse the hand-assembled compiled programs"


def test_single_extremal_time_window_matches_hand_assembled():
    g = rmat_graph(100, 550, seed=5)
    spec = WindowSpec("time", 6, capacity=8)

    bp, _, basis = _hand_basis(g)
    dec = _ones_decisions(basis, window=8, agg="max")
    hand = EagrEngine(basis, dec, make_aggregate("max"), spec, headroom=2.0)

    sess = EagrSession(g)
    h = sess.register(Query(agg="max", window=spec))
    rng = np.random.default_rng(1)
    readers = np.asarray(sess.readers)
    for _ in range(5):
        ids = rng.choice(bp.writers, 17)
        vals = rng.normal(size=17).astype(np.float32)
        hand.write_batch(ids, vals, batch_size=bucket_batch(17))
        sess.update(ids, vals)
        q = rng.choice(readers, 5)
        np.testing.assert_array_equal(
            np.asarray(sess.read(h, q)),
            np.asarray(hand.read_batch(q, batch_size=bucket_batch(5))))


# ---------------------------------------------------------- sharded stacked
def test_sharded_bit_identical_to_hand_assembled_stack():
    from repro.distributed.eagr_shard import partition_overlay
    from repro.distributed.stacked import StackedShardedEngine

    g = rmat_graph(150, 900, seed=3)
    spec = WindowSpec("tuple", 4)

    bp, _, basis = _hand_basis(g)
    dec = _ones_decisions(basis)
    sharded = partition_overlay(basis, dec, n_shards=4, seed=0, headroom=2.0)
    hand = StackedShardedEngine(sharded, make_aggregate("sum"), spec,
                                base_capacity=g.n_nodes)

    sess = EagrSession(g, shards=4)
    h = sess.register(Query(agg="sum", window=spec))

    rng = np.random.default_rng(2)
    readers = np.asarray(sess.readers)
    for _ in range(3):
        ids = rng.choice(bp.writers, 48)
        vals = rng.normal(size=48).astype(np.float32)
        hand.write_batch(ids, vals, batch_size=bucket_batch(48))
        sess.update(ids, vals)
        q = rng.choice(readers, 12)
        np.testing.assert_array_equal(
            np.asarray(sess.read(h, q)),
            np.asarray(hand.read_batch(q, batch_size=bucket_batch(12))))


# ------------------------------------------------------------ dynamic churn
def _churn(step_rng, mutate_both, readers, n_base):
    op = int(step_rng.integers(0, 4))
    if op == 0:
        mutate_both("add_edge", int(step_rng.integers(0, n_base)),
                    int(step_rng.choice(readers)))
    elif op == 1:
        mutate_both("delete_probe", int(step_rng.choice(readers)))
    elif op == 2:
        nid = int(step_rng.integers(1000, 2000))
        mutate_both("add_node", nid,
                    {int(x) for x in step_rng.integers(0, n_base, 3)},
                    {int(step_rng.choice(readers))})
    else:
        mutate_both("delete_new", None)


def test_dynamic_churn_bit_identical_to_hand_assembled():
    """Session-driven churn (mutate -> flush -> read) equals the hand path
    (DynamicOverlay journal -> drain_delta -> EagrEngine.apply_delta) bit for
    bit after every burst."""
    g = rmat_graph(120, 700, seed=3)
    spec = WindowSpec("tuple", 4)

    bp, hand_dyn, basis = _hand_basis(g)
    dec = _ones_decisions(basis)
    hand = EagrEngine(basis, dec, make_aggregate("sum"), spec, headroom=2.0)

    sess = EagrSession(g)
    h = sess.register(Query(agg="sum", window=spec))
    rng = np.random.default_rng(7)
    readers = list(hand_dyn.reader_inputs)

    def mutate_both(kind, *args):
        if kind == "add_edge":
            u, v = args
            hand_dyn.add_edge(u, v)
            sess.add_edge(u, v)
        elif kind == "delete_probe":
            (r,) = args
            if hand_dyn.reader_inputs.get(r):
                u = int(next(iter(hand_dyn.reader_inputs[r])))
                hand_dyn.delete_edge(u, r)
                sess.delete_edge(u, r)
        elif kind == "add_node":
            u, ins, outs = args
            hand_dyn.add_node(u, ins, outs)
            sess.add_node(u, ins, outs)
        else:
            victims = [k for k in list(hand_dyn.reader_inputs) if k >= 1000]
            if victims:
                u = int(rng.choice(victims))
                hand_dyn.delete_node(u)
                sess.delete_node(u)

    for _ in range(10):
        ids = rng.choice(bp.writers, 16)
        vals = rng.normal(size=16).astype(np.float32)
        hand.write_batch(ids, vals, batch_size=bucket_batch(16))
        sess.update(ids, vals)
        for _ in range(3):
            _churn(rng, mutate_both, readers, 120)
        hand.apply_delta(hand_dyn.drain_delta())
        sess.flush()
        pool = [r for r in hand_dyn.reader_inputs
                if hand_dyn.reader_inputs[r]
                and r in hand.plan.reader_node_of_base]
        q = rng.choice(pool, 6)
        np.testing.assert_array_equal(
            np.asarray(sess.read(h, q)),
            np.asarray(hand.read_batch(q, batch_size=bucket_batch(6))))


def test_sharded_churn_bit_identical_to_hand_assembled():
    from repro.distributed.eagr_shard import ShardedDynamic, partition_overlay
    from repro.distributed.stacked import StackedShardedEngine

    g = rmat_graph(150, 900, seed=3)
    spec = WindowSpec("tuple", 4)
    bp, _, basis = _hand_basis(g)
    dec = _ones_decisions(basis)
    sharded = partition_overlay(basis, dec, n_shards=2, seed=0, headroom=2.0)
    hand = StackedShardedEngine(sharded, make_aggregate("sum"), spec,
                                base_capacity=g.n_nodes)
    hand_sd = ShardedDynamic(sharded, hand)

    sess = EagrSession(g, shards=2)
    h = sess.register(Query(agg="sum", window=spec))
    rng = np.random.default_rng(4)
    readers = np.asarray(sess.readers)

    for _ in range(6):
        ids = rng.choice(bp.writers, 32)
        vals = rng.normal(size=32).astype(np.float32)
        hand.write_batch(ids, vals, batch_size=bucket_batch(32))
        sess.update(ids, vals)
        u, v = int(rng.integers(0, 150)), int(rng.choice(readers))
        hand_sd.add_edge(u, v)
        sess.add_edge(u, v)
        hand_sd.apply()
        sess.flush()
        q = rng.choice(readers, 8)
        np.testing.assert_array_equal(
            np.asarray(sess.read(h, q)),
            np.asarray(hand.read_batch(q, batch_size=bucket_batch(8))))


def test_session_churn_zero_uploads_and_one_patch_trace():
    """The PR-4 guarantees survive the facade: once the patch machinery is
    warm, session-driven in-capacity churn performs no implicit host->device
    transfer inside flush() and stays on one cached apply_patch_step
    executable (transfer-guard harness from tests/test_device_patch.py)."""
    g = rmat_graph(120, 700, seed=3)
    sess = EagrSession(g)
    h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    rng = np.random.default_rng(5)
    readers = np.asarray(sess.readers)
    sess.update(rng.choice(sess.writers, 16),
                rng.normal(size=16).astype(np.float32))
    # warm every patch-path program once: slot claim, retire, node add with a
    # fresh writer row, node retire (window-row reset)
    sess.add_edge(int(sess.writers[0]), int(readers[0]))
    sess.flush()
    sess.delete_edge(int(sess.writers[0]), int(readers[0]))
    sess.flush()
    sess.add_node(1900, in_neighbors={int(sess.writers[0])},
                  out_readers={int(readers[0])})
    sess.flush()
    sess.delete_node(1900)
    sess.flush()

    c0 = apply_patch_step._cache_size()
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(10):
            sess.add_edge(int(rng.integers(0, 120)), int(rng.choice(readers)))
            for res in sess.flush():
                assert res is None or not res.recompiled, \
                    "uniform churn exceeded headroom"
    assert apply_patch_step._cache_size() == c0, \
        "session churn must stay on the cached apply_patch_step traces"
    sess.update(rng.choice(sess.writers, 16),
                rng.normal(size=16).astype(np.float32))
    out = sess.read(h, rng.choice(readers, 6))
    assert np.isfinite(np.asarray(out)).all()


# ------------------------------------------------------- grouping + sharing
def test_compatible_queries_share_one_engine_group():
    g = rmat_graph(100, 550, seed=5)
    sess = EagrSession(g)
    a = sess.register(Query(agg="count", window=WindowSpec("tuple", 4)))
    b = sess.register(Query(agg="count", window=WindowSpec("tuple", 4),
                            readers=sess.readers[:3]))
    c = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    d = sess.register(Query(agg="count", window=WindowSpec("tuple", 8)))
    assert a.group is b.group and a.group.engine is b.group.engine
    assert c.group is not a.group and d.group is not a.group
    assert sess.n_engine_groups == 3
    # scoped handle rejects out-of-scope reads; unscoped sibling answers them
    outside = [r for r in sess.readers if r not in b.readers][:2]
    with pytest.raises(ValueError, match="readers scope"):
        sess.read(b, outside)
    sess.update(sess.writers[:8], np.ones(8, np.float32))
    np.testing.assert_array_equal(
        np.asarray(sess.read(b, sess.readers[:3])),
        np.asarray(sess.read(a, sess.readers[:3])))
    sess.unregister(b)
    assert sess.n_engine_groups == 3  # a still holds the group
    sess.unregister(a)
    assert sess.n_engine_groups == 2
    with pytest.raises(ValueError, match="unknown query handle"):
        sess.read(a, sess.readers[:1])


def test_adaptation_keeps_answers_exact():
    """adapt_every re-decides the frontier under observed traffic; answers
    must keep matching the window-level oracle across re-adoptions."""
    g = rmat_graph(150, 900, seed=3)
    sess = EagrSession(g, adapt_every=5)
    h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    rng = np.random.default_rng(1)
    before = h.group.engine.plan.decision.copy()
    for _ in range(10):
        sess.update(rng.choice(sess.writers, 32),
                    rng.normal(size=32).astype(np.float32))
        sess.read(h, rng.choice(sess.readers, 16))
    after = h.group.engine.plan.decision
    n = min(len(before), len(after))
    assert (before[:n] != after[:n]).any(), "traffic skew produced no flip"
    sample = sess.readers[:5]
    out = sess.read(h, sample)
    for i, b in enumerate(sample):
        want = h.group.engine.oracle_read(int(b), sess._master.reader_inputs)
        np.testing.assert_allclose(np.ravel(out[i]), np.ravel(want),
                                   rtol=1e-4, atol=1e-4)


def test_continuous_query_pins_all_push():
    g = rmat_graph(100, 550, seed=5)
    wf, rf = make_freqs(100, seed=2)
    sess = EagrSession(g, write_freq=wf, read_freq=rf)
    cont = sess.register(Query(agg="count", continuous=True))
    opt = sess.register(Query(agg="count"))
    assert (cont.group.engine.plan.decision == D.PUSH).all()
    assert cont.group is not opt.group  # freshness class splits the group
    sess.update(sess.writers[:16], np.ones(16, np.float32))
    np.testing.assert_array_equal(
        np.asarray(sess.read(cont, sess.readers[:6])),
        np.asarray(sess.read(opt, sess.readers[:6])))


# ----------------------------------------------------------- validation API
def test_make_aggregate_names_valid_set():
    with pytest.raises(ValueError, match=r"unknown aggregate 'bogus'.*avg"):
        make_aggregate("bogus")
    with pytest.raises(ValueError, match=r"must be a string or Aggregate"):
        make_aggregate(3)
    with pytest.raises(ValueError, match=r"bad arguments for aggregate"):
        make_aggregate("sum", k=2)
    agg = make_aggregate("count")
    assert make_aggregate(agg) is agg
    with pytest.raises(ValueError, match="already constructed"):
        make_aggregate(agg, k=2)
    assert make_aggregate("TOP-K", k=2, domain=8).name == "topk"


@pytest.mark.parametrize("query,match", [
    (Query(agg="bogus"), r"unknown aggregate"),
    (Query(agg="count", window=WindowSpec("sliding", 4)), r"window kind"),
    (Query(agg="count", window=WindowSpec("time", 10)), r"ring capacity"),
    (Query(agg="count", window=WindowSpec("tuple", 0)), r"size must be >= 1"),
    (Query(agg="count", window=WindowSpec("tuple", 8, capacity=4)),
     r"cannot fit"),
    (Query(agg="topk", window=WindowSpec("tuple", 4, value_dim=3)),
     r"value_dim"),
    (Query(agg="sum", agg_kwargs={"value_dim": 3}), r"value_dim"),
    (Query(agg="count", readers=[]), r"readers is empty"),
])
def test_query_validation_rejects_at_register_time(query, match):
    with pytest.raises(ValueError, match=match):
        query.resolve()
    sess = EagrSession(build_bipartite(rmat_graph(40, 160, seed=1)))
    with pytest.raises(ValueError, match=match):
        sess.register(query)


def test_session_guards_write_stream_shape():
    g = rmat_graph(60, 260, seed=1)
    sess = EagrSession(g)
    h = sess.register(Query(agg="count"))
    with pytest.raises(ValueError, match="value_dim"):
        sess.register(Query(
            agg="sum", agg_kwargs={"value_dim": 2},
            window=WindowSpec("tuple", 4, value_dim=2)))
    with pytest.raises(ValueError, match="shape"):
        sess.update(sess.writers[:4], np.ones((4, 2), np.float32))
    with pytest.raises(ValueError, match="no queries registered"):
        EagrSession(g).update([0], np.ones(1, np.float32))
    # an emptied session stops constraining the write-value shape
    sess.unregister(h)
    h2 = sess.register(Query(agg="sum", agg_kwargs={"value_dim": 2},
                             window=WindowSpec("tuple", 4, value_dim=2)))
    sess.update(sess.writers[:4], np.ones((4, 2), np.float32))
    assert np.asarray(sess.read(h2, sess.readers[:2])).shape == (2, 2)


def test_custom_aggregate_declares_write_arity():
    """A user-defined vector aggregate registers through the front door by
    declaring Aggregate(value_dim=...) — the session is no narrower than the
    engine tier it fronts."""
    import jax.numpy as jnp

    from repro.core.aggregates import Aggregate

    l2 = Aggregate(name="sumsq", pao_dim=2, combine="sum",
                   lift=lambda v: (v.reshape(v.shape[0], -1) ** 2
                                   ).astype(jnp.float32),
                   finalize=lambda p: p, supports_subtraction=True,
                   value_dim=2)
    sess = EagrSession(rmat_graph(60, 260, seed=1))
    h = sess.register(Query(agg=l2, window=WindowSpec("tuple", 4,
                                                      value_dim=2)))
    sess.update(sess.writers[:8], np.full((8, 2), 2.0, np.float32))
    out = np.asarray(sess.read(h, sess.readers[:3]))
    assert out.shape == (3, 2) and (out >= 0).all()
    with pytest.raises(ValueError, match="value_dim"):
        Query(agg=l2).resolve()  # default scalar window can't feed it
