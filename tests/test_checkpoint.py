"""Durable sessions: checkpoint/restore of live EAGr state.

The contract under test (PR 9):

  * ``EagrSession.save`` -> ``EagrSession.restore`` is BIT-identical — the
    restored session answers every read exactly as the saved one would, for
    scalar and vector aggregates, tuple and time windows, single-engine and
    stacked-sharded deployments — without re-running construction or plan
    compilation;
  * restore may RESHARD (N -> M shards, or to a single engine): window rings
    redistribute by base writer id, plans recompile over the saved master
    overlay, answers stay exact;
  * a process killed mid-save (before or after the manifest lands in the
    temp directory) never corrupts the latest committed checkpoint;
  * ``SessionRecoveryDriver`` replays the event stream deterministically
    from the recorded sequence number — a crashed-and-recovered run is
    bit-identical to an uninterrupted one;
  * the lifecycle satellites: ``stats()`` / ``SessionStats``, typed
    ``FlushReport`` / ``AdaptReport`` (back-compatible with list/int use),
    deprecated stat aliases.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.window import WindowSpec
from repro.distributed.checkpoint import CheckpointManager
from repro.distributed.fault import SessionRecoveryDriver
from repro.graphs.generators import rmat_graph
from repro.session import (
    AdaptReport,
    EagrSession,
    FlushReport,
    Query,
    SessionStats,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _graph():
    return rmat_graph(90, 500, seed=7)


def _drive(sess, handle, *, rounds=5, n=20, seed=3, vd=1, integral=False):
    """Deterministic traffic; returns a read probe + its pre-save answer."""
    rng = np.random.default_rng(seed)
    W = np.asarray(sess.writers)
    for _ in range(rounds):
        ids = rng.choice(W, n)
        if integral:
            vals = rng.integers(-4, 5, size=(n, vd) if vd > 1 else n)
            vals = vals.astype(np.float32)
        elif vd > 1:
            vals = rng.normal(size=(n, vd)).astype(np.float32)
        else:
            vals = np.abs(rng.normal(size=n)).astype(np.float32)
        sess.update(ids, vals)
    q = rng.choice(np.asarray(sess.readers), 16)
    return q, np.asarray(sess.read(handle, q))


# ------------------------------------------------------------ bit-identical
@pytest.mark.parametrize("qkw,vd", [
    (dict(agg="sum", window=WindowSpec("tuple", 4)), 1),
    (dict(agg="max", window=WindowSpec("time", 3.0, capacity=8)), 1),
    (dict(agg="topk", agg_kwargs={"k": 3, "domain": 32},
          window=WindowSpec("tuple", 6, capacity=8)), 1),
    (dict(agg="sum", agg_kwargs={"value_dim": 3},
          window=WindowSpec("tuple", 4, value_dim=3)), 3),
    (dict(agg="avg", window=WindowSpec("tuple", 5, capacity=8),
          continuous=True), 1),
], ids=["sum-tuple", "max-time", "topk", "sum-vec3", "avg-continuous"])
def test_roundtrip_bit_identical_single(tmp_path, qkw, vd):
    sess = EagrSession(_graph())
    h = sess.register(Query(**qkw))
    q, want = _drive(sess, h, vd=vd)
    step = sess.save(str(tmp_path), blocking=True)
    assert step == sess._seq

    r = EagrSession.restore(str(tmp_path))
    (h2,) = r.queries
    assert r._seq == sess._seq
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)), want)

    # continued identical traffic stays in lockstep (exercises window
    # advance, expiry deadlines for time windows, PAO reuse)
    rng = np.random.default_rng(11)
    W = np.asarray(sess.writers)
    for _ in range(3):
        ids = rng.choice(W, 10)
        vals = rng.normal(size=(10, vd)).astype(np.float32) if vd > 1 \
            else np.abs(rng.normal(size=10)).astype(np.float32)
        sess.update(ids, vals)
        r.update(ids, vals)
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)),
                                  np.asarray(sess.read(h, q)))


def test_roundtrip_sharded_with_churn(tmp_path):
    sess = EagrSession(_graph(), shards=4)
    h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    W, R = np.asarray(sess.writers), np.asarray(sess.readers)
    # structural churn BEFORE save: the checkpoint must carry the patched
    # per-shard overlays, not the construction-time partition
    sess.delete_edge(int(W[0]), int(R[3]))
    sess.add_edge(int(W[1]), int(R[3]))
    report = sess.flush()
    assert report.patched + report.recompiled >= 1
    q, want = _drive(sess, h)
    sess.save(str(tmp_path), blocking=True)

    r = EagrSession.restore(str(tmp_path))
    (h2,) = r.queries
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)), want)

    # post-restore churn: the lazily rebuilt journals must patch the
    # restored plans exactly as the original session's journals do
    new = int(max(W.max(), R.max())) + 1
    for s in (sess, r):
        s.add_node(new, out_readers=[int(R[2]), int(R[5])])
    rep_r = r.flush()
    assert rep_r.journal_nodes >= 1
    for s, hh in ((sess, h), (r, h2)):
        s.update(np.full(6, new, np.int64), np.ones(6, np.float32))
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)),
                                  np.asarray(sess.read(h, q)))


def test_restore_skips_construction_and_compile(tmp_path, monkeypatch):
    sess = EagrSession(_graph())
    h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    q, want = _drive(sess, h)
    sess.save(str(tmp_path), blocking=True)

    # a same-shape restore must never re-run VNM construction or plan
    # compilation — that is the whole recovery-time claim
    import repro.core.engine as engine_mod
    import repro.session as session_mod

    def boom(*a, **k):
        raise AssertionError("restore re-ran the cold path")

    monkeypatch.setattr(session_mod, "construct_vnm", boom)
    monkeypatch.setattr(engine_mod, "compile_plan", boom)
    r = EagrSession.restore(str(tmp_path))
    (h2,) = r.queries
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)), want)


# ---------------------------------------------------------------- resharding
@pytest.mark.parametrize("old,new", [(4, 2), (4, 8), (4, 0), (0, 2)],
                         ids=["4to2", "4to8", "4tosingle", "singleto2"])
def test_restore_with_resharding(tmp_path, old, new):
    sess = EagrSession(_graph(), shards=old or None)
    h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    # integral values: resharding may legitimately change reduction order,
    # integer-valued float32 keeps every order exact
    q, want = _drive(sess, h, integral=True)
    sess.save(str(tmp_path), blocking=True)

    r = EagrSession.restore(str(tmp_path), shards=new)
    assert r.n_shards == new
    (h2,) = r.queries
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)), want)

    # the resharded session keeps serving: writes, reads, churn
    rng = np.random.default_rng(5)
    W = np.asarray(sess.writers)
    for _ in range(2):
        ids = rng.choice(W, 12)
        vals = rng.integers(0, 5, size=12).astype(np.float32)
        sess.update(ids, vals)
        r.update(ids, vals)
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)),
                                  np.asarray(sess.read(h, q)))


def test_reshard_time_window_expiry(tmp_path):
    """Extremal aggregate + time window across a reshard: the rebuilt expiry
    deadlines must still force re-evaluation when entries age out."""
    sess = EagrSession(_graph(), shards=2)
    h = sess.register(Query(agg="max",
                            window=WindowSpec("time", 3.0, capacity=8)))
    q, _ = _drive(sess, h, integral=True)
    sess.save(str(tmp_path), blocking=True)
    r = EagrSession.restore(str(tmp_path), shards=0)
    (h2,) = r.queries
    # advance the clock past the window with writes to a single writer: old
    # maxima must expire identically on both sides
    w = int(np.asarray(sess.writers)[0])
    for _ in range(6):
        sess.update(np.asarray([w]), np.zeros(1, np.float32))
        r.update(np.asarray([w]), np.zeros(1, np.float32))
        np.testing.assert_array_equal(np.asarray(r.read(h2, q)),
                                      np.asarray(sess.read(h, q)))


# ------------------------------------------------------------ property-based
@given(st.sampled_from(["sum", "max", "count"]),
       st.sampled_from([0, 2]),
       st.integers(0, 3))
@settings(max_examples=8, deadline=None)
def test_roundtrip_parity_property(tmp_path_factory, agg, shards, seed):
    tmp = tmp_path_factory.mktemp("ckpt")
    sess = EagrSession(rmat_graph(70, 360, seed=9), shards=shards or None)
    spec = WindowSpec("time", 2.0, capacity=6) if agg == "max" \
        else WindowSpec("tuple", 3)
    h = sess.register(Query(agg=agg, window=spec))
    q, want = _drive(sess, h, rounds=4, n=12, seed=seed, integral=True)
    sess.save(str(tmp), blocking=True)
    r = EagrSession.restore(str(tmp))
    (h2,) = r.queries
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)), want)


# ------------------------------------------------------------- crash safety
_CRASH_CHILD = """
import os, sys
import numpy as np
from repro.graphs.generators import rmat_graph
from repro.session import EagrSession, Query
from repro.core.window import WindowSpec

g = rmat_graph(90, 500, seed=7)
sess = EagrSession(g)
sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
rng = np.random.default_rng(0)
W = np.asarray(sess.writers)
sess.update(rng.choice(W, 20), np.ones(20, np.float32))
sess.save(sys.argv[1], blocking=True)        # step 1 commits
sess.update(rng.choice(W, 20), np.ones(20, np.float32))
os.environ["EAGR_CKPT_CRASH"] = sys.argv[2]  # arm the fault
sess.save(sys.argv[1], blocking=True)        # step 2 dies mid-write
raise SystemExit("unreachable: crash hook did not fire")
"""


@pytest.mark.parametrize("stage", ["arrays", "manifest"])
def test_kill_mid_save_preserves_committed_manifest(tmp_path, stage):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("EAGR_CKPT_CRASH", None)
    p = subprocess.run([sys.executable, "-c", _CRASH_CHILD,
                        str(tmp_path), stage],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 17, p.stderr[-2000:]
    # the aborted step must not be listed as restorable...
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.all_steps() == [1]
    # ...and the previous committed checkpoint restores cleanly
    r = EagrSession.restore(str(tmp_path))
    assert r._seq == 1
    (h,) = r.queries
    q = np.asarray(r.readers)[:8]
    assert np.isfinite(np.asarray(r.read(h, q))).all()


def test_recovery_driver_replay_determinism(tmp_path):
    g = _graph()

    def make_session():
        s = EagrSession(g)
        s.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
        return s

    W = np.asarray(make_session().writers)

    def make_batch(seq):
        rng = np.random.default_rng(1000 + seq)
        return rng.choice(W, 16), rng.normal(size=16).astype(np.float32)

    d_fault, d_clean = str(tmp_path / "a"), str(tmp_path / "b")
    drv = SessionRecoveryDriver(make_session, make_batch, d_fault,
                                ckpt_every=8)
    s_fault = drv.run(30, fail_at={13, 27})
    assert drv.report.restarts == 2
    assert s_fault._seq == 30

    s_clean = SessionRecoveryDriver(make_session, make_batch, d_clean,
                                    ckpt_every=8).run(30)
    (hf,), (hc,) = s_fault.queries, s_clean.queries
    q = np.asarray(s_clean.readers)[:20]
    np.testing.assert_array_equal(np.asarray(s_fault.read(hf, q)),
                                  np.asarray(s_clean.read(hc, q)))


def test_auto_checkpoint_and_gc(tmp_path):
    sess = EagrSession(_graph(), ckpt_dir=str(tmp_path), ckpt_every=2,
                       ckpt_keep=2)
    sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    W = np.asarray(sess.writers)
    for _ in range(7):
        sess.update(W[:5], np.ones(5, np.float32))
    sess.wait_for_checkpoint()
    steps = CheckpointManager(str(tmp_path)).all_steps()
    assert steps[-1] == 6          # every 2nd update batch checkpointed
    assert len(steps) <= 2         # keep-count enforced by gc
    assert sess.stats().last_checkpoint_step == 6


def test_save_quiesces_pending_churn(tmp_path):
    sess = EagrSession(_graph())
    h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    q, _ = _drive(sess, h)
    W, R = np.asarray(sess.writers), np.asarray(sess.readers)
    sess.add_edge(int(W[0]), int(R[1]))
    assert sess._pending
    sess.save(str(tmp_path), blocking=True)    # must flush first
    assert not sess._pending
    r = EagrSession.restore(str(tmp_path))
    (h2,) = r.queries
    np.testing.assert_array_equal(np.asarray(r.read(h2, q)),
                                  np.asarray(sess.read(h, q)))


# ------------------------------------------------------ lifecycle satellites
def test_stats_and_typed_reports(tmp_path):
    sess = EagrSession(_graph(), ingest_depth=2, ingest_batch=64)
    h = sess.register(Query(agg="sum", window=WindowSpec("tuple", 4)))
    q, _ = _drive(sess, h, rounds=3)

    stats = sess.stats()
    assert isinstance(stats, SessionStats)
    assert stats.n_queries == 1 and stats.n_engine_groups == 1
    assert stats.updates == sess._seq == 3
    assert stats.frontier.get("steps", 0) >= 1
    assert stats.ingest is not None and stats.ingest.events_in == 60
    assert stats.construction is sess.overlay_stats

    W, R = np.asarray(sess.writers), np.asarray(sess.readers)
    r0 = int(R[1])
    w0 = next(int(w) for w in W if int(w) not in sess.neighborhood(r0))
    sess.add_edge(w0, r0)
    report = sess.flush()
    assert isinstance(report, FlushReport)
    # back-compat: still the per-group result list
    (res,) = report
    assert res is None or not res.recompiled
    assert report.patched + report.recompiled + report.relayout >= 1

    flips = sess.adapt()
    assert isinstance(flips, AdaptReport)
    assert flips == sum(flips.per_group) and flips.flips == int(flips)
    assert flips + 0 == int(flips)  # int arithmetic holds

    # ingest counters survive save/restore
    sess.save(str(tmp_path), blocking=True)
    r = EagrSession.restore(str(tmp_path))
    assert r.stats().ingest.events_in == 60
    (h2,) = r.queries
    r.update(W[:4], np.ones(4, np.float32))
    assert r.stats().ingest.events_in == 64


def test_restore_errors(tmp_path):
    with pytest.raises(FileNotFoundError):
        EagrSession.restore(str(tmp_path / "empty"))
    sess = EagrSession(_graph())
    with pytest.raises(ValueError, match="no checkpoint directory"):
        sess.save()
    # a raw (non-session) checkpoint payload is rejected up front
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_payload(0, {"x": np.zeros(3)}, {}, blocking=True)
    with pytest.raises(ValueError, match="not an EagrSession payload"):
        EagrSession.restore(str(tmp_path))


def test_payload_manager_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    arrays = {"a.b": np.arange(6).reshape(2, 3),
              "c": np.float32([1.5, -2.0])}
    mgr.save_payload(3, arrays, {"k": [1, 2]}, blocking=True)
    got, objs, step = mgr.restore_payload()
    assert step == 3 and objs == {"k": [1, 2]}
    for k, v in arrays.items():
        np.testing.assert_array_equal(got[k], v)
        assert got[k].dtype == np.asarray(v).dtype
