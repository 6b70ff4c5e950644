"""The dense level sweep bounded by the plan's live extent: each level's
``live_blocks`` is derived from its slot tables, the level loop stops after
the last live level and the chunk loop after each level's last live chunk,
and every answer stays bit-identical to the sweep over all padded levels
and blocks — after churn that claims a slot past the old extent too, and
on a stack of shards whose extents differ."""
import functools

import jax
import numpy as np
import pytest

from conftest import make_freqs
from repro.core import dataflow as D
from repro.core import engine as E
from repro.core.aggregates import make_aggregate
from repro.core.bipartite import build_bipartite
from repro.core.dynamic import DynamicOverlay
from repro.core.overlay import Overlay
from repro.core.vnm import construct_vnm
from repro.core.window import WindowSpec
from repro.graphs.generators import rmat_graph
from repro.kernels.segment_agg import ops
from repro.streams import alerts as A


def _bits(x) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x, np.float32)).view(np.uint32)


def _unbounded(arrays: E.PlanArrays) -> E.PlanArrays:
    """The same tables with every level's extent set to all its blocks: the
    sweep then visits every padded level and block, as before the bound."""
    def full(t):
        L, nb = t.tile_of_block.shape[-2:]
        return t._replace(live_blocks=jax.numpy.full(
            t.live_blocks.shape, nb, jax.numpy.int32))
    return arrays._replace(push=full(arrays.push), pull=full(arrays.pull))


def test_live_extent_is_one_past_the_last_live_block():
    e = ops.E_BLK
    seg = np.full((4, 6 * e), -1, np.int32)
    seg[0, 3] = 7                      # block 0 only
    seg[1, 2 * e + 5] = 1              # a hole at block 0-1, live block 2
    seg[1, 4 * e] = 2                  # and block 4
    seg[3, 6 * e - 1] = 0              # the last slot of the last block
    np.testing.assert_array_equal(np.asarray(ops.live_extent(seg)),
                                  [1, 5, 0, 6])
    assert E._live_levels_host(np.asarray(ops.live_extent(seg))) == 4
    assert int(E._live_levels(E.level_tables(
        seg, seg * 0, seg * 0.0, np.zeros((4, 6), np.int32),
        np.zeros((4, 6), np.int32), np.zeros((4, 8), bool)))) == 4


# ------------------------------------------------------ bounded = unbounded
@pytest.fixture(scope="module")
def systems():
    """Two engines per backend over one small overlay compiled with 2x
    headroom, so levels and blocks are padded: min-cut decisions (push and
    pull tables) and all push (what standing alerts need)."""
    g = rmat_graph(150, 900, seed=3)
    bp = build_bipartite(g)
    ov, _ = construct_vnm(bp, variant="vnm_a", max_iterations=3, seed=0)
    wf, rf = make_freqs(150, seed=1)
    mincut, _ = D.decide_mincut(ov, wf, rf, D.cost_model_for("sum"))
    return bp, ov, {"mincut": mincut,
                    "push": np.full(ov.n_nodes, D.PUSH, np.int64)}


def _engine(systems, backend, agg, decisions):
    bp, ov, dec = systems
    eng = E.EagrEngine(ov, dec[decisions], make_aggregate(agg),
                       WindowSpec("tuple", 4), backend=backend, headroom=2.0)
    rng = np.random.default_rng(5)
    for _ in range(3):
        eng.write_batch(rng.choice(bp.writers, 24),
                        rng.integers(0, 9, 24).astype(np.float32),
                        batch_size=24)
    return eng, rng


def _step_outputs(step, eng, rng, arrays):
    """One step of kind ``step`` over ``arrays`` from the engine's state, as
    host arrays; each kind through a fresh jit so the chunk size in force
    is the one traced."""
    meta, agg, spec = eng.plan.meta, eng.agg, eng.spec
    rows = rng.integers(0, len(eng.plan.writer_node), 16).astype(np.int32)
    vals = rng.integers(0, 9, 16).astype(np.float32)
    mask = np.ones(16, bool)
    extra = () if agg.combine == "sum" else (np.float32(0.0),)
    body = E.write_step_sum if agg.combine == "sum" else \
        E.write_step_extremal
    if step == "write":
        out = jax.jit(body, static_argnums=(0, 1, 2))(
            meta, agg, spec, arrays, eng.state, rows, vals, mask, *extra)
        return [out.pao]
    if step == "alert_write":
        al = eng.alerts
        out = jax.jit(A.alert_write_step, static_argnums=(0, 1, 2, 3, 4))(
            body, meta, agg, spec, al.cap, arrays, eng.state, al.state,
            rows, vals, mask, *extra)
        ns, new_a, count, idx, avals, fired, m = out
        return [ns.pao, count, idx, avals, fired, m, new_a.last_m]
    if step == "refresh":
        return [jax.jit(E.refresh_pao_step, static_argnums=(0, 1, 2))(
            meta, agg, spec, arrays, eng.state.windows, eng.state.now)]
    nodes = np.asarray(sorted(eng.plan.reader_node_of_base.values()),
                       np.int32)
    ans, raw = jax.jit(E.read_step, static_argnums=(0, 1))(
        meta, agg, arrays, eng.state, nodes, np.ones(len(nodes), bool))
    return [ans, raw]


@pytest.mark.parametrize("step", ["write", "alert_write", "refresh", "read"])
@pytest.mark.parametrize("agg", ["sum", "max"])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_bounded_sweep_is_bit_identical(systems, monkeypatch, backend, agg,
                                        step):
    if backend == "pallas":
        # one block per kernel call: the chunk bound skips every all-padding
        # block past a level's extent, not just whole levels
        monkeypatch.setattr(ops, "CHUNK_BYTES", 1)
    eng, rng = _engine(systems, backend, agg,
                       "push" if step == "alert_write" else "mincut")
    tables = eng.plan.arrays.pull if step == "read" else eng.plan.arrays.push
    live = np.asarray(tables.live_blocks)
    L, nb = tables.tile_of_block.shape
    # the bound engages: padded levels past the last live one, and a
    # padded tail past some live level's extent
    assert 0 < E._live_levels_host(live) < L
    assert live.max() < nb
    if step == "alert_write":
        al = A.AlertSet(cap=8)
        bases = sorted(eng.plan.reader_node_of_base)
        al.register(0, A.AlertSpec(above=6.0), bases, dynamic=False)
        eng.attach_alerts(al)
    seed = int(rng.integers(1 << 30))
    got = _step_outputs(step, eng, np.random.default_rng(seed),
                        eng.plan.arrays)
    want = _step_outputs(step, eng, np.random.default_rng(seed),
                         _unbounded(eng.plan.arrays))
    eng.alerts = None
    assert np.any(np.asarray(got[0]) != 0)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        if g.dtype == np.float32:
            np.testing.assert_array_equal(_bits(g), _bits(w))
        else:
            np.testing.assert_array_equal(g, w)


# ------------------------------------------------------------------- churn
def _tail_claim_system(backend):
    """A hand-built overlay whose only level holds exactly one full edge
    block, all in the last row tile: 256 writers (nodes 0-255), 128 readers
    (nodes 256-383, one tile) with two writers each. With 2x headroom the
    level has a tail padding block, which the slot pools give to that
    tile, so the next edge into the tile claims a slot past the extent."""
    n_w, n_r = 256, 128
    kinds = ["W"] * n_w + ["R"] * n_r
    origin = list(range(n_w)) + list(range(1000, 1000 + n_r))
    in_edges = [[] for _ in range(n_w)] + [
        [((2 * i) % n_w, 1), ((2 * i + 1) % n_w, 1)] for i in range(n_r)]
    ov = Overlay(kinds=kinds, origin=origin, in_edges=in_edges)
    ris = {1000 + i: {(2 * i) % n_w, (2 * i + 1) % n_w} for i in range(n_r)}
    dyn = DynamicOverlay.from_overlay(ov, ris)
    base = dyn.to_overlay(prune=False)
    dec = np.full(base.n_nodes, D.PUSH, np.int64)
    eng = E.EagrEngine(base, dec, make_aggregate("sum"),
                       WindowSpec("tuple", 4), backend=backend, headroom=2.0)
    return eng, dyn


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_claim_into_a_tail_padding_block_is_reduced_at_once(monkeypatch,
                                                            backend):
    monkeypatch.setattr(ops, "CHUNK_BYTES", 1)  # one block per chunk
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "0")
    eng, dyn = _tail_claim_system(backend)
    assert np.asarray(eng.plan.arrays.push.live_blocks)[0] == 1
    assert eng.plan.arrays.push.tile_of_block.shape[1] == 2
    rng = np.random.default_rng(2)
    history = [(rng.integers(0, 256, 64), rng.integers(0, 9, 64))
               for _ in range(3)]
    for ids, vals in history[:2]:
        eng.write_batch(ids, vals.astype(np.float32), batch_size=64)

    dyn.add_edge(7, 1000 + 127)   # a third writer for the tile's last reader
    res = eng.apply_delta(dyn.drain_delta())
    assert res.kind == "patched"
    live = np.asarray(eng.plan.arrays.push.live_blocks)
    assert live[0] == 2, "the claim moved the extent: it must be rederived"
    assert np.array_equal(eng.plan.push_live_blocks(), live)
    ids, vals = history[2]
    eng.write_batch(ids, vals.astype(np.float32), batch_size=64)

    fresh = E.EagrEngine(dyn.to_overlay(prune=False),
                         np.full(dyn.to_overlay(prune=False).n_nodes, D.PUSH,
                                 np.int64), make_aggregate("sum"),
                         WindowSpec("tuple", 4), backend=backend,
                         plan=E.compile_plan(
                             dyn.to_overlay(prune=False),
                             np.full(dyn.to_overlay(prune=False).n_nodes,
                                     D.PUSH, np.int64), backend=backend))
    for ids, vals in history:
        fresh.write_batch(ids, vals.astype(np.float32), batch_size=64)
    readers = np.arange(1000, 1128)
    got = eng.read_batch(readers)
    np.testing.assert_array_equal(_bits(got), _bits(fresh.read_batch(readers)))
    # the new edge reached its reader: writer 7's window feeds it now
    want = [eng.oracle_read(int(b), dyn.reader_inputs) for b in readers]
    np.testing.assert_array_equal(np.ravel(got), np.ravel(want))


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_stacked_shards_with_different_extents(monkeypatch, backend):
    """Under ``vmap`` the bounded loops run to the largest shard's extent
    and select per shard; every shard's answers equal the unbounded
    sweep's."""
    from repro.distributed.eagr_shard import partition_overlay
    from repro.distributed.stacked import StackedShardedEngine

    if backend == "pallas":
        monkeypatch.setattr(ops, "CHUNK_BYTES", 1)
    monkeypatch.setenv("EAGR_SPARSE_WRITE", "0")
    g = rmat_graph(200, 1200, seed=9)
    bp = build_bipartite(g)
    ov, _ = construct_vnm(bp, variant="vnm_a", max_iterations=3, seed=0)
    dec = np.full(ov.n_nodes, D.PUSH, np.int64)
    agg, spec = make_aggregate("sum"), WindowSpec("tuple", 4)
    sharded = partition_overlay(ov, dec, n_shards=3, seed=0,
                                backend=backend, headroom=2.0)
    engines = [StackedShardedEngine(sharded, agg, spec, mesh=None)
               for _ in range(2)]
    engines[1].arrays = _unbounded(engines[1].arrays)
    live = np.asarray(engines[0].arrays.push.live_blocks)
    assert len({tuple(r) for r in live}) > 1, "shards share one extent"
    nb = engines[0].arrays.push.tile_of_block.shape[-1]
    assert live.max() < nb

    rng = np.random.default_rng(4)
    batches = [(rng.choice(bp.writers, 64),
                rng.integers(0, 8, 64).astype(np.float32)) for _ in range(3)]
    readers = np.asarray(sorted(bp.reader_input_sets()))[:40]
    got = []
    for eng in engines:
        for ids, vals in batches:
            eng.write_batch(ids, vals, batch_size=64)
        got.append((_bits(eng.state.pao), _bits(eng.read_batch(readers))))
    np.testing.assert_array_equal(got[0][0], got[1][0])
    np.testing.assert_array_equal(got[0][1], got[1][1])
    assert np.any(got[0][1] != 0)


# ------------------------------------------------------ restore and counter
def test_restore_rederives_the_extent():
    g = rmat_graph(120, 700, seed=3)
    ov, _ = construct_vnm(build_bipartite(g), variant="vnm_a",
                          max_iterations=2, seed=0)
    plan = E.compile_plan(ov, np.full(ov.n_nodes, D.PUSH, np.int64),
                          backend="xla", pad=E.grow_pad(E.measure_plan(
                              ov, np.full(ov.n_nodes, D.PUSH, np.int64))))
    arrays, objs = E.plan_snapshot(plan)
    assert not any("live_blocks" in k for k in arrays)
    back = E.plan_from_snapshot(arrays, objs)
    for name in ("push", "pull"):
        np.testing.assert_array_equal(
            np.asarray(getattr(back.arrays, name).live_blocks),
            np.asarray(getattr(plan.arrays, name).live_blocks))


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_dense_sweep_blocks_count_what_the_loops_visit(monkeypatch, backend):
    monkeypatch.setattr(ops, "CHUNK_BYTES", 1)
    eng, _ = _tail_claim_system(backend)
    fn = functools.partial(eng.plan.dense_sweep_blocks, 1)
    L, nb = eng.plan.arrays.push.tile_of_block.shape
    # one live level of one live block: pallas visits that block, xla the
    # whole live level
    assert fn() == ((1 if backend == "pallas" else nb), L * nb)
