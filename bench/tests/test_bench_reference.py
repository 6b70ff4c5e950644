"""The plain reference against a CPU ``EagrSession`` on small graphs, for
both configurations' queries, and the comparison's power to fail."""
import numpy as np
import pytest

from bench import gen, harness
from bench import reference as R
from bench.tests.tiny import tiny_cell

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _drive(cell, seed, n_cycles=6):
    """Cold session over the tiny cell's dataset, driven through the
    harness's own ``Caller``; returns ``(dataset, caller)``."""
    ds = gen.make_dataset(cell.config["graph"],
                          float(cell.config["popularity"]["alpha"]))
    session, _ = harness.build_session(cell.config, ds)
    handle = session.queries[0]
    alert = session.alerts[0] if session.alerts else None
    drv = harness.Caller(session, handle, alert,
                         cell.config["session"]["ingest_batch"])
    for op in gen.Stream(ds, cell.traffic, cell.config["values"],
                         seed).cycles(n_cycles):
        drv.do(op)
        if drv.ingest is None and op.kind == "update":
            drv.ingest = session.stats().ingest
    drv.barrier()
    return ds, drv


@pytest.mark.parametrize("name", ["alerts-1m.bulk", "feed-mincut-120k.mixed"])
def test_reference_agrees_with_the_session(name):
    cell = tiny_cell(name)
    ds, drv = _drive(cell, seed=5)
    q = cell.config["query"]
    ref = R.Reference(ds.n_nodes, ds.src, ds.dst, agg=q["agg"],
                      size=q["window"])
    ref.set_stream(np.concatenate(drv.ids), np.concatenate(drv.values))
    assert drv.reads
    for _, readers, got, cutoff in drv.reads:
        assert np.array_equal(got, ref.answers(readers, cutoff))
    checks = harness.compare(cell, ds, drv, seed=5)["checks"]
    assert all(v == 0 for v, _ in checks.values()), checks
    if "alert" in cell.config:
        assert drv.fired, "the tiny alert cell should fire"


def test_reference_sees_count_windows_cap():
    # writer 0 feeds readers 1 and 2; writer 3 feeds reader 2
    ref = R.Reference(4, np.array([0, 0, 3]), np.array([1, 2, 2]),
                      agg="count", size=2)
    ref.set_stream(np.array([0, 0, 0, 3]), np.ones(4, np.float32))
    assert ref.answers(np.array([1, 2]), 4).tolist() == [2.0, 3.0]
    assert ref.answers(np.array([1, 2]), 1).tolist() == [1.0, 1.0]


def test_reference_sum_window_keeps_the_last_writes():
    ref = R.Reference(2, np.array([0]), np.array([1]), agg="sum", size=2)
    ref.set_stream(np.zeros(4, np.int64), np.array([1, 2, 4, 8], np.float32))
    assert [ref.answers(np.array([1]), c)[0] for c in range(5)] == \
        [0, 1, 3, 6, 12]


@pytest.mark.parametrize("agg", ["count", "sum"])
def test_many_answers_are_the_answers_in_any_order(agg):
    rng = np.random.default_rng(3)
    n = 50
    src, dst = rng.integers(0, n, 400), rng.integers(0, n, 400)
    ref = R.Reference(n, src, dst, agg=agg, size=3)
    ref.set_stream(rng.integers(0, n, 300), rng.integers(0, 9, 300))
    reads = [(rng.integers(0, n, 20), int(c)) for c in (250, 0, 17, 300, 17)]
    got = ref.many_answers(reads)
    for (readers, cut), g in zip(reads, got):
        assert np.array_equal(g, ref.answers(readers, cut))


def test_alert_replay_keeps_the_largest_batch_delta():
    # writer 0 feeds readers 1 and 2, writer 3 feeds reader 2
    ref = R.Reference(4, np.array([0, 0, 3]), np.array([1, 2, 2]),
                      agg="count", size=8)
    ref.set_stream(np.array([0, 3, 3, 0, 0]), np.ones(5, np.float32))
    ref.fired([3, 5], above=100.0, hysteresis=1.0, debounce=0.0)
    # reader 2 gains 3 in the first batch, readers 1 and 2 gain 2 after
    assert ref.max_batch_delta == 3.0


def test_device_batches_follow_the_ring():
    ops = [("update", 5), ("update", 5), ("read", 0), ("drain", 0),
           ("update", 8), ("barrier", 0)]
    cut, op_of = R.device_batches(ops, 4)
    assert cut == [4, 8, 10, 14, 18]
    assert op_of == [0, 1, 2, 4, 4]


def test_perturbed_answer_is_caught():
    cell = tiny_cell("feed-mincut-120k.mixed")
    ds, drv = _drive(cell, seed=6)
    k, readers, got, cutoff = drv.reads[-1]
    bad = got.copy()
    bad[0] += 1
    drv.reads[-1] = (k, readers, bad, cutoff)
    checks = harness.compare(cell, ds, drv, seed=6)["checks"]
    assert checks["read_mismatches"][0] == 1
    assert checks["read_max_abs_err"][0] == 1


def test_perturbed_firing_is_caught():
    cell = tiny_cell("alerts-1m.bulk")
    ds, drv = _drive(cell, seed=6)
    now, ids, vals, at = drv.fired[0]
    drv.fired[0] = (now, ids, vals + 1, at)
    checks = harness.compare(cell, ds, drv, seed=6)["checks"]
    assert checks["fired_mismatches"][0] >= 2


def test_bf16_edge_values_are_caught(monkeypatch):
    """The program's own lower-precision path (``EAGR_SEGAGG_BF16=1``) on
    values that bfloat16 cannot hold (257 rounds to 256)."""
    monkeypatch.setenv("EAGR_SEGAGG_BF16", "1")
    cell = tiny_cell("feed-mincut-120k.mixed",
                     values={"low": 257, "high": 257})
    ds, drv = _drive(cell, seed=8)
    checks = harness.compare(cell, ds, drv, seed=8)["checks"]
    assert checks["read_mismatches"][0] > 0


def test_bfloat16_reference_standing_in_is_caught():
    """The control: the reference held in bfloat16 in the program's place."""
    cell = tiny_cell("feed-mincut-120k.mixed")
    ds, drv = _drive(cell, seed=9)
    assert all(v == 0 for v, _ in
               harness.compare(cell, ds, drv, seed=9)["checks"].values())
    ctl = harness.compare(cell, ds, drv, seed=9, stand_in="bfloat16")
    assert ctl["checks"]["read_mismatches"][0] > 0
    assert ctl["checks"]["read_max_abs_err"][0] > 0
