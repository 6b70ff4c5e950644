"""The benchmark's generators: the dataset is fixed by ``graph_seed``; the
stream by ``--seed``, and cut into blocks in any way."""
import numpy as np
import pytest

from bench import gen

GRAPH = {"generator": "powerlaw", "n_nodes": 3000, "n_edges": 18000,
         "beta": 0.75, "sharing": 0.5, "group": 16, "graph_seed": 3}
TRAFFIC = {"cycle": [{"op": "update", "events": 50}, {"op": "drain"},
                     {"op": "read", "readers": 20}]}
VALUES = {"low": 0, "high": 7}


def _stream(ds, seed, n=6):
    return gen.Stream(ds, TRAFFIC, VALUES, seed).cycles(n)


def _same(a, b) -> bool:
    return len(a) == len(b) and all(
        x.kind == y.kind
        and (x.ids is None or np.array_equal(x.ids, y.ids))
        and (x.values is None or np.array_equal(x.values, y.values))
        for x, y in zip(a, b))


def test_dataset_depends_only_on_graph_seed():
    a = gen.make_dataset(GRAPH, 1.0)
    b = gen.make_dataset(dict(GRAPH), 1.0)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert np.array_equal(a.write_freq, b.write_freq)
    c = gen.make_dataset(dict(GRAPH, graph_seed=4), 1.0)
    assert not (len(a.src) == len(c.src) and np.array_equal(a.src, c.src)
                and np.array_equal(a.dst, c.dst))


def test_graph_matches_the_programs_generator():
    from repro.graphs.generators import powerlaw_graph

    g = powerlaw_graph(3000, 18000, beta=0.75, sharing=0.5, seed=3)
    src, dst = g.edge_list()
    key = np.sort(src.astype(np.int64) * 3000 + dst)
    s, d = gen.powerlaw_edges(3000, 18000, beta=0.75, sharing=0.5, seed=3)
    assert np.array_equal(key, s * 3000 + d)


def test_frequencies_follow_writes():
    ds = gen.make_dataset(GRAPH, 1.0)
    assert np.isclose(ds.write_freq.sum(), 1.0)
    assert np.isclose(ds.read_freq.sum(), 1.0)
    both = np.intersect1d(ds.writers, ds.readers)
    ratio = ds.read_freq[both] / ds.write_freq[both]
    assert np.allclose(ratio, ratio[0])  # read frequency linear in write


@pytest.mark.parametrize("seed", [0, 12345, 2**31 + 17, 2**40 + 3, -5])
def test_same_seed_same_stream(seed):
    ds = gen.make_dataset(GRAPH, 1.0)
    assert _same(_stream(ds, seed), _stream(ds, seed))
    assert not _same(_stream(ds, seed), _stream(ds, seed + 1))


def test_stream_is_the_same_however_it_is_cut():
    ds = gen.make_dataset(GRAPH, 1.0)
    s = gen.Stream(ds, TRAFFIC, VALUES, 99)
    pieces = s.cycles(2) + s.cycles(1) + s.cycles(3)
    assert _same(pieces, _stream(ds, 99))


def test_stream_draws_from_its_sets_and_range():
    ds = gen.make_dataset(GRAPH, 1.0)
    ops = _stream(ds, 7, n=20)
    ups = [o for o in ops if o.kind == "update"]
    assert all(len(o.ids) == 50 for o in ups)
    ids = np.concatenate([o.ids for o in ups])
    vals = np.concatenate([o.values for o in ups])
    assert np.isin(ids, ds.writers).all()
    assert vals.min() >= 0 and vals.max() <= 7
    reads = np.concatenate([o.ids for o in ops if o.kind == "read"])
    assert np.isin(reads, ds.readers).all()
    # Zipf(1): the most popular writer is drawn far more than the median
    counts = np.bincount(ids, minlength=ds.n_nodes)[ds.writers]
    assert counts.max() > 10 * max(1, np.median(counts))
