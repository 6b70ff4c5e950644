"""Datasets and traffic for the benchmark, drawn from seeds.

The graph is a deployment's dataset: it depends only on the configuration's
``graph_seed``, as a public benchmark's dataset is fixed per scale factor.
Popularity (the Zipf ranks of writers and readers) comes from that seed too.
``--seed`` draws the stream alone: which writer each event comes from, its
value, and which readers each read asks for, in the order the traffic file's
cycle of operations lays down. Every seed gives the same sizes and the same
order of operations, so seeds change the data and never the amount of work.

The generators are copies of the program's ``powerlaw_graph``,
``zipf_frequencies`` and the read-follows-write rule of ``generate_trace``
(paper §5.1), kept here so that no change to the program moves the yardstick.
"""
from __future__ import annotations

import dataclasses

import numpy as np


# ------------------------------------------------------------------- graph
def powerlaw_edges(n_nodes: int, n_edges: int, *, beta: float = 0.75,
                   sharing: float = 0.0, group: int = 16,
                   seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Edge list ``(src, dst)`` of a power-law graph, deduplicated and sorted
    by ``src * n_nodes + dst``. Edge ``u -> v`` means reader ``v`` consumes
    writer ``u``'s stream.

    Destinations are drawn by inverse CDF from w_rank ∝ rank^-beta over a
    random node permutation (in-degree tail exponent ≈ 1 + 1/beta); sources
    are uniform. ``sharing`` routes that fraction of each reader's in-edges
    to a writer set shared by its group of ``group`` consecutive readers.
    The draws are those of the program's ``powerlaw_graph`` with the same
    arguments, so the two give the same graph.
    """
    rng = np.random.default_rng(seed)
    w = np.arange(1, n_nodes + 1, dtype=np.float64) ** (-beta)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    perm = rng.permutation(n_nodes).astype(np.int32)

    def powerlaw_nodes(k: int) -> np.ndarray:
        return perm[np.searchsorted(cdf, rng.random(k)).astype(np.int32)]

    n_shared = int(round(n_edges / n_nodes * sharing)) if sharing > 0 else 0
    parts_src, parts_dst = [], []
    if n_shared:
        n_groups = (n_nodes + group - 1) // group
        proto = powerlaw_nodes(n_groups * n_shared).reshape(n_groups, n_shared)
        readers = np.arange(n_nodes, dtype=np.int32)
        parts_src.append(proto[readers // group].ravel())
        parts_dst.append(np.repeat(readers, n_shared))
    m = int((n_edges - n_shared * n_nodes) * 1.08) + 16
    parts_src.append(rng.integers(0, n_nodes, m, dtype=np.int32))
    parts_dst.append(powerlaw_nodes(m))
    src = np.concatenate(parts_src)
    dst = np.concatenate(parts_dst)
    keep = src != dst
    src, dst = src[keep][:n_edges], dst[keep][:n_edges]
    key = np.unique(src.astype(np.int64) * n_nodes + dst)
    return key // n_nodes, key % n_nodes


def zipf_frequencies(n: int, alpha: float = 1.0, seed: int = 0) -> np.ndarray:
    """Normalized Zipf(alpha) frequencies randomly assigned to ``n`` items."""
    rng = np.random.default_rng(seed)
    ranks = rng.permutation(n) + 1
    f = 1.0 / np.power(ranks.astype(np.float64), alpha)
    return f / f.sum()


@dataclasses.dataclass
class Dataset:
    """A configuration's graph and popularity, fixed by ``graph_seed``."""

    n_nodes: int
    src: np.ndarray          # (m,) int64 edge sources (writers)
    dst: np.ndarray          # (m,) int64 edge destinations (readers)
    writers: np.ndarray      # ascending ids with an out-edge
    readers: np.ndarray      # ascending ids with an in-edge
    write_freq: np.ndarray   # (n_nodes,) sums to 1 over writers
    read_freq: np.ndarray    # (n_nodes,) sums to 1 over readers


def frequencies(n_nodes: int, writers: np.ndarray, readers: np.ndarray, *,
                alpha: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Paper §5.1: Zipf(alpha) write frequencies over the writers; a node that
    both writes and reads is read as often as it writes, and read-only nodes
    get a fresh Zipf at a tenth of the write mass."""
    wf = np.zeros(n_nodes)
    wf[writers] = zipf_frequencies(len(writers), alpha, seed)
    rf = np.zeros(n_nodes)
    common = np.intersect1d(writers, readers)
    rf[common] = wf[common]
    only_read = np.setdiff1d(readers, common)
    if only_read.size:
        rf[only_read] = zipf_frequencies(len(only_read), alpha, seed + 1) \
            * wf.sum() * 0.1
    return wf, rf / max(rf.sum(), 1e-300)


def make_dataset(graph: dict, alpha: float) -> Dataset:
    """The dataset a configuration's ``graph`` block and Zipf ``alpha``
    describe."""
    if graph["generator"] != "powerlaw":
        raise ValueError(f"unknown graph generator {graph['generator']!r}")
    n = int(graph["n_nodes"])
    seed = int(graph["graph_seed"])
    src, dst = powerlaw_edges(n, int(graph["n_edges"]),
                              beta=float(graph["beta"]),
                              sharing=float(graph["sharing"]),
                              group=int(graph["group"]), seed=seed)
    writers = np.unique(src)
    readers = np.unique(dst)
    wf, rf = frequencies(n, writers, readers, alpha=alpha, seed=seed)
    return Dataset(n, src, dst, writers, readers, wf, rf)


# ------------------------------------------------------------------ stream
@dataclasses.dataclass(frozen=True)
class Op:
    """One call the embedding application makes on the session."""

    kind: str                      # "update" | "read" | "drain"
    ids: np.ndarray | None = None  # writer ids (update) or reader ids (read)
    values: np.ndarray | None = None


def seed_words(seed: int) -> list[int]:
    """Any whole number, negative or beyond 64 bits, as SeedSequence words."""
    seed = int(seed)
    words = [1 if seed < 0 else 0]
    seed = abs(seed)
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


class Stream:
    """The traffic's cycle of operations, repeated, with ids and values drawn
    from ``seed``. Writers, values and readers each come from a generator of
    their own, consumed in order, so the stream is the same however it is cut
    into blocks."""

    def __init__(self, ds: Dataset, traffic: dict, values: dict, seed: int):
        self.cycle = [dict(op) for op in traffic["cycle"]]
        for op in self.cycle:
            if op["op"] not in ("update", "read", "drain"):
                raise ValueError(f"unknown traffic op {op['op']!r}")
        self.lo, self.hi = int(values["low"]), int(values["high"])
        ss = np.random.SeedSequence(seed_words(seed))
        self._rng_w, self._rng_v, self._rng_r = (
            np.random.default_rng(s) for s in ss.spawn(3))
        self._writers = ds.writers
        self._readers = ds.readers
        self._cdf_w = _cdf(ds.write_freq[ds.writers])
        self._cdf_r = _cdf(ds.read_freq[ds.readers])

    def cycles(self, n: int) -> list[Op]:
        """The next ``n`` cycles' operations, drawn in one vectorized block."""
        per = [int(op.get("events", 0)) for op in self.cycle]
        rper = [int(op.get("readers", 0)) for op in self.cycle]
        n_ev, n_rd = sum(per) * n, sum(rper) * n
        w = self._writers[_draw(self._cdf_w, self._rng_w.random(n_ev))]
        v = self._rng_v.integers(self.lo, self.hi + 1, n_ev).astype(np.float32)
        r = self._readers[_draw(self._cdf_r, self._rng_r.random(n_rd))]
        out, ie, ir = [], 0, 0
        for _ in range(n):
            for op, k, kr in zip(self.cycle, per, rper):
                if op["op"] == "update":
                    out.append(Op("update", w[ie: ie + k], v[ie: ie + k]))
                    ie += k
                elif op["op"] == "read":
                    out.append(Op("read", r[ir: ir + kr]))
                    ir += kr
                else:
                    out.append(Op("drain"))
        return out


def _cdf(p: np.ndarray) -> np.ndarray:
    c = np.cumsum(p, dtype=np.float64)
    return c / c[-1]


def _draw(cdf: np.ndarray, u: np.ndarray) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, u, side="right"), len(cdf) - 1)
