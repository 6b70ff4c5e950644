"""Plain reference for the benchmark's comparison.

It computes, from the data graph's edge list and the stream as it was
submitted, what every read and every alert must return. It imports nothing of
the program: no overlay, no plan, no window ring, no oracle.

* A writer's window holds its last ``size`` writes (a tuple window). Its
  value is the number of them (``count``) or their sum (``sum``).
* A reader's answer is the sum of its in-neighbours' window values, over
  every event submitted before the read.
* The session's clock ticks once per device batch: the ingest ring sends a
  batch when ``batch`` events have filled it, and a read, a fired-set drain
  or the closing barrier sends the part-filled batch. After each device
  batch every alerted reader's measure is compared as the standing alert's
  state machine says (``above``/``hysteresis``/``debounce``): it fires when
  its measure changes to a value above the threshold while armed and at
  least ``debounce`` ticks after its last firing; firing disarms it, and a
  change back to ``above - hysteresis`` or below re-arms it.

Values are whole numbers and every sum stays below 2**24, so float32 answers
are exact and the comparison is for equality.

``store="bfloat16"`` holds every window value, answer and measure in
bfloat16, the next precision below the configurations' float32: that
reference, put in the program's place, is the comparison's control.
"""
from __future__ import annotations

import numpy as np


class Reference:
    """Windows over one submitted stream, and the graph to sum them over."""

    def __init__(self, n_nodes: int, src: np.ndarray, dst: np.ndarray, *,
                 agg: str, size: int, store: str = "float64"):
        if agg not in ("count", "sum"):
            raise ValueError(f"the reference knows count and sum, not {agg!r}")
        if store == "float64":
            self._store = lambda x: x
        else:
            import ml_dtypes

            dt = getattr(ml_dtypes, store)
            self._store = lambda x: np.asarray(x).astype(dt).astype(
                np.float64)
        self.n = int(n_nodes)
        self.agg, self.size = agg, int(size)
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        by_dst = np.argsort(dst, kind="stable")
        self.in_ptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(dst, minlength=self.n), out=self.in_ptr[1:])
        self.in_idx = src[by_dst]
        by_src = np.argsort(src, kind="stable")
        self.out_ptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=self.n), out=self.out_ptr[1:])
        self.out_idx = dst[by_src]
        self.set_stream(np.zeros(0, np.int64), np.zeros(0, np.float32))

    def set_stream(self, ids: np.ndarray, values: np.ndarray) -> None:
        """The events in the order they were submitted."""
        ids = np.asarray(ids, np.int64)
        self._ids = ids
        self.n_events = len(ids)
        # events grouped by writer, in submission order within a writer
        self._key = ids * (self.n_events + 1) + np.arange(self.n_events)
        order = np.argsort(self._key, kind="stable")
        self._key = self._key[order]
        v = np.asarray(values, np.float64)[order]
        self._csum = np.concatenate([[0.0], np.cumsum(v)])
        # where each writer's events start in that order
        self._start = np.searchsorted(
            self._key, np.arange(self.n + 1, dtype=np.int64) * (ids.size + 1))
        self.max_batch_delta = None

    def window_values(self, writers: np.ndarray, cutoff) -> np.ndarray:
        """Each writer's window value over the first ``cutoff`` events
        (``cutoff`` a scalar or one per writer)."""
        w = np.asarray(writers, np.int64)
        end = np.searchsorted(self._key,
                              w * (self.n_events + 1) + np.asarray(cutoff))
        return self._window(w, end)

    def _window(self, w: np.ndarray, end: np.ndarray) -> np.ndarray:
        """Window values of writers ``w`` whose events in submission order
        end before position ``end`` of the writer-grouped order."""
        start = self._start[w]
        lo = np.maximum(start, end - self.size)
        if self.agg == "count":
            return self._store((end - lo).astype(np.float64))
        return self._store(self._csum[end] - self._csum[lo])

    def neighbours(self, readers: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """``(owner, writer)``: each reader's in-neighbours, flattened, with
        the position in ``readers`` each one belongs to."""
        r = np.asarray(readers, np.int64)
        lens = self.in_ptr[r + 1] - self.in_ptr[r]
        starts = np.repeat(self.in_ptr[r] - (np.cumsum(lens) - lens), lens)
        pos = starts + np.arange(int(lens.sum()), dtype=np.int64)
        return np.repeat(np.arange(len(r)), lens), self.in_idx[pos]

    def answers(self, readers: np.ndarray, cutoff: int) -> np.ndarray:
        """What a read of ``readers`` issued after ``cutoff`` events returns."""
        owner, w = self.neighbours(readers)
        vals = self.window_values(w, cutoff)
        return self._store(np.bincount(owner, weights=vals,
                                       minlength=len(readers)))

    def many_answers(self, reads: list[tuple[np.ndarray, int]]
                     ) -> list[np.ndarray]:
        """``answers`` for many ``(readers, cutoff)`` reads. The reads are
        taken in the order of their cutoffs, with each writer's count of
        events so far carried from one to the next."""
        out: list[np.ndarray | None] = [None] * len(reads)
        seen = np.zeros(self.n, np.int64)
        done = 0
        for k in sorted(range(len(reads)), key=lambda k: reads[k][1]):
            readers, cutoff = reads[k]
            seen += np.bincount(self._ids[done:cutoff], minlength=self.n)
            done = max(done, int(cutoff))
            owner, w = self.neighbours(readers)
            vals = self._window(w, self._start[w] + seen[w])
            out[k] = self._store(np.bincount(owner, weights=vals,
                                             minlength=len(readers)))
        return out

    def fired(self, cutoffs: list[int], *, above: float, hysteresis: float,
              debounce: float, alerted: np.ndarray | None = None
              ) -> set[tuple[int, int, float]]:
        """``(tick, reader, measure)`` of every firing of an ``above`` alert
        over the device batches that end at ``cutoffs`` (tick ``k`` is the
        batch ending at ``cutoffs[k]``). ``alerted`` defaults to every reader
        with an in-neighbour. Keeps the largest change of a reader's measure
        in one batch in ``max_batch_delta``."""
        n = self.n
        active = np.diff(self.in_ptr) > 0 if alerted is None else alerted
        m = np.zeros(n)
        last_m = np.zeros(n)
        armed = np.ones(n, bool)
        last_fire = np.full(n, -np.inf)
        wv = np.zeros(n)
        out: set[tuple[int, int, float]] = set()
        prev = 0
        for tick, cut in enumerate(cutoffs):
            w = np.unique(self._ids[prev:cut])
            prev = cut
            if not len(w):
                continue
            new = self.window_values(w, cut)
            delta = new - wv[w]
            wv[w] = new
            moved = delta != 0
            w, delta = w[moved], delta[moved]
            lens = self.out_ptr[w + 1] - self.out_ptr[w]
            pos = np.repeat(self.out_ptr[w] - (np.cumsum(lens) - lens), lens) \
                + np.arange(int(lens.sum()), dtype=np.int64)
            rd = self.out_idx[pos]
            step = np.bincount(rd, weights=np.repeat(delta, lens),
                               minlength=n)
            m += step
            top = float(np.abs(step).max())
            self.max_batch_delta = max(self.max_batch_delta or 0.0, top)
            rows = np.unique(rd)
            rows = rows[active[rows]]
            mr = self._store(m[rows])
            changed = mr != last_m[rows]
            rows, mr = rows[changed], mr[changed]
            fire = armed[rows] & (mr > above) \
                & (tick - last_fire[rows] >= debounce)
            inside = mr <= above - hysteresis
            armed[rows] = np.where(fire, False, armed[rows] | inside)
            last_fire[rows[fire]] = tick
            last_m[rows] = mr
            out.update((tick, int(r), float(v))
                       for r, v in zip(rows[fire], mr[fire]))
        return out


def device_batches(ops, batch: int) -> tuple[list[int], list[int]]:
    """Where the ingest ring cuts the stream into device batches:
    ``(cutoffs, op_of_batch)``, the events submitted when each batch is sent
    and the index of the operation that sent it. ``ops`` is a sequence of
    ``(kind, n_events)`` with kind ``update``, ``read``, ``drain`` or
    ``barrier``."""
    cutoffs: list[int] = []
    op_of: list[int] = []
    total = fill = 0
    for i, (kind, n) in enumerate(ops):
        if kind == "update":
            while n:
                take = min(batch - fill, n)
                fill += take
                total += take
                n -= take
                if fill == batch:
                    cutoffs.append(total)
                    op_of.append(i)
                    fill = 0
        elif fill:
            cutoffs.append(total)
            op_of.append(i)
            fill = 0
    return cutoffs, op_of
