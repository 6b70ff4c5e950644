"""Async double-buffered streaming ingest (ROADMAP: millions of events/s).

The synchronous write path pays, per arrival batch: host routing, a
``device_put``, one O(n_nodes) device step, and the Python gap between
batches. :class:`IngestPipeline` restructures that into a pipeline over a
small ring of pre-allocated power-of-two host batch buffers:

  * arriving events accumulate into the current ring slot (vectorized
    copies — no per-event Python anywhere on this path);
  * when a slot reaches ``device_batch`` events it is routed (one
    ``BaseRoutes`` table lookup per engine) and dispatched through
    ``EagrEngine.write_rows`` — JAX async dispatch returns immediately, so
    the host fills and routes slot N+1 while the device still runs the step
    for slot N;
  * backpressure is explicit: the only steady-state ``block_until_ready``
    sits at the ring boundary — a slot's buffers are reused only once its
    in-flight step finished, which is also what makes buffer reuse safe when
    ``device_put`` zero-copy aliases host memory on CPU;
  * :meth:`flush` dispatches the partial slot and drains every token — a
    full pipeline barrier. ``EagrSession.flush`` runs it *before* draining
    churn journals, so structural patches keep their ordering with respect
    to writes. :meth:`drain` dispatches without blocking: a subsequent
    read's data dependency through the engine state already observes every
    dispatched batch in order.

Coalescing — ``device_batch`` larger than the arrival batch — is where the
sustained-throughput win comes from: the device step sweeps O(n_nodes +
E_push) state per batch regardless of batch size, so folding k arrival
batches into one device batch amortizes that sweep k ways. The logical
clock consequently ticks once per *device* batch, not once per ``submit``;
for time windows pick ``device_batch`` so one tick still means what the
window size expects. Bit-for-bit parity with the synchronous path holds
whenever the synchronous driver uses the same batch boundaries
(``write_batch(ids, vals, batch_size=device_batch)`` per full slot) — the
parity tests in ``tests/test_ingest.py`` pin exactly that.

``IngestStats`` is the counter block (in the style of PR 6's
``ConstructionStats``): events in/dispatched/dropped, batches, stall and
barrier time, ring occupancy high-water.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import bucket_batch

__all__ = ["IngestPipeline", "IngestStats"]


@dataclasses.dataclass
class IngestStats:
    """Ingest counters; ``events_in`` minus ``events_dispatched`` is the
    current fill of the accumulating slot."""

    events_in: int = 0          # events submitted to the pipeline
    events_dispatched: int = 0  # events handed to the device (incl. masked)
    events_dropped: int = 0     # lanes no engine routed (unknown writers)
    batches: int = 0            # device batches dispatched
    partial_batches: int = 0    # dispatches below device_batch (flush/drain)
    flushes: int = 0            # full pipeline barriers
    stall_s: float = 0.0        # time blocked on ring backpressure
    barrier_s: float = 0.0      # time blocked inside flush()
    max_in_flight: int = 0      # ring occupancy high-water mark

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class IngestPipeline:
    """Double-buffered ingest ring over one or more engines sharing a write
    stream (an ``EagrSession``'s engine groups, or hand-assembled engines).

    Parameters
    ----------
    engines : list
        ``EagrEngine`` and/or ``StackedShardedEngine`` instances. Single
        engines dispatch through the pre-routed ``write_rows`` entry;
        stacked engines route on-device and go through ``write_batch``.
    depth : int
        Ring slots (>= 1). ``depth=1`` degenerates to synchronous-but-
        coalesced; ``depth=2`` is classic double buffering. More depth only
        helps when device steps vary a lot in latency.
    device_batch : int
        Events per device step; bucketed to a power of two. This is the
        coalescing factor — and the logical-clock granularity.
    value_dim : int | None
        Raw write arity; defaults to the first engine's window spec.
    """

    def __init__(self, engines, *, depth: int = 2, device_batch: int = 8192,
                 value_dim: int | None = None,
                 stats: "IngestStats | None" = None):
        if not engines:
            raise ValueError("IngestPipeline needs at least one engine")
        self.engines = list(engines)
        self.depth = max(1, int(depth))
        self.device_batch = bucket_batch(int(device_batch))
        if value_dim is None:
            value_dim = self.engines[0].spec.value_dim
        self.value_dim = int(value_dim)
        B = self.device_batch
        vshape = (B,) if self.value_dim == 1 else (B, self.value_dim)
        self._ids = [np.zeros(B, np.int64) for _ in range(self.depth)]
        self._vals = [np.zeros(vshape, np.float32) for _ in range(self.depth)]
        self._tokens: list = [None] * self.depth
        # per-slot fired-set readback marks: [(AlertSet, seq)] recorded at
        # dispatch — collected once the slot's token barrier proves those
        # steps completed. Empty for sessions without standing alerts, so
        # the non-alert path adds no transfers (transfer-guard invariant).
        self._alert_marks: list = [None] * self.depth
        self._slot = 0
        self._fill = 0
        # a restored session hands back its saved counter block so lifetime
        # ingest stats survive checkpoint/restore (and pipeline re-creation)
        self.stats = stats if stats is not None else IngestStats()

    # ------------------------------------------------------------------ intake
    def submit(self, ids, values=None) -> None:
        """Feed a batch of events (any size) into the ring. Dispatches each
        slot the moment it fills; never blocks except on ring backpressure."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        if values is None:
            values = np.ones(len(ids), np.float32)
        vals = np.asarray(values, np.float32)
        want = (len(ids),) if self.value_dim == 1 \
            else (len(ids), self.value_dim)
        if vals.shape != want:
            raise ValueError(f"submit values shape {vals.shape} != {want} "
                             f"(pipeline value_dim={self.value_dim})")
        self.stats.events_in += len(ids)
        B, off = self.device_batch, 0
        while off < len(ids):
            take = min(B - self._fill, len(ids) - off)
            s, f = self._slot, self._fill
            self._ids[s][f: f + take] = ids[off: off + take]
            self._vals[s][f: f + take] = vals[off: off + take]
            self._fill += take
            off += take
            if self._fill == B:
                self._dispatch(B)

    # ---------------------------------------------------------------- dispatch
    def _dispatch(self, n: int) -> None:
        with jax.profiler.TraceAnnotation(
                "eagr.dispatch", batch=int(self.engines[0]._now_host)):
            self._send(n)
        # advance the ring; the next slot's buffers may still back an
        # in-flight step — the pipeline's only steady-state sync point
        self._slot = (self._slot + 1) % self.depth
        self._fill = 0
        tok = self._tokens[self._slot]
        if tok is not None:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("eagr.ring_wait"):
                jax.block_until_ready(tok)
            self.stats.stall_s += time.perf_counter() - t0
            self._tokens[self._slot] = None
            self._collect_marks(self._slot)

    def _send(self, n: int) -> None:
        """Route the current slot's first ``n`` events and hand them to
        every engine; record the slot's completion token."""
        s, B = self._slot, self.device_batch
        ids, vals = self._ids[s], self._vals[s]
        if n < B:
            # partial slot (flush/drain): poison the tail so routing masks it
            ids[n:] = -1
            vals[n:] = 0.0
            self.stats.partial_batches += 1
        dropped = n
        for eng in self.engines:
            routes = getattr(getattr(eng, "plan", None), "routes", None)
            if routes is None:
                # stacked shard engine: ids route on-device via owner maps
                eng.write_batch(ids[:n], vals[:n], batch_size=B)
                dropped = 0
                continue
            with jax.profiler.TraceAnnotation("eagr.route"):
                rows, mask = routes.writer_rows(ids)
                n_live = int(np.count_nonzero(mask))
                dropped = min(dropped, n - n_live)
                v = vals
                if n_live < n:
                    # zero dead lanes: their values are dead under the mask,
                    # but keep non-finite garbage out of the masked multiply
                    v = np.where(
                        mask.reshape((-1,) + (1,) * (vals.ndim - 1)),
                        vals, 0.0)
            # expand this coalesced device batch's frontier here (host side,
            # before dispatch) so the sparse-vs-dense decision and the
            # active-block bucket are pinned per batch, not per event
            with jax.profiler.TraceAnnotation("eagr.expand"):
                act = eng.frontier_active(rows, mask, n_live=n_live)
            eng.write_rows(rows, v, mask, n_live=n_live, active=act)
        self.stats.events_dispatched += n
        self.stats.events_dropped += dropped
        self.stats.batches += 1
        # `state.now` is an output of the step just dispatched: readiness of
        # this token == completion of every engine's device batch for slot s.
        # It is also DONATED into the engine's next step — token a detached
        # copy (dispatched now, before any later donation) so the ring
        # barrier never blocks on a donated buffer. ``jnp.copy``, not
        # ``+ 0``: the scalar constant would be an implicit transfer under
        # the transfer guard
        self._tokens[s] = [jnp.copy(eng.state.now) for eng in self.engines]
        marks = [(al, al.seq) for al in
                 (getattr(eng, "alerts", None) for eng in self.engines)
                 if al is not None]
        self._alert_marks[s] = marks or None
        self.stats.max_in_flight = max(
            self.stats.max_in_flight,
            sum(t is not None for t in self._tokens))

    def _collect_marks(self, slot: int) -> None:
        """Pop the fired sets whose steps the freed slot's token proves done.
        The ``device_get`` inside ``collect_upto`` copies completed buffers —
        it never becomes a steady-state sync point."""
        marks = self._alert_marks[slot]
        if marks is not None:
            self._alert_marks[slot] = None
            for al, upto in marks:
                al.collect_upto(upto)

    # ----------------------------------------------------------------- control
    def drain(self) -> None:
        """Dispatch the partial slot without blocking: a read issued next
        observes every submitted event through its data dependency on the
        engine state (device steps execute in dispatch order)."""
        if self._fill:
            self._dispatch(self._fill)

    def flush(self) -> None:
        """Pipeline barrier: dispatch the partial slot, then block until
        every in-flight device step completed. Run before structural churn
        lands (``EagrSession.flush`` does) so patch ordering — and donated /
        host-aliased buffer reuse — stays safe."""
        self.drain()
        t0 = time.perf_counter()
        for i, tok in enumerate(self._tokens):
            if tok is not None:
                with jax.profiler.TraceAnnotation("eagr.ring_wait"):
                    jax.block_until_ready(tok)
                self._tokens[i] = None
            self._collect_marks(i)
        self.stats.barrier_s += time.perf_counter() - t0
        self.stats.flushes += 1

    @property
    def in_flight(self) -> int:
        return sum(t is not None for t in self._tokens)

    @property
    def pending(self) -> int:
        """Events accumulated in the current slot, not yet dispatched."""
        return self._fill
