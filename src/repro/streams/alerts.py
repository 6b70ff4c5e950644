"""Standing alerts: device-evaluated predicate queries over reader PAOs.

EAGr's motivating workloads are continuous *alerting* queries — anomaly
detection, local threshold alerts — yet a poll-everything client must read
back O(readers) measures per batch just to notice the handful that moved.
This module turns the predicate around: alerts are registered once as dense
per-reader threshold arrays plus an armed/fired state vector
(:class:`AlertState`), and evaluation is **fused into the write step**
(:func:`alert_write_step`): after the (frontier-sparse) write body lands, the
finalized measure of every *alerted* row is compared against its previous
value — only rows the batch (or a time-window expiry) actually changed can
differ, so the predicate check is exactly the reachable-reader restriction,
expressed as one vectorized compare instead of a gather. What crosses the
host boundary per batch is a compact fired set: a count plus a
fixed-capacity padded index/value buffer (``jnp.nonzero(..., size=K)``), so
steady state keeps one trace and one tiny transfer, never an O(readers)
poll.

Semantics (canonical — the poll oracle replicates them bit for bit):

* a row *fires* when its measure **changes** to a tripping value while the
  row is armed and its debounce interval has elapsed:
  ``trip = (m > above) | (m < below) | (|m - ref| > delta)``
* firing disarms the row; it re-arms when a later change lands back
  *inside* the band by the hysteresis margin
  (``below + hysteresis <= m <= above - hysteresis``), so a reader
  flapping across a threshold re-fires at most once per excursion;
* ``debounce`` (logical ticks = device batches) lower-bounds the spacing
  between fires of one row regardless of arming;
* ``ref`` — the delta-vs-previous baseline — re-bases to the fired value.

Unset thresholds default to the never-trip identities (``+inf`` / ``-inf``),
so a spec may use any subset of the three predicates.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregates import Aggregate
from repro.core.dataflow import PUSH

# Aggregates whose finalize output is value-shaped (comparable against a
# threshold). topk finalizes to *indices* — order predicates on it are
# meaningless, so it is rejected at registration.
ALERT_COMPATIBLE = ("sum", "count", "avg", "max", "min")


def alert_cap(default: int = 1024) -> int:
    """Fired-set capacity K (``EAGR_ALERT_CAP``): the padded per-batch fired
    buffer holds up to K (index, value) pairs. A batch firing more than K
    alerts still reports the exact set — the collector falls back to reading
    the full fired vector for that batch (rare; size K for your worst batch
    to stay on the compact path)."""
    return int(os.environ.get("EAGR_ALERT_CAP", str(default)) or default)


def alert_eval_enabled() -> bool:
    """``EAGR_ALERT_EVAL=0`` detaches alert evaluation from the write path
    (registered state is kept; nothing fires) — the A/B switch the benchmark
    uses to measure the piggyback's marginal cost."""
    return os.environ.get("EAGR_ALERT_EVAL", "1").strip() != "0"


# --------------------------------------------------------------------- specs
@dataclasses.dataclass(frozen=True)
class AlertSpec:
    """One standing predicate, broadcast over the readers it is registered
    on. ``above`` / ``below`` / ``delta`` may each be a scalar or a
    per-reader array (matched positionally against the registration's reader
    list); unset predicates never trip. ``component`` selects the payload
    lane of vector-valued aggregates."""

    above: float | np.ndarray | None = None   # fire when measure > above
    below: float | np.ndarray | None = None   # fire when measure < below
    delta: float | np.ndarray | None = None   # fire when |m - ref| > delta
    hysteresis: float = 0.0                   # re-arm margin inside the band
    debounce: float = 0.0                     # min ticks between fires
    component: int = 0                        # payload lane for vector values

    def _field(self, name: str, n: int, fill: float) -> np.ndarray:
        v = getattr(self, name)
        if v is None:
            return np.full(n, fill, np.float32)
        arr = np.broadcast_to(np.asarray(v, np.float32), (n,))
        return np.ascontiguousarray(arr)

    def tables(self, n: int) -> dict[str, np.ndarray]:
        """Dense per-reader threshold columns for ``n`` registered readers."""
        return {
            "hi": self._field("above", n, np.inf),
            "lo": self._field("below", n, -np.inf),
            "dthr": self._field("delta", n, np.inf),
            "hys": np.full(n, float(self.hysteresis), np.float32),
            "deb": np.full(n, float(self.debounce), np.float32),
            "comp": np.full(n, int(self.component), np.int32),
        }

    def to_json(self) -> dict:
        out = {"hysteresis": float(self.hysteresis),
               "debounce": float(self.debounce),
               "component": int(self.component)}
        for f in ("above", "below", "delta"):
            v = getattr(self, f)
            if v is None:
                out[f] = None
            elif np.ndim(v) == 0:
                out[f] = float(v)
            else:
                out[f] = np.asarray(v, np.float32).tolist()
        return out

    @classmethod
    def from_json(cls, d: dict) -> "AlertSpec":
        kw = {}
        for f in ("above", "below", "delta"):
            v = d.get(f)
            kw[f] = None if v is None else (
                float(v) if np.ndim(v) == 0 else np.asarray(v, np.float32))
        return cls(hysteresis=float(d.get("hysteresis", 0.0)),
                   debounce=float(d.get("debounce", 0.0)),
                   component=int(d.get("component", 0)), **kw)


class AlertState(NamedTuple):
    """Device half of the alert set: dense per-row columns over the overlay's
    node axis ((n_rows,) single-engine, (S, n_rows) stacked) so the fused
    write+eval body indexes them with no gather. Rows without an alert are
    ``active=False`` and carry never-trip thresholds."""

    active: jnp.ndarray      # bool — row has a registered alert
    armed: jnp.ndarray       # bool — eligible to fire
    hi: jnp.ndarray          # f32 upper threshold (+inf = unset)
    lo: jnp.ndarray          # f32 lower threshold (-inf = unset)
    dthr: jnp.ndarray        # f32 delta-vs-ref threshold (+inf = unset)
    hys: jnp.ndarray         # f32 hysteresis margin
    deb: jnp.ndarray         # f32 debounce (logical ticks)
    comp: jnp.ndarray        # i32 payload component
    last_fire: jnp.ndarray   # f32 eval time of the last fire (-inf = never)
    ref: jnp.ndarray         # f32 delta baseline (re-based on fire)
    last_m: jnp.ndarray      # f32 measure at the last evaluation


DYNAMIC_FIELDS = ("armed", "last_fire", "ref", "last_m")


@dataclasses.dataclass(frozen=True)
class FiredBatch:
    """One device batch's fired set, in ascending base-id order."""

    now: float               # logical eval time of the triggering batch
    base_ids: np.ndarray     # (k,) int64 fired reader base ids
    values: np.ndarray       # (k,) f32 measures at fire time
    aids: np.ndarray         # (k,) int64 alert handle id per fired reader
    overflow: bool = False   # fired count exceeded the compact capacity
                             # (set is still exact — recovered densely)

    def __len__(self) -> int:
        return len(self.base_ids)


@dataclasses.dataclass
class AlertStats:
    """Fired-set readback counters of one :class:`AlertSet`."""

    batches_collected: int = 0  # device batches whose fired set was read back
    overflow_batches: int = 0   # of those, batches whose compact buffer was
                                # full, so the dense fired/m vectors were read


# ------------------------------------------------------------- device bodies
def _measure(agg: Aggregate, pao: jnp.ndarray, comp: jnp.ndarray
             ) -> jnp.ndarray:
    """(n_rows,) finalized measure per row, at each row's payload lane."""
    fin = agg.finalize(pao)
    if fin.ndim == 1:
        fin = fin[:, None]
    c = jnp.clip(comp, 0, fin.shape[1] - 1)
    return jnp.take_along_axis(fin, c[:, None], axis=1)[:, 0]


def alert_eval(agg: Aggregate, astate: AlertState, pao: jnp.ndarray,
               now: jnp.ndarray, cap: int):
    """Evaluate every alerted row against the post-write PAO. Pure and
    jit-safe; all shapes are fixed, so the fused write+eval program keeps one
    trace per batch bucket. Returns ``(new_state, count, idx, vals, fired,
    m)`` — ``idx``/``vals`` are the compact (K,) fired buffer (-1 padded, row
    order), ``fired``/``m`` the dense vectors the collector falls back to
    when ``count > K``."""
    m = _measure(agg, pao, astate.comp)
    # only rows whose *measure* changed this batch are evaluated — untouched
    # rows compare equal by construction, so this is exactly the batch's
    # reachable-reader restriction (plus time-window expiries)
    changed = astate.active & (m != astate.last_m)
    trip = (m > astate.hi) | (m < astate.lo) | \
        (jnp.abs(m - astate.ref) > astate.dthr)
    can_fire = (now - astate.last_fire) >= astate.deb
    fired = changed & astate.armed & trip & can_fire
    inside = (m <= astate.hi - astate.hys) & (m >= astate.lo + astate.hys)
    armed = jnp.where(fired, False, astate.armed | (changed & inside))
    new = astate._replace(
        armed=armed,
        last_fire=jnp.where(fired, now, astate.last_fire),
        ref=jnp.where(fired, m, astate.ref),
        last_m=jnp.where(changed, m, astate.last_m),
    )
    idx = jnp.nonzero(fired, size=cap, fill_value=-1)[0].astype(jnp.int32)
    count = jnp.sum(fired, dtype=jnp.int32)
    vals = jnp.where(idx >= 0, m[jnp.maximum(idx, 0)], 0.0)
    return new, count, idx, vals, fired, m


def alert_write_step(step, meta, agg: Aggregate, spec, cap: int, arrays,
                     state, astate: AlertState, rows, vals, mask, *extra):
    """A write step with alert evaluation fused in: ``step`` is one of the
    pure engine write bodies (dense/sparse x sum/extremal — a static
    argument, so each combination keeps its own cache entry) and the
    evaluation reads the post-step PAO at the step's own eval instant
    (``new_now - 1``: the step increments the clock on return)."""
    ns = step(meta, agg, spec, arrays, state, rows, vals, mask, *extra)
    with jax.named_scope("eagr.alert_eval"):
        new_a, count, idx, avals, fired, m = alert_eval(
            agg, astate, ns.pao, ns.now - 1.0, cap)
    return ns, new_a, count, idx, avals, fired, m


# One jitted entry for every (step body, plan shape) combination. Non-alert
# sessions never call this — their write bodies, traces, and transfer
# behavior are untouched. Engine state and alert state are donated (callers
# rebind both every step, like the plain write bodies).
_alert_write = functools.partial(
    jax.jit, static_argnums=(0, 1, 2, 3, 4),
    donate_argnums=(6, 7))(alert_write_step)


def _reader_nodes(plan, bases: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(node, found) for each base against one plan — the dense route LUT
    when the plan carries one, the host dict otherwise (stacked shard
    plans)."""
    routes = getattr(plan, "routes", None)
    if routes is not None:
        return routes.reader_nodes(bases)
    rnb = plan.reader_node_of_base
    node = np.fromiter((rnb.get(int(b), -1) for b in bases),
                       np.int64, len(bases)).astype(np.int32)
    return node, node >= 0


# --------------------------------------------------------------- host manager
class AlertSet:
    """Host bookkeeping for the alerts attached to one engine (single or
    stacked): the registered specs as per-base SoA columns, the device
    :class:`AlertState`, row placement (base id -> (shard, node)), the
    in-flight fired buffers awaiting readback, and the host-side queue of
    collected :class:`FiredBatch` es.

    Lifecycle: ``register``/``unregister`` edit the SoA and rebuild the
    device columns via :meth:`sync`; the engine calls ``sync`` again after
    every structural patch so churn carries alert rows (retired readers drop
    out, moved readers follow their node, query-wide alerts pick up new
    readers). ``push_pending`` (engine write path) and ``collect``
    (ring-boundary readback) move fired sets host-side without adding a sync
    point."""

    def __init__(self, cap: int | None = None):
        self.cap = int(cap) if cap else alert_cap()
        self.enabled = alert_eval_enabled()
        # ------------------------- per-base SoA (registration order)
        self._base = np.zeros(0, np.int64)
        self._aid = np.zeros(0, np.int64)
        self._static = {f: np.zeros(0, np.float32) for f in
                        ("hi", "lo", "dthr", "hys", "deb")}
        self._static["comp"] = np.zeros(0, np.int32)
        self._dyn = {"armed": np.zeros(0, bool),
                     "last_fire": np.zeros(0, np.float32),
                     "ref": np.zeros(0, np.float32),
                     "last_m": np.zeros(0, np.float32)}
        self._specs: dict[int, AlertSpec] = {}
        self._dynamic_aids: set[int] = set()  # readers=None registrations
        # ------------------------- placement (rebuilt by sync)
        self._shard = np.zeros(0, np.int32)   # owner shard per base (0 single)
        self._node = np.zeros(0, np.int32)    # overlay node per base
        self._placed = np.zeros(0, bool)      # base resolved to a live row
        self._row_base: np.ndarray | None = None  # (S, n_rows) node -> base
        self._row_aid: np.ndarray | None = None   # (S, n_rows) node -> aid
        self.state: AlertState | None = None
        self._stacked = False
        # ------------------------- fired-set plumbing
        self._pending: collections.deque = collections.deque()
        self.fired: collections.deque[FiredBatch] = collections.deque()
        self.dropped_bases = 0   # alerted readers retired by churn (cumulative)
        # monotone dispatch/readback sequence numbers: the ingest ring marks
        # each slot with `seq` at dispatch and collects up to that mark when
        # the slot's token barrier proves those steps completed
        self.seq = 0        # fused steps dispatched (push_pending calls)
        self.seq_done = 0   # pending entries read back (collect pops)
        self.stats = AlertStats()

    # ------------------------------------------------------------ properties
    @property
    def n_alerts(self) -> int:
        return len(self._base)

    @property
    def n_placed(self) -> int:
        return int(np.count_nonzero(self._placed))

    def __bool__(self) -> bool:
        return self.n_alerts > 0

    # ---------------------------------------------------------- registration
    def register(self, aid: int, spec: AlertSpec, bases, *, dynamic: bool,
                 engine=None) -> None:
        """Add one spec over ``bases`` (ascending base ids). ``dynamic``
        registrations (session ``readers=None``) re-resolve to the engine's
        full reader set on every sync, so churn-added readers inherit the
        spec. Overlapping a base already alerted by another registration is
        an error — each reader row holds one predicate."""
        bases = np.unique(np.asarray(bases, np.int64).reshape(-1))
        if len(bases) == 0 and not dynamic:
            raise ValueError("register_alert: empty reader set")
        clash = np.intersect1d(bases, self._base)
        if len(clash):
            raise ValueError(
                f"readers {clash[:8].tolist()} already carry an alert; "
                "unregister it first (one predicate per reader row)")
        tables = spec.tables(len(bases))
        self._base = np.concatenate([self._base, bases])
        self._aid = np.concatenate(
            [self._aid, np.full(len(bases), aid, np.int64)])
        for f, col in tables.items():
            self._static[f] = np.concatenate([self._static[f], col])
        self._dyn["armed"] = np.concatenate(
            [self._dyn["armed"], np.ones(len(bases), bool)])
        self._dyn["last_fire"] = np.concatenate(
            [self._dyn["last_fire"], np.full(len(bases), -np.inf, np.float32)])
        # ref / last_m seed from the current measure at sync (NaN sentinel)
        for f in ("ref", "last_m"):
            self._dyn[f] = np.concatenate(
                [self._dyn[f], np.full(len(bases), np.nan, np.float32)])
        self._specs[aid] = spec
        if dynamic:
            self._dynamic_aids.add(aid)
        if engine is not None:
            try:
                self.sync(engine)
            except Exception:
                # roll the rejected registration back (e.g. PULL-decided
                # readers) so the set stays consistent for its peers
                self._take(self._aid != aid)
                self._specs.pop(aid, None)
                self._dynamic_aids.discard(aid)
                raise

    def unregister(self, aid: int, engine=None) -> None:
        if aid not in self._specs:
            return
        self._pull_dynamic()
        keep = self._aid != aid
        self._take(keep)
        del self._specs[aid]
        self._dynamic_aids.discard(aid)
        if engine is not None:
            self.sync(engine)

    def _take(self, keep: np.ndarray) -> None:
        self._base = self._base[keep]
        self._aid = self._aid[keep]
        for d in (self._static, self._dyn):
            for f in d:
                d[f] = d[f][keep]
        self._shard = self._shard[: len(self._base)]
        self._node = self._node[: len(self._base)]
        self._placed = np.zeros(len(self._base), bool)  # sync re-resolves

    # ----------------------------------------------------------------- sync
    def _plans(self, engine) -> list:
        sp = getattr(engine, "shard_plans", None)
        return list(sp) if sp is not None else [engine.plan]

    def _pull_dynamic(self) -> None:
        """Fold the device dynamic columns (armed/debounce/ref state) back
        into the per-base host mirrors at the current placement — the carry
        step before any re-layout (churn sync, checkpoint snapshot)."""
        if self.state is None or not self._placed.any():
            return
        host = {f: np.asarray(jax.device_get(getattr(self.state, f)))
                for f in DYNAMIC_FIELDS}
        p = self._placed
        for f in DYNAMIC_FIELDS:
            col = host[f] if self._stacked else host[f][None]
            self._dyn[f][p] = col[self._shard[p], self._node[p]]

    def sync(self, engine, retired=()) -> None:
        """(Re)build placement + device columns against the engine's current
        plan(s). Called at registration and after every structural patch /
        plan adoption: alerted bases follow their reader node, bases whose
        reader retired are dropped (``retired`` from the patch result speeds
        the common case; a full re-resolve catches the rest), and dynamic
        registrations pick up readers that churn added."""
        self._pull_dynamic()
        plans = self._plans(engine)
        self._stacked = getattr(engine, "shard_plans", None) is not None
        S, n_rows = len(plans), plans[0].meta.n_nodes

        if retired is not None and len(retired):
            gone = np.isin(self._base, np.asarray(list(retired), np.int64))
            if gone.any():
                self.dropped_bases += int(np.count_nonzero(gone))
                self._take(~gone)
        # dynamic registrations: adopt any reader base not yet alerted
        for aid in sorted(self._dynamic_aids):
            have = set(self._base.tolist())
            fresh = sorted(
                b for p in plans for b in p.reader_node_of_base
                if b not in have)
            if fresh:
                spec = self._specs[aid]
                del self._specs[aid]  # re-entrant register() guard
                dyn_flag = True
                self._dynamic_aids.discard(aid)
                try:
                    self.register(aid, spec, fresh, dynamic=dyn_flag)
                finally:
                    self._specs[aid] = spec
                    if dyn_flag:
                        self._dynamic_aids.add(aid)

        # ---------------------------------------------------- row placement
        M = len(self._base)
        shard = np.zeros(M, np.int32)
        node = np.full(M, -1, np.int32)
        for s, p in enumerate(plans):
            rn, ok = _reader_nodes(p, self._base) if M else \
                (np.zeros(0, np.int32), np.zeros(0, bool))
            place = ok & (node < 0)
            shard[place] = s
            node[place] = rn[place]
        placed = node >= 0
        lost = ~placed
        if lost.any():
            self.dropped_bases += int(np.count_nonzero(lost))
            self._take(placed)
            shard, node, placed = shard[placed], node[placed], \
                placed[placed]
            M = len(self._base)
        # alerts predicate on PAO currency: only PUSH-decided readers are
        # always current after a write step
        for s, p in enumerate(plans):
            mine = placed & (shard == s)
            if mine.any() and (p.decision[node[mine]] != PUSH).any():
                bad = self._base[mine][p.decision[node[mine]] != PUSH]
                raise ValueError(
                    f"alerted readers {bad[:8].tolist()} are PULL-decided — "
                    "alerts need push-maintained readers (register the query "
                    "with continuous=True)")
        self._shard, self._node, self._placed = shard, node, placed

        # ------------------------------------------- node -> base/aid LUTs
        self._row_base = np.full((S, n_rows), -1, np.int64)
        self._row_aid = np.full((S, n_rows), -1, np.int64)
        self._row_base[shard, node] = self._base
        self._row_aid[shard, node] = self._aid

        # ------------------------------------------------ measure seeding
        nan = np.isnan(self._dyn["last_m"]) | np.isnan(self._dyn["ref"])
        if nan.any():
            m = self._measures_host(engine, plans)
            for f in ("ref", "last_m"):
                col = self._dyn[f]
                col[np.isnan(col)] = m[np.isnan(col)]

        # ------------------------------------------------- device columns
        shape = (S, n_rows) if self._stacked else (n_rows,)
        cols = {
            "active": np.zeros(shape, bool),
            "armed": np.zeros(shape, bool),
            "hi": np.full(shape, np.inf, np.float32),
            "lo": np.full(shape, -np.inf, np.float32),
            "dthr": np.full(shape, np.inf, np.float32),
            "hys": np.zeros(shape, np.float32),
            "deb": np.zeros(shape, np.float32),
            "comp": np.zeros(shape, np.int32),
            "last_fire": np.full(shape, -np.inf, np.float32),
            "ref": np.zeros(shape, np.float32),
            "last_m": np.zeros(shape, np.float32),
        }
        at = (shard, node) if self._stacked else (node,)
        cols["active"][at] = True
        for f, col in self._static.items():
            cols[f][at] = col
        for f, col in self._dyn.items():
            cols[f][at] = col
        host_state = AlertState(**cols)
        put = getattr(engine, "_put_alert_state", jax.device_put)
        self.state = put(host_state)

    def _measures_host(self, engine, plans) -> np.ndarray:
        """Current finalized measure per registered base (one device_get;
        only runs at registration / churn barriers, never per batch)."""
        pao = np.asarray(jax.device_get(engine.state.pao))
        if not self._stacked:
            pao = pao[None]
        fin = engine.agg.FINALIZE(pao.reshape(-1, pao.shape[-1]))
        fin = np.asarray(fin, np.float32).reshape(pao.shape[0],
                                                  pao.shape[1], -1)
        comp = np.clip(self._static["comp"], 0, fin.shape[-1] - 1)
        return fin[self._shard, self._node, comp]

    # --------------------------------------------------------- fired plumbing
    def push_pending(self, now: float, count, idx, vals, fired, m) -> None:
        """Stash one step's device fired buffers (no transfer, no sync —
        readback happens at :meth:`collect`)."""
        self._pending.append((float(now), count, idx, vals, fired, m))
        self.seq += 1

    @property
    def pending(self) -> int:
        return len(self._pending)

    def collect(self, n: int | None = None) -> int:
        """Read back up to ``n`` pending fired sets (all when ``None``) into
        host :class:`FiredBatch` es. Callers sequence this after the device
        steps have completed (the ingest ring collects exactly the freed
        slot's batches after its token barrier), so the ``device_get`` here
        is a completed-buffer copy, not a synchronization point."""
        n = len(self._pending) if n is None else min(n, len(self._pending))
        out = 0
        for _ in range(n):
            now, count, idx, vals, fired, m = self._pending.popleft()
            self.seq_done += 1
            self.stats.batches_collected += 1
            dense0 = self.stats.overflow_batches
            with jax.profiler.TraceAnnotation(
                    "eagr.fired_readback", batch=int(now)) as span:
                cd = np.asarray(jax.device_get(count))
                # stacked: the psum'd global total, replicated over the
                # shard axis — one scalar readback regardless of shard count
                total = int(cd.reshape(-1)[0]) if cd.ndim else int(cd)
                batch = self._to_batch(now, idx, vals, fired, m) \
                    if total else None
                span.set_metadata(
                    overflow=self.stats.overflow_batches - dense0)
            if batch is not None and len(batch):
                self.fired.append(batch)
                out += 1
        return out

    def _to_batch(self, now, idx, vals, fired, m) -> FiredBatch:
        idx_h = np.asarray(jax.device_get(idx))
        vals_h = np.asarray(jax.device_get(vals))
        if not self._stacked:
            idx_h, vals_h = idx_h[None], vals_h[None]
        S = idx_h.shape[0]
        overflow = False
        rows_s, rows_n, rows_v = [], [], []
        fired_h = None
        for s in range(S):
            live = idx_h[s] >= 0
            k = int(np.count_nonzero(live))
            # per-shard overflow: the compact buffer truncated — recover the
            # exact set from the dense fired vector (rare path, one transfer)
            if k == self.cap:
                if fired_h is None:
                    self.stats.overflow_batches += 1
                    fired_h = np.asarray(jax.device_get(fired))
                    m_h = np.asarray(jax.device_get(m))
                    if not self._stacked:
                        fired_h, m_h = fired_h[None], m_h[None]
                nodes = np.flatnonzero(fired_h[s])
                if len(nodes) > k:
                    overflow = True
                    rows_s.append(np.full(len(nodes), s, np.int32))
                    rows_n.append(nodes.astype(np.int32))
                    rows_v.append(m_h[s][nodes].astype(np.float32))
                    continue
            rows_s.append(np.full(k, s, np.int32))
            rows_n.append(idx_h[s][live])
            rows_v.append(vals_h[s][live])
        sh = np.concatenate(rows_s) if rows_s else np.zeros(0, np.int32)
        nd = np.concatenate(rows_n) if rows_n else np.zeros(0, np.int32)
        vv = np.concatenate(rows_v) if rows_v else np.zeros(0, np.float32)
        bases = self._row_base[sh, nd]
        aids = self._row_aid[sh, nd]
        live = bases >= 0
        order = np.argsort(bases[live], kind="stable")
        return FiredBatch(now=now, base_ids=bases[live][order],
                          values=vv[live][order], aids=aids[live][order],
                          overflow=overflow)

    def collect_upto(self, upto: int) -> int:
        """Read back pending fired sets up through dispatch sequence ``upto``
        (a :attr:`seq` value recorded when those steps were enqueued). A
        no-op when an interleaved :meth:`collect` already drained past the
        mark, so ring-boundary bookkeeping stays correct even if the user
        drains mid-ring."""
        return self.collect(max(0, upto - self.seq_done))

    def pop_fired(self) -> list[FiredBatch]:
        out = list(self.fired)
        self.fired.clear()
        return out

    # ------------------------------------------------------------ checkpoint
    def snapshot(self) -> tuple[dict, list]:
        """Per-base packed arrays + JSON spec descriptors. The packed layout
        is placement-free (base ids, not rows), so a reshard restore places
        the same armed/debounce state onto whatever layout the restored
        session compiles — restored sessions never re-fire stale alerts."""
        self._pull_dynamic()
        arrays = {"base": self._base.copy(), "aid": self._aid.copy()}
        for d in (self._static, self._dyn):
            for f, col in d.items():
                arrays[f] = col.copy()
        specs = [{"aid": int(a), "dynamic": a in self._dynamic_aids,
                  "spec": self._specs[a].to_json()}
                 for a in sorted(self._specs)]
        return arrays, specs

    @classmethod
    def from_snapshot(cls, arrays: dict, specs: list, *,
                      cap: int | None = None) -> "AlertSet":
        alerts = cls(cap)
        alerts._base = np.asarray(arrays["base"], np.int64)
        alerts._aid = np.asarray(arrays["aid"], np.int64)
        M = len(alerts._base)
        for f in alerts._static:
            alerts._static[f] = np.asarray(
                arrays[f], alerts._static[f].dtype)
        for f in alerts._dyn:
            alerts._dyn[f] = np.asarray(arrays[f], alerts._dyn[f].dtype)
        alerts._shard = np.zeros(M, np.int32)
        alerts._node = np.full(M, -1, np.int32)
        alerts._placed = np.zeros(M, bool)
        for s in specs:
            alerts._specs[int(s["aid"])] = AlertSpec.from_json(s["spec"])
            if s.get("dynamic"):
                alerts._dynamic_aids.add(int(s["aid"]))
        return alerts


# ----------------------------------------------------------------- validation
def check_alert_aggregate(agg: Aggregate) -> int:
    """Reject aggregates whose finalize output is not value-shaped and
    return the measure dimensionality (payload lanes ``component`` may
    select)."""
    if agg.name not in ALERT_COMPATIBLE:
        raise ValueError(
            f"aggregate {agg.name!r} cannot back an alert — its finalize "
            f"output is not an ordered value (supported: "
            f"{', '.join(ALERT_COMPATIBLE)})")
    fin = np.asarray(agg.FINALIZE(np.zeros((1, agg.pao_dim), np.float32)))
    return int(fin.reshape(1, -1).shape[1])


# ------------------------------------------------------------------ poll oracle
class PollOracle:
    """The baseline this subsystem replaces, kept as the parity/bench
    reference: after every device batch, gather + ``device_get`` the
    finalized measures of **all** alerted readers (O(alerts) transfer per
    batch) and run the identical state machine on host. Same f32 values,
    same comparisons — fired sets must match the push path bit for bit."""

    def __init__(self, alerts: AlertSet):
        arrays, _ = alerts.snapshot()
        self.base = arrays["base"]
        self.aid = arrays["aid"]
        self.static = {f: arrays[f] for f in
                       ("hi", "lo", "dthr", "hys", "deb", "comp")}
        # adopt the full dynamic state, not just ref/last_m — an oracle
        # seeded from a mid-stream alert set (post-restore parity) must
        # carry armed/debounce state or it re-fires what already fired
        self.armed = arrays["armed"].copy()
        self.last_fire = arrays["last_fire"].copy()
        self.ref = arrays["ref"].copy()
        self.last_m = arrays["last_m"].copy()
        self._nodes = None

    def resync(self, engine) -> None:
        """Re-resolve reader nodes (registration / after churn)."""
        nodes, ok = _reader_nodes(engine.plan, self.base)
        keep = ok
        if not keep.all():
            self.base, self.aid = self.base[keep], self.aid[keep]
            for f in self.static:
                self.static[f] = self.static[f][keep]
            for f in ("armed", "last_fire", "ref", "last_m"):
                setattr(self, f, getattr(self, f)[keep])
            nodes = nodes[keep]
        self._nodes = jnp.asarray(nodes.astype(np.int32))

    def poll(self, engine, now: float) -> FiredBatch:
        """One poll step: the O(alerts) readback the push path avoids."""
        if self._nodes is None:
            self.resync(engine)
        fin = np.asarray(jax.device_get(
            engine.agg.finalize(engine.state.pao[self._nodes])),
            np.float32)
        if fin.ndim == 1:
            fin = fin[:, None]
        m = fin[np.arange(len(self.base)),
                np.clip(self.static["comp"], 0, fin.shape[1] - 1)]
        now32 = np.float32(now)
        changed = m != self.last_m
        trip = (m > self.static["hi"]) | (m < self.static["lo"]) | \
            (np.abs(m - self.ref) > self.static["dthr"])
        can_fire = (now32 - self.last_fire) >= self.static["deb"]
        fired = changed & self.armed & trip & can_fire
        inside = (m <= self.static["hi"] - self.static["hys"]) & \
            (m >= self.static["lo"] + self.static["hys"])
        self.armed = np.where(fired, False, self.armed | (changed & inside))
        self.last_fire = np.where(fired, now32, self.last_fire)
        self.ref = np.where(fired, m, self.ref)
        self.last_m = np.where(changed, m, self.last_m)
        hit = np.flatnonzero(fired)
        order = np.argsort(self.base[hit], kind="stable")
        return FiredBatch(now=float(now), base_ids=self.base[hit][order],
                          values=m[hit][order], aids=self.aid[hit][order])
