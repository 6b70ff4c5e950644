"""Stacked SPMD execution of reader-partitioned EAGr shards (paper §7).

``eagr_shard.partition_overlay`` + ``align_shard_plans`` already force every
shard's ``ExecPlan`` onto one ``PlanMeta`` and identical array shapes. This
module takes the remaining step: all shards' ``PlanArrays``, window state and
PAOs are stacked along a leading shard axis and write/read run as **one**
compiled program over a device mesh —

  * the incoming batch is split into per-shard chunks and **all-gathered
    on-device** (the write replication the paper describes),
  * each shard masks the gathered batch to its owned writer rows through a
    device-resident owner map (base id -> local writer row, -1 elsewhere),
  * reads run shard-local and the per-shard answers come back with a single
    ``psum`` collective (each reader lives on exactly one shard, so the sum
    over shards is a gather).

The per-shard body is the *pure* engine step (``engine.write_step_sum`` /
``write_step_extremal`` / ``read_step``) — identical math to the per-shard
host loop, which stays in ``eagr_shard`` as the parity / benchmark baseline.
On a mesh of >= n_shards devices the body runs under ``shard_map``; with
fewer devices (CPU tier-1) the same body runs under
``vmap(axis_name=SHARD_AXIS)``, so both paths trace the same collectives.

Structural churn is device-resident too: a shard's delta is lowered once
(``plan_patch.PatchProgram``) and replayed on the owning slice of the stacked
pytree under the same shard_map/vmap machinery (``_stacked_patch`` — masked,
donated, no host scatter), and owner-map rows are patched in place
(``_scatter_owner_rows``). What stays host-side: delta journaling
(``ShardedDynamic``) and the slot-pool bookkeeping inside ``plan_patch``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.aggregates import Aggregate
from repro.core.engine import (
    EngineState,
    _refresh_pao,
    plan_arrays_shard,
    read_step,
    stack_plan_arrays,
    write_step_extremal,
    write_step_extremal_sparse,
    write_step_sum,
    write_step_sum_sparse,
)
from repro.core.plan_patch import _OOB, _bucket, apply_patch_program
from repro.core.window import (
    WindowSpec,
    init_windows,
    pad_window_rows,
    place_window_shard,
    reset_window_rows,
    stack_windows,
    window_shard,
)
from repro.launch.mesh import SHARD_AXIS, make_shard_mesh

BASE_BUCKET = 256  # owner maps grow in power-of-two multiples of this


def _bucket_base_cap(n: int) -> int:
    """Owner-map capacity bucket: power-of-two multiples of BASE_BUCKET so a
    growing base-id space rarely changes the stacked program's input shapes."""
    k = -(-max(1, n) // BASE_BUCKET)
    return BASE_BUCKET * (1 << (k - 1).bit_length())


def _run_stacked(mesh, body, args):
    """Run the per-shard ``body`` over every leading-axis slice of ``args`` —
    under ``shard_map`` on a real shard mesh, else under ``vmap`` with the
    same axis name so the body's collectives mean the same thing."""
    if mesh is None:
        return jax.vmap(body, axis_name=SHARD_AXIS)(*args)

    def dev_body(*dev_args):
        # one shard per device: peel the local (length-1) shard axis so the
        # body is written once for both execution paths
        out = body(*jax.tree.map(lambda x: x[0], dev_args))
        return jax.tree.map(lambda x: x[None], out)

    specs = jax.tree.map(lambda _: P(SHARD_AXIS), args)
    return jax.shard_map(dev_body, mesh=mesh, in_specs=specs,
                         out_specs=P(SHARD_AXIS), check_vma=False)(*args)


# ------------------------------------------------------------- jit programs
# One jitted program per (meta, agg, spec, mesh) for the WHOLE stack — the
# trace-count tests assert N-shard execution compiles exactly once.
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _stacked_write_sum(meta, agg, spec, mesh, arrays, state, wmap,
                       ids, vals, valid):
    def body(arrays, state, wmap, ids_c, vals_c, valid_c):
        ids = lax.all_gather(ids_c, SHARD_AXIS, tiled=True)
        vals = lax.all_gather(vals_c, SHARD_AXIS, tiled=True)
        valid = lax.all_gather(valid_c, SHARD_AXIS, tiled=True)
        rows = wmap[jnp.clip(ids, 0, wmap.shape[0] - 1)]
        mask = valid & (rows >= 0)
        return write_step_sum(meta, agg, spec, arrays, state,
                              jnp.maximum(rows, 0), vals, mask)

    return _run_stacked(mesh, body, (arrays, state, wmap, ids, vals, valid))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _stacked_write_extremal(meta, agg, spec, mesh, arrays, state, wmap,
                            ids, vals, valid, prev_now):
    def body(arrays, state, wmap, ids_c, vals_c, valid_c, prev):
        ids = lax.all_gather(ids_c, SHARD_AXIS, tiled=True)
        vals = lax.all_gather(vals_c, SHARD_AXIS, tiled=True)
        valid = lax.all_gather(valid_c, SHARD_AXIS, tiled=True)
        rows = wmap[jnp.clip(ids, 0, wmap.shape[0] - 1)]
        mask = valid & (rows >= 0)
        return write_step_extremal(meta, agg, spec, arrays, state,
                                   jnp.maximum(rows, 0), vals, mask, prev)

    return _run_stacked(mesh, body,
                        (arrays, state, wmap, ids, vals, valid, prev_now))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _stacked_write_sum_sparse(meta, agg, spec, mesh, arrays, state, wmap,
                              ids, vals, valid, active):
    """Frontier-sparse twin of ``_stacked_write_sum``: each shard's slice of
    ``active`` (a per-level tuple of (S, K_l) arrays) is the host-expanded
    active-block list for that shard's own plan — the batch is still
    globally all-gathered, but each shard's level sweep only touches its own
    reachable blocks."""
    def body(arrays, state, wmap, ids_c, vals_c, valid_c, act):
        ids = lax.all_gather(ids_c, SHARD_AXIS, tiled=True)
        vals = lax.all_gather(vals_c, SHARD_AXIS, tiled=True)
        valid = lax.all_gather(valid_c, SHARD_AXIS, tiled=True)
        rows = wmap[jnp.clip(ids, 0, wmap.shape[0] - 1)]
        mask = valid & (rows >= 0)
        return write_step_sum_sparse(meta, agg, spec, arrays, state,
                                     jnp.maximum(rows, 0), vals, mask, act)

    return _run_stacked(mesh, body,
                        (arrays, state, wmap, ids, vals, valid, active))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _stacked_write_extremal_sparse(meta, agg, spec, mesh, arrays, state,
                                   wmap, ids, vals, valid, prev_now, active):
    def body(arrays, state, wmap, ids_c, vals_c, valid_c, prev, act):
        ids = lax.all_gather(ids_c, SHARD_AXIS, tiled=True)
        vals = lax.all_gather(vals_c, SHARD_AXIS, tiled=True)
        valid = lax.all_gather(valid_c, SHARD_AXIS, tiled=True)
        rows = wmap[jnp.clip(ids, 0, wmap.shape[0] - 1)]
        mask = valid & (rows >= 0)
        return write_step_extremal_sparse(meta, agg, spec, arrays, state,
                                          jnp.maximum(rows, 0), vals, mask,
                                          prev, act)

    return _run_stacked(
        mesh, body,
        (arrays, state, wmap, ids, vals, valid, prev_now, active))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _stacked_write_alert(step, meta, agg, spec, cap, mesh, arrays, state,
                         astate, wmap, ids, vals, valid, *extra):
    """Stacked twin of ``streams.alerts._alert_write``: the per-shard pure
    write body (``step`` — dense/sparse x sum/extremal, a static argument)
    plus the alert predicate sweep over that shard's own slice of the alert
    columns. Each reader is owned by exactly one shard, so the per-shard
    compact fired buffers are disjoint by construction and the only
    cross-shard exchange is ONE collective: the psum of the per-shard fired
    counts, which replicates the batch's global total so the host readback
    touches a single scalar."""
    def body(arrays, state, astate, wmap, ids_c, vals_c, valid_c, *extra):
        ids = lax.all_gather(ids_c, SHARD_AXIS, tiled=True)
        vals = lax.all_gather(vals_c, SHARD_AXIS, tiled=True)
        valid = lax.all_gather(valid_c, SHARD_AXIS, tiled=True)
        rows = wmap[jnp.clip(ids, 0, wmap.shape[0] - 1)]
        mask = valid & (rows >= 0)
        ns = step(meta, agg, spec, arrays, state, jnp.maximum(rows, 0),
                  vals, mask, *extra)
        from repro.streams.alerts import alert_eval
        na, count, idx, avals, fired, m = alert_eval(
            agg, astate, ns.pao, ns.now - 1.0, cap)
        total = lax.psum(count, SHARD_AXIS)
        return ns, na, total, idx, avals, fired, m

    return _run_stacked(
        mesh, body,
        (arrays, state, astate, wmap, ids, vals, valid) + extra)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _stacked_read(meta, agg, mesh, arrays, state, rmap, ids, valid):
    def body(arrays, state, rmap, ids_c, valid_c):
        ids = lax.all_gather(ids_c, SHARD_AXIS, tiled=True)
        valid = lax.all_gather(valid_c, SHARD_AXIS, tiled=True)
        nodes = rmap[jnp.clip(ids, 0, rmap.shape[0] - 1)]
        own = valid & (nodes >= 0)
        ans, _ = read_step(meta, agg, arrays, state,
                           jnp.maximum(nodes, 0), own)
        ownb = own.reshape(own.shape + (1,) * (ans.ndim - own.ndim))
        # every reader is owned by exactly one shard, so the cross-shard sum
        # of masked answers IS the gather of per-shard results
        return lax.psum(jnp.where(ownb, ans, jnp.zeros_like(ans)), SHARD_AXIS)

    out = _run_stacked(mesh, body, (arrays, state, rmap, ids, valid))
    return out[0]  # replicated across the shard axis


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _stacked_patch(mesh, arrays, prog, flags):
    """Patch ONE shard's slice of the stacked ``PlanArrays`` pytree entirely
    on device: every shard runs the same lowered ``PatchProgram`` body
    (``plan_patch.apply_patch_program``) over its own slice, and the per-shard
    flag keeps only the owning shard's patched tables — the stack is donated,
    so churn on shard k rewrites the tables in place with no host scatter and
    no desync of the mesh. One cache entry per (mesh, program-bucket)."""
    def body(arrays, prog, flag):
        patched = apply_patch_program(arrays, prog)
        return jax.tree.map(lambda p, o: jnp.where(flag, p, o),
                            patched, arrays)

    if mesh is None:
        return jax.vmap(body, in_axes=(0, None, 0),
                        axis_name=SHARD_AXIS)(arrays, prog, flags)

    def dev_body(arrays, prog, flag):
        out = body(jax.tree.map(lambda x: x[0], arrays), prog, flag[0])
        return jax.tree.map(lambda x: x[None], out)

    arr_specs = jax.tree.map(lambda _: P(SHARD_AXIS), arrays)
    prog_specs = jax.tree.map(lambda _: P(), prog)
    return jax.shard_map(dev_body, mesh=mesh,
                         in_specs=(arr_specs, prog_specs, P(SHARD_AXIS)),
                         out_specs=P(SHARD_AXIS),
                         check_vma=False)(arrays, prog, flags)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_owner_rows(m, shard, base, val):
    """Rewrite individual (shard, base-id) owner-map entries in place;
    shape-bucket padding carries an out-of-bounds base id and is dropped."""
    return m.at[shard, base].set(val, mode="drop")


# ----------------------------------------------------------------------- API
class StackedShardedEngine:
    """N reader-partitioned shards, one jit trace, one device program.

    Owns the stacked runtime state of a ``ShardedOverlay`` whose plans were
    aligned by ``align_shard_plans``:

      arrays   PlanArrays pytree, every leaf (S, ...)
      state    EngineState — windows (S, n_writers, cap), pao (S, n_nodes, d),
               now (S,)
      maps     writer_map / reader_map (S, base_cap) int32, -1 = not owned

    ``write_batch`` / ``read_batch`` take *global* batches of base ids —
    routing happens on-device (all-gather + owner-map mask), replacing the
    host-side ``shard_write_batch`` / ``shard_read_batch`` scatter. Structural
    churn patches one shard slice at a time (``apply_delta``); a growth
    fallback on any shard triggers a stack-wide realign + ``restack``.
    """

    def __init__(self, sharded, aggregate: Aggregate,
                 window: WindowSpec | None = None, *,
                 mesh: "str | object | None" = "auto",
                 base_capacity: int | None = None):
        metas = {p.meta for p in sharded.shard_plans}
        if len(metas) != 1:
            raise ValueError(
                "shard plans are not aligned to one PlanMeta — build the "
                f"ShardedOverlay through align_shard_plans (got {metas})")
        self.sharded = sharded
        self.agg = aggregate
        self.spec = window or WindowSpec(kind="tuple", size=1)
        self.meta = sharded.shard_plans[0].meta
        self.n_shards = sharded.n_shards
        self.mesh = make_shard_mesh(self.n_shards) if mesh == "auto" else mesh
        self.arrays = self._commit(stack_plan_arrays(
            [p.arrays for p in sharded.shard_plans]))
        self.state = self._commit(self.init_state())
        self._base_cap = _bucket_base_cap(base_capacity or 1)
        self._reader_owner: dict[int, int] = {}
        self._pending_retired: dict[int, list[int]] = {}
        self._needs_restack = False
        self.alerts = None  # streams.alerts.AlertSet (attach_alerts)
        self.pin_push = False  # continuous groups: churn-added nodes stay PUSH
        # host-side clocks mirror EagrEngine's; `now` advances in lockstep
        # (every global batch runs on every shard) but the last PAO-eval
        # instant is PER SHARD — a slice patch refreshes one shard's PAOs
        # without touching its siblings' expiry recompute windows
        self._now_host = 0.0
        self._last_eval_now = np.zeros(self.n_shards, np.float32)
        self.refresh_owner_maps()

    @property
    def shard_plans(self):
        """Aligned per-shard ``ExecPlan`` list (the seam ``AlertSet.sync``
        resolves reader rows against)."""
        return self.sharded.shard_plans

    def attach_alerts(self, alerts) -> None:
        """Attach an ``AlertSet`` over the stack: rows resolve to (owner
        shard, node) and every subsequent global batch runs the fused
        write+eval program with per-shard disjoint fired buffers."""
        self.alerts = alerts
        alerts.sync(self)

    def _put_alert_state(self, host_state):
        """Alert columns are stacked (S, n_rows) leaves — pin them to the
        canonical shard-axis sharding like every other stacked input so the
        fused program keeps one cache entry."""
        return self._commit(jax.device_put(host_state))

    # ------------------------------------------------------------------ state
    def _commit(self, tree):
        """Pin every stacked leaf to the canonical shard-axis sharding. Host-
        side mutations (slice patches, owner-map rebuilds) otherwise leave
        arrays with ad-hoc shardings, and jit keys its cache on input
        shardings — committing keeps the stack on ONE compiled program."""
        if self.mesh is None:
            return tree
        sh = jax.sharding.NamedSharding(self.mesh, P(SHARD_AXIS))
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def init_state(self) -> EngineState:
        windows = stack_windows(
            [init_windows(self.meta.n_writers, self.spec)
             for _ in range(self.n_shards)])
        pao = jnp.stack([self.agg.init_pao(self.meta.n_nodes)
                         for _ in range(self.n_shards)])
        return EngineState(windows, pao, jnp.zeros((self.n_shards,),
                                                   jnp.float32))

    def adopt_state(self, state: EngineState, *, now_host: float,
                    last_eval_now) -> None:
        """Adopt a restored stacked ``EngineState`` plus the host clock
        mirror and the per-shard last-PAO-eval instants (checkpoint restore
        seam). The state is committed to the canonical shard sharding and
        taken verbatim — no PAO refresh, so restored reads stay bit-identical
        to the saved session's."""
        self.state = self._commit(state)
        self._now_host = float(now_host)
        self._last_eval_now = np.asarray(last_eval_now,
                                         np.float32).reshape(-1).copy()
        if len(self._last_eval_now) != self.n_shards:
            raise ValueError(
                f"last_eval_now has {len(self._last_eval_now)} shards, "
                f"engine has {self.n_shards}")

    def refresh_owner_maps(self) -> None:
        """Rebuild the device-resident base-id routing maps from the host
        plans (after construction and after structural churn). Capacity grows
        in buckets so the stacked programs keep their traced shapes."""
        plans = self.sharded.shard_plans
        top = 0
        for p in plans:
            for m in (p.writer_row_of_base, p.reader_node_of_base):
                if m:
                    top = max(top, max(m) + 1)
        self._base_cap = max(self._base_cap, _bucket_base_cap(top))
        wmap = np.full((self.n_shards, self._base_cap), -1, np.int32)
        rmap = np.full((self.n_shards, self._base_cap), -1, np.int32)
        self._reader_owner = {}
        for s, p in enumerate(plans):
            wm, rm = p.writer_row_of_base, p.reader_node_of_base
            if wm:
                b = np.fromiter(wm.keys(), np.int64, len(wm))
                wmap[s, b] = np.fromiter(wm.values(), np.int64, len(wm))
            if rm:
                b = np.fromiter(rm.keys(), np.int64, len(rm))
                rmap[s, b] = np.fromiter(rm.values(), np.int64, len(rm))
                self._reader_owner.update((int(x), s) for x in b)
        # dense host twin of the reader map's "some shard owns this base id"
        # predicate — the read path's unknown-id check is one vectorized
        # gather against it instead of a per-id dict probe
        self._reader_known = (rmap >= 0).any(axis=0)
        self.writer_map = self._commit(jax.device_put(wmap))
        self.reader_map = self._commit(jax.device_put(rmap))

    def _chunk(self, ids: np.ndarray, vals: np.ndarray | None,
               batch_size: int | None):
        """Pad a global batch to a multiple of n_shards and split it into the
        per-shard chunks the on-device all-gather reassembles."""
        B = batch_size or max(1, len(ids))
        if B < len(ids):
            raise ValueError(f"batch_size={B} < batch of {len(ids)}")
        S = self.n_shards
        Bp = -(-B // S) * S
        idp = np.zeros(Bp, np.int32)
        idp[: len(ids)] = ids
        valid = np.zeros(Bp, bool)
        # ids outside the owner maps' range are owned by no shard (the
        # device-side clip would otherwise alias them onto base id 0)
        valid[: len(ids)] = (ids >= 0) & (ids < self._base_cap)
        # explicit device_put (never jnp.asarray): transfers stay visible to
        # transfer guards, and the arrays are freshly allocated per call so a
        # CPU zero-copy alias can't race the async dispatch
        out = [jax.device_put(idp.reshape(S, -1)),
               jax.device_put(valid.reshape(S, -1))]
        if vals is not None:
            vp = np.zeros((Bp,) + vals.shape[1:], np.float32)
            vp[: len(ids)] = vals
            out.append(jax.device_put(vp.reshape((S, -1) + vals.shape[1:])))
        return out

    # -------------------------------------------------------------- execution
    def _frontier_active(self, base_ids: np.ndarray):
        """Per-shard frontier expansion for one global batch: a ragged
        per-level tuple of stacked (S, K_l) active-block arrays, or ``None``
        for the dense stacked sweep. All shards must go sparse together (one
        program runs the whole stack), sharing each level's max bucketed
        width; shard plans are aligned, so one block count pads every slice.
        Extremal time windows stay dense — the stacked path has no
        expiry-heap bookkeeping to bound the stale set."""
        from repro.core import frontier as F

        mode = F.sparse_mode()
        if mode == "0" or self.meta.backend == "xla_unrolled":
            return None
        if self.agg.combine != "sum" and self.spec.kind == "time":
            return None
        ids = np.asarray(base_ids, np.int64).reshape(-1)
        acts = []
        for p in self.sharded.shard_plans:
            rows, mask = p.routes.writer_rows(ids)
            density = None
            if mode == "auto":
                nb = p.arrays.push.tile_of_block.shape[1]
                n_live = int(np.count_nonzero(mask))
                if nb < 8 or n_live > F.sparse_rowfrac() * p.meta.n_writers:
                    return None
                density = F.sparse_density()
            exact = self.agg.combine == "sum"
            if p.frontier is None or p.frontier.exact != exact:
                p.frontier = F.FrontierIndex.build(p, exact=exact)
            act = p.frontier.expand(np.unique(rows[mask]), density=density)
            if act is None:
                return None
            acts.append(act)
        nb = self.sharded.shard_plans[0].arrays.push.tile_of_block.shape[1]
        out = []
        for l in range(len(acts[0])):
            K = max(a[l].shape[0] for a in acts)  # max of bucketed widths
            out.append(np.stack([
                np.pad(a[l], (0, K - a[l].shape[0]), constant_values=nb)
                for a in acts]).astype(np.int32))
        return tuple(out)

    def write_batch(self, base_ids: np.ndarray, values: np.ndarray,
                    batch_size: int | None = None) -> None:
        """Apply one *global* write batch. Every shard sees the whole batch
        (the paper's write replication) and keeps the writes it consumes;
        writes owned by no shard are dropped on-device, like the single
        engine drops writes that feed no reader. When every shard's batch
        frontier expands (``EAGR_SPARSE_WRITE``), the level sweeps run the
        frontier-sparse bodies over the stacked active-block lists."""
        base_ids = np.asarray(base_ids)
        values = np.asarray(values, np.float32)
        active = self._frontier_active(base_ids)
        ids, valid, vals = self._chunk(base_ids, values, batch_size)
        if active is not None:
            act_d = jax.device_put(tuple(
                np.ascontiguousarray(a) for a in active))
        al = self.alerts
        with_alerts = al is not None and al.enabled and al.n_placed
        if self.agg.combine == "sum":
            extra = () if active is None else (act_d,)
            step = write_step_sum if active is None else \
                write_step_sum_sparse
            plain = _stacked_write_sum if active is None else \
                _stacked_write_sum_sparse
        else:
            # unlike EagrEngine there is no all-dropped-batch skip (a global
            # batch always dispatches), so no expiry-deadline bookkeeping —
            # only the per-shard prev-eval clocks the touched-writer
            # restriction needs. _last_eval_now is treated as immutable and
            # REBOUND, never mutated: jnp.asarray may zero-copy alias the
            # numpy buffer, and an in-place write would race the async
            # dispatch reading it
            prev = jax.device_put(self._last_eval_now)
            self._last_eval_now = np.full(self.n_shards, self._now_host,
                                          np.float32)
            extra = (prev,) if active is None else (prev, act_d)
            step = write_step_extremal if active is None else \
                write_step_extremal_sparse
            plain = _stacked_write_extremal if active is None else \
                _stacked_write_extremal_sparse
        if with_alerts:
            now_eval = self._now_host
            out = _stacked_write_alert(
                step, self.meta, self.agg, self.spec, al.cap, self.mesh,
                self.arrays, self.state, al.state, self.writer_map,
                ids, vals, valid, *extra)
            self.state, al.state, total, idx, avals, fired, m = out
            al.push_pending(now_eval, total, idx, avals, fired, m)
        else:
            self.state = plain(
                self.meta, self.agg, self.spec, self.mesh, self.arrays,
                self.state, self.writer_map, ids, vals, valid, *extra)
        self._now_host += 1.0

    def read_batch(self, base_ids: np.ndarray,
                   batch_size: int | None = None) -> np.ndarray:
        """Answer one global read batch: shard-local pull sweeps, one psum to
        gather the per-shard answers. Raises for base ids no shard owns."""
        base_ids = np.asarray(base_ids)
        ids64 = base_ids.astype(np.int64).reshape(-1)
        known = np.zeros(len(ids64), bool)
        inb = (ids64 >= 0) & (ids64 < len(self._reader_known))
        known[inb] = self._reader_known[ids64[inb]]
        if not known.all():
            raise ValueError(
                f"read_batch: base ids "
                f"{sorted(set(map(int, ids64[~known])))[:8]} are owned "
                f"by no shard (not readers of any shard overlay)")
        ids, valid = self._chunk(base_ids, None, batch_size)
        ans = _stacked_read(self.meta, self.agg, self.mesh, self.arrays,
                            self.state, self.reader_map, ids, valid)
        return np.asarray(jax.device_get(ans))[: len(base_ids)]

    # ----------------------------------------------------- structural updates
    def apply_delta(self, s: int, delta, *, growth: float = 2.0):
        """Patch shard ``s``'s plan (§3.3) and, when the patch stayed within
        capacity, replay the SAME lowered ``PatchProgram`` on exactly that
        slice of the stacked pytree (``_stacked_patch``, masked + donated) —
        the other shards' tables, windows and PAOs are untouched, no table
        travels through the host, and every stacked program keeps its trace.
        Owner-map rows are scattered in place the same way. A growth fallback
        defers to ``restack``."""
        from repro.core.plan_patch import patch_plan

        plan = self.sharded.shard_plans[s]
        wm_before = dict(plan.writer_row_of_base)
        rm_before = dict(plan.reader_node_of_base)
        res = patch_plan(plan, delta, overlay=self.sharded.shards[s],
                         growth=growth, pin_push=self.pin_push)
        if res.reason == "empty delta":
            return res
        self.sharded.shard_plans[s] = res.plan
        self.sharded.writer_rows[s] = res.plan.writer_row_of_base
        if res.recompiled:
            # shapes moved: the caller realigns every shard to the new padded
            # dims (ShardedDynamic.ensure_aligned) and then restacks
            self._pending_retired[s] = list(res.retired_writer_rows)
            self._needs_restack = True
            return res
        flags = np.zeros(self.n_shards, bool)
        flags[s] = True
        self.arrays = self._commit(_stacked_patch(
            self.mesh, self.arrays, res.program, jax.device_put(flags)))
        self._refresh_shard_state(s, res.retired_writer_rows)
        self._patch_owner_maps(s, wm_before, rm_before, res.plan)
        if self.alerts is not None:
            self.alerts.sync(self, retired=res.retired_reader_bases)
        return res

    def _patch_owner_maps(self, s: int, wm_before: dict, rm_before: dict,
                          plan) -> None:
        """Scatter only shard ``s``'s changed owner-map rows (base id ->
        writer row / reader node) instead of rebuilding + re-uploading the
        whole (S, base_cap) maps per delta. A base id past the current
        capacity bucket falls back to the full rebuild (a traced-shape
        growth, so the stacked programs retrace once at the crossing)."""
        wm, rm = plan.writer_row_of_base, plan.reader_node_of_base
        w_edits = [(b, r) for b, r in wm.items() if wm_before.get(b) != r]
        w_edits += [(b, -1) for b in wm_before if b not in wm]
        r_edits = [(b, n) for b, n in rm.items() if rm_before.get(b) != n]
        r_edits += [(b, -1) for b in rm_before if b not in rm]
        if not (w_edits or r_edits):
            return
        if max(b for b, _ in w_edits + r_edits) >= self._base_cap:
            self.refresh_owner_maps()
            return
        for b, n in r_edits:
            if n >= 0:
                self._reader_owner[int(b)] = s
                self._reader_known[int(b)] = True
            elif self._reader_owner.get(int(b)) == s:
                # only the still-owning shard may unregister: a reader that
                # MOVED shards may have been claimed by its new home already
                self._reader_owner.pop(int(b), None)
                self._reader_known[int(b)] = False
        if w_edits:
            self.writer_map = self._commit(
                self._scatter_map_edits(self.writer_map, s, w_edits))
        if r_edits:
            self.reader_map = self._commit(
                self._scatter_map_edits(self.reader_map, s, r_edits))

    def _scatter_map_edits(self, m, s: int, edits: list):
        k = _bucket(len(edits), 16)
        base = np.full(k, _OOB, np.int32)
        val = np.zeros(k, np.int32)
        for i, (b, v) in enumerate(edits):
            base[i], val[i] = b, v
        shard = np.full(k, s, np.int32)
        return _scatter_owner_rows(m, *jax.device_put((shard, base, val)))

    def _refresh_shard_state(self, s: int, retired_rows) -> None:
        """Migrate one shard's window/PAO slice after an in-capacity patch:
        retired writer rows are zeroed and the slice's PAOs repaired by the
        same cached ``_refresh_pao`` program single engines use."""
        win_s = window_shard(self.state.windows, s)
        if retired_rows:
            win_s = reset_window_rows(win_s, retired_rows)
        pao_s = _refresh_pao(self.meta, self.agg, self.spec,
                             plan_arrays_shard(self.arrays, s), win_s,
                             self.state.now[s])
        self.state = self._commit(EngineState(
            place_window_shard(self.state.windows, s, win_s),
            self.state.pao.at[s].set(pao_s),
            self.state.now))
        # only THIS shard's PAOs were just evaluated — its siblings keep
        # their own last-eval instants (and with them their expiry windows);
        # rebind rather than mutate (the old buffer may back a live jnp alias)
        lev = self._last_eval_now.copy()
        lev[s] = self._now_host
        self._last_eval_now = lev

    def adopt_shard_plans(self) -> None:
        """Public seam for externally replaced shard plans (a decision
        re-adoption or an out-of-band realign): re-adopt the whole stack from
        ``sharded.shard_plans``. The overlay structure is unchanged on this
        path, so windows survive by position; arrays restack, PAO slices
        refresh, owner maps rebuild."""
        self._needs_restack = True
        self.restack()

    def restack(self) -> None:
        """Re-adopt every shard plan after a stack-wide realignment (a growth
        fallback on any shard): new meta, re-stacked arrays, window rows
        padded per shard, all PAO slices refreshed, owner maps rebuilt."""
        plans = self.sharded.shard_plans
        metas = {p.meta for p in plans}
        if len(metas) != 1:
            raise ValueError(f"restack on misaligned shard plans: {metas}")
        self.meta = plans[0].meta
        self.arrays = self._commit(stack_plan_arrays([p.arrays for p in plans]))
        wins, paos = [], []
        for s in range(self.n_shards):
            w = pad_window_rows(window_shard(self.state.windows, s),
                                self.meta.n_writers)
            retired = self._pending_retired.pop(s, None)
            if retired:
                w = reset_window_rows(w, retired)
            wins.append(w)
            paos.append(_refresh_pao(self.meta, self.agg, self.spec,
                                     plan_arrays_shard(self.arrays, s), w,
                                     self.state.now[s]))
        self.state = self._commit(EngineState(stack_windows(wins),
                                              jnp.stack(paos),
                                              self.state.now))
        self._last_eval_now = np.full(self.n_shards, self._now_host,
                                      np.float32)
        self._needs_restack = False
        self.refresh_owner_maps()
        if self.alerts is not None:
            self.alerts.sync(self)
