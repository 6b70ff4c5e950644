"""Vectorized JAX execution engine over a compiled overlay (paper §2.2.2).

The paper's runtime is event-at-a-time Java with two thread pools; on TPU the
equivalent is *batched dataflow*: a batch of writes (or reads) is one jitted
program over dense arrays. The overlay is compiled (host-side, once) into a
leveled CSR ``ExecPlan``; at runtime the plan only reacts — no per-event
reasoning, which is exactly the paper's design goal.

Substrate layout. The plan is split into a hashable ``PlanMeta`` (static jit
argument: shapes + backend) and a ``PlanArrays`` pytree of *runtime* device
arrays — stacked, tile-padded per-level routing tables built through
``segment_agg.ops.make_leveled_plan``. The jitted bodies ``lax.fori_loop``
over the level axis, dynamically slicing one level's tables per iteration, so

  * program op count is constant in overlay depth, and
  * two overlays whose padded table shapes match (levels bucketed to 4,
    edge blocks to powers of two) reuse one compiled program — an overlay
    restructure (§3.3) is a table swap, not a retrace.

Per-level reduce-by-key runs on a pluggable backend chosen at plan-compile
time: ``pallas`` (the TPU segment_agg kernel; interpret mode off-TPU),
``xla`` (segment_sum/segment_max fallback), or ``xla_unrolled`` (the legacy
Python unroll over levels, kept as the benchmark baseline).

Write path (combine='sum', invertible aggregates):
    window append -> per-writer PAO delta -> per-level
    ``delta[dst] += segment_sum(sign * delta[src])`` restricted to *push* dsts.

Write path (combine='max'/'min', non-invertible):
    window append -> recompute written writers from their windows -> per-level
    recompute of push nodes (``segment_max`` over all in-edges; idempotent).

Read path (the *pull* sweep):
    demand up-sweep from requested pull readers through pull ancestors ->
    per-level masked compute down-sweep -> gather + FINALIZE at readers.

Push nodes are always current, so a read on a push reader is a single gather —
the paper's low-latency case. The per-batch work is O(|E_push|) for writes and
O(|E_pull demanded|) for reads, matching the paper's cost model amortized over
the batch.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregates import Aggregate
from repro.core.dataflow import PULL, PUSH
from repro.core.overlay import Overlay
from repro.core.window import (
    WindowSpec,
    WindowState,
    apply_writes,
    init_windows,
    pad_window_rows,
    reset_window_rows,
    stale_rows,
    window_pao,
)
from repro.kernels.segment_agg.ops import (
    E_BLK,
    R_BLK,
    chunk_blocks,
    live_extent,
    make_leveled_plan,
    segment_agg_active,
    segment_agg_gather_level,
)

BACKENDS = ("pallas", "xla", "xla_unrolled")


def bucket_batch(n: int, floor: int = 16) -> int:
    """Power-of-two batch bucketing: varying user batch sizes land on a
    handful of padded shapes, so the jitted write/read programs retrace at
    most log2(max_batch) times per engine instead of once per distinct size."""
    return max(floor, 1 << (max(1, int(n)) - 1).bit_length())


class BaseRoutes:
    """Dense base-id -> (writer row, reader node) routing tables.

    The steady-state event routing path: :meth:`writer_rows` /
    :meth:`reader_nodes` are O(B) vectorized numpy (clip + gather + validity
    mask) with zero Python work per event, replacing the per-event dict
    lookups the write/read hot paths used to run. The ``ExecPlan`` dicts stay
    authoritative for churn bookkeeping (shard owner maps, retired-writer
    accounting, the test oracle); this table mirrors them — built in bulk by
    ``compile_plan`` and maintained incrementally by ``plan_patch`` under
    churn (one table edit per delta entry, never per event). Capacity grows
    in power-of-two buckets so a new high base id rarely reallocates; absent
    entries are ``-1``.
    """

    __slots__ = ("writer_row", "reader_node")

    def __init__(self, cap: int = 1):
        cap = self._bucket(cap)
        self.writer_row = np.full(cap, -1, np.int32)
        self.reader_node = np.full(cap, -1, np.int32)

    @staticmethod
    def _bucket(n: int) -> int:
        return max(256, 1 << (max(1, int(n)) - 1).bit_length())

    @property
    def cap(self) -> int:
        return len(self.writer_row)

    @classmethod
    def from_maps(cls, writer_row_of_base: dict, reader_node_of_base: dict
                  ) -> "BaseRoutes":
        top = max((max(m) for m in (writer_row_of_base, reader_node_of_base)
                   if m), default=0)
        routes = cls(top + 1)
        for table, m in ((routes.writer_row, writer_row_of_base),
                         (routes.reader_node, reader_node_of_base)):
            if m:
                table[np.fromiter(m.keys(), np.int64, len(m))] = \
                    np.fromiter(m.values(), np.int64, len(m))
        return routes

    def _grow(self, top: int) -> None:
        if top < self.cap:
            return
        cap = self._bucket(top + 1)
        for name in ("writer_row", "reader_node"):
            old = getattr(self, name)
            new = np.full(cap, -1, np.int32)
            new[: len(old)] = old
            setattr(self, name, new)

    # ------------------------------------------- churn maintenance (per delta)
    def set_writer(self, base: int, row: int) -> None:
        self._grow(int(base))
        self.writer_row[int(base)] = row

    def clear_writer(self, base: int) -> None:
        if 0 <= int(base) < self.cap:
            self.writer_row[int(base)] = -1

    def set_reader(self, base: int, node: int) -> None:
        self._grow(int(base))
        self.reader_node[int(base)] = node

    def clear_reader(self, base: int) -> None:
        if 0 <= int(base) < self.cap:
            self.reader_node[int(base)] = -1

    # ------------------------------------------------- hot path (per batch)
    def writer_rows(self, base_ids) -> tuple[np.ndarray, np.ndarray]:
        """Route one batch: ``(rows, mask)`` with masked lanes pinned to row
        0 — the padding pattern the masked write bodies drop."""
        ids = np.asarray(base_ids, np.int64).reshape(-1)
        rows = self.writer_row[np.clip(ids, 0, self.cap - 1)]
        mask = (ids >= 0) & (ids < self.cap) & (rows >= 0)
        return np.where(mask, rows, 0).astype(np.int32), mask

    def reader_nodes(self, base_ids) -> tuple[np.ndarray, np.ndarray]:
        ids = np.asarray(base_ids, np.int64).reshape(-1)
        nodes = self.reader_node[np.clip(ids, 0, self.cap - 1)]
        mask = (ids >= 0) & (ids < self.cap) & (nodes >= 0)
        return np.where(mask, nodes, 0).astype(np.int32), mask


def default_backend() -> str:
    env = os.environ.get("EAGR_BACKEND", "").strip()
    if env:
        if env not in BACKENDS:
            raise ValueError(f"EAGR_BACKEND={env!r}; choose from {BACKENDS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


class LevelTables(NamedTuple):
    """One edge set (push or pull) as stacked per-level kernel-layout tables.

    All tables are (L, e_pad) / (L, n_blocks) with padding slots ``seg == -1``
    (source 0, sign 0) so a padded slot contributes nothing on any backend.
    ``touched`` marks, per level, the destination rows the level recomputes.
    ``live_blocks`` is derived from ``seg`` (``ops.live_extent``): build
    tables with :func:`level_tables`, which rederives it, never by hand.
    """

    seg: jnp.ndarray            # (L, e_pad) int32 destination rows, -1 pad
    src: jnp.ndarray            # (L, e_pad) int32 source rows, 0 pad
    sign: jnp.ndarray           # (L, e_pad) f32 edge signs, 0 pad
    tile_of_block: jnp.ndarray  # (L, n_blocks) int32
    first_of_tile: jnp.ndarray  # (L, n_blocks) int32
    touched: jnp.ndarray        # (L, n_nodes) bool
    live_blocks: jnp.ndarray    # (L,) int32 per-level live extent in blocks


def level_tables(seg, src, sign, tile_of_block, first_of_tile,
                 touched) -> LevelTables:
    """``LevelTables`` over the given tables, with ``live_blocks`` derived
    from ``seg`` on the device. Traceable (the patch program calls it)."""
    seg = jnp.asarray(seg)
    return LevelTables(seg=seg, src=jnp.asarray(src), sign=jnp.asarray(sign),
                       tile_of_block=jnp.asarray(tile_of_block),
                       first_of_tile=jnp.asarray(first_of_tile),
                       touched=jnp.asarray(touched),
                       live_blocks=live_extent(seg))


class PlanArrays(NamedTuple):
    """Runtime half of the plan: a pytree of device arrays (jit-traced, so
    plans with equal shapes share one compiled program)."""

    decision: jnp.ndarray       # (n_nodes,) int32 PUSH/PULL
    writer_node: jnp.ndarray    # (n_writers,) int32; padding rows -> n_nodes
    push: LevelTables
    pull: LevelTables
    demand_dst: jnp.ndarray     # (L, d_pad) int32 gather rows, pad -> n_nodes
    demand_src: jnp.ndarray     # (L, d_pad) int32 scatter rows, pad -> n_nodes


@dataclasses.dataclass(frozen=True)
class PlanMeta:
    """Static half of the plan: the shape/backing information a jitted body
    needs at trace time. Hashable; used as a static jit argument."""

    n_nodes: int
    n_writers: int
    n_levels: int        # padded level-loop trip count
    unroll_levels: int   # real depth iterated by 'xla_unrolled'; 0 for looped
                         # backends so restructures with equal padded shapes
                         # share one jit cache entry
    n_row_tiles: int
    backend: str
    interpret: bool
    bf16: bool = False   # EAGR_SEGAGG_BF16: edge values stream as bfloat16
                         # (2x VMEM headroom); accumulation stays fp32


@dataclasses.dataclass
class ExecPlan:
    """Host-compiled execution plan: the overlay as dense leveled-CSR tables.

    No Python-level per-level edge lists — the per-level structure lives in
    the stacked ``PlanArrays`` tables; only host-side id maps stay as dicts.
    """

    meta: PlanMeta
    arrays: PlanArrays
    depth: int                           # real overlay depth (levels)
    decision: np.ndarray                 # (n,) PUSH/PULL (host copy)
    level: np.ndarray                    # (n,)
    writer_node: np.ndarray              # (n_writers,) overlay node per row
    writer_row_of_base: dict[int, int]   # base id -> window row
    reader_node_of_base: dict[int, int]  # base id -> overlay node
    routes: "BaseRoutes | None" = None   # dense mirror of the two dicts —
                                         # the vectorized hot-path router
    n_push_edges: int = 0
    n_pull_edges: int = 0
    host: object | None = None           # plan_patch.PlanHost mirror (lazy);
                                         # owned by the incremental patch path
    patches_applied: int = 0
    frontier: object | None = None       # frontier.FrontierIndex — writer-row
                                         # -> per-level push blocks, built
                                         # lazily on first sparse write
    reader_frontier: object | None = None  # frontier.ReaderFrontierIndex —
                                           # read-path twin; invalidated by
                                           # any structural patch
    _push_live: tuple | None = dataclasses.field(default=None, repr=False)
                                         # (device leaf, its host copy)

    @property
    def n_nodes(self) -> int:
        return self.meta.n_nodes

    @property
    def n_levels(self) -> int:
        return self.depth

    @property
    def n_writers(self) -> int:
        return len(self.writer_node)

    def push_live_blocks(self) -> np.ndarray:
        """Host copy of the push tables' ``live_blocks``: read back once per
        table set (after a compile, a patch or a restore swaps the leaf),
        never per step."""
        leaf = self.arrays.push.live_blocks
        if self._push_live is None or self._push_live[0] is not leaf:
            self._push_live = (leaf, np.asarray(jax.device_get(leaf)))
        return self._push_live[1]

    def dense_sweep_blocks(self, width: int) -> tuple[int, int]:
        """``(visited, padded)`` edge blocks of one dense push sweep over
        values ``width`` lanes wide: the blocks the bounded level and chunk
        loops visit (each live level's extent rounded up to whole chunks on
        ``pallas``, whole levels on ``xla``), and all ``L x n_blocks``."""
        live = self.push_live_blocks()
        L, nb = self.arrays.push.tile_of_block.shape
        if self.meta.backend == "xla_unrolled":
            return self.depth * nb, L * nb
        if self.meta.backend == "pallas":
            c = chunk_blocks(nb, width, self.meta.bf16)
            return int(sum(-(-int(b) // c) * c for b in live)), L * nb
        return int(_live_levels_host(live)) * nb, L * nb


def _build_tables(per_level: list[list[tuple[int, int, int]]],
                  pad_levels: int | None, pad_blocks: int | None,
                  pad_nodes: int) -> LevelTables:
    """Stack one edge set's per-level (src, dst, sign) triples into padded
    kernel-layout tables via ``make_leveled_plan``."""
    segs, srcs, signs = [], [], []
    for tris in per_level:
        arr = np.asarray(tris, dtype=np.int64).reshape(-1, 3)
        segs.append(arr[:, 1])
        srcs.append(arr[:, 0])
        signs.append(arr[:, 2])
    lp = make_leveled_plan(segs, pad_nodes, pad_levels=pad_levels,
                           pad_blocks=pad_blocks)
    L, E = lp.n_levels, lp.e_pad
    src = np.zeros((L, E), np.int32)
    sign = np.zeros((L, E), np.float32)
    touched = np.zeros((L, pad_nodes), bool)
    for l in range(len(segs)):
        src[l] = lp.layout(l, srcs[l].astype(np.int32), fill=0)
        sign[l] = lp.layout(l, signs[l].astype(np.float32), fill=0.0)
        touched[l, segs[l]] = True
    return level_tables(lp.seg, src, sign, lp.tile_of_block,
                        lp.first_of_tile, touched)


@dataclasses.dataclass(frozen=True)
class PlanPad:
    """Explicit padding targets so several plans (e.g. sibling shards) share
    one compiled program shape. Any field left at 0 keeps the natural size."""

    n_nodes: int = 0
    n_writers: int = 0
    n_levels: int = 0
    push_blocks: int = 0
    pull_blocks: int = 0
    demand_edges: int = 0


def _collect_levels(overlay: Overlay, decision: np.ndarray, level: np.ndarray):
    """Split overlay edges into per-level push/pull/demand triples."""
    n_levels = int(level.max()) if overlay.n_nodes else 0
    per_level_push: list[list[tuple[int, int, int]]] = [[] for _ in range(n_levels)]
    per_level_pull: list[list[tuple[int, int, int]]] = [[] for _ in range(n_levels)]
    per_level_demand: list[list[tuple[int, int]]] = [[] for _ in range(n_levels)]
    for dst in range(overlay.n_nodes):
        l = int(level[dst]) - 1
        for src, sign in overlay.in_edges[dst]:
            if decision[dst] == PUSH:
                per_level_push[l].append((src, dst, sign))
            else:
                per_level_pull[l].append((src, dst, sign))
                if decision[src] == PULL:
                    per_level_demand[l].append((dst, src))
    return per_level_push, per_level_pull, per_level_demand, n_levels


def measure_plan(overlay: Overlay, decisions: np.ndarray) -> PlanPad:
    """The padded table dimensions ``compile_plan`` would produce, computed
    host-side without building or uploading any tables — equal to
    ``plan_dims(compile_plan(overlay, decisions))``. Used to align several
    plans (e.g. sibling shards) before compiling each exactly once."""
    from repro.kernels.segment_agg.ops import leveled_plan_blocks

    decision = np.asarray(decisions, dtype=np.int64)
    level = overlay.levels()
    push, pull, demand, n_levels = _collect_levels(overlay, decision, level)

    def bucket_blocks(per_level):
        nb = leveled_plan_blocks(
            [np.asarray(t, np.int64).reshape(-1, 3)[:, 1] for t in per_level])
        return 1 << (nb - 1).bit_length()

    d_real = max((len(p) for p in demand), default=0)
    return PlanPad(
        n_nodes=overlay.n_nodes,
        n_writers=len(overlay.writer_nodes()),
        n_levels=max(1, -(-n_levels // 4) * 4),
        push_blocks=bucket_blocks(push),
        pull_blocks=bucket_blocks(pull),
        demand_edges=max(1, -(-d_real // 256) * 256),
    )


def compile_plan(overlay: Overlay, decisions: np.ndarray, *,
                 backend: str | None = None,
                 pad: PlanPad | None = None) -> ExecPlan:
    backend = backend or default_backend()
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
    pad = pad or PlanPad()
    level = overlay.levels()
    decision = np.asarray(decisions, dtype=np.int64)
    n_nodes = max(overlay.n_nodes, pad.n_nodes)

    writers = overlay.writer_nodes()
    writer_node = np.array(writers, dtype=np.int64)
    writer_row_of_base = {overlay.origin[v]: i for i, v in enumerate(writers)}
    reader_node_of_base = {overlay.origin[v]: v for v in overlay.reader_nodes()}

    per_level_push, per_level_pull, per_level_demand, n_levels = \
        _collect_levels(overlay, decision, level)

    pad_levels = pad.n_levels or None
    push = _build_tables(per_level_push, pad_levels,
                         pad.push_blocks or None, n_nodes)
    pull = _build_tables(per_level_pull, pad_levels,
                         pad.pull_blocks or None, n_nodes)
    L = push.seg.shape[0]

    d_real = max((len(p) for p in per_level_demand), default=0)
    d_pad = max(pad.demand_edges, max(1, -(-d_real // 256) * 256))
    demand_dst = np.full((L, d_pad), n_nodes, np.int32)
    demand_src = np.full((L, d_pad), n_nodes, np.int32)
    for l, pairs in enumerate(per_level_demand):
        if pairs:
            arr = np.asarray(pairs, dtype=np.int64)
            demand_dst[l, : len(pairs)] = arr[:, 0]
            demand_src[l, : len(pairs)] = arr[:, 1]

    n_writers = max(len(writer_node), pad.n_writers)
    wnode = np.full(n_writers, n_nodes, np.int32)
    wnode[: len(writer_node)] = writer_node

    dec_pad = np.full(n_nodes, PULL, np.int64)
    dec_pad[: overlay.n_nodes] = decision

    meta = PlanMeta(
        n_nodes=n_nodes,
        n_writers=n_writers,
        n_levels=L,
        unroll_levels=n_levels if backend == "xla_unrolled" else 0,
        n_row_tiles=max(1, -(-n_nodes // R_BLK)),
        backend=backend,
        interpret=(backend == "pallas" and jax.default_backend() != "tpu"),
        bf16=os.environ.get("EAGR_SEGAGG_BF16", "0").strip() == "1",
    )
    arrays = PlanArrays(
        decision=jnp.asarray(dec_pad, jnp.int32),
        writer_node=jnp.asarray(wnode),
        push=push,
        pull=pull,
        demand_dst=jnp.asarray(demand_dst),
        demand_src=jnp.asarray(demand_src),
    )
    return ExecPlan(
        meta=meta,
        arrays=arrays,
        depth=n_levels,
        decision=decision,
        level=level,
        writer_node=writer_node,
        writer_row_of_base=writer_row_of_base,
        reader_node_of_base=reader_node_of_base,
        routes=BaseRoutes.from_maps(writer_row_of_base, reader_node_of_base),
        n_push_edges=sum(len(p) for p in per_level_push),
        n_pull_edges=sum(len(p) for p in per_level_pull),
    )


def plan_dims(plan: ExecPlan) -> PlanPad:
    """The plan's padded table dimensions, as alignment targets."""
    return PlanPad(
        n_nodes=plan.meta.n_nodes,
        n_writers=plan.meta.n_writers,
        n_levels=plan.meta.n_levels,
        push_blocks=plan.arrays.push.seg.shape[1] // E_BLK,
        pull_blocks=plan.arrays.pull.seg.shape[1] // E_BLK,
        demand_edges=plan.arrays.demand_dst.shape[1],
    )


def grow_pad(pad: PlanPad, growth: float = 2.0) -> PlanPad:
    """Scale padding targets by ``growth`` so a plan compiled now has slot /
    node / level headroom for structural churn (§3.3): in-capacity updates
    then patch the tables in place instead of recompiling."""
    g = max(1.0, float(growth))

    def up(x, mult):
        x = max(1, int(np.ceil(x * g)))
        return -(-x // mult) * mult

    blocks = lambda x: 1 << (max(1, int(np.ceil(x * g))) - 1).bit_length()
    return PlanPad(
        n_nodes=up(pad.n_nodes, R_BLK),
        n_writers=up(pad.n_writers, 8),
        n_levels=up(pad.n_levels, 4),
        push_blocks=blocks(pad.push_blocks),
        pull_blocks=blocks(pad.pull_blocks),
        demand_edges=up(pad.demand_edges, 256),
    )


class EngineState(NamedTuple):
    windows: WindowState
    pao: jnp.ndarray      # (n_nodes, pao_dim)
    now: jnp.ndarray      # scalar fp32 logical clock


# ------------------------------------------------------------ level execution
def _level_reduce(meta: PlanMeta, tables: LevelTables, l, val: jnp.ndarray,
                  op: str) -> jnp.ndarray:
    """Reduce-by-destination of one level's edge contributions gathered from
    ``val`` (n_nodes, F). ``l`` may be traced (fori_loop) or a Python int
    (xla_unrolled). Rows outside the level's touched set are undefined —
    callers mask. op: 'sum' (signed) | 'max' | 'min'. On ``pallas`` the
    gather runs chunk by chunk inside the kernel's chunk loop, which stops
    at the level's live extent."""
    with jax.named_scope("eagr.level_gather"):
        seg, src, sign = tables.seg[l], tables.src[l], tables.sign[l]
    if meta.backend == "pallas":
        with jax.named_scope("eagr.level_gather"):
            tob, fot = tables.tile_of_block[l], tables.first_of_tile[l]
            vk = -val if op == "min" else val
        out = segment_agg_gather_level(
            vk, src, sign if op == "sum" else None, seg, tob, fot,
            tables.live_blocks[l], n_rows=meta.n_nodes,
            n_row_tiles=meta.n_row_tiles,
            op="max" if op in ("max", "min") else "sum",
            interpret=meta.interpret, bf16=meta.bf16)
        return -out if op == "min" else out
    with jax.named_scope("eagr.level_gather"):
        x = val[src]
        if op == "sum":
            x = x * sign[:, None]
    if meta.bf16:
        # match the pallas bf16 semantics: edge values rounded to bfloat16,
        # the segment reduction itself in fp32
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    dst = jnp.where(seg >= 0, seg, meta.n_nodes)
    if op == "sum":
        out = jax.ops.segment_sum(x, dst, num_segments=meta.n_nodes + 1)
    elif op == "max":
        out = jax.ops.segment_max(x, dst, num_segments=meta.n_nodes + 1)
    else:
        out = jax.ops.segment_min(x, dst, num_segments=meta.n_nodes + 1)
    return out[: meta.n_nodes]


# --------------------------------------------------- frontier-sparse execution
def _gather_active(meta: PlanMeta, tables: LevelTables, l, active_l):
    """Compact one level's edge tables to its K active blocks. ``active_l``
    is (K,) int32 ascending block indices, padded with ``n_blocks`` —
    padding lanes gather a real block but are neutralized to the slot-padding
    pattern (seg -1, src 0, sign 0) every backend already drops."""
    nb = tables.tile_of_block.shape[1]
    with jax.named_scope("eagr.level_gather"):
        ab = jnp.minimum(active_l, nb - 1)
        valid = (active_l < nb)[:, None]
        seg_c = jnp.where(valid, tables.seg[l].reshape(nb, E_BLK)[ab], -1)
        src_c = jnp.where(valid, tables.src[l].reshape(nb, E_BLK)[ab], 0)
        sign_c = jnp.where(valid, tables.sign[l].reshape(nb, E_BLK)[ab], 0.0)
        tob_c = tables.tile_of_block[l][ab]
    return (seg_c.reshape(-1), src_c.reshape(-1), sign_c.reshape(-1), tob_c)


def _level_reduce_active(meta: PlanMeta, seg_c, src_c, sign_c, tob_c,
                         val: jnp.ndarray, op: str) -> jnp.ndarray:
    """``_level_reduce`` over a compacted active-block edge subset: the
    gather, the kernel grid, and the segment reduction all shrink from the
    level's padded edge capacity to K*E_BLK."""
    with jax.named_scope("eagr.level_gather"):
        x = val[src_c]
        if op == "sum":
            x = x * sign_c[:, None]
    if meta.backend == "pallas":
        kern_op = "max" if op in ("max", "min") else "sum"
        xk = -x if op == "min" else x
        out = segment_agg_active(
            xk, seg_c, tob_c, n_rows=meta.n_nodes,
            n_row_tiles=meta.n_row_tiles, op=kern_op,
            interpret=meta.interpret, bf16=meta.bf16)
        return -out if op == "min" else out
    if meta.bf16:
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    dst = jnp.where(seg_c >= 0, seg_c, meta.n_nodes)
    if op == "sum":
        out = jax.ops.segment_sum(x, dst, num_segments=meta.n_nodes + 1)
    elif op == "max":
        out = jax.ops.segment_max(x, dst, num_segments=meta.n_nodes + 1)
    else:
        out = jax.ops.segment_min(x, dst, num_segments=meta.n_nodes + 1)
    return out[: meta.n_nodes]


def _row_active(meta: PlanMeta, seg_c) -> jnp.ndarray:
    """(n_nodes,) bool: destinations with at least one active edge this
    level. The sparse twin of ``touched`` — the index guarantees any
    destination with a nonzero/changed contribution has its *whole* slot
    range active, so masking to these rows is exact, and rows sharing a
    block with the frontier but outside it see only zero contributions."""
    dst = jnp.where(seg_c >= 0, seg_c, meta.n_nodes)
    return jnp.zeros((meta.n_nodes + 1,), bool).at[dst].set(
        True, mode="promise_in_bounds")[: meta.n_nodes]


def _live_levels(tables: LevelTables) -> jnp.ndarray:
    """Traced level-loop bound: one past the last level with a live block
    (0 when none). Levels past it hold only padding and touch no row."""
    lb = tables.live_blocks
    return jnp.max(jnp.where(
        lb > 0, jnp.arange(1, lb.shape[-1] + 1, dtype=jnp.int32), 0))


def _live_levels_host(live: np.ndarray) -> int:
    """Host twin of :func:`_live_levels` over a host copy of the extents."""
    hit = np.flatnonzero(np.asarray(live) > 0)
    return int(hit[-1]) + 1 if hit.size else 0


def _level_loop(meta: PlanMeta, body, init, tables: LevelTables):
    """fori_loop over the levels up to ``tables``' last live one (a traced
    bound, so churn that moves it never retraces) — or the legacy Python
    unroll over real levels for the 'xla_unrolled' baseline backend."""
    if meta.backend == "xla_unrolled":
        for l in range(meta.unroll_levels):
            init = body(l, init)
        return init
    return jax.lax.fori_loop(0, _live_levels(tables), body, init)


# ---------------------------------------------------------------- step bodies
# The write/read/refresh bodies are *pure* functions of
# ``(meta, agg, spec, arrays, state, batch) -> state`` with no per-engine
# Python state: ``meta`` is static shape info, everything else is traced
# arrays. They are exposed unjitted (``write_step_sum`` etc.) so callers can
# embed them in larger programs — ``distributed/stacked.py`` vmaps/shard_maps
# them over a leading shard axis — while the jitted single-engine wrappers
# below keep their own cache entries.
def _sum_writes(meta: PlanMeta, agg: Aggregate, spec: WindowSpec,
                arrays: PlanArrays, state: EngineState, rows, vals, mask):
    """Head of the sum write bodies: the window update, and each written
    writer's PAO delta (new entries less evicted ones) scattered onto its
    overlay node. Returns ``(windows, delta)``."""
    with jax.named_scope("eagr.apply_writes"):
        windows, evicted, evicted_valid = apply_writes(
            state.windows, spec, rows, vals,
            jnp.full(rows.shape, state.now, jnp.float32), mask)
        delta_w = agg.lift(vals) * mask[:, None].astype(jnp.float32)
        delta_w -= agg.lift(evicted) \
            * evicted_valid[:, None].astype(jnp.float32)
        delta = jnp.zeros((meta.n_nodes, agg.pao_dim), dtype=jnp.float32)
        return windows, delta.at[arrays.writer_node[rows]].add(
            delta_w, mode="drop")


def _extremal_writes(meta: PlanMeta, agg: Aggregate, spec: WindowSpec,
                     arrays: PlanArrays, state: EngineState, rows, vals, mask,
                     prev_now):
    """Head of the extremal write bodies: the window update, the touched
    writers' recomputed PAOs written onto their overlay nodes, and the
    changed-node mask the level sweep propagates. Returns ``(windows, pao,
    changed)``."""
    with jax.named_scope("eagr.apply_writes"):
        windows, _, _ = apply_writes(
            state.windows, spec, rows, vals,
            jnp.full(rows.shape, state.now, jnp.float32), mask)
        wp = window_pao(windows, spec, agg, now=state.now)
        touched_w = jnp.zeros((meta.n_writers,), bool).at[rows].max(
            mask, mode="drop")
        if spec.kind == "time":  # tuple windows only evict on write
            touched_w = touched_w | stale_rows(state.windows, spec, prev_now,
                                               state.now)
        old_w = state.pao[jnp.minimum(arrays.writer_node, meta.n_nodes - 1)]
        new_w = jnp.where(touched_w[:, None], wp, old_w)
        pao = state.pao.at[arrays.writer_node].set(new_w, mode="drop")
        changed = jnp.zeros((meta.n_nodes + 1,), bool).at[
            arrays.writer_node].max(touched_w, mode="promise_in_bounds")
        return windows, pao, changed


def write_step_sum(meta: PlanMeta, agg: Aggregate, spec: WindowSpec,
                   arrays: PlanArrays, state: EngineState, rows, vals, mask):
    windows, delta = _sum_writes(meta, agg, spec, arrays, state, rows,
                                 vals, mask)

    def level(l, delta):
        contrib = _level_reduce(meta, arrays.push, l, delta, "sum")
        # untouched rows are undefined kernel output (uninitialized tiles) —
        # only the level's destinations may accumulate
        with jax.named_scope("eagr.level_combine"):
            return delta + jnp.where(arrays.push.touched[l][:, None],
                                     contrib, 0.0)

    delta = _level_loop(meta, level, delta, arrays.push)
    with jax.named_scope("eagr.level_combine"):
        pao = state.pao + delta
    return EngineState(windows, pao, state.now + 1.0)


def write_step_extremal(meta: PlanMeta, agg: Aggregate, spec: WindowSpec,
                        arrays: PlanArrays, state: EngineState, rows, vals,
                        mask, prev_now):
    """Non-invertible write path, restricted to the *touched* writer set: the
    rows written this batch plus (time windows) the rows with an entry that
    expired since ``prev_now`` — the last instant writer PAOs were evaluated.
    Untouched rows keep their stored PAO (identical to recomputing them), and
    the level sweep only overwrites destinations downstream of a touched
    writer, so the recompute is confined to the changed closure instead of
    every writer and every push node per batch."""
    windows, pao, changed = _extremal_writes(
        meta, agg, spec, arrays, state, rows, vals, mask, prev_now)

    def level(l, carry):
        pao, changed = carry
        new = _level_reduce(meta, arrays.push, l, pao, agg.combine)
        with jax.named_scope("eagr.level_combine"):
            seg = arrays.push.seg[l]
            dst = jnp.where(seg >= 0, seg, meta.n_nodes)
            ch = jax.ops.segment_max(
                changed[arrays.push.src[l]].astype(jnp.int32), dst,
                num_segments=meta.n_nodes + 1) > 0
            upd = arrays.push.touched[l] & ch[: meta.n_nodes]
            pao = jnp.where(upd[:, None], new, pao)
            changed = changed.at[: meta.n_nodes].max(upd)
            return pao, changed

    pao, _ = _level_loop(meta, level, (pao, changed), arrays.push)
    return EngineState(windows, pao, state.now + 1.0)


def refresh_pao_step(meta: PlanMeta, agg: Aggregate, spec: WindowSpec,
                     arrays: PlanArrays, windows, now) -> jnp.ndarray:
    """Recompute the full PAO array from the writer windows through the push
    tables — the state repair after a structural patch (``apply_delta``):
    rewired push nodes get exact values, retired rows fall back to the
    aggregate identity, pull rows are left for the read-path demand sweep.
    One cached program per plan shape, so in-capacity churn never retraces."""
    wp = window_pao(windows, spec, agg, now=now)
    pao = agg.init_pao(meta.n_nodes)
    pao = pao.at[arrays.writer_node].set(wp[: meta.n_writers], mode="drop")

    def level(l, pao):
        new = _level_reduce(meta, arrays.push, l, pao, agg.combine)
        with jax.named_scope("eagr.level_combine"):
            return jnp.where(arrays.push.touched[l][:, None], new, pao)

    return _level_loop(meta, level, pao, arrays.push)


@jax.named_scope("eagr.read_step")
def read_step(meta: PlanMeta, agg: Aggregate, arrays: PlanArrays,
              state: EngineState, reader_nodes, mask):
    decision = arrays.decision
    demand = jnp.zeros((meta.n_nodes + 1,), dtype=jnp.bool_)
    is_pull_target = mask & (decision[reader_nodes] == PULL)
    demand = demand.at[reader_nodes].max(is_pull_target)

    # demand pairs of level l exist only beside pull edges of level l, so
    # the pull tables' live levels bound both sweeps
    top = meta.unroll_levels if meta.backend == "xla_unrolled" \
        else _live_levels(arrays.pull)

    def demand_level(i, demand):  # dst level descending
        l = top - 1 - i
        return demand.at[arrays.demand_src[l]].max(demand[arrays.demand_dst[l]])

    demand = _level_loop(meta, demand_level, demand, arrays.pull)
    take = (demand[: meta.n_nodes] & (decision == PULL))[:, None]
    val = state.pao

    def level(l, val):  # level ascending
        computed = _level_reduce(meta, arrays.pull, l, val, agg.combine)
        # only overwrite rows that this level actually computed
        with jax.named_scope("eagr.level_combine"):
            return jnp.where(take & arrays.pull.touched[l][:, None],
                             computed, val)

    val = _level_loop(meta, level, val, arrays.pull)
    answers = val[reader_nodes]
    return agg.finalize(answers), answers


# Frontier-sparse twins of the step bodies: identical math, but each level
# gathers only the batch frontier's active edge blocks (``active`` is the
# (L, K) host-expanded block list — see ``core/frontier.py`` for why a
# superset of the reachable blocks is bit-identical to the dense sweep).
# One cached trace per (batch bucket, K bucket); callers fall back to the
# dense bodies when the frontier is too dense to pay for the gather.
def write_step_sum_sparse(meta: PlanMeta, agg: Aggregate, spec: WindowSpec,
                          arrays: PlanArrays, state: EngineState, rows, vals,
                          mask, active):
    windows, delta = _sum_writes(meta, agg, spec, arrays, state, rows,
                                 vals, mask)

    # Python unroll, not fori_loop: the active tuple is ragged (one bucketed
    # width per level), and levels whose frontier is empty cost nothing
    for l in range(meta.n_levels):
        if active[l].shape[0] == 0:
            continue
        seg_c, src_c, sign_c, tob_c = _gather_active(
            meta, arrays.push, l, active[l])
        contrib = _level_reduce_active(
            meta, seg_c, src_c, sign_c, tob_c, delta, "sum")
        with jax.named_scope("eagr.level_combine"):
            ra = arrays.push.touched[l] & _row_active(meta, seg_c)
            delta = delta + jnp.where(ra[:, None], contrib, 0.0)
    with jax.named_scope("eagr.level_combine"):
        pao = state.pao + delta
    return EngineState(windows, pao, state.now + 1.0)


def write_step_extremal_sparse(meta: PlanMeta, agg: Aggregate,
                               spec: WindowSpec, arrays: PlanArrays,
                               state: EngineState, rows, vals, mask,
                               prev_now, active):
    windows, pao, changed = _extremal_writes(
        meta, agg, spec, arrays, state, rows, vals, mask, prev_now)

    for l in range(meta.n_levels):  # ragged active tuple: Python unroll
        if active[l].shape[0] == 0:
            continue
        seg_c, src_c, sign_c, tob_c = _gather_active(
            meta, arrays.push, l, active[l])
        new = _level_reduce_active(
            meta, seg_c, src_c, sign_c, tob_c, pao, agg.combine)
        with jax.named_scope("eagr.level_combine"):
            dst = jnp.where(seg_c >= 0, seg_c, meta.n_nodes)
            ch = jax.ops.segment_max(
                changed[src_c].astype(jnp.int32), dst,
                num_segments=meta.n_nodes + 1) > 0
            upd = arrays.push.touched[l] & ch[: meta.n_nodes]
            pao = jnp.where(upd[:, None], new, pao)
            changed = changed.at[: meta.n_nodes].max(upd)
    return EngineState(windows, pao, state.now + 1.0)


DEM_CHUNK = 256  # demand slots per active chunk (d_pad is a multiple of 256)


@jax.named_scope("eagr.read_step")
def read_step_sparse(meta: PlanMeta, agg: Aggregate, arrays: PlanArrays,
                     state: EngineState, reader_nodes, mask, dem_active,
                     pull_active):
    """``read_step`` with the demand up-sweep restricted to active
    DEM_CHUNK-slot chunks and the pull down-sweep to active edge blocks —
    both expanded host-side from ``ReaderFrontierIndex``."""
    decision = arrays.decision
    nc = arrays.demand_dst.shape[1] // DEM_CHUNK
    demand = jnp.zeros((meta.n_nodes + 1,), dtype=jnp.bool_)
    is_pull_target = mask & (decision[reader_nodes] == PULL)
    demand = demand.at[reader_nodes].max(is_pull_target)

    # d_pad below one chunk means the plan has no real demand pairs at all
    # (compile_plan only leaves d_pad=1 when d_real == 0): the sweep is a
    # no-op, and reshaping to (0, DEM_CHUNK) chunks would be ill-formed.
    # Python unroll over the ragged active tuples, dst level descending;
    # levels with no active chunks cost nothing
    if nc:
        for l in range(meta.n_levels - 1, -1, -1):
            if dem_active[l].shape[0] == 0:
                continue
            ac = jnp.minimum(dem_active[l], nc - 1)
            validc = (dem_active[l] < nc)[:, None]
            dsts = jnp.where(
                validc, arrays.demand_dst[l].reshape(nc, DEM_CHUNK)[ac],
                meta.n_nodes).reshape(-1)
            srcs = jnp.where(
                validc, arrays.demand_src[l].reshape(nc, DEM_CHUNK)[ac],
                meta.n_nodes).reshape(-1)
            demand = demand.at[srcs].max(demand[dsts])
    take = (demand[: meta.n_nodes] & (decision == PULL))[:, None]
    val = state.pao

    for l in range(meta.n_levels):  # level ascending
        if pull_active[l].shape[0] == 0:
            continue
        seg_c, src_c, sign_c, tob_c = _gather_active(
            meta, arrays.pull, l, pull_active[l])
        computed = _level_reduce_active(
            meta, seg_c, src_c, sign_c, tob_c, val, agg.combine)
        with jax.named_scope("eagr.level_combine"):
            ra = arrays.pull.touched[l] & _row_active(meta, seg_c)
            val = jnp.where(take & ra[:, None], computed, val)
    answers = val[reader_nodes]
    return agg.finalize(answers), answers


# Single-engine jitted entry points over the pure step bodies. The write
# bodies donate the engine state: the window/PAO buffers are rewritten in
# place (callers always rebind ``eng.state`` to the result — the consumed
# pytree must never be read again), which keeps steady-state ingest from
# allocating a fresh multi-MB state per batch.
_write_body_sum = functools.partial(
    jax.jit, static_argnums=(0, 1, 2), donate_argnums=(4,))(write_step_sum)
_write_body_extremal = functools.partial(
    jax.jit, static_argnums=(0, 1, 2), donate_argnums=(4,))(write_step_extremal)
_write_body_sum_sparse = functools.partial(
    jax.jit, static_argnums=(0, 1, 2),
    donate_argnums=(4,))(write_step_sum_sparse)
_write_body_extremal_sparse = functools.partial(
    jax.jit, static_argnums=(0, 1, 2),
    donate_argnums=(4,))(write_step_extremal_sparse)
_refresh_pao = functools.partial(
    jax.jit, static_argnums=(0, 1, 2))(refresh_pao_step)
_read_body = functools.partial(jax.jit, static_argnums=(0, 1))(read_step)
_read_body_sparse = functools.partial(
    jax.jit, static_argnums=(0, 1))(read_step_sparse)


# ------------------------------------------------------------- stacked pytrees
def stack_plan_arrays(arrays: list[PlanArrays]) -> PlanArrays:
    """Stack aligned per-shard ``PlanArrays`` along a new leading shard axis.
    All inputs must share one program shape (``align_shard_plans``)."""
    shapes = {jax.tree.map(jnp.shape, a) for a in arrays}
    if len(shapes) != 1:
        raise ValueError(f"cannot stack misaligned plan arrays: {shapes}")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *arrays)


def plan_arrays_shard(stacked: PlanArrays, s: int) -> PlanArrays:
    """One shard's slice of a stacked ``PlanArrays`` pytree."""
    return jax.tree.map(lambda x: x[s], stacked)


def place_plan_arrays(stacked: PlanArrays, s: int,
                      arrays: PlanArrays) -> PlanArrays:
    """Write one shard's (patched) tables back into the stacked pytree —
    shapes must match, so jitted consumers keep their compiled program."""
    return jax.tree.map(lambda st, x: st.at[s].set(x), stacked, arrays)


# ------------------------------------------------------------ serialization
_DERIVED = frozenset({"live_blocks"})  # rederived on restore, never saved


def _plan_array_leaves(arrays: PlanArrays):
    """Deterministic (name, leaf) walk of a ``PlanArrays`` pytree with dotted
    names (``push.seg`` ...) — the checkpoint codec's stable key space."""
    for name, val in arrays._asdict().items():
        if isinstance(val, LevelTables):
            for f, sub in val._asdict().items():
                if f not in _DERIVED:
                    yield f"{name}.{f}", sub
        else:
            yield name, val


def _map_to_pairs(m: dict[int, int]) -> np.ndarray:
    """A host id map as one (2, len) int64 array (keys row, values row)."""
    out = np.empty((2, len(m)), np.int64)
    if m:
        out[0] = np.fromiter(m.keys(), np.int64, len(m))
        out[1] = np.fromiter(m.values(), np.int64, len(m))
    return out


def plan_snapshot(plan: ExecPlan) -> tuple[dict, dict]:
    """Serialize a live ``ExecPlan`` to ``(arrays, objs)``: a flat dict of
    host numpy arrays plus a JSON-safe object dict. Everything a bit-identical
    restore needs travels verbatim — device tables, host decision/level (from
    the patch bookkeeping when present, so in-capacity churn since compile is
    reflected), id maps — while derived caches (routes LUT, ``PlanHost``,
    frontier indexes) are rebuilt on the other side."""
    arrays = {f"pa.{name}": np.asarray(jax.device_get(leaf))
              for name, leaf in _plan_array_leaves(plan.arrays)}
    host = plan.host
    if host is not None:
        decision = np.asarray(host.decision[: host.n_real], np.int64)
        level = np.asarray(host.level[: host.n_real], np.int64)
    else:
        decision = np.asarray(plan.decision, np.int64)
        level = np.asarray(plan.level, np.int64)
    arrays.update({
        "decision": decision,
        "level": level,
        "writer_node": np.asarray(plan.writer_node, np.int64),
        "wrob": _map_to_pairs(plan.writer_row_of_base),
        "rnob": _map_to_pairs(plan.reader_node_of_base),
    })
    objs = {
        "meta": dataclasses.asdict(plan.meta),
        "depth": int(plan.depth),
        "n_push_edges": int(plan.n_push_edges),
        "n_pull_edges": int(plan.n_pull_edges),
        "patches_applied": int(plan.patches_applied),
    }
    return arrays, objs


def plan_from_snapshot(arrays: dict, objs: dict) -> ExecPlan:
    """Rebuild an ``ExecPlan`` from :func:`plan_snapshot` output without
    compiling anything. ``interpret`` is recomputed for the restoring host
    (a TPU save restores on CPU and vice versa); lazy derived state
    (``PlanHost``, frontier indexes) stays unmaterialized until first use."""
    meta = PlanMeta(**objs["meta"])
    if meta.backend == "pallas":
        meta = dataclasses.replace(
            meta, interpret=jax.default_backend() != "tpu")
    def put(name):
        return jax.device_put(arrays[f"pa.{name}"])

    def tables(name):
        return level_tables(**{f: put(f"{name}.{f}")
                               for f in LevelTables._fields
                               if f not in _DERIVED})

    pa = PlanArrays(
        decision=put("decision"), writer_node=put("writer_node"),
        push=tables("push"), pull=tables("pull"),
        demand_dst=put("demand_dst"), demand_src=put("demand_src"))
    wrob = {int(k): int(v) for k, v in zip(*arrays["wrob"])}
    rnob = {int(k): int(v) for k, v in zip(*arrays["rnob"])}
    return ExecPlan(
        meta=meta, arrays=pa, depth=int(objs["depth"]),
        decision=np.asarray(arrays["decision"], np.int64),
        level=np.asarray(arrays["level"], np.int64),
        writer_node=np.asarray(arrays["writer_node"], np.int64),
        writer_row_of_base=wrob, reader_node_of_base=rnob,
        routes=BaseRoutes.from_maps(wrob, rnob),
        n_push_edges=int(objs["n_push_edges"]),
        n_pull_edges=int(objs["n_pull_edges"]),
        patches_applied=int(objs["patches_applied"]))


# ----------------------------------------------------------------------- API
class EagrEngine:
    """Runtime for one compiled ego-centric aggregate query."""

    def __init__(self, overlay: Overlay, decisions: np.ndarray, aggregate: Aggregate,
                 window: WindowSpec | None = None, *, backend: str | None = None,
                 plan: ExecPlan | None = None, headroom: float | None = None):
        if aggregate.combine != "sum":
            neg = any(s < 0 for ins in overlay.in_edges for _, s in ins)
            if neg and not aggregate.supports_subtraction:
                raise ValueError("overlay has negative edges but aggregate is not subtractable")
        self.overlay = overlay
        self.agg = aggregate
        self.spec = window or WindowSpec(kind="tuple", size=1)
        if plan is None:
            pad = (grow_pad(measure_plan(overlay, decisions), headroom)
                   if headroom and headroom > 1.0 else None)
            plan = compile_plan(overlay, decisions, backend=backend, pad=pad)
        self.plan = plan
        # standing alerts (streams.alerts.AlertSet) — None for the common
        # case, so non-alert sessions keep the plain write bodies untouched
        self.alerts = None
        # continuous groups pin every churn-added node PUSH through patches
        # (always-fresh readers; alert evaluation depends on it)
        self.pin_push = False
        self._rebind()
        self.state = self.init_state()
        # host-side logical clock mirror + extremal-path eviction bookkeeping:
        # `_expiry` holds the eval times of batches whose entries are still
        # inside the time window; an all-dropped batch only needs the device
        # program when one of them crosses the expiry boundary.
        self._now_host = 0.0
        self._last_eval_now = 0.0
        self._expiry: list[float] = []
        # per write step: K (active-block capacity) for sparse steps, -1 for
        # dense — the frontier-size distribution the bench harness reports
        self.frontier_log: list[int] = []

    def _rebind(self) -> None:
        """(Re)bind the jitted bodies to the current plan arrays. Called at
        init and after ``apply_delta`` swaps the table pytree; as long as the
        plan's ``PlanMeta`` and array shapes are unchanged the bound bodies
        hit the existing jit cache entries."""
        body = (_write_body_sum if self.agg.combine == "sum"
                else _write_body_extremal)
        self._write = functools.partial(
            body, self.plan.meta, self.agg, self.spec, self.plan.arrays)
        body_sp = (_write_body_sum_sparse if self.agg.combine == "sum"
                   else _write_body_extremal_sparse)
        self._write_sparse = functools.partial(
            body_sp, self.plan.meta, self.agg, self.spec, self.plan.arrays)
        self._read = functools.partial(
            _read_body, self.plan.meta, self.agg, self.plan.arrays)
        self._read_sparse = functools.partial(
            _read_body_sparse, self.plan.meta, self.agg, self.plan.arrays)
        if self.alerts is not None:
            from repro.streams.alerts import _alert_write
            step = (write_step_sum if self.agg.combine == "sum"
                    else write_step_extremal)
            step_sp = (write_step_sum_sparse if self.agg.combine == "sum"
                       else write_step_extremal_sparse)
            cap = self.alerts.cap
            self._write_alert = functools.partial(
                _alert_write, step, self.plan.meta, self.agg, self.spec,
                cap, self.plan.arrays)
            self._write_alert_sparse = functools.partial(
                _alert_write, step_sp, self.plan.meta, self.agg, self.spec,
                cap, self.plan.arrays)

    def attach_alerts(self, alerts) -> None:
        """Attach an ``AlertSet``: resolves its reader rows against the live
        plan, binds the fused write+eval bodies, and from the next write on
        every batch carries its own compact fired-set evaluation."""
        self.alerts = alerts
        self._rebind()
        try:
            alerts.sync(self)
        except Exception:
            self.alerts = None
            raise

    def init_state(self) -> EngineState:
        windows = init_windows(self.plan.meta.n_writers, self.spec)
        pao = self.agg.init_pao(self.plan.meta.n_nodes)
        return EngineState(windows, pao, jnp.float32(0.0))

    def adopt_state(self, state: EngineState, *, now_host: float,
                    last_eval_now: float, expiry=()) -> None:
        """Adopt a restored ``EngineState`` plus the host-side clock mirror
        and extremal expiry bookkeeping (checkpoint restore seam). The state
        is taken verbatim — no PAO refresh, so restored answers stay
        bit-identical to the saved session's."""
        self.state = state
        self._now_host = float(now_host)
        self._last_eval_now = float(last_eval_now)
        self._expiry = sorted(float(t) for t in expiry)

    # ------------------------------------------------------------- execution
    def write_batch(self, base_ids: np.ndarray, values: np.ndarray,
                    batch_size: int | None = None) -> None:
        """Apply a batch of writes (base node ids + raw values). Values are
        (B,) scalars or (B, value_dim) vectors matching the window spec.
        Writes to nodes that feed no reader (e.g. node g in the paper's
        Figure 1) are masked out — nothing consumes them. Routing is one
        vectorized ``BaseRoutes`` table lookup; without an explicit
        ``batch_size`` the batch pads to the power-of-two ``bucket_batch``
        bucket, so varying arrival sizes stay on a handful of compiled
        shapes."""
        base_ids = np.asarray(base_ids)
        values = np.asarray(values, np.float32)
        rows, mask = self.plan.routes.writer_rows(base_ids)
        n_live = int(np.count_nonzero(mask))
        if n_live == 0 and batch_size is None:
            if self.agg.combine == "sum" or self.spec.kind == "tuple":
                # every write was dropped; skip the jit call but still advance
                # the logical clock, matching what the masked program does
                # (sum adds a zero delta; tuple-window extremal recomputes an
                # unchanged pao — neither depends on `now`)
                self.state = self.state._replace(now=self.state.now + 1.0)
                self._now_host += 1.0
                return
            if not (self._expiry
                    and self._expiry[0] < self._now_host - self.spec.size):
                # extremal + time window, but no live entry crosses the expiry
                # boundary at this instant: the masked program would recompute
                # an unchanged pao — skip it and just advance the clock
                self.state = self.state._replace(now=self.state.now + 1.0)
                self._now_host += 1.0
                return
            # an entry expires at this evaluation instant: the masked program
            # must run — one all-masked lane refreshes the touched writer
            # PAOs at the new `now`
            rows, mask = np.zeros(1, np.int32), np.zeros(1, bool)
            values = np.zeros((1,) + values.shape[1:], np.float32)
        B = batch_size or bucket_batch(len(rows))
        if B < len(rows):
            # legacy callers size the batch to the *kept* count — compact the
            # live lanes (vectorized) instead of rejecting the batch
            if n_live > B:
                raise ValueError(f"batch_size={B} < {n_live} routed writes")
            live = np.flatnonzero(mask)
            rows, values, mask = rows[live], values[live], mask[live]
        elif not mask.all():
            # dropped lanes must not contribute: their raw values are dead
            # under the mask, but zero them so non-finite garbage (inf * 0)
            # can't leak through the masked multiply
            m = mask.reshape((-1,) + (1,) * (values.ndim - 1))
            values = np.where(m, values, 0.0).astype(np.float32)
        pad = B - len(rows)
        if pad:
            mask = np.concatenate([mask, np.zeros(pad, bool)])
            rows = np.concatenate([rows, np.zeros(pad, np.int32)])
            values = np.concatenate(
                [values, np.zeros((pad,) + values.shape[1:], np.float32)])
        self.write_rows(rows, values, mask, n_live=n_live)

    def frontier_active(self, rows: np.ndarray, mask: np.ndarray,
                        n_live: int | None = None):
        """Decide + expand this batch's frontier: the ragged per-level
        active-block tuple for the sparse write bodies, or ``None`` for the
        dense sweep. ``None`` whenever sparseness can't be exact (the
        xla_unrolled baseline backend; an extremal time window with entries
        expiring outside the batch) or can't pay (EAGR_SPARSE_WRITE=0; auto
        mode with a large batch or a frontier past the density threshold).
        Builds the plan's ``FrontierIndex`` lazily on first use."""
        from repro.core import frontier as F

        mode = F.sparse_mode()
        meta = self.plan.meta
        if mode == "0" or meta.backend == "xla_unrolled":
            return None
        if self.agg.combine != "sum" and self.spec.kind == "time" and \
                self._expiry and \
                self._expiry[0] < self._now_host - self.spec.size:
            # rows expire at this eval instant: the touched set exceeds the
            # batch frontier, only the dense sweep sees all of it
            return None
        if n_live is None:
            n_live = int(np.count_nonzero(mask))
        if n_live == 0:
            return None
        density = None
        if mode == "auto":
            nb = self.plan.arrays.push.tile_of_block.shape[1]
            if nb < 8 or n_live > F.sparse_rowfrac() * meta.n_writers:
                return None  # frontier ~ overlay: expansion can't win
            density = F.sparse_density()
        exact = self.agg.combine == "sum"
        if self.plan.frontier is None or self.plan.frontier.exact != exact:
            self.plan.frontier = F.FrontierIndex.build(self.plan,
                                                       exact=exact)
        rows_u = np.unique(np.asarray(rows)[np.asarray(mask, bool)])
        return self.plan.frontier.expand(rows_u, density=density)

    def write_rows(self, rows: np.ndarray, vals: np.ndarray,
                   mask: np.ndarray, *, n_live: int | None = None,
                   active="auto") -> None:
        """Pre-routed write dispatch: ``rows`` are window rows (see
        ``ExecPlan.routes``), masked lanes carry row 0 / value 0 and the
        batch is already padded to its compiled shape. This is the ingest
        pipeline's entry point — one explicit ``device_put`` of the batch
        triple, then the async jitted step (no implicit transfers, no host
        sync: the call returns while the device step runs). ``n_live``
        (host-side count of live lanes) feeds the extremal expiry-heap
        bookkeeping; it defaults to a reduction of ``mask``. ``active``
        selects the step: ``"auto"`` asks :meth:`frontier_active`, ``None``
        forces the dense sweep, and a per-level active-block tuple runs the
        frontier-sparse bodies over exactly those edge blocks (the ingest
        pipeline passes its own pre-expanded tuple)."""
        if n_live is None:
            n_live = int(np.count_nonzero(mask))
        if isinstance(active, str):
            active = self.frontier_active(rows, mask, n_live=n_live)
        self.frontier_log.append(
            -1 if active is None else sum(a.shape[0] for a in active))
        if len(self.frontier_log) > (1 << 20):
            del self.frontier_log[: (1 << 19)]
        span = {"batch": int(self._now_host)}
        if active is None:
            # the dense sweep's extent, from the host copy of the tables'
            # live extents: what the bounded level and chunk loops visit
            span["live_blocks"], span["padded_blocks"] = \
                self.plan.dense_sweep_blocks(self.agg.pao_dim)
        with jax.profiler.TraceAnnotation("eagr.put", **span):
            self._put_step(rows, vals, mask, n_live, active)
        self._now_host += 1.0

    def _put_step(self, rows, vals, mask, n_live: int, active) -> None:
        """One explicit ``device_put`` of the routed batch (and its active
        blocks), then the async jitted write step."""
        rows_d, vals_d, mask_d = jax.device_put(
            (np.ascontiguousarray(rows, np.int32),
             np.ascontiguousarray(vals, np.float32),
             np.ascontiguousarray(mask, bool)))
        if active is not None:
            act_d = jax.device_put(tuple(
                np.ascontiguousarray(a, np.int32) for a in active))
        if self.agg.combine == "sum":
            extra = () if active is None else (act_d,)
        else:
            if self.spec.kind == "time":
                if n_live:
                    heapq.heappush(self._expiry, self._now_host)
                boundary = self._now_host - self.spec.size
                while self._expiry and self._expiry[0] < boundary:
                    heapq.heappop(self._expiry)  # reflected by this refresh
            prev = self._last_eval_now
            self._last_eval_now = self._now_host
            prev_d = jax.device_put(np.float32(prev))
            extra = (prev_d,) if active is None else (prev_d, act_d)
        al = self.alerts
        if al is not None and al.enabled and al.n_placed:
            # fused write+eval: same step body plus the alert predicate
            # sweep, one program — fired sets stay on device until the
            # caller's readback boundary
            fn = self._write_alert if active is None \
                else self._write_alert_sparse
            now_eval = self._now_host
            self.state, al.state, count, idx, avals, fired, m = fn(
                self.state, al.state, rows_d, vals_d, mask_d, *extra)
            al.push_pending(now_eval, count, idx, avals, fired, m)
        elif active is None:
            self.state = self._write(self.state, rows_d, vals_d, mask_d,
                                     *extra)
        else:
            self.state = self._write_sparse(self.state, rows_d, vals_d,
                                            mask_d, *extra)

    # -------------------------------------------------- structural updates
    def apply_delta(self, delta, *, growth: float = 2.0):
        """Apply a ``DynamicOverlay.drain_delta()`` mutation log to the live
        plan (§3.3 end to end). In-capacity updates route through the
        device-resident patch program: the delta is lowered to a
        ``plan_patch.PatchProgram`` and one cached ``apply_patch_step`` call
        rewrites the donated ``PlanArrays`` pytree in place — zero table
        uploads, every compiled body keeps its program (the old pytree is
        consumed by the donation; ``_rebind`` below re-points the jitted
        partials at the patched arrays). A tile/level/capacity overflow
        falls back to ``compile_plan`` with ``growth`` headroom so the next
        churn burst patches cheaply. Engine state is migrated: new writer
        rows are live immediately, retired writer windows are zeroed, and
        all push PAOs are repaired by one (cached) refresh program.
        Returns the ``plan_patch.PatchResult``."""
        from repro.core.plan_patch import patch_plan

        res = patch_plan(self.plan, delta, overlay=self.overlay,
                         growth=growth, pin_push=self.pin_push)
        if res.reason == "empty delta":
            return res  # nothing changed: skip the state refresh entirely
        self.plan = res.plan
        if res.recompiled and res.overlay is not None:
            self.overlay = res.overlay
        windows = pad_window_rows(self.state.windows, self.plan.meta.n_writers)
        if res.retired_writer_rows:
            windows = reset_window_rows(windows, res.retired_writer_rows)
        pao = _refresh_pao(self.plan.meta, self.agg, self.spec,
                           self.plan.arrays, windows, self.state.now)
        self.state = EngineState(windows, pao, self.state.now)
        self._last_eval_now = self._now_host
        self._rebind()
        if self.alerts is not None:
            # carry alert rows through churn: retired readers drop, moved
            # readers follow their node, query-wide alerts adopt new readers
            self.alerts.sync(self, retired=res.retired_reader_bases)
        return res

    def adopt_decisions(self, decisions: np.ndarray) -> "ExecPlan":
        """Recompile the live plan with new push/pull decisions (§4.8
        adaptive re-decision) and migrate engine state in place: the overlay
        is unchanged, so writer rows keep their positions and the windows
        survive untouched; PAOs are refreshed for the new push set. Padded
        dims are floored at the current plan's, so when the new decisions fit
        the existing table budget every jitted body keeps its compiled
        program. Host patch bookkeeping (slot pools, retired-writer bases,
        parity mirror) is re-seeded so structural churn keeps patching in
        place afterwards. Returns the adopted plan."""
        from repro.core.plan_patch import carry_plan_bookkeeping

        host = self.plan.host
        ov = host.export_overlay() if host is not None else self.overlay
        new = compile_plan(ov, np.asarray(decisions, dtype=np.int64),
                           backend=self.plan.meta.backend,
                           pad=plan_dims(self.plan))
        carry_plan_bookkeeping(new, self.plan, ov)
        self.overlay = ov
        self.adopt_plan(new)
        return new

    def adopt_plan(self, plan: ExecPlan) -> None:
        """Swap in a structurally-equivalent recompiled plan (e.g. a shard
        realigned to a new shared program shape) and migrate engine state:
        windows resize to the new writer capacity, PAOs are refreshed."""
        self.plan = plan
        windows = pad_window_rows(self.state.windows, plan.meta.n_writers)
        pao = _refresh_pao(plan.meta, self.agg, self.spec, plan.arrays,
                           windows, self.state.now)
        self.state = EngineState(windows, pao, self.state.now)
        self._last_eval_now = self._now_host
        self._rebind()
        if self.alerts is not None:
            self.alerts.sync(self)

    def read_batch(self, base_ids: np.ndarray, batch_size: int | None = None):
        """Answer a batch of reads. Returns finalized answers (B, ...).
        Routing and the unknown-reader check are one vectorized table
        lookup; the batch pads to the ``bucket_batch`` bucket unless
        ``batch_size`` pins the shape."""
        base_ids = np.asarray(base_ids)
        with jax.profiler.TraceAnnotation("eagr.read_route"):
            nodes, known = self.plan.routes.reader_nodes(base_ids)
            if not known.all():
                bad = np.asarray(base_ids, np.int64).reshape(-1)[~known]
                raise ValueError(
                    f"read_batch: base ids {sorted(set(map(int, bad)))[:8]} "
                    f"are not readers of this overlay (no reader node "
                    f"registered)")
            B = batch_size or bucket_batch(len(nodes))
            if B < len(nodes):
                raise ValueError(f"batch_size={B} < batch of {len(nodes)}")
            act = self._reader_active(nodes)
        with jax.profiler.TraceAnnotation("eagr.put"):
            pad = B - len(nodes)
            mask = np.concatenate([np.ones(len(nodes), bool),
                                   np.zeros(pad, bool)])
            nodes = np.concatenate([nodes, np.zeros(pad, np.int32)])
            nodes_d, mask_d = jax.device_put((nodes, mask))
            if act is None:
                ans, _ = self._read(self.state, nodes_d, mask_d)
            else:
                dem_d, pull_d = jax.device_put(
                    (tuple(np.ascontiguousarray(a, np.int32) for a in act[0]),
                     tuple(np.ascontiguousarray(a, np.int32)
                           for a in act[1])))
                ans, _ = self._read_sparse(self.state, nodes_d, mask_d,
                                           dem_d, pull_d)
        with jax.profiler.TraceAnnotation("eagr.read_wait"):
            return np.asarray(jax.device_get(ans))[: len(base_ids)]

    def _reader_active(self, nodes: np.ndarray):
        """Read-path twin of :meth:`frontier_active`: ``(dem_active,
        pull_active)`` chunk/block arrays for the sparse demand + pull
        sweeps, or ``None`` for the dense read. Auto mode only pays for the
        expansion on small reader batches."""
        from repro.core import frontier as F

        mode = F.sparse_mode()
        meta = self.plan.meta
        if mode == "0" or meta.backend == "xla_unrolled":
            return None
        density = None
        if mode == "auto":
            if len(nodes) > F.sparse_rowfrac() * meta.n_nodes:
                return None
            density = F.sparse_density()
        if self.plan.reader_frontier is None:
            self.plan.reader_frontier = F.ReaderFrontierIndex.build(self.plan)
        return self.plan.reader_frontier.expand(np.unique(nodes),
                                                density=density)

    # --------------------------------------------------------------- oracle
    def oracle_read(self, base_id: int, reader_inputs: dict[int, set[int]]):
        """Reference answer computed directly from the writer windows
        (independent of the overlay) — the ground truth for tests."""
        wp = np.asarray(jax.device_get(
            window_pao(self.state.windows, self.spec, self.agg, now=self.state.now)))
        acc = self.agg.INITIALIZE()
        count = np.asarray(jax.device_get(self.state.windows.count))
        for w in reader_inputs[base_id]:
            row = self.plan.writer_row_of_base[w]
            if count[row] == 0:
                continue
            if self.agg.combine == "sum":
                acc = acc + wp[row]
            elif self.agg.combine == "max":
                acc = np.maximum(acc, wp[row])
            else:
                acc = np.minimum(acc, wp[row])
        return self.agg.FINALIZE(acc)
