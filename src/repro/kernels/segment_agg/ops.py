"""jit'd wrapper for the segment_agg kernel.

The kernel requires edges sorted by destination and padded so no E_BLK edge
block straddles an R_BLK row tile. For static graph structure (GNN adjacency,
EAGr overlay levels) that plan is built once on the host (``make_plan``) and
reused every step; only the edge *values* are runtime data.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segment_agg.segment_agg import (
    E_BLK,
    F_BLK,
    R_BLK,
    segment_agg_call,
)


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: identity hash, so a
class SegmentPlan:                             # plan can be a static jit arg
    """Host-compiled routing plan for one static (seg, n_rows) structure."""

    perm: np.ndarray            # (E,) original edge -> slot in padded layout
    seg_padded: np.ndarray      # (E_pad,) int32, -1 padding
    tile_of_block: np.ndarray   # (n_edge_blocks,) int32
    first_of_tile: np.ndarray   # (n_edge_blocks,) int32
    n_rows: int
    n_row_tiles: int
    e_pad: int

    @property
    def pad_overhead(self) -> float:
        return self.e_pad / max(1, len(self.perm)) - 1.0


def make_plan(seg: np.ndarray, n_rows: int) -> SegmentPlan:
    """Group edges by row tile, pad each tile's edge count to a multiple of
    E_BLK, and record block->tile routing for the scalar-prefetch index maps."""
    seg = np.asarray(seg, dtype=np.int64)
    order = np.argsort(seg, kind="stable")
    n_row_tiles = max(1, -(-n_rows // R_BLK))

    tile = seg[order] // R_BLK
    slots = []
    seg_chunks = []
    tob, fot = [], []
    e_cursor = 0
    for t in range(n_row_tiles):
        idx = order[tile == t]
        if idx.size == 0:
            continue
        n_blocks = -(-idx.size // E_BLK)
        padded = n_blocks * E_BLK
        slots.append((idx, e_cursor))
        chunk = np.full(padded, -1, dtype=np.int32)
        chunk[: idx.size] = seg[idx]
        seg_chunks.append(chunk)
        tob.extend([t] * n_blocks)
        fot.extend([1] + [0] * (n_blocks - 1))
        e_cursor += padded
    if e_cursor == 0:  # no edges at all: one dummy block routed to tile 0
        seg_chunks.append(np.full(E_BLK, -1, dtype=np.int32))
        tob, fot = [0], [1]
        e_cursor = E_BLK

    perm = np.zeros(len(seg), dtype=np.int64)
    for idx, base in slots:
        perm[idx] = base + np.arange(idx.size)
    return SegmentPlan(
        perm=perm,
        seg_padded=np.concatenate(seg_chunks),
        tile_of_block=np.asarray(tob, dtype=np.int32),
        first_of_tile=np.asarray(fot, dtype=np.int32),
        n_rows=n_rows,
        n_row_tiles=n_row_tiles,
        e_pad=e_cursor,
    )


@functools.partial(jax.jit, static_argnames=("plan", "op", "interpret"))
def _run(plan: SegmentPlan, x: jnp.ndarray, op: str, interpret: bool) -> jnp.ndarray:
    E, F = x.shape
    f_pad = -(-F // F_BLK) * F_BLK
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, f_pad - F)))
    xp = jnp.zeros((plan.e_pad, f_pad), dtype=jnp.float32)
    xp = xp.at[jnp.asarray(plan.perm)].set(xf)
    out = segment_agg_call(
        xp,
        jnp.asarray(plan.seg_padded),
        jnp.asarray(plan.tile_of_block),
        jnp.asarray(plan.first_of_tile),
        jnp.zeros((plan.n_row_tiles * R_BLK, f_pad), jnp.float32),
        op=op,
        interpret=interpret,
    )
    out = out[: plan.n_rows, :F]
    if op == "max":
        visited = jax.ops.segment_sum(
            jnp.ones((plan.e_pad,), jnp.float32),
            jnp.where(jnp.asarray(plan.seg_padded) >= 0,
                      jnp.asarray(plan.seg_padded), plan.n_rows),
            num_segments=plan.n_rows + 1)[: plan.n_rows]
        out = jnp.where(visited[:, None] > 0, out, 0.0)
    return out


def segment_agg(x: jnp.ndarray, plan: SegmentPlan, *, op: str = "sum",
                interpret: bool = True) -> jnp.ndarray:
    """Aggregate edge values x (E, F) by the plan's destination rows.
    Returns (n_rows, F) fp32. Rows with no edges are 0 (both ops)."""
    if x.ndim == 1:
        x = x[:, None]
    return _run(plan, x, op, interpret)


# --------------------------------------------------------------- leveled plans
@dataclasses.dataclass(frozen=True, eq=False)
class LeveledPlan:
    """A stack of per-level ``SegmentPlan`` routings padded to one shape.

    All levels share the same edge-slot capacity (``e_pad``) and block count,
    so a jitted program can ``fori_loop`` over levels, dynamically slicing one
    level's routing tables per iteration — the program size is independent of
    the number of levels. Padding slots carry ``seg == -1`` (dropped by the
    kernel); padding *blocks* are routed to the last real block's output tile
    so the kernel's consecutive-revisit invariant still holds on hardware.
    """

    seg: np.ndarray             # (L, e_pad) int32, -1 padding
    tile_of_block: np.ndarray   # (L, n_blocks) int32
    first_of_tile: np.ndarray   # (L, n_blocks) int32
    perms: tuple                # per level: original edge index -> padded slot
    tile_slots: np.ndarray      # (L, n_row_tiles, 2) int32 [start, stop) slot
                                # range routed to each row tile (free-slot pool)
    n_rows: int
    n_row_tiles: int
    n_levels: int
    e_pad: int

    def layout(self, level: int, values: np.ndarray, fill=0,
               dtype=None) -> np.ndarray:
        """Place a per-edge companion array (e.g. sources, signs) of one level
        into that level's padded kernel slot order."""
        values = np.asarray(values)
        out = np.full((self.e_pad,) + values.shape[1:], fill,
                      dtype=dtype or values.dtype)
        out[self.perms[level]] = values
        return out


def tile_slot_ranges(tob_row: np.ndarray, n_row_tiles: int) -> np.ndarray:
    """Per-tile claimable slot ranges of one level's block routing.

    Blocks routed to the same tile are consecutive (the kernel's revisit
    invariant), so each tile owns at most one run of blocks; padding blocks
    are routed to the last real tile and therefore extend its run. Returns
    (n_row_tiles, 2) int32 [start, stop) slot ranges; tiles with no blocks
    get an empty range. A slot is *free* iff it lies in its tile's range and
    currently holds ``seg == -1``.
    """
    tob_row = np.asarray(tob_row, dtype=np.int64)
    out = np.zeros((n_row_tiles, 2), dtype=np.int32)
    for t in range(n_row_tiles):
        hit = np.flatnonzero(tob_row == t)
        if hit.size:
            out[t, 0] = hit[0] * E_BLK
            out[t, 1] = (hit[-1] + 1) * E_BLK
    return out


def patch_level(seg: jnp.ndarray, src: jnp.ndarray, sign: jnp.ndarray,
                level: int, slots: np.ndarray, seg_vals: np.ndarray,
                src_vals: np.ndarray, sign_vals: np.ndarray):
    """Rewrite individual edge slots of one level in the stacked tables.

    Retiring an edge writes ``seg=-1, src=0, sign=0`` (the padding pattern —
    every backend drops it); a new edge claims a free slot inside the owning
    tile's block range. Padded dims are untouched, so a jitted program over
    the tables keeps its compiled shape. Returns the three updated tables.

    ``scatter_slots`` / ``scatter_rows`` below are the jit-embeddable
    generalizations (batched across levels, out-of-bounds indices dropped)
    that ``plan_patch.apply_patch_step`` composes into the device-resident
    update program.
    """
    sl = jnp.asarray(np.asarray(slots, dtype=np.int64))
    return (
        seg.at[level, sl].set(jnp.asarray(np.asarray(seg_vals, np.int32))),
        src.at[level, sl].set(jnp.asarray(np.asarray(src_vals, np.int32))),
        sign.at[level, sl].set(jnp.asarray(np.asarray(sign_vals, np.float32))),
    )


def scatter_slots(table: jnp.ndarray, lvl: jnp.ndarray, slot: jnp.ndarray,
                  vals: jnp.ndarray) -> jnp.ndarray:
    """Scatter individual (level, column) edits into one stacked (L, X)
    table. Padding entries carry an out-of-bounds level and are dropped, so
    edit arrays can be shape-bucketed without masking. Edits are unique by
    construction (last-write-wins resolution happens at lowering time).
    Traceable (jit-safe)."""
    return table.at[lvl, slot].set(vals, mode="drop", unique_indices=True)


def scatter_rows(table: jnp.ndarray, lvl: jnp.ndarray,
                 rows: jnp.ndarray) -> jnp.ndarray:
    """Replace whole level rows of a stacked table (the relayout tier).
    Out-of-bounds ``lvl`` entries (shape-bucket padding) are dropped."""
    return table.at[lvl].set(rows, mode="drop", unique_indices=True)


def tile_occupancy(seg: jnp.ndarray, tile_of_block: jnp.ndarray,
                   n_row_tiles: int) -> jnp.ndarray:
    """Per-(level, row-tile) count of live edge slots, computed on device from
    the stacked tables: the occupancy counters the patch path's tier
    escalation mirrors host-side (a tile whose occupancy plus the incoming
    claim exceeds its slot range forces a level relayout)."""
    L, e_pad = seg.shape
    tob = jnp.repeat(tile_of_block, E_BLK, axis=1)         # (L, e_pad)

    def one_level(seg_row, tob_row):
        t = jnp.where(seg_row >= 0, tob_row, n_row_tiles)
        return jax.ops.segment_sum(jnp.ones((e_pad,), jnp.int32), t,
                                   num_segments=n_row_tiles + 1)[:n_row_tiles]

    return jax.vmap(one_level)(seg, tob)


def relayout_level(dst: np.ndarray, src: np.ndarray, sign: np.ndarray,
                   n_rows: int, n_blocks: int, e_pad: int):
    """Rebuild one level's full kernel-layout rows from its current edge set.

    The medium-cost patch path: used when a slot claim fails (tile overflow /
    previously-empty tile) but the level still fits the plan's per-level block
    budget. Returns ``(seg_row, src_row, sign_row, tob_row, fot_row)`` padded
    to ``(e_pad,)`` / ``(n_blocks,)``, or ``None`` if the level needs more
    than ``n_blocks`` blocks (caller falls back to a full recompile).
    """
    p = make_plan(np.asarray(dst, dtype=np.int64), n_rows)
    k = p.tile_of_block.size
    if k > n_blocks:
        return None
    seg_row = np.full(e_pad, -1, dtype=np.int32)
    src_row = np.zeros(e_pad, dtype=np.int32)
    sign_row = np.zeros(e_pad, dtype=np.float32)
    seg_row[: p.e_pad] = p.seg_padded
    src_row[p.perm] = np.asarray(src, dtype=np.int32)
    sign_row[p.perm] = np.asarray(sign, dtype=np.float32)
    tob_row = np.zeros(n_blocks, dtype=np.int32)
    fot_row = np.zeros(n_blocks, dtype=np.int32)
    tob_row[:k] = p.tile_of_block
    tob_row[k:] = p.tile_of_block[-1] if k else 0  # keep revisits consecutive
    fot_row[:k] = p.first_of_tile
    if k == 0:
        fot_row[0] = 1  # empty level: init tile 0, aggregate nothing
    return seg_row, src_row, sign_row, tob_row, fot_row


def count_blocks(seg: np.ndarray) -> int:
    """Edge blocks ``make_plan`` would emit for one segment list: per-tile
    edge counts rounded up to E_BLK blocks (>=1, the dummy block)."""
    seg = np.asarray(seg, dtype=np.int64)
    if seg.size == 0:
        return 1
    _, counts = np.unique(seg // R_BLK, return_counts=True)
    return int(sum(-(-c // E_BLK) for c in counts))


def leveled_plan_blocks(segs: list[np.ndarray]) -> int:
    """The (pre-bucketing) per-level block count ``make_leveled_plan`` pads
    to — without building any tables. Bucket with the same next-power-of-two
    rule to predict the final shape."""
    return max((count_blocks(s) for s in segs), default=1)


def make_leveled_plan(segs: list[np.ndarray], n_rows: int, *,
                      pad_levels: int | None = None,
                      pad_blocks: int | None = None) -> LeveledPlan:
    """Route each level's destination segments through ``make_plan`` and stack
    the results into one padded (L, e_pad) table set.

    ``pad_levels`` / ``pad_blocks`` optionally force the padded level count and
    per-level block count (must be >= the natural sizes) so plans for different
    structures — restructured overlays, sibling shards — share one compiled
    program shape. Defaults bucket levels to a multiple of 4 and blocks to the
    next power of two for the same reason.
    """
    plans = [make_plan(np.asarray(s), n_rows) for s in segs]
    nb_real = max((p.e_pad // E_BLK for p in plans), default=1)
    nb = pad_blocks or max(1, 1 << (nb_real - 1).bit_length())
    if nb < nb_real:
        raise ValueError(f"pad_blocks={nb} < required {nb_real}")
    L_real = len(plans)
    L = pad_levels or max(1, -(-L_real // 4) * 4)
    if L < L_real:
        raise ValueError(f"pad_levels={L} < required {L_real}")
    e_pad = nb * E_BLK

    seg = np.full((L, e_pad), -1, dtype=np.int32)
    tob = np.zeros((L, nb), dtype=np.int32)
    fot = np.zeros((L, nb), dtype=np.int32)
    perms = []
    for l, p in enumerate(plans):
        k = p.tile_of_block.size
        seg[l, : p.e_pad] = p.seg_padded
        tob[l, :k] = p.tile_of_block
        tob[l, k:] = p.tile_of_block[-1] if k else 0  # keep revisits consecutive
        fot[l, :k] = p.first_of_tile
        perms.append(p.perm.copy())
    for l in range(L_real, L):
        fot[l, 0] = 1  # dummy level: init tile 0, aggregate nothing
        perms.append(np.zeros(0, dtype=np.int64))
    n_row_tiles = max(1, -(-n_rows // R_BLK))
    tile_slots = np.stack([tile_slot_ranges(tob[l], n_row_tiles)
                           for l in range(L)])
    return LeveledPlan(
        seg=seg, tile_of_block=tob, first_of_tile=fot, perms=tuple(perms),
        tile_slots=tile_slots, n_rows=n_rows, n_row_tiles=n_row_tiles,
        n_levels=L, e_pad=e_pad,
    )


# Padded edge values (E_BLK x F_pad lanes per block) one kernel call streams:
# a level of a 1M-node / 10M-edge overlay holds 65,536 blocks, whose f32
# (e_pad, 128) buffer alone is 8.6 GB of a v5e's 16 GB HBM, so larger levels
# are reduced in consecutive block chunks into one aliased accumulator.
CHUNK_BYTES = 1 << 29


def _reduce_blocks(x, seg, tob, fot, *, n_rows: int, n_row_tiles: int,
                   op: str, interpret: bool, bf16: bool) -> jnp.ndarray:
    """Kernel over the edge blocks of ``x`` (n_blocks * E_BLK, F) in chunks
    of at most ``CHUNK_BYTES`` of padded values; returns (n_rows, F)."""
    n_blocks = tob.shape[0]
    F = x.shape[1]
    f_pad = -(-F // F_BLK) * F_BLK
    dt = jnp.bfloat16 if bf16 else jnp.float32
    fit = max(1, CHUNK_BYTES // (E_BLK * f_pad * jnp.dtype(dt).itemsize))
    c = min(n_blocks & -n_blocks, 1 << (fit.bit_length() - 1))

    def chunk(i, acc):
        take = lambda a, n: jax.lax.dynamic_slice_in_dim(a, i * n, n)
        xc = jnp.pad(take(x, c * E_BLK).astype(dt), ((0, 0), (0, f_pad - F)))
        return segment_agg_call(xc, take(seg, c * E_BLK), take(tob, c),
                                take(fot, c), acc, op=op, interpret=interpret)

    acc = jnp.zeros((n_row_tiles * R_BLK, f_pad), jnp.float32)
    if n_blocks == c:
        acc = chunk(0, acc)
    else:
        acc = jax.lax.fori_loop(0, n_blocks // c, chunk, acc)
    # through the transpose: a plain [:n_rows, :F] slice keeps the
    # accumulator's (8, 128) tiling, and on TPU every (n_rows, F) array fed
    # by it (the level-loop carry) then pads F to 128 lanes in HBM
    return acc.T[:F, :n_rows].T


def segment_agg_level(x: jnp.ndarray, seg: jnp.ndarray, tob: jnp.ndarray,
                      fot: jnp.ndarray, *, n_rows: int, n_row_tiles: int,
                      op: str = "sum", interpret: bool = True,
                      bf16: bool = False) -> jnp.ndarray:
    """Run the kernel on one level of a ``LeveledPlan``.

    ``x`` is (e_pad, F) edge values already in the level's padded slot order
    (use ``LeveledPlan.layout`` for static companions or gather through a
    laid-out source-index array for runtime values). All arguments may be
    traced — in particular slices of the stacked tables inside a loop over
    levels. Returns (n_rows, F); rows the level never touches carry no
    result, so callers mask by their own touched set.
    ``bf16`` streams edge values into VMEM as bfloat16 (2x block headroom);
    the kernels cast per block and accumulate in fp32 either way.
    """
    return _reduce_blocks(x, seg, tob, fot, n_rows=n_rows,
                          n_row_tiles=n_row_tiles, op=op,
                          interpret=interpret, bf16=bf16)


def segment_agg_active(x: jnp.ndarray, seg: jnp.ndarray, tob: jnp.ndarray, *,
                       n_rows: int, n_row_tiles: int, op: str = "sum",
                       interpret: bool = True,
                       bf16: bool = False) -> jnp.ndarray:
    """Run the kernel on a *compacted* active-block subset of one level.

    ``x`` (K*E_BLK, F), ``seg`` (K*E_BLK,) and ``tob`` (K,) are the gathered
    slices of the K active edge blocks, in ascending block order — an
    ascending subset of a sorted level stays sorted, and ``tob`` stays
    non-decreasing, so the kernel's consecutive-revisit invariant holds and
    the grid (which is sized from ``x``) simply shrinks to K blocks. The
    first-of-tile flags are recomputed from the compacted ``tob`` (a tile's
    first *active* block initializes it). Output rows in tiles with no active
    block carry no result — callers mask by the active destination set.
    """
    fot = jnp.concatenate([jnp.ones((1,), jnp.int32),
                           (tob[1:] != tob[:-1]).astype(jnp.int32)])
    return _reduce_blocks(x, seg, tob, fot, n_rows=n_rows,
                          n_row_tiles=n_row_tiles, op=op,
                          interpret=interpret, bf16=bf16)
