"""Sorted-segment aggregation as a Pallas TPU kernel.

TPU has no atomic scatter; the systolic array *is* the scatter engine when
reduce-by-key is expressed as a small matmul. Edges arrive sorted by
destination row and padded (host-side, see ops.py) so that **no edge block
straddles a row-tile boundary**. Then:

  grid = (n_feat_tiles, n_edge_blocks)           # edge blocks minor => all
                                                 # revisits of an output tile
                                                 # are consecutive
  P[i, e] = 1  iff  seg[e] == tile_row0 + i      # (R_BLK, E_BLK) one-hot
  out_tile += P @ x_block                        # MXU matmul, fp32 accum

The block->tile routing (``tile_of_block``) and the first-visit flags are
scalar-prefetched (PrefetchScalarGridSpec) so the output BlockSpec's
index_map can read them — the TPU DMA engine then streams each edge block to
the right output tile with no host involvement.

The output aliases an accumulator input, so one level may be reduced in
several calls over consecutive block ranges (``ops._reduce_blocks`` bounds
the padded edge buffer that way): a tile whose first block lies in an earlier
call resumes from that call's partial result, and every tile still
accumulates its blocks in one sequential order whatever the split.

The 'max' variant replaces the matmul with masked-broadcast maxima (VPU):
one statically unrolled step per edge of the block, each a lane-broadcast of
that edge's one-hot column against a sublane-broadcast of its value row, so
every intermediate stays one (R_BLK, F_BLK) tile.

``seg`` reaches the kernel as (n_blocks, 1, E_BLK): a 1-D block would carry
Mosaic's T(256) tiling where XLA lays the array out T(1024), and the chip's
compiler refuses the mismatch. The reshape is a bitcast (layout T(1, 128)).

VMEM working set (fp32, E_BLK=256, R_BLK=128, F_BLK=128):
  x 128 KiB + out 64 KiB + seg 1 KiB + one-hot 128 KiB  ≈  0.4 MiB  « 16 MiB.
All matmul dims are 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

E_BLK = 256     # edges per block
R_BLK = 128     # output rows per tile (MXU-aligned)
F_BLK = 128     # feature lanes per tile


def _start_tile(tob_ref, fot_ref, acc_ref, out_ref, identity) -> None:
    """Open an output tile: its first block of the level sets the identity;
    the first block of a later call's run resumes from ``acc``."""
    b = pl.program_id(1)
    new_run = (b == 0) | (tob_ref[jnp.maximum(b - 1, 0)] != tob_ref[b])

    @pl.when(fot_ref[b] == 1)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, identity)

    @pl.when((fot_ref[b] == 0) & new_run)
    def _resume():
        out_ref[...] = acc_ref[...]


def _sum_kernel(tob_ref, fot_ref, seg_ref, x_ref, acc_ref, out_ref):
    _start_tile(tob_ref, fot_ref, acc_ref, out_ref, 0.0)
    local = seg_ref[...] - tob_ref[pl.program_id(1)] * R_BLK  # (1, E_BLK)
    # one-hot scatter matrix on the MXU: (R_BLK, E_BLK) @ (E_BLK, F_BLK);
    # HIGHEST keeps the f32 edge values exact through the systolic array
    rows = jax.lax.broadcasted_iota(jnp.int32, (R_BLK, E_BLK), 0)
    p = (rows == local).astype(jnp.float32)   # padding (seg -1) never hits
    out_ref[...] += jax.lax.dot(
        p, x_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _max_kernel(tob_ref, fot_ref, seg_ref, x_ref, acc_ref, out_ref):
    neg = jnp.float32(-3.0e38)
    _start_tile(tob_ref, fot_ref, acc_ref, out_ref, neg)
    local = seg_ref[...] - tob_ref[pl.program_id(1)] * R_BLK  # (1, E_BLK)
    rows = jax.lax.broadcasted_iota(jnp.int32, (R_BLK, E_BLK), 0)
    hit = rows == local                         # (R_BLK, E_BLK) one-hot
    x = x_ref[...].astype(jnp.float32)
    acc = out_ref[...]
    # static slices only: Mosaic lowers no dynamic_slice of a value, and no
    # lane->sublane shape cast a 3-D (R, E, F) broadcast would need
    for e in range(E_BLK):
        acc = jnp.maximum(acc, jnp.where(hit[:, e:e + 1], x[e:e + 1, :], neg))
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def segment_agg_call(
    x: jnp.ndarray,              # (E_pad, F_pad), blocked-by-tile order
    seg: jnp.ndarray,            # (E_pad,) int32, sorted, padding = -1
    tile_of_block: jnp.ndarray,  # (n_edge_blocks,) int32
    first_of_tile: jnp.ndarray,  # (n_edge_blocks,) int32 (1 = first block of tile)
    acc: jnp.ndarray,            # (n_row_tiles * R_BLK, F_pad) f32, aliased
    *,
    op: str = "sum",
    interpret: bool = True,
) -> jnp.ndarray:
    """Reduce the edge blocks into ``acc`` (returned, updated in place):
    tiles these blocks visit are (re)computed, every other tile keeps its
    ``acc`` contents."""
    n_edge_blocks = x.shape[0] // E_BLK
    kernel = _sum_kernel if op == "sum" else _max_kernel
    tile = pl.BlockSpec((R_BLK, F_BLK), lambda f, b, tob, fot: (tob[b], f))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(acc.shape[1] // F_BLK, n_edge_blocks),
        in_specs=[
            pl.BlockSpec((None, 1, E_BLK),
                         lambda f, b, tob, fot: (b, 0, 0)),               # seg
            pl.BlockSpec((E_BLK, F_BLK), lambda f, b, tob, fot: (b, f)),  # x
            tile,                                                         # acc
        ],
        out_specs=tile,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(tile_of_block, first_of_tile, seg.reshape(n_edge_blocks, 1, E_BLK), x,
      acc)
