"""Compile rehearsal for a TPU v5e: the main-path kernels and the dense write
step (its level and chunk loops bounded by the tables' live extents) at the
plan shapes of a 1M-node / 10M-edge power-law overlay, compiled for a
described (not attached) chip. Nothing runs; a compile that passes
means the chip's compiler accepts the program and it fits device memory.

This is the only file that describes the chip. The topology is described
inside a module fixture, never at import, so every xdist worker collects the
same tests and only the worker given this file loads libtpu.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.aggregates import make_aggregate
from repro.core.engine import (
    EngineState,
    LevelTables,
    PlanArrays,
    PlanMeta,
    _write_body_sum,
)
from repro.core.window import WindowSpec, WindowState
from repro.kernels.segment_agg.ops import (
    segment_agg_active,
    segment_agg_gather_level,
    segment_agg_level,
)
from repro.kernels.segment_agg.segment_agg import E_BLK, R_BLK

# Padded plan of the continuous count group over powerlaw_graph(1M, 10M,
# sharing=0.5) with the session's default 2x headroom, as chip_smoke.py
# printed it on a v5e: 2,128,469 overlay nodes -> 4,257,024 rows, 65,536
# push blocks per level.
N_NODES = 4_257_024
N_WRITERS = 1_989_024
N_LEVELS = 8
N_BLOCKS = 65_536
ACTIVE_BLOCKS = 8_192       # p50 sparse-step width at 1M/10M (streaming bench)
WRITE_BATCH = 65_536
HBM_BYTES = 16e9

KERNELS = [("sum", False), ("sum", True), ("max", False)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one v5e"
    return total


@pytest.mark.parametrize("op,bf16", KERNELS)
def test_segment_agg_level_compiles_dense(sds, op, bf16):
    e_pad = N_BLOCKS * E_BLK

    def level(x, seg, tob, fot):
        return segment_agg_level(x, seg, tob, fot, n_rows=N_NODES,
                                 n_row_tiles=N_NODES // R_BLK, op=op,
                                 interpret=False, bf16=bf16)

    compiled = jax.jit(level).lower(
        sds((e_pad, 1), jnp.float32), sds((e_pad,), jnp.int32),
        sds((N_BLOCKS,), jnp.int32), sds((N_BLOCKS,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("op,bf16", KERNELS)
def test_segment_agg_gather_level_compiles_bounded(sds, op, bf16):
    """The dense path's entry: the gather of each chunk's edge values runs
    inside the chunk loop, whose trip count is the traced live extent."""
    e_pad = N_BLOCKS * E_BLK

    def level(val, src, sign, seg, tob, fot, live):
        return segment_agg_gather_level(
            val, src, sign if op == "sum" else None, seg, tob, fot, live,
            n_rows=N_NODES, n_row_tiles=N_NODES // R_BLK, op=op,
            interpret=False, bf16=bf16)

    compiled = jax.jit(level).lower(
        sds((N_NODES, 1), jnp.float32), sds((e_pad,), jnp.int32),
        sds((e_pad,), jnp.float32), sds((e_pad,), jnp.int32),
        sds((N_BLOCKS,), jnp.int32), sds((N_BLOCKS,), jnp.int32),
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " while(" in text
    _fits(compiled)


@pytest.mark.parametrize("op,bf16", KERNELS)
def test_segment_agg_active_compiles(sds, op, bf16):
    k_pad = ACTIVE_BLOCKS * E_BLK

    def active(x, seg, tob):
        return segment_agg_active(x, seg, tob, n_rows=N_NODES,
                                  n_row_tiles=N_NODES // R_BLK, op=op,
                                  interpret=False, bf16=bf16)

    compiled = jax.jit(active).lower(
        sds((k_pad, 1), jnp.float32), sds((k_pad,), jnp.int32),
        sds((ACTIVE_BLOCKS,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_dense_write_step_fits_one_chip(sds):
    meta = PlanMeta(n_nodes=N_NODES, n_writers=N_WRITERS, n_levels=N_LEVELS,
                    unroll_levels=0, n_row_tiles=N_NODES // R_BLK,
                    backend="pallas", interpret=False)
    L = N_LEVELS

    def tables(n_blocks):
        ep = n_blocks * E_BLK
        return LevelTables(
            sds((L, ep), jnp.int32), sds((L, ep), jnp.int32),
            sds((L, ep), jnp.float32), sds((L, n_blocks), jnp.int32),
            sds((L, n_blocks), jnp.int32), sds((L, N_NODES), jnp.bool_),
            sds((L,), jnp.int32))

    arrays = PlanArrays(
        sds((N_NODES,), jnp.int32), sds((N_WRITERS,), jnp.int32),
        tables(N_BLOCKS), tables(2), sds((L, 256), jnp.int32),
        sds((L, 256), jnp.int32))
    spec = WindowSpec("tuple", 32)
    state = EngineState(
        WindowState(sds((N_WRITERS, 32), jnp.float32),
                    sds((N_WRITERS, 32), jnp.float32),
                    sds((N_WRITERS,), jnp.int32),
                    sds((N_WRITERS,), jnp.int32)),
        sds((N_NODES, 1), jnp.float32), sds((), jnp.float32))
    B = WRITE_BATCH
    compiled = _write_body_sum.lower(
        meta, make_aggregate("count"), spec, arrays, state,
        sds((B,), jnp.int32), sds((B,), jnp.float32),
        sds((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the edge values are gathered one chunk at a time inside the bounded
    # chunk loop: no gather ever makes a whole padded level's values
    gathers = [int(n) for n in re.findall(
        r"= f32\[(\d+)(?:,1)?\]\{[^}]*\} gather\(", text)]
    assert gathers and max(gathers) < N_BLOCKS * E_BLK
    _fits(compiled)


def test_kernel_and_scopes_keep_their_trace_names(sds):
    """The device trace names each operation by its HLO instruction, with
    its scope path: the kernel's instruction stays ``%segment_agg_call…``
    (the pattern the benchmark's roofline reader matches), and the lane
    padding and the kernel call carry their ``eagr.*`` scopes."""
    import re

    e_pad = N_BLOCKS * E_BLK

    def level(x, seg, tob, fot):
        return segment_agg_level(x, seg, tob, fot, n_rows=N_NODES,
                                 n_row_tiles=N_NODES // R_BLK, op="sum",
                                 interpret=False)

    text = jax.jit(level).lower(
        sds((e_pad, 1), jnp.float32), sds((e_pad,), jnp.int32),
        sds((N_BLOCKS,), jnp.int32), sds((N_BLOCKS,), jnp.int32)
    ).compile().as_text()
    kernels = [ln.strip() for ln in text.splitlines()
               if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert kernels
    for ln in kernels:
        assert re.match(r"(ROOT )?%segment_agg_call[.\w]* = ", ln), ln
        assert "eagr.segment_agg" in ln
    pads = [ln for ln in text.splitlines() if " pad(" in ln]
    assert pads and all("eagr.lane_pad" in ln for ln in pads)
