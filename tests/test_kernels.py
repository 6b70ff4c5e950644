"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.ops import flash_attention, flash_decode
from repro.kernels.flash_attention.ref import attention_ref, decode_ref
from repro.kernels.segment_agg.ops import make_plan, segment_agg
from repro.kernels.segment_agg.ref import segment_agg_ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("E,F,n_rows", [
    (100, 8, 17), (1000, 64, 300), (37, 5, 10), (4096, 128, 128),
    (513, 200, 77), (1, 1, 1), (2000, 96, 1000),
])
@pytest.mark.parametrize("op", ["sum", "max"])
def test_segment_agg_sweep(E, F, n_rows, op):
    seg = RNG.integers(0, n_rows, E)
    x = RNG.normal(size=(E, F)).astype(np.float32)
    plan = make_plan(seg, n_rows)
    out = np.asarray(segment_agg(jnp.asarray(x), plan, op=op))
    ref = np.asarray(segment_agg_ref(jnp.asarray(x), jnp.asarray(seg), n_rows, op=op))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_segment_agg_dtypes(dtype):
    seg = RNG.integers(0, 50, 500)
    x = RNG.normal(size=(500, 32)).astype(np.float32)
    plan = make_plan(seg, 50)
    out = np.asarray(segment_agg(jnp.asarray(x, dtype=dtype), plan, op="sum"))
    ref = np.asarray(segment_agg_ref(jnp.asarray(x, dtype=dtype).astype(jnp.float32),
                                     jnp.asarray(seg), 50, op="sum"))
    tol = 1e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


def test_segment_agg_empty_rows():
    seg = np.array([5, 5, 5])
    x = np.ones((3, 4), np.float32)
    plan = make_plan(seg, 10)
    out = np.asarray(segment_agg(jnp.asarray(x), plan, op="max"))
    assert np.allclose(out[5], 1.0) and np.allclose(out[0], 0.0)


@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (1, 2, 1, 128, 32), (2, 4, 2, 256, 64), (1, 8, 8, 512, 64),
    (2, 6, 2, 200, 48),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, S, D, causal):
    q = jnp.asarray(RNG.normal(size=(B, Hq, S, D)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, D)).astype(np.float32))
    out = flash_attention(q, k, v, causal=causal, q_blk=128, k_blk=128,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Hq,Hkv,Skv,D", [
    (2, 4, 2, 512, 64), (1, 8, 1, 1024, 32), (3, 6, 3, 300, 64),
])
def test_flash_decode_sweep(B, Hq, Hkv, Skv, D):
    q = jnp.asarray(RNG.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)).astype(np.float32))
    lens = jnp.asarray(RNG.integers(1, Skv, B).astype(np.int32))
    out = flash_decode(q, k, v, lens, k_blk=128, interpret=True)
    ref = decode_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("V,D,n_ids,n_bags", [
    (100, 16, 64, 8), (1000, 32, 256, 16), (500, 64, 100, 100),
    (64, 8, 16, 1),
])
def test_embedding_bag_sweep(V, D, n_ids, n_bags):
    table = jnp.asarray(RNG.normal(size=(V, D)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, V, n_ids).astype(np.int32))
    cuts = np.sort(RNG.choice(np.arange(1, n_ids), size=n_bags - 1,
                              replace=False)) if n_bags > 1 else np.array([], np.int64)
    offs = jnp.asarray(np.concatenate([[0], cuts]).astype(np.int32))
    out = embedding_bag(table, ids, offs, n_bags=n_bags, interpret=True)
    bags = np.zeros(n_ids, np.int32)
    offs_np = np.asarray(offs)
    for i in range(n_bags):
        end = offs_np[i + 1] if i + 1 < n_bags else n_ids
        bags[offs_np[i]:end] = i
    ref = embedding_bag_ref(table, ids, jnp.asarray(bags), n_bags)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_embedding_bag_weighted():
    table = jnp.asarray(RNG.normal(size=(50, 8)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, 50, 32).astype(np.int32))
    offs = jnp.asarray(np.arange(0, 32, 4).astype(np.int32))
    w = jnp.asarray(RNG.normal(size=32).astype(np.float32))
    out = embedding_bag(table, ids, offs, n_bags=8, weights=w, interpret=True)
    bags = np.repeat(np.arange(8, dtype=np.int32), 4)
    ref = embedding_bag_ref(table, ids, jnp.asarray(bags), 8, weights=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_segment_agg_matches_engine_path():
    """The Pallas segment kernel computes the same contraction the EAGr engine
    and GNNs use via jax.ops.segment_sum."""
    E, F, n = 777, 36, 99
    seg = RNG.integers(0, n, E)
    x = RNG.normal(size=(E, F)).astype(np.float32)
    plan = make_plan(seg, n)
    out = np.asarray(segment_agg(jnp.asarray(x), plan, op="sum"))
    ref = np.asarray(jax.ops.segment_sum(jnp.asarray(x), jnp.asarray(seg),
                                         num_segments=n))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("bf16", [False, True])
def test_segment_agg_chunked_level_bit_identical(op, bf16, monkeypatch):
    """A level reduced in several calls over block chunks (one aliased
    accumulator, tiles split across calls resume from it) must equal the
    single call bit for bit, down to one block per call."""
    from repro.kernels.segment_agg import ops

    n = 700
    segs = [RNG.integers(0, n, 3000), RNG.integers(0, n // 3, 900)]
    lp = ops.make_leveled_plan(segs, n)
    x = jnp.asarray(RNG.normal(size=(lp.e_pad, 3)).astype(np.float32))

    def run(l):
        out = ops.segment_agg_level(
            x, jnp.asarray(lp.seg[l]), jnp.asarray(lp.tile_of_block[l]),
            jnp.asarray(lp.first_of_tile[l]), n_rows=n,
            n_row_tiles=lp.n_row_tiles, op=op, bf16=bf16)
        touched = np.zeros(n, bool)
        touched[lp.seg[l][lp.seg[l] >= 0]] = True
        return np.asarray(out)[touched]

    whole = [run(l) for l in range(len(segs))]
    monkeypatch.setattr(ops, "CHUNK_BYTES", 1)  # one block per kernel call
    for l, want in enumerate(whole):
        np.testing.assert_array_equal(run(l), want)
