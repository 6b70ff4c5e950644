"""NequIP: E(3)-equivariant interatomic potential [arXiv:2101.03164].

Features are direct sums of O(3) irreps, stored as {l: (N, mul, 2l+1)}.
One interaction block (paper Fig. 1):

  for each path (l1, l2, l3) with l1 in features, l2 in Y(r_ij), l3 <= l_max:
     m_ij^{l3} += R_path(|r_ij|) * CG[l3 l1 l2] (h_j^{l1} (x) Y^{l2}(r_ij))
  h_i <- SelfInteraction( h_i , sum_{j in N(i)} m_ij )       (per-l linear)
  h_i <- Gate(h_i)     (silu on l=0; l>0 gated by learned scalar sigmoid)

R_path is an MLP over n_rbf Bessel radial basis functions with a smooth
polynomial cutoff envelope. CG intertwiners come from
repro.models.gnn.equivariant (numerically exact, host-side constants).

Config: 5 layers, 32 channels per irrep, l_max = 2, 8 RBFs, cutoff 5 A.

Output: per-atom scalar energies (l=0 head) summed per graph; forces =
-grad(E, positions), exercised in tests for equivariance.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain
from repro.models.common import ParamSpec
from repro.models.gnn.equivariant import intertwiner, real_sph_harm, tp_paths


@dataclasses.dataclass(frozen=True)
class NequIPConfig:
    name: str = "nequip"
    n_layers: int = 5
    d_hidden: int = 32          # multiplicity per irrep
    l_max: int = 2
    n_rbf: int = 8
    cutoff: float = 5.0
    n_species: int = 10
    radial_hidden: int = 64
    compute_dtype: Any = jnp.float32

    @property
    def ls(self) -> tuple[int, ...]:
        return tuple(range(self.l_max + 1))

    @property
    def paths(self):
        return tp_paths(self.ls, self.ls, self.ls)


# --------------------------------------------------------------- radial basis
def bessel_rbf(dist: jnp.ndarray, n_rbf: int, cutoff: float) -> jnp.ndarray:
    """sin(n pi d / rc) / d Bessel basis with smooth polynomial envelope."""
    d = jnp.maximum(dist, 1e-6)[..., None]
    n = jnp.arange(1, n_rbf + 1, dtype=d.dtype)
    basis = jnp.sqrt(2.0 / cutoff) * jnp.sin(n * jnp.pi * d / cutoff) / d
    u = jnp.clip(dist / cutoff, 0.0, 1.0)[..., None]
    env = 1.0 - 10.0 * u**3 + 15.0 * u**4 - 6.0 * u**5   # C2 cutoff poly
    return basis * env


# -------------------------------------------------------------------- params
def param_specs(cfg: NequIPConfig):
    mul, ls, L = cfg.d_hidden, cfg.ls, cfg.n_layers
    n_paths = len(cfg.paths)
    layer = {
        # radial MLP -> one weight per (path, channel)
        "rad_w0": ParamSpec((L, cfg.n_rbf, cfg.radial_hidden), ("layers", None, None)),
        "rad_b0": ParamSpec((L, cfg.radial_hidden), ("layers", None), init_scale=0.0),
        "rad_w1": ParamSpec((L, cfg.radial_hidden, n_paths * mul),
                            ("layers", None, "mlp")),
        # per-l self-interaction mixing after aggregation (input: mul * n_in_paths)
        **{f"self_l{l}": ParamSpec(
            (L, mul * (1 + sum(1 for (a, b, c) in cfg.paths if c == l)), mul),
            ("layers", None, None)) for l in ls},
        # gate scalars for l>0 irreps
        "gate_w": ParamSpec((L, mul, mul * cfg.l_max), ("layers", None, None)),
        "gate_b": ParamSpec((L, mul * cfg.l_max), ("layers", None), init_scale=0.0),
    }
    return {
        "embed": ParamSpec((cfg.n_species, mul), ("vocab", None)),
        "layers": layer,
        "out_w0": ParamSpec((mul, mul), (None, None)),
        "out_b0": ParamSpec((mul,), (None,), init_scale=0.0),
        "out_w1": ParamSpec((mul, 1), (None, None)),
    }


# ------------------------------------------------------------------- forward
def _interaction(lp, feats, sh, rad, edge_src, edge_dst, n_nodes, cfg,
                 *, gather=None, scatter=None):
    """One NequIP interaction block. feats: {l: (N, mul, 2l+1)}.

    gather/scatter hooks let the shard_map path (forward_energy_shardmap)
    reuse the exact same math with destination-partitioned edges:
      gather(f)  default f[edge_src]       (pjit: GSPMD all-gathers f)
      scatter(m) default segment_sum(m, edge_dst, n_nodes)
                                           (pjit: full-size local buffers)
    """
    mul = cfg.d_hidden
    gather = gather or (lambda f: f[edge_src])
    scatter = scatter or (lambda m: constrain(
        jax.ops.segment_sum(m, edge_dst, num_segments=n_nodes),
        ("act_nodes", None, None)))
    # per-edge, per-path, per-channel radial weights
    h = jax.nn.silu(rad @ lp["rad_w0"] + lp["rad_b0"])
    w = (h @ lp["rad_w1"]).reshape(-1, len(cfg.paths), mul)   # (E, P, mul)

    src_full = {l: gather(feats[l]) for l in cfg.ls}     # one gather per l
    msgs = {l: [] for l in cfg.ls}
    for p_idx, (l1, l2, l3) in enumerate(cfg.paths):
        T = jnp.asarray(intertwiner(l1, l2, l3), feats[l1].dtype)  # (2l3+1,2l1+1,2l2+1)
        src_f = src_full[l1]                             # (E, mul, 2l1+1)
        y = sh[l2]                                       # (E, 2l2+1)
        m = jnp.einsum("kij,eci,ej->eck", T, src_f, y)   # (E, mul, 2l3+1)
        msgs[l3].append(constrain(m * w[:, p_idx, :, None],
                                  ("act_edges", None, None)))
    out = {}
    for l in cfg.ls:
        # NOTE (measured, see EXPERIMENTS.md §Perf I10): under GSPMD each
        # segment_sum scatter builds a full-size local node buffer per shard
        # and each gather all-gathers the node features — the structural fix
        # is forward_energy_shardmap (EAGr's reader partitioning applied to
        # GNNs), used for the huge full-graph shapes.
        stack = [feats[l]] + [scatter(m) for m in msgs[l]]
        cat = jnp.concatenate(stack, axis=1)             # (N, mul*(1+P_l), 2l+1)
        out[l] = constrain(jnp.einsum("nci,cd->ndi", cat, lp[f"self_l{l}"]),
                           ("act_nodes", None, None))
    # gate nonlinearity
    scalars = out[0][..., 0]                              # (N, mul)
    gates = jax.nn.sigmoid(scalars @ lp["gate_w"] + lp["gate_b"])
    gates = gates.reshape(-1, cfg.l_max, mul)
    new = {0: jax.nn.silu(scalars)[..., None]}
    for i, l in enumerate(range(1, cfg.l_max + 1)):
        new[l] = out[l] * gates[:, i, :, None]
    # residual on scalars (higher l start at zero features in layer 0)
    new[0] = new[0] + feats[0]
    return new


def forward_energy(params, positions, species, edge_src, edge_dst, edge_mask,
                   node_mask, graph_ids, n_graphs, cfg: NequIPConfig):
    """positions (N,3), species (N,), edges (E,). Returns (n_graphs,) energies."""
    cdt = cfg.compute_dtype
    n = positions.shape[0]
    rel = positions[edge_dst] - positions[edge_src]       # (E, 3)
    # grad-safe norm: masked/self edges have rel = 0; plain norm() gives NaN grads
    dist = jnp.sqrt(jnp.sum(rel * rel, axis=-1) + 1e-12)
    unit = rel / dist[:, None]
    emask = (edge_mask & (dist < cfg.cutoff)).astype(cdt)[:, None]
    # cast basis functions to compute dtype: fp32 sh/rad would silently
    # promote every edge message back to fp32
    sh = {l: (jnp.asarray(_sph(l, unit)) * emask).astype(cdt) for l in cfg.ls}
    rad = (bessel_rbf(dist, cfg.n_rbf, cfg.cutoff) * emask).astype(cdt)

    mul = cfg.d_hidden
    feats = {0: (jnp.take(params["embed"], species, axis=0)
                 * node_mask.astype(cdt)[:, None])[..., None]}
    for l in range(1, cfg.l_max + 1):
        feats[l] = jnp.zeros((n, mul, 2 * l + 1), cdt)

    inter = jax.checkpoint(
        lambda lp, feats: _interaction(lp, feats, sh, rad, edge_src,
                                       edge_dst, n, cfg))
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda x, i=i: x[i], params["layers"])
        feats = inter(lp, feats)

    scalars = feats[0][..., 0]
    e_atom = jax.nn.silu(scalars @ params["out_w0"] + params["out_b0"])
    e_atom = (e_atom @ params["out_w1"])[:, 0] * node_mask.astype(cdt)
    return jax.ops.segment_sum(e_atom, graph_ids, num_segments=n_graphs)


def forward_energy_shardmap(params, positions, species, edge_src, edge_dst,
                            edge_mask, node_mask, graph_ids, n_graphs,
                            cfg: NequIPConfig, mesh, axis_names):
    """Destination-partitioned message passing via shard_map — EAGr §7's
    reader partitioning applied to GNNs.

    INPUT CONTRACT (the input pipeline's job, declared here): node arrays are
    sharded into contiguous ranges over ``axis_names``; shard s's edge slice
    contains only edges whose DESTINATION lies in s's node range (any source).
    Then each shard: all-gathers the (small) per-l node features ONCE per
    layer, computes its local edges' messages, and segment-sums into its OWN
    node range — no full-size scatter buffers, no per-path all-gathers.

    graph_ids are ignored: the huge full-graph shapes have n_graphs == 1
    (energy = psum of local atom energies), which is the only regime where
    this path is selected.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import no_constrain

    cdt = cfg.compute_dtype
    n = positions.shape[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_shards = 1
    for a in axis_names:
        n_shards *= sizes[a]
    assert n % n_shards == 0, (n, n_shards)
    n_local = n // n_shards
    mul = cfg.d_hidden

    def shard_fn(prm, pos_l, spec_l, esrc, edst, emask_l, nmask_l):
        # flattened shard rank in the row-major order of axis_names
        rank = jnp.int32(0)
        for a in axis_names:
            rank = rank * sizes[a] + jax.lax.axis_index(a)

        pos_f = jax.lax.all_gather(pos_l, axis_names, axis=0, tiled=True)
        rel = pos_f[edst] - pos_f[esrc]
        dist = jnp.sqrt(jnp.sum(rel * rel, axis=-1) + 1e-12)
        unit = rel / dist[:, None]
        em = (emask_l & (dist < cfg.cutoff)).astype(cdt)[:, None]
        sh = {l: (jnp.asarray(_sph(l, unit)) * em).astype(cdt) for l in cfg.ls}
        rad = (bessel_rbf(dist, cfg.n_rbf, cfg.cutoff) * em).astype(cdt)

        # local destination segment ids; foreign/masked edges -> sink n_local
        edst_loc = edst - rank * n_local
        ok = (edst_loc >= 0) & (edst_loc < n_local)
        seg = jnp.where(ok, edst_loc, n_local)

        def gather(f_local):
            f_full = jax.lax.all_gather(f_local, axis_names, axis=0, tiled=True)
            return f_full[esrc]

        def scatter(m):
            return jax.ops.segment_sum(m, seg, num_segments=n_local + 1)[:n_local]

        feats = {0: (jnp.take(prm["embed"], spec_l, axis=0)
                     * nmask_l.astype(cdt)[:, None])[..., None]}
        for l in range(1, cfg.l_max + 1):
            feats[l] = jnp.zeros((n_local, mul, 2 * l + 1), cdt)

        inter = jax.checkpoint(
            lambda lp, feats: _interaction(lp, feats, sh, rad, esrc, None,
                                           None, cfg, gather=gather,
                                           scatter=scatter))
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda x, i=i: x[i], prm["layers"])
            feats = inter(lp, feats)

        scalars = feats[0][..., 0]
        e_atom = jax.nn.silu(scalars @ prm["out_w0"] + prm["out_b0"])
        e_atom = (e_atom @ prm["out_w1"])[:, 0] * nmask_l.astype(cdt)
        return jax.lax.psum(e_atom.sum()[None], axis_names)

    spec_n = P(axis_names)         # node/edge arrays: dim0 sharded
    p_specs = jax.tree.map(lambda _: P(), params)   # params replicated
    with no_constrain():
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(p_specs, spec_n, spec_n, spec_n, spec_n, spec_n, spec_n),
            out_specs=P(),
            check_vma=False,
        )(params, positions, species, edge_src, edge_dst, edge_mask, node_mask)


def _sph(l: int, unit: jnp.ndarray) -> jnp.ndarray:
    """jnp version of the host real_sph_harm formulas (traceable)."""
    x, y, z = unit[..., 0], unit[..., 1], unit[..., 2]
    if l == 0:
        return jnp.ones(unit.shape[:-1] + (1,), unit.dtype)
    if l == 1:
        return unit
    if l == 2:
        s3 = np.sqrt(3.0)
        return jnp.stack([
            x * y, y * z, (2 * z * z - x * x - y * y) / (2 * s3),
            x * z, (x * x - y * y) / 2.0], axis=-1) * s3
    raise NotImplementedError(l)


def energy_and_forces(params, positions, species, edge_src, edge_dst, edge_mask,
                      node_mask, graph_ids, n_graphs, cfg: NequIPConfig):
    def e_total(pos):
        return forward_energy(params, pos, species, edge_src, edge_dst,
                              edge_mask, node_mask, graph_ids, n_graphs, cfg).sum()
    e, grad = jax.value_and_grad(e_total)(positions)
    energies = forward_energy(params, positions, species, edge_src, edge_dst,
                              edge_mask, node_mask, graph_ids, n_graphs, cfg)
    return energies, -grad, e


def loss_fn(params, batch, cfg: NequIPConfig, force_weight: float = 1.0,
            use_forces: bool = True):
    """batch: dict with positions/species/edge_src/edge_dst/edge_mask/node_mask/
    graph_ids/energy_targets/force_targets. n_graphs = len(energy_targets).
    use_forces=False skips the grad-through-energy force term (used for the
    huge assigned graph shapes where there is no force supervision anyway)."""
    n_graphs = batch["energy_targets"].shape[0]

    def e_total(pos):
        e = forward_energy(params, pos, batch["species"], batch["edge_src"],
                           batch["edge_dst"], batch["edge_mask"],
                           batch["node_mask"], batch["graph_ids"],
                           n_graphs, cfg)
        return e.sum(), e

    if not use_forces:
        _, energies = e_total(batch["positions"])
        e_loss = jnp.mean((energies - batch["energy_targets"].astype(jnp.float32)) ** 2)
        return e_loss, {"e_mse": e_loss, "f_mse": jnp.float32(0.0)}

    (_, energies), grad = jax.value_and_grad(e_total, has_aux=True)(batch["positions"])
    forces = -grad
    e_loss = jnp.mean((energies - batch["energy_targets"].astype(jnp.float32)) ** 2)
    nm = batch["node_mask"].astype(jnp.float32)[:, None]
    f_loss = jnp.sum(((forces - batch["force_targets"]) ** 2) * nm) / jnp.maximum(nm.sum() * 3, 1.0)
    loss = e_loss + force_weight * f_loss
    return loss, {"e_mse": e_loss, "f_mse": f_loss}
