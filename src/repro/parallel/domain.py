"""Spatial domain decomposition of the coupled spin-lattice system.

State layout: cell-major arrays ``(CX, CY, CZ, K, ...)`` - a global grid of
link cells (each at least cutoff+skin wide) with a fixed per-cell atom
capacity K.  The grid's leading spatial dims are sharded over the device
mesh (pod->Z, data->X, model->Y by default); each device owns a rectangular
slab of cells, exactly like one MPI rank's sub-domain in the paper's LAMMPS
implementation.

One evaluation = halo exchange (6 ppermutes) + 27-stencil streaming
accumulation of the NEP-SPIN descriptor + MLP inference + psum of the
energy.  Forces and spin torques come from ``jax.grad`` of this scalar: the
adjoint of the halo exchange IS the ghost-force fold-back communication, so
the distributed gradient is exact by construction.

The fixed (cells x capacity) layout is the TPU adaptation of the paper's
pre-staging: rectangular, statically-shaped, fully predicated - no
gather/scatter neighbor packing on device.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.descriptor import (NEPSpinSpec, init_accumulators, accumulate,
                                   finalize)
from repro.core.potential import NEPSpinParams, mlp_energy
from repro.parallel.sharding import shard_map_compat
from repro.utils import units


@dataclasses.dataclass(frozen=True)
class DomainSpec:
    """Static description of the decomposition."""

    cells: tuple[int, int, int]          # global link-cell grid (CX, CY, CZ)
    capacity: int                        # atoms per cell (K)
    cutoff: float
    box: tuple[float, float, float]      # global box [A]
    # mesh axis name sharding each spatial dim (None = replicated/local)
    axis_map: tuple[str | None, str | None, str | None] = ("data", "model",
                                                           None)
    # neighbor-list skin [A]: cells must be >= cutoff+skin wide so a pruned
    # per-device table survives between half-skin-triggered rebuilds (the
    # sharded fused loop; 0.0 keeps the legacy per-eval stencil semantics)
    skin: float = 0.0

    @property
    def cell_size(self) -> tuple[float, float, float]:
        return tuple(b / c for b, c in zip(self.box, self.cells))

    @property
    def rc(self) -> float:
        """Neighbor-table reach: cutoff + skin."""
        return self.cutoff + self.skin

    def check(self):
        for b, c in zip(self.box, self.cells):
            assert b / c >= self.rc, (
                f"cell size {b/c:.3f} < cutoff+skin {self.rc}; stencil "
                "would miss neighbors")

    def check_loop(self, mesh: Mesh):
        """Extra invariants the sharded fused loop needs: every global dim
        >= 3 (27-stencil cells must be distinct) and sharded dims divisible
        by their mesh axis."""
        self.check()
        assert min(self.cells) >= 3, (
            f"global cell grid {self.cells} too small for the 27-stencil")
        for d, name in enumerate(self.axis_map):
            if name is not None:
                n = mesh.shape[name]
                assert self.cells[d] % n == 0, (
                    f"cells[{d}]={self.cells[d]} not divisible by mesh "
                    f"axis {name}={n}")

    def local_shape(self, mesh: Mesh) -> tuple[int, int, int]:
        """Per-device cell-grid dims under ``mesh``."""
        return tuple(
            c // (mesh.shape[name] if name is not None else 1)
            for c, name in zip(self.cells, self.axis_map))

    def pspec(self, *trailing) -> P:
        return P(*self.axis_map, *trailing)


class DomainState(NamedTuple):
    """Cell-binned spin-lattice state (positions are GLOBAL coordinates)."""

    pos: jax.Array    # (CX, CY, CZ, K, 3)
    vel: jax.Array    # (CX, CY, CZ, K, 3)
    spin: jax.Array   # (CX, CY, CZ, K, 3)
    types: jax.Array  # (CX, CY, CZ, K) int32, -1 = empty slot
    mask: jax.Array   # (CX, CY, CZ, K) bool


def pack_domain(spec: DomainSpec, pos, vel, spin, types,
                extras: dict | None = None):
    """Host-side binning of flat atom arrays into the cell grid.

    ``extras`` maps name -> (N, ...) array to bin alongside (e.g. original
    atom ids for the sharded loop); when given, returns
    ``(DomainState, {name: packed})`` with extras filled with -1.
    """
    pos = np.asarray(pos)
    box = np.asarray(spec.box)
    cells = np.asarray(spec.cells)
    ci = np.clip((pos / box * cells).astype(np.int64), 0, cells - 1)
    flat = (ci[:, 0] * spec.cells[1] + ci[:, 1]) * spec.cells[2] + ci[:, 2]
    order = np.argsort(flat, kind="stable")
    k = spec.capacity
    n_cells = int(np.prod(cells))
    counts = np.bincount(flat, minlength=n_cells)
    if counts.max() > k:
        raise ValueError(f"cell overflow: max {counts.max()} > capacity {k}")
    slot = np.zeros(pos.shape[0], np.int64)
    slot[order] = np.arange(pos.shape[0]) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)

    def scatter(a, fill):
        out = np.full((n_cells * k, *a.shape[1:]), fill, a.dtype)
        out[flat * k + slot] = a
        return out.reshape(*spec.cells, k, *a.shape[1:])

    state = DomainState(
        pos=jnp.asarray(scatter(pos, 0.0)),
        vel=jnp.asarray(scatter(np.asarray(vel), 0.0)),
        spin=jnp.asarray(scatter(np.asarray(spin), 0.0)),
        types=jnp.asarray(scatter(np.asarray(types), -1)),
        mask=jnp.asarray(scatter(np.ones(pos.shape[0], bool), False)),
    )
    if extras is None:
        return state
    packed = {name: jnp.asarray(scatter(np.asarray(a), -1))
              for name, a in extras.items()}
    return state, packed


def unpack_domain(state: DomainState):
    """Flatten back to (N, ...) dropping empty slots (host-side)."""
    mask = np.asarray(state.mask).reshape(-1)
    sel = np.nonzero(mask)[0]
    def flat(a, tail):
        return np.asarray(a).reshape(-1, *tail)[sel]
    return (flat(state.pos, (3,)), flat(state.vel, (3,)),
            flat(state.spin, (3,)), flat(state.types, ()))


def unbin_cells(aid, *arrays):
    """Host-side inverse of the cell binning, in ORIGINAL atom order.

    ``aid`` is the (CX, CY, CZ, K) original-atom-id block the sharded loop
    carries through migrations (-1 = empty slot); each of ``arrays`` is a
    cell-blocked (CX, CY, CZ, K, ...) field.  Returns the (N, ...) arrays
    ordered by atom id - the canonical unsharded form the elastic-restart
    loader re-bins onto a new grid (the same inverse ``Engine._sync_domain``
    applies at observation boundaries).
    """
    aidf = np.asarray(aid).reshape(-1)
    sel = np.nonzero(aidf >= 0)[0]
    n = sel.size
    order = np.empty(n, np.int64)
    order[aidf[sel]] = sel
    outs = []
    for a in arrays:
        a = np.asarray(a)
        outs.append(a.reshape(-1, *a.shape[4:])[order])
    return tuple(outs)


# 27-point stencil shifts
_SHIFTS = [(dx, dy, dz) for dx in (-1, 0, 1) for dy in (-1, 0, 1)
           for dz in (-1, 0, 1)]


def _local_energy(
    spec: NEPSpinSpec,
    dspec: DomainSpec,
    params: NEPSpinParams,
    pos, spin, types, mask,           # local blocks (cx,cy,cz,K,...)
    field,                            # (3,) Tesla or None
    moments,                          # (n_types,)
):
    """Per-device energy: halo exchange + 27-shift streaming accumulation."""
    from repro.parallel.halo import exchange_halo

    dtype = pos.dtype
    box = jnp.asarray(dspec.box, dtype)
    ids = jnp.arange(int(np.prod(mask.shape)), dtype=jnp.int32)
    # globally unique slot ids for self-pair exclusion: offset by device index
    dev = jnp.asarray(0, jnp.int32)
    for name in dspec.axis_map:
        if name is not None:
            dev = dev * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    ids = ids.reshape(mask.shape) + dev * jnp.asarray(
        int(np.prod(mask.shape)), jnp.int32) + 1
    ids = jnp.where(mask, ids, 0)  # 0 = empty

    ext_pos = exchange_halo(pos, dspec.axis_map)
    ext_spin = exchange_halo(spin, dspec.axis_map)
    ext_type = exchange_halo(types, dspec.axis_map)
    ext_ids = exchange_halo(ids, dspec.axis_map)

    cx, cy, cz, k = mask.shape
    ti = jnp.where(mask, types, 0)
    acc0 = init_accumulators(spec, (cx, cy, cz, k), dtype)
    eps = jnp.asarray(1e-12 if dtype == jnp.float32 else 1e-30, dtype)
    shifts = jnp.asarray(_SHIFTS, jnp.int32)  # (27, 3)

    # scan over the 27-point stencil: 27x smaller HLO than unrolling (keeps
    # the 512-device dry-run compile tractable); the body is rematerialized
    # in the backward pass so pair blocks are never all live at once.
    @jax.checkpoint
    def stencil_body(acc, shift):
        sx, sy, sz = 1 + shift[0], 1 + shift[1], 1 + shift[2]
        zero = jnp.zeros((), shift.dtype)
        npos = jax.lax.dynamic_slice(ext_pos, (sx, sy, sz, zero, zero),
                                     (cx, cy, cz, k, 3))
        nspin = jax.lax.dynamic_slice(ext_spin, (sx, sy, sz, zero, zero),
                                      (cx, cy, cz, k, 3))
        ntype = jax.lax.dynamic_slice(ext_type, (sx, sy, sz, zero),
                                      (cx, cy, cz, k))
        nids = jax.lax.dynamic_slice(ext_ids, (sx, sy, sz, zero),
                                     (cx, cy, cz, k))
        # pair block: own atoms (K) x neighbor-cell atoms (K)
        dr = npos[..., None, :, :] - pos[..., :, None, :]
        dr = dr - box * jnp.round(dr / box)      # min-image (global PBC)
        dist = jnp.sqrt(jnp.sum(dr * dr, axis=-1) + eps)
        pmask = (mask[..., :, None] & (nids[..., None, :] > 0)
                 & (ids[..., :, None] != nids[..., None, :])
                 & (dist <= dspec.cutoff))
        acc = accumulate(
            spec, params.desc_params(), acc, dr, dist, pmask,
            ti, jnp.broadcast_to(jnp.where(nids > 0, ntype, 0)[..., None, :],
                                 (cx, cy, cz, k, k)),
            spin, jnp.broadcast_to(nspin[..., None, :, :],
                                   (cx, cy, cz, k, k, 3)))
        return acc, None

    acc, _ = jax.lax.scan(stencil_body, acc0, shifts)

    q = finalize(spec, acc, spin)
    e = mlp_energy(params, q.reshape(-1, spec.n_desc), ti.reshape(-1))
    e = jnp.where(mask.reshape(-1), e, 0.0)
    etot = jnp.sum(e)
    if field is not None:
        mom = jnp.where(mask, moments[ti], 0.0)
        etot = etot - units.MU_B * jnp.sum(
            mom[..., None] * spin * jnp.asarray(field, dtype))
    for name in dspec.axis_map:
        if name is not None:
            etot = jax.lax.psum(etot, name)
    return etot


def distributed_energy_fn(
    spec: NEPSpinSpec,
    dspec: DomainSpec,
    mesh: Mesh,
    field=None,
    moments=None,
):
    """Build E(params, state) with shard_map over the spatial mesh.

    Returns (energy_fn, energy_forces_field_fn); both are jit-able and
    differentiable - the gradient re-uses the halo adjoint for ghost-force
    fold-back.
    """
    mom = moments if moments is not None else jnp.ones((max(spec.n_types, 1),))
    cell_spec = dspec.pspec()            # P(axes..., ) for (CX,CY,CZ,...) dims

    def _energy_local(params, pos, spin, types, mask):
        return _local_energy(spec, dspec, params, pos, spin, types, mask,
                             field, mom)

    _energy = shard_map_compat(
        _energy_local, mesh,
        in_specs=(P(), dspec.pspec(None, None), dspec.pspec(None, None),
                  dspec.pspec(None), dspec.pspec(None)),
        out_specs=P())

    def energy(params, state: DomainState):
        return _energy(params, state.pos, state.spin, state.types, state.mask)

    def energy_forces_field(params, state: DomainState):
        e, g = jax.value_and_grad(
            lambda p, s: _energy(params, p, s, state.types, state.mask),
            argnums=(0, 1))(state.pos, state.spin)
        return e, -g[0], -g[1]

    def raw_energy_forces_field(params, pos, spin, types, mask):
        e, g = jax.value_and_grad(
            lambda p, s: _energy(params, p, s, types, mask),
            argnums=(0, 1))(pos, spin)
        return e, -g[0], -g[1]

    energy_forces_field.raw = raw_energy_forces_field
    return energy, energy_forces_field


# ---------------------------------------------------------------------------
# Pre-staged (pruned) evaluation path - the paper's Phase-A/B pre-staging
# ---------------------------------------------------------------------------
#
# The 27-cell stencil enumerates 27*K candidates per atom but only ~40-55
# fall inside the cutoff: ~7x of the pair arithmetic is masked waste. Like
# the paper's SVE2 pre-staging (scalar cutoff filter -> packed SoA buffer ->
# predicated vector batches), we build a pruned per-atom neighbor table
# (distance-sorted top-M into the halo-extended arrays) once per skin
# violation, and the per-step evaluation streams exactly M candidates.
# Solids barely diffuse, so the table survives many steps.

def _ext_flat(x, dspec):
    """Halo-extend and flatten spatial+slot dims -> (n_ext, ...)."""
    from repro.parallel.halo import exchange_halo
    ext = exchange_halo(x, dspec.axis_map)
    return ext.reshape(-1, *x.shape[4:]) if x.ndim > 4 else \
        ext.reshape(-1)


def build_domain_table(spec, dspec, capacity, pos, types, mask):
    """Per-device pruned neighbor table (call inside shard_map).

    Returns (idx (cx,cy,cz,K,M) int32 into the flattened extended arrays,
    nbr_mask (cx,cy,cz,K,M) bool).
    """
    from repro.parallel.halo import exchange_halo
    cx, cy, cz, k = mask.shape
    dtype = pos.dtype
    box = jnp.asarray(dspec.box, dtype)
    eps = 1e-12 if dtype == jnp.float32 else 1e-30

    # globally unique slot ids (offset by device index) so ghost ids from
    # neighboring devices never collide with local ids in self-exclusion
    dev = jnp.asarray(0, jnp.int32)
    for name in dspec.axis_map:
        if name is not None:
            dev = dev * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    ids = jnp.arange(cx * cy * cz * k, dtype=jnp.int32).reshape(mask.shape)
    ids = ids + dev * jnp.asarray(cx * cy * cz * k, jnp.int32)
    ids = jnp.where(mask, ids, -1)
    ext_pos = exchange_halo(pos, dspec.axis_map)
    ext_ids = exchange_halo(ids, dspec.axis_map)
    # mark ghosts with distinct ids so self-pairs are excluded but ghost
    # copies of the same atom (impossible within cutoff; box >= 4 cells)
    # need no special casing
    exf_pos = ext_pos.reshape(-1, 3)
    exf_ids = ext_ids.reshape(-1)

    # candidate flat indices for each cell: its 27-neighborhood
    ex_cx, ex_cy, ex_cz = cx + 2, cy + 2, cz + 2

    def cell_flat(ix, iy, iz):          # index into extended flat array
        return ((ix * ex_cy + iy) * ex_cz + iz)

    cells_x = jnp.arange(cx)
    cells_y = jnp.arange(cy)
    cells_z = jnp.arange(cz)
    gx, gy, gz = jnp.meshgrid(cells_x, cells_y, cells_z, indexing="ij")
    offs = jnp.asarray(_SHIFTS, jnp.int32)          # (27, 3)
    nb_cell = cell_flat(gx[..., None] + 1 + offs[:, 0],
                        gy[..., None] + 1 + offs[:, 1],
                        gz[..., None] + 1 + offs[:, 2])  # (cx,cy,cz,27)
    cand = (nb_cell[..., :, None] * k
            + jnp.arange(k)[None, None, None, None, :])  # (cx,cy,cz,27,K)
    cand = cand.reshape(cx, cy, cz, 27 * k)

    cpos = exf_pos[cand]                            # (cx,cy,cz,27K,3)
    cids = exf_ids[cand]
    own_ids = jnp.where(mask, ids, -2)
    dr = cpos[..., None, :, :] - pos[..., :, None, :]   # (...,K,27K,3)
    dr = dr - box * jnp.round(dr / box)
    d2 = jnp.sum(dr * dr, axis=-1)
    cids_b = jnp.broadcast_to(cids[..., None, :], d2.shape)
    good = ((cids_b >= 0)
            & (cids_b != own_ids[..., None])
            & (d2 <= dspec.cutoff ** 2)
            & mask[..., None])
    neg = jnp.where(good, -d2, -jnp.inf)
    m_cap = min(capacity, neg.shape[-1])
    vals, sel = jax.lax.top_k(neg, m_cap)           # (cx,cy,cz,K,M)
    nbr_mask = vals > -jnp.inf
    idx = jnp.take_along_axis(
        jnp.broadcast_to(cand[..., None, :], d2.shape), sel, axis=-1)
    idx = jnp.where(nbr_mask, idx, 0)
    return idx.astype(jnp.int32), nbr_mask


def _local_energy_pruned(spec, dspec, params, pos, spin, types, mask,
                         tbl_idx, tbl_mask, field, moments):
    """Per-device energy via the pruned table: ONE accumulate pass over M
    candidates instead of 27 stencil blocks."""
    dtype = pos.dtype
    box = jnp.asarray(dspec.box, dtype)
    eps = jnp.asarray(1e-12 if dtype == jnp.float32 else 1e-30, dtype)
    exf_pos = _ext_flat(pos, dspec)
    exf_spin = _ext_flat(spin, dspec)
    exf_type = _ext_flat(jnp.maximum(types, 0), dspec)

    npos = exf_pos[tbl_idx]                         # (cx,cy,cz,K,M,3)
    nspin = exf_spin[tbl_idx]
    ntype = exf_type[tbl_idx]
    dr = npos - pos[..., None, :]
    dr = dr - box * jnp.round(dr / box)
    dist = jnp.sqrt(jnp.sum(dr * dr, axis=-1) + eps)
    pmask = tbl_mask & (dist <= dspec.cutoff)

    ti = jnp.where(mask, types, 0)
    acc = init_accumulators(spec, mask.shape, dtype)
    acc = accumulate(spec, params.desc_params(), acc, dr, dist, pmask,
                     ti, ntype, spin, nspin)
    q = finalize(spec, acc, spin)
    e = mlp_energy(params, q.reshape(-1, spec.n_desc), ti.reshape(-1))
    e = jnp.where(mask.reshape(-1), e, 0.0)
    etot = jnp.sum(e)
    if field is not None:
        mom = jnp.where(mask, moments[ti], 0.0)
        etot = etot - units.MU_B * jnp.sum(
            mom[..., None] * spin * jnp.asarray(field, dtype))
    for name in dspec.axis_map:
        if name is not None:
            etot = jax.lax.psum(etot, name)
    return etot


def distributed_energy_fn_pruned(spec, dspec, mesh, capacity=64,
                                 field=None, moments=None):
    """Pre-staged variant: (build_table_fn, energy_forces_field_fn).

    build_table(state-arrays) -> (idx, mask) per device; the evaluation
    consumes the table (skin-test-triggered rebuilds, like md.simulate).
    """
    from jax.sharding import PartitionSpec as P
    mom = moments if moments is not None else jnp.ones((max(spec.n_types,
                                                            1),))
    cell = dspec.pspec

    build = shard_map_compat(
        partial(build_domain_table, spec, dspec, capacity), mesh,
        in_specs=(cell(None, None), cell(None), cell(None)),
        out_specs=(cell(None, None), cell(None, None)))

    def _energy_local(params, pos, spin, types, mask, tbl_idx, tbl_mask):
        return _local_energy_pruned(spec, dspec, params, pos, spin, types,
                                    mask, tbl_idx, tbl_mask, field, mom)

    _energy = shard_map_compat(
        _energy_local, mesh,
        in_specs=(P(), cell(None, None), cell(None, None), cell(None),
                  cell(None), cell(None, None), cell(None, None)),
        out_specs=P())

    def energy_forces_field(params, pos, spin, types, mask, tbl_idx,
                            tbl_mask):
        e, g = jax.value_and_grad(
            lambda p, s: _energy(params, p, s, types, mask, tbl_idx,
                                 tbl_mask), argnums=(0, 1))(pos, spin)
        return e, -g[0], -g[1]

    return build, energy_forces_field


# ---------------------------------------------------------------------------
# Production TPU path: fused Pallas kernels over the pruned domain table
# ---------------------------------------------------------------------------
#
# Composition of the three production pieces: (1) the pruned pre-staged
# neighbor table, (2) the fused NEP Pallas kernels (K1 descriptor+ANN+
# adjoints, K2 pair-symmetric force/torque - repro.kernels.nep), and
# (3) halo exchange of the adjoint accumulators (the paper's q_Fp
# communication step): each device runs K1 on its own atoms, exchanges the
# per-atom adjoints with its 26 neighbors (one extra halo round), gathers
# neighbor adjoints through the same pruned table, and runs K2 - forces and
# torques come out pair-symmetric with NO reverse force scatter.
# ``mode`` selects the kernel executor (repro.kernels.nep.kernel): on TPU
# the pallas_call compiles to Mosaic kernels; on CPU "auto" resolves to
# the compiled lax.map tiling ("xla_tiled"); "interpret" remains the slow
# per-ref debugging oracle.

def distributed_kernel_force_fn(spec, dspec, mesh, capacity=64,
                                field=None, moments=None, mode="auto"):
    """Returns (build_table_fn, energy_forces_field_fn) matching the
    signatures of distributed_energy_fn_pruned, but evaluated with the
    fused Pallas kernels instead of autodiff."""
    from jax.sharding import PartitionSpec as P
    from repro.kernels.nep.kernel import (gather_abar, nep_atom_pass,
                                          nep_force_pass)
    from repro.parallel.halo import exchange_halo

    mom = moments if moments is not None else jnp.ones((max(spec.n_types,
                                                            1),))
    cell = dspec.pspec

    build = shard_map_compat(
        partial(build_domain_table, spec, dspec, capacity), mesh,
        in_specs=(cell(None, None), cell(None), cell(None)),
        out_specs=(cell(None, None), cell(None, None)))

    def body(params, pos, spin, types, mask, tbl_idx, tbl_mask):
        cx, cy, cz, k = mask.shape
        n_loc = cx * cy * cz * k
        m_cap = tbl_idx.shape[-1]
        dtype = pos.dtype
        box = jnp.asarray(dspec.box, dtype)
        eps = jnp.asarray(1e-12 if dtype == jnp.float32 else 1e-30, dtype)

        exf_pos = _ext_flat(pos, dspec)
        exf_spin = _ext_flat(spin, dspec)
        exf_type = _ext_flat(jnp.maximum(types, 0), dspec)

        idx_f = tbl_idx.reshape(n_loc, m_cap)
        msk_f = tbl_mask.reshape(n_loc, m_cap)
        npos = exf_pos[idx_f]
        dr = npos - pos.reshape(n_loc, 1, 3)
        dr = dr - box * jnp.round(dr / box)
        dist2 = jnp.sum(dr * dr, axis=-1)
        msk_f = msk_f & (dist2 <= dspec.cutoff ** 2)
        sj = exf_spin[idx_f]
        tj = exf_type[idx_f]
        ti = jnp.where(mask, types, 0).reshape(n_loc)
        si = spin.reshape(n_loc, 3)
        amask = mask.reshape(n_loc)

        # K1: descriptor + ANN + adjoint accumulators (per-atom)
        e, hdir, abar = nep_atom_pass(spec, params, dr, msk_f, amask, ti,
                                      tj, si, sj, mode=mode)

        # q_Fp exchange: adjoints of ghosts via one extra halo round
        n_rows = abar.shape[0]
        ext = exchange_halo(abar.T.reshape(cx, cy, cz, k, n_rows),
                            dspec.axis_map)
        abar_j = gather_abar(ext.reshape(-1, n_rows).T, idx_f)

        # K2: fused pair-symmetric force + torque (one neighbor pass)
        f, h2 = nep_force_pass(spec, params, dr, msk_f, ti, tj, si, sj,
                               abar, abar_j, mode=mode)
        heff = hdir + h2
        etot = jnp.sum(jnp.where(amask, e, 0.0))
        if field is not None:
            momv = jnp.where(amask, mom[ti], 0.0)
            etot = etot - units.MU_B * jnp.sum(
                momv[:, None] * si * jnp.asarray(field, dtype))
            heff = heff + units.MU_B * momv[:, None] * jnp.asarray(field,
                                                                   dtype)
        for name in dspec.axis_map:
            if name is not None:
                etot = jax.lax.psum(etot, name)
        shape = (cx, cy, cz, k, 3)
        return etot, f.reshape(shape), heff.reshape(shape)

    effn = shard_map_compat(
        body, mesh,
        in_specs=(P(), cell(None, None), cell(None, None), cell(None),
                  cell(None), cell(None, None), cell(None, None)),
        out_specs=(P(), cell(None, None), cell(None, None)))

    return build, effn


# ---------------------------------------------------------------------------
# Sharded fused MD loop: per-device building blocks
# ---------------------------------------------------------------------------
#
# Everything below runs INSIDE shard_map on one device's (cx, cy, cz, K, ...)
# block and is consumed by repro.md.simulate.SimulationSharded, the domain-
# decomposed twin of the fused single-device driver.  The layout contract:
#
# * atom rows live in fixed-capacity link cells; ``types == -1`` marks empty
#   slots (the occupancy mask is derived, never carried separately);
# * the per-device pruned neighbor table (``Neighborhood`` with cell-major
#   (cx, cy, cz, K, M) blocks) indexes the *halo-extended flat* arrays - one
#   position halo after each drift refreshes ``dr`` for every owned pair;
# * neighbor spins are re-exchanged inside each potential evaluation (spins
#   change between evaluations at fixed positions), and the spin-gradient
#   fold-back is the automatic adjoint of that exchange;
# * reaction forces scattered onto ghost rows return to their owners through
#   one explicit ``fold_halo`` round (the paper's reverse communication);
# * at rebuild, atoms migrate to their new cells (possibly on a neighboring
#   device) through ONE fused multi-field exchange; capacity overflow and
#   out-of-reach migrations are *counted*, never silently dropped - the
#   driver raises at the next chunk boundary.


def _ext_flat_index(local_shape: tuple[int, int, int], k: int):
    """Candidate bookkeeping for the 27-stencil over the halo-extended grid.

    Returns (cand, own, shift_id):
      cand  (cx, cy, cz, 27*K) int32 - ext-flat slot index of every stencil
            candidate of each cell;
      own   (cx, cy, cz, K) int32    - each slot's own ext-flat index;
      shift_id (27*K,) int32         - which of the 27 shifts a candidate
            column came from (column-major pairing with ``_SHIFTS``).
    """
    cx, cy, cz = local_shape
    ex_cy, ex_cz = cy + 2, cz + 2

    def cell_flat(ix, iy, iz):
        return (ix * ex_cy + iy) * ex_cz + iz

    gx, gy, gz = jnp.meshgrid(jnp.arange(cx), jnp.arange(cy),
                              jnp.arange(cz), indexing="ij")
    offs = jnp.asarray(_SHIFTS, jnp.int32)                     # (27, 3)
    nb_cell = cell_flat(gx[..., None] + 1 + offs[:, 0],
                        gy[..., None] + 1 + offs[:, 1],
                        gz[..., None] + 1 + offs[:, 2])        # (cx,cy,cz,27)
    cand = (nb_cell[..., :, None] * k
            + jnp.arange(k)[None, None, None, None, :])        # (...,27,K)
    cand = cand.reshape(cx, cy, cz, 27 * k).astype(jnp.int32)
    own = (cell_flat(gx + 1, gy + 1, gz + 1)[..., None] * k
           + jnp.arange(k)[None, None, None, :]).astype(jnp.int32)
    shift_id = jnp.repeat(jnp.arange(27, dtype=jnp.int32), k)
    return cand, own, shift_id


def build_local_table(dspec: DomainSpec, local_shape: tuple[int, int, int],
                      capacity: int, pos, types, allgather: bool = False):
    """Per-device pruned neighbor table (call inside shard_map).

    Enumerates each owned atom's 27-stencil candidates in the halo-extended
    block, keeps the ``capacity`` nearest within cutoff+skin (top-k, like
    the flat tables), and returns a cell-major table:
    (idx (cx,cy,cz,K,M) int32 into the ext-flat arrays - self-padded where
    invalid, mask, tj neighbor types).  One fused (pos, types) halo round.
    """
    from repro.parallel.halo import exchange_halo_multi

    cx, cy, cz = local_shape
    k = types.shape[3]
    dtype = pos.dtype
    box = jnp.asarray(dspec.box, dtype)
    rc = dspec.rc
    occ = types >= 0

    ext = exchange_halo_multi({"pos": pos, "types": types},
                              dspec.axis_map, tag="rebuild",
                              allgather=allgather)
    exf_pos = ext["pos"].reshape(-1, 3)
    exf_typ = ext["types"].reshape(-1)

    cand, own, _ = _ext_flat_index(local_shape, k)
    cpos = exf_pos[cand]                                # (cx,cy,cz,27K,3)
    cocc = exf_typ[cand] >= 0
    dr = cpos[..., None, :, :] - pos[..., :, None, :]   # (...,K,27K,3)
    dr = dr - box * jnp.round(dr / box)
    d2 = jnp.sum(dr * dr, axis=-1)
    good = (cocc[..., None, :]
            & (cand[..., None, :] != own[..., :, None])
            & (d2 <= rc * rc)
            & occ[..., None])
    neg = jnp.where(good, -d2, -jnp.inf)
    m_cap = min(capacity, neg.shape[-1])
    vals, sel = jax.lax.top_k(neg, m_cap)               # (cx,cy,cz,K,M)
    mask = vals > -jnp.inf
    idx = jnp.take_along_axis(
        jnp.broadcast_to(cand[..., None, :], d2.shape), sel, axis=-1)
    idx = jnp.where(mask, idx, own[..., None])          # self-pad invalid
    tj = jnp.where(mask, exf_typ[idx], 0)
    return idx.astype(jnp.int32), mask, tj.astype(jnp.int32)


def migrate_cells(dspec: DomainSpec, local_shape: tuple[int, int, int],
                  pos, vel, spin, types, aid, allgather: bool = False):
    """Re-bin every atom into its current cell, moving emigrants to the
    neighboring device that owns their new cell (call inside shard_map).

    Between rebuilds atoms move less than the skin, so the new cell is
    always within the 27-stencil of the old one: ONE fused multi-field halo
    round makes every migrating atom visible to its new owner, and each
    target cell packs its claimants with a predicated rank-scatter.

    Returns (pos, vel, spin, types, aid, n_moved, n_dropped) with the
    per-device counts NOT yet psummed:
      n_moved   - owned atoms that changed cell (diagnostics);
      n_dropped - atoms lost to capacity overflow in some cell plus atoms
                  that moved further than one cell (skin violation).  The
                  driver psums this and fails loudly at chunk boundaries.
    """
    from repro.parallel.halo import exchange_halo_multi

    cx, cy, cz = local_shape
    k = types.shape[3]
    n_cells = cx * cy * cz
    dtype = pos.dtype
    box = jnp.asarray(dspec.box, dtype)
    cells = jnp.asarray(dspec.cells, jnp.int32)
    occ = types >= 0

    # new global cell of every owned atom (positions are PBC-wrapped)
    newc = jnp.floor(pos / box * cells.astype(dtype)).astype(jnp.int32)
    newc = jnp.clip(newc, 0, cells - 1)                 # fp edge guard

    # this device's global coords of each slot
    offs = []
    for d, name in enumerate(dspec.axis_map):
        o = (jax.lax.axis_index(name) * local_shape[d]
             if name is not None else 0)
        offs.append(o)
    gx, gy, gz = jnp.meshgrid(jnp.arange(cx) + offs[0],
                              jnp.arange(cy) + offs[1],
                              jnp.arange(cz) + offs[2], indexing="ij")
    ownc = jnp.stack([jnp.broadcast_to(g[..., None], types.shape)
                      for g in (gx, gy, gz)], axis=-1).astype(jnp.int32)

    # minimum-image cell displacement on the periodic global grid
    delta = jnp.mod(newc - ownc, cells)
    delta = jnp.where(delta > cells // 2, delta - cells, delta)
    in_reach = jnp.all(jnp.abs(delta) <= 1, axis=-1) & occ
    moved = in_reach & jnp.any(delta != 0, axis=-1)
    n_moved = jnp.sum(moved.astype(jnp.int32))
    n_out_of_reach = jnp.sum(
        (occ & ~in_reach).astype(jnp.int32))
    # -1 encodes "not claimable" (empty slot or skin-violating jump)
    enc = jnp.where(in_reach,
                    ((delta[..., 0] + 1) * 3 + (delta[..., 1] + 1)) * 3
                    + (delta[..., 2] + 1), -1).astype(jnp.int32)

    ext = exchange_halo_multi(
        {"pos": pos, "vel": vel, "spin": spin,
         "types": types, "aid": aid, "enc": enc},
        dspec.axis_map, tag="migrate", allgather=allgather)

    cand, _, shift_id = _ext_flat_index(local_shape, k)
    cand_enc = ext["enc"].reshape(-1)[cand]             # (cx,cy,cz,27K)
    # a candidate seen through stencil shift s belongs here iff its cell
    # displacement is exactly -s
    offs27 = jnp.asarray(_SHIFTS, jnp.int32)            # (27, 3)
    want = (((-offs27[:, 0] + 1) * 3 + (-offs27[:, 1] + 1)) * 3
            + (-offs27[:, 2] + 1))                      # (27,)
    belongs = cand_enc == want[shift_id][None, None, None, :]

    rank = jnp.cumsum(belongs.astype(jnp.int32), axis=-1) - 1
    slot = jnp.where(belongs & (rank < k), rank, k)     # k = dump column
    n_overflow = jnp.sum((belongs & (rank >= k)).astype(jnp.int32))

    payload = jnp.concatenate(
        [ext["pos"].reshape(-1, 3), ext["vel"].reshape(-1, 3),
         ext["spin"].reshape(-1, 3),
         ext["types"].reshape(-1, 1).astype(dtype),
         ext["aid"].reshape(-1, 1).astype(dtype)], axis=-1)[cand]
    nf = payload.shape[-1]
    rows = jnp.broadcast_to(
        jnp.arange(n_cells, dtype=jnp.int32)[:, None], (n_cells, 27 * k))
    out = jnp.zeros((n_cells, k + 1, nf), dtype)
    out = out.at[rows.reshape(-1), slot.reshape(-1)].set(
        payload.reshape(n_cells, 27 * k, nf).reshape(-1, nf))
    got = jnp.zeros((n_cells, k + 1), bool).at[
        rows.reshape(-1), slot.reshape(-1)].set(belongs.reshape(-1))
    out, got = out[:, :k], got[:, :k]

    def field(sl, tail):
        a = out[..., sl].reshape(cx, cy, cz, k, *tail)
        return jnp.where(got.reshape(cx, cy, cz, k).reshape(
            cx, cy, cz, k, *([1] * len(tail))), a, 0.0)

    new_types = jnp.where(got, jnp.round(out[..., 9]).astype(jnp.int32),
                          -1).reshape(cx, cy, cz, k)
    new_aid = jnp.where(got, jnp.round(out[..., 10]).astype(jnp.int32),
                        -1).reshape(cx, cy, cz, k)
    return (field(slice(0, 3), (3,)), field(slice(3, 6), (3,)),
            field(slice(6, 9), (3,)), new_types, new_aid,
            n_moved, n_overflow + n_out_of_reach)


class DomainNbh(NamedTuple):
    """Per-device pruned-table blocks of the sharded fused loop.

    ``idx``/``mask``/``tj`` are table-static (valid until the next rebuild)
    and index the halo-extended flat arrays; ``dr`` (and, on the fused-
    gather path, the neighbor-spin block ``sj``) is refreshed by ONE fused
    halo exchange per drift.  The cell-major twin of
    :class:`repro.md.neighbor.Neighborhood`.
    """

    idx: jax.Array   # (cx, cy, cz, K, M) int32 into ext-flat slots
    mask: jax.Array  # (cx, cy, cz, K, M) bool
    tj: jax.Array    # (cx, cy, cz, K, M) int32 neighbor types
    dr: jax.Array    # (cx, cy, cz, K, M, 3) min-imaged pair vectors
    sj: jax.Array    # (cx, cy, cz, K, M, 3) neighbor spins; (0,) when the
                     # evaluator re-exchanges spins per evaluation


def make_domain_refresh(dspec: DomainSpec,
                        local_shape: tuple[int, int, int],
                        barrier: bool = True,
                        spin_in_gather: bool = True,
                        allgather: bool = False):
    """THE one halo exchange per drift, as a standalone closure.

    ``refresh(pos, nbh[, spin], tag) -> nbh`` packs boundary positions
    (and, with ``spin_in_gather``, spins) into a single fused round, then
    runs the pruned-table gather of min-imaged pair vectors (and neighbor
    spins).  Interior cells read a :func:`~repro.parallel.halo.local_wrap`
    image instead of the exchanged one, so their gather carries no
    ppermute dependence and XLA may overlap it with the exchange
    (repro.parallel.overlap).  Shared by the autodiff
    (:func:`make_domain_evaluator`) and Pallas-kernel
    (:func:`make_domain_kernel_evaluator`) sharded evaluators.
    """
    from repro.parallel.halo import (exchange_halo, exchange_halo_multi,
                                     local_wrap)
    from repro.parallel.overlap import issue_early, shell_slabs

    # the engine turns the issue-early scheduling hint off on the
    # replica-batched loop (barrier=False): whether the hint helps once the
    # exchange is vmapped over replicas has not been measured, so that
    # path keeps the schedule XLA picks on its own
    early = issue_early if barrier else (lambda x: x)
    axis_map = dspec.axis_map
    slabs = shell_slabs(local_shape)
    boxt = tuple(dspec.box)

    def refresh_pos_only(pos, nbh: DomainNbh, tag) -> DomainNbh:
        dtype = pos.dtype
        box = jnp.asarray(boxt, dtype)
        extc = early(exchange_halo(pos, axis_map, tag=tag,
                                   allgather=allgather))
        extl = local_wrap(pos)
        extc_f, extl_f = extc.reshape(-1, 3), extl.reshape(-1, 3)
        dr = jnp.zeros(nbh.idx.shape + (3,), dtype)
        for sl, interior in slabs:
            src = extl_f if interior else extc_f
            drs = src[nbh.idx[sl]] - pos[sl][..., None, :]
            drs = drs - box * jnp.round(drs / box)
            dr = dr.at[sl].set(drs)
        return nbh._replace(dr=dr)

    def refresh_fused(pos, nbh: DomainNbh, spin, tag) -> DomainNbh:
        """Positions AND spins in one fused halo round per drift."""
        dtype = pos.dtype
        box = jnp.asarray(boxt, dtype)
        ext = exchange_halo_multi({"pos": pos, "spin": spin}, axis_map,
                                  tag=tag, allgather=allgather)
        extc_p = early(ext["pos"]).reshape(-1, 3)
        extc_s = early(ext["spin"]).reshape(-1, 3)
        extl_p = local_wrap(pos).reshape(-1, 3)
        extl_s = local_wrap(spin).reshape(-1, 3)
        dr = jnp.zeros(nbh.idx.shape + (3,), dtype)
        sj = jnp.zeros(nbh.idx.shape + (3,), dtype)
        for sl, interior in slabs:
            src_p, src_s = ((extl_p, extl_s) if interior
                            else (extc_p, extc_s))
            drs = src_p[nbh.idx[sl]] - pos[sl][..., None, :]
            drs = drs - box * jnp.round(drs / box)
            dr = dr.at[sl].set(drs)
            sj = sj.at[sl].set(src_s[nbh.idx[sl]])
        return nbh._replace(dr=dr, sj=sj)

    def refresh(pos, nbh: DomainNbh, spin=None, tag: str = "drift-pos"
                ) -> DomainNbh:
        if spin_in_gather and spin is not None:
            return refresh_fused(pos, nbh, spin, tag)
        return refresh_pos_only(pos, nbh, tag)

    return refresh


def make_domain_evaluator(potential, dspec: DomainSpec,
                          local_shape: tuple[int, int, int],
                          barrier: bool = True,
                          spin_in_gather: bool = True,
                          allgather: bool = False):
    """Per-device gather/compute closures for the sharded fused loop.

    Returns ``(refresh, compute)``:

    * ``refresh(pos, nbh[, spin], tag) -> nbh`` - THE one halo exchange
      per drift: positions (and, with ``spin_in_gather``, spins) packed
      into a single fused round, then the pruned-table gather of
      min-imaged pair vectors (and neighbor spins).  Interior cells read a
      :func:`~repro.parallel.halo.local_wrap` image instead of the
      exchanged one, so their gather carries no ppermute dependence and
      XLA may overlap it with the exchange (repro.parallel.overlap).
    * ``compute(nbh, spin, types, field) -> (E, F, H_eff)`` - the gather-
      once evaluation on cell-major blocks, reusing the potential's
      ``pair_energies``/``site_moments`` surfaces.  All ghost
      contributions - reaction forces AND neighbor-spin gradients - fold
      back to their owners in ONE fused adjoint round
      (:func:`repro.parallel.halo.fold_halo_multi`), the explicit
      transpose of the forward exchange.

    ``spin_in_gather=True`` is the classical two-message distributed MD
    step (one forward exchange per drift, one adjoint fold per
    evaluation); it is exact when each step evaluates the potential once
    at fixed spins.  Self-consistent midpoint iterations re-evaluate at
    *updated* spins, so drivers must pass ``spin_in_gather=False`` there -
    the evaluator then re-exchanges spin ghosts inside every evaluation.

    Both potentials' flat ``compute`` methods and this evaluator route the
    same per-atom energy math, so sharded and single-device trajectories
    agree to roundoff (tests/test_domain_loop.py).
    """
    from repro.parallel.halo import (exchange_halo, fold_halo,
                                     fold_halo_multi, local_wrap)
    from repro.parallel.overlap import issue_early, shell_slabs

    # the engine turns the issue-early scheduling hint off on the
    # replica-batched loop (barrier=False): whether the hint helps once the
    # exchange is vmapped over replicas has not been measured, so that
    # path keeps the schedule XLA picks on its own
    early = issue_early if barrier else (lambda x: x)
    axis_map = dspec.axis_map
    slabs = shell_slabs(local_shape)
    cx, cy, cz = local_shape

    refresh = make_domain_refresh(dspec, local_shape, barrier=barrier,
                                  spin_in_gather=spin_in_gather,
                                  allgather=allgather)

    def fold_pair_grads(nbh, g_dr, g_sj, k, dtype):
        """ONE fused adjoint round: reaction forces + neighbor-spin
        gradients scattered onto ext slots travel back to their owners
        together (the paper's reverse-communication step)."""
        g_f = jnp.where(nbh.mask[..., None], g_dr, 0.0)
        direct = jnp.sum(g_f, axis=-2)
        g_s = jnp.where(nbh.mask[..., None], g_sj, 0.0)
        n_ext = (cx + 2) * (cy + 2) * (cz + 2) * k
        payload = jnp.concatenate([g_f, g_s], axis=-1)     # (..., M, 6)
        scat = jnp.zeros((n_ext, 6), dtype).at[nbh.idx.reshape(-1)].add(
            payload.reshape(-1, 6)).reshape(cx + 2, cy + 2, cz + 2, k, 6)
        folded = fold_halo(scat, axis_map, tag="adjoint",
                           allgather=allgather)
        return direct - folded[..., :3], folded[..., 3:]

    def compute_fused(nbh: DomainNbh, spin, types, field=None):
        """Evaluation from pre-gathered (dr, sj) blocks: zero forward
        communication; one fused adjoint fold."""
        k, m_cap = types.shape[3], nbh.idx.shape[-1]
        dtype = spin.dtype
        occ = types >= 0
        ti = jnp.where(occ, types, 0)
        eps = jnp.asarray(1e-30, dtype)

        def etot(dr, s, sj):
            drf = dr.reshape(-1, m_cap, 3)
            dist = jnp.sqrt(jnp.sum(drf * drf, axis=-1) + eps)
            er = potential.pair_energies(
                drf, dist, nbh.mask.reshape(-1, m_cap), ti.reshape(-1),
                nbh.tj.reshape(-1, m_cap), s.reshape(-1, 3),
                sj.reshape(-1, m_cap, 3))
            e = jnp.sum(jnp.where(occ.reshape(-1), er, 0.0))
            if field is not None:
                mom = jnp.where(occ, potential.site_moments(ti), 0.0)
                e = e - units.MU_B * jnp.sum(
                    mom[..., None] * s * jnp.asarray(field, dtype))
            return e

        e_loc, (g_dr, g_si, g_sj) = jax.value_and_grad(
            etot, argnums=(0, 1, 2))(nbh.dr, spin, nbh.sj)
        force, g_nbr = fold_pair_grads(nbh, g_dr, g_sj, k, dtype)
        # energy stays DEVICE-LOCAL here: the driver folds its global psum
        # into the once-per-step scalar reduction (with the skin test)
        return e_loc, force, -(g_si + g_nbr)

    def compute_exchanging(nbh: DomainNbh, spin, types, field=None):
        """Evaluation that re-exchanges spin ghosts (midpoint iterations
        evaluate at updated spins): one spin halo per evaluation, ghosts
        gathered per slab (interior from the comm-free local wrap)."""
        k, m_cap = types.shape[3], nbh.idx.shape[-1]
        dtype = spin.dtype
        occ = types >= 0
        ti_full = jnp.where(occ, types, 0)
        eps = jnp.asarray(1e-30, dtype)

        s_extc = early(exchange_halo(spin, axis_map, tag="spin",
                                     allgather=allgather))
        s_extl = local_wrap(spin)

        def etot(dr, s, extc, extl):
            extc_f, extl_f = extc.reshape(-1, 3), extl.reshape(-1, 3)
            e = jnp.zeros((), dtype)
            for sl, interior in slabs:
                src = extl_f if interior else extc_f
                idx_s = nbh.idx[sl].reshape(-1, m_cap)
                mask_s = nbh.mask[sl].reshape(-1, m_cap)
                tj_s = nbh.tj[sl].reshape(-1, m_cap)
                ti_s = ti_full[sl].reshape(-1)
                occ_s = occ[sl].reshape(-1)
                dr_s = dr[sl].reshape(-1, m_cap, 3)
                si_s = s[sl].reshape(-1, 3)
                sj_s = src[idx_s]
                dist = jnp.sqrt(jnp.sum(dr_s * dr_s, axis=-1) + eps)
                er = potential.pair_energies(dr_s, dist, mask_s, ti_s,
                                             tj_s, si_s, sj_s)
                e = e + jnp.sum(jnp.where(occ_s, er, 0.0))
            if field is not None:
                mom = jnp.where(occ, potential.site_moments(ti_full), 0.0)
                e = e - units.MU_B * jnp.sum(
                    mom[..., None] * s * jnp.asarray(field, dtype))
            return e

        e_loc, (g_dr, g_s, g_extc, g_extl) = jax.value_and_grad(
            etot, argnums=(0, 1, 2, 3))(nbh.dr, spin, s_extc, s_extl)

        # fused adjoint round: force reaction + comm-ghost spin gradients;
        # local-wrap gradients fold back without wire traffic
        g = jnp.where(nbh.mask[..., None], g_dr, 0.0)
        direct = jnp.sum(g, axis=-2)
        n_ext = (cx + 2) * (cy + 2) * (cz + 2) * k
        scat = jnp.zeros((n_ext, 3), dtype).at[nbh.idx.reshape(-1)].add(
            g.reshape(-1, 3)).reshape(cx + 2, cy + 2, cz + 2, k, 3)
        folded = fold_halo_multi({"react": scat, "gspin": g_extc},
                                 axis_map, tag="adjoint",
                                 allgather=allgather)
        g_local = fold_halo(g_extl, (None, None, None))
        force = direct - folded["react"]
        heff = -(g_s + folded["gspin"] + g_local)
        # energy stays device-local (see compute_fused)
        return e_loc, force, heff

    return refresh, (compute_fused if spin_in_gather
                     else compute_exchanging)


def make_domain_kernel_evaluator(potential, dspec: DomainSpec,
                                 local_shape: tuple[int, int, int],
                                 barrier: bool = True,
                                 allgather: bool = False):
    """Pallas-kernel (refresh, compute) for the sharded fused loop.

    Routes the fused NEP-SPIN kernels (repro.kernels.nep) through the
    domain decomposition using the paper's actual distributed algorithm:

    * K1 (``nep_atom_pass``) runs on the device-local cell-major slots
      (empty slots masked via ``amask`` - their energy, field, and adjoint
      accumulators come out exactly zero);
    * the per-atom adjoint accumulators Abar travel to neighboring devices
      in ONE fused halo round (tag ``"qfp"`` - the paper's q_Fp
      communication step), replacing the autodiff path's reaction-force
      fold: the pair-symmetric partial-force formula of K2
      (``nep_force_pass``) needs only a *gather* of neighbor adjoints,
      never a reverse scatter;
    * K2 then produces complete forces and torque fields for the owned
      atoms in a single neighbor traversal.

    Requires the one-halo-per-drift gather (``spin_in_gather``; i.e. not
    self-consistent midpoint configs): ``compute`` consumes the ``dr`` AND
    ``sj`` blocks refreshed by the drift exchange.  The kernel executor
    comes from ``potential.mode``: "auto" resolves to non-interpret Pallas
    on TPU (Mosaic kernels) and to the compiled lax.map tiling on CPU.
    """
    from repro.kernels.nep.kernel import (gather_abar, nep_atom_pass,
                                          nep_force_pass)
    from repro.parallel.halo import exchange_halo_multi

    spec, params = potential.spec, potential.params
    mode = potential.mode
    refresh = make_domain_refresh(dspec, local_shape, barrier=barrier,
                                  spin_in_gather=True, allgather=allgather)
    cx, cy, cz = local_shape
    axis_map = dspec.axis_map

    def compute(nbh: DomainNbh, spin, types, field=None):
        k = types.shape[3]
        m_cap = nbh.idx.shape[-1]
        dtype = spin.dtype
        occ = types >= 0
        ti = jnp.where(occ, types, 0)
        n_slots = cx * cy * cz * k
        flat = lambda a, tail: a.reshape((n_slots,) + tail)
        dr_f = flat(nbh.dr, (m_cap, 3))
        mask_f = flat(nbh.mask, (m_cap,))
        occ_f = flat(occ, ())
        ti_f = flat(ti, ())
        tj_f = flat(nbh.tj, (m_cap,))
        si_f = flat(spin, (3,))
        sj_f = flat(nbh.sj, (m_cap, 3))

        # K1: energy + direct field + adjoint accumulators (empty slots
        # are amask-zeroed, so they contribute nothing here or through the
        # exchange below)
        e, hdir, abar = nep_atom_pass(spec, params, dr_f, mask_f, occ_f,
                                      ti_f, tj_f, si_f, sj_f, mode=mode)

        # the q_Fp exchange: ONE fused halo of every Abar channel
        n_rows = abar.shape[0]
        abar_blk = abar.T.reshape((cx, cy, cz, k, n_rows))
        ext = exchange_halo_multi({"abar": abar_blk}, axis_map, tag="qfp",
                                  allgather=allgather)["abar"]
        idx_f = nbh.idx.reshape(n_slots, m_cap)  # ext-flat slots
        abar_j = gather_abar(ext.reshape(-1, n_rows).T, idx_f)

        # K2: fused force + torque, no reverse scatter
        f, h2 = nep_force_pass(spec, params, dr_f, mask_f, ti_f, tj_f,
                               si_f, sj_f, abar, abar_j, mode=mode)
        e_loc = jnp.sum(e)                   # masked rows are exact zeros
        force = f.reshape(types.shape + (3,))
        heff = (hdir + h2).reshape(types.shape + (3,))
        if field is not None:
            mom = jnp.where(occ, potential.site_moments(ti), 0.0)
            fld = jnp.asarray(field, dtype)
            e_loc = e_loc - units.MU_B * jnp.sum(
                mom[..., None] * spin * fld)
            heff = heff + units.MU_B * mom[..., None] * fld
        # energy stays DEVICE-LOCAL (the driver's fused scalar reduction
        # globalizes it, exactly as on the autodiff path)
        return e_loc, force, heff

    return refresh, compute
