"""Neighbor tables for short-range ML potentials.

Two constructions:

* ``dense_neighbor_table`` - O(N^2) masked all-pairs table.  Used for tests,
  physics validation, and any system below a few thousand atoms.

* ``cell_neighbor_table`` - linked-cell construction with fixed per-cell
  capacity.  This is the scalable path: it is what the spatial domain
  decomposition shards (each device owns a slab of cells), and its
  fixed-capacity output is the TPU analogue of the paper's SVE2 "Phase A
  pre-staging" (pack valid neighbors into a rectangular buffer, then the
  compute kernel runs fully predicated over a static shape).  The search
  lays the 27-cell stencil's candidate ids and coordinates out once per
  cell and gives each atom its cell's row: a gather of whole rows, where
  fetching each candidate for each atom would be a per-element gather.

Both return a ``NeighborTable`` with per-atom index lists + validity mask.
Crystalline solids (the paper's regime) do not diffuse, so the table is
reusable across many steps; ``needs_rebuild`` implements the standard
half-skin displacement test.

The gather -> compute split (the fused MD hot loop, DESIGN: one gather per
position change):

* ``gather_blocks`` packs everything a potential needs that depends on the
  *table* (idx, mask, neighbor types) plus the position-dependent ``dr``
  block into a :class:`Neighborhood`;
* ``refresh_dr`` refreshes only ``dr`` after a drift (the table-static
  blocks are reused);
* potentials evaluate from the ``Neighborhood`` alone (``compute`` methods),
  differentiating w.r.t. ``dr`` and assembling atomic forces with
  ``assemble_pair_forces`` - so the two spin half-steps and every midpoint
  iteration at unchanged positions reuse one gathered block instead of
  re-gathering per evaluation.

``cell_order`` returns the linked-cell-bin permutation used by the fused
driver to keep neighbor gathers near-contiguous (the TPU/JAX analogue of the
paper's NUMA-aware first-touch layout).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry.profiling import phase


class NeighborTable(NamedTuple):
    idx: jax.Array    # (N, M) int32 neighbor indices (self-padded where invalid)
    mask: jax.Array   # (N, M) bool
    r0: jax.Array     # (N, 3) positions at build time (for skin test)
    cutoff: jax.Array  # () scalar: cutoff + skin used at build

    @property
    def capacity(self) -> int:
        return self.idx.shape[1]


def dense_neighbor_table(
    pos: jax.Array, box: jax.Array, cutoff: float, capacity: int,
    skin: float = 0.5,
) -> NeighborTable:
    """All-pairs neighbor table with minimum-image PBC.

    Selects up to ``capacity`` nearest neighbors inside cutoff+skin per atom
    (distance-sorted, so truncation drops the farthest ones).
    """
    with phase("rebuild.search"):
        n = pos.shape[0]
        rc = cutoff + skin
        dr = pos[None, :, :] - pos[:, None, :]
        dr = dr - box * jnp.round(dr / box)
        d2 = jnp.sum(dr * dr, axis=-1)
        d2 = d2.at[jnp.arange(n), jnp.arange(n)].set(jnp.inf)  # exclude self
        within = d2 <= rc * rc
        # distance-sorted top-k selection (paper: cutoff filter + packing)
        neg = jnp.where(within, -d2, -jnp.inf)
        vals, idx = jax.lax.top_k(neg, min(capacity, n))
        mask = vals > -jnp.inf
        if idx.shape[1] < capacity:  # pad columns if capacity > n
            pad = capacity - idx.shape[1]
            idx = jnp.pad(idx, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        # self-pad invalid slots
        idx = jnp.where(mask, idx, jnp.arange(n)[:, None])
    return NeighborTable(idx=idx.astype(jnp.int32), mask=mask,
                         r0=pos, cutoff=jnp.asarray(rc))


def needs_rebuild(table: NeighborTable, pos: jax.Array, box: jax.Array,
                  skin: float = 0.5) -> jax.Array:
    """True if any atom moved more than skin/2 since the table was built."""
    dr = pos - table.r0
    dr = dr - box * jnp.round(dr / box)
    return jnp.max(jnp.sum(dr * dr, axis=-1)) > (skin * 0.5) ** 2


def gather_neighbors(
    pos: jax.Array, spin: jax.Array, types: jax.Array,
    table: NeighborTable, box: jax.Array,
):
    """Gather per-neighbor quantities from a table.

    Returns (dr (N,M,3) displacement r_j - r_i with min-image, dist (N,M),
    nbr_spin (N,M,3), nbr_type (N,M), mask (N,M)).
    """
    nbr_pos = pos[table.idx]                       # (N, M, 3)
    dr = nbr_pos - pos[:, None, :]
    dr = dr - box * jnp.round(dr / box)
    dist = jnp.sqrt(jnp.sum(dr * dr, axis=-1) + 1e-30)
    return dr, dist, spin[table.idx], types[table.idx], table.mask


# ---------------------------------------------------------------------------
# Gather -> compute split (fused hot loop)
# ---------------------------------------------------------------------------

class Neighborhood(NamedTuple):
    """Pre-gathered neighbor blocks consumed by potential ``compute``.

    ``idx``/``mask``/``tj`` are table-static (valid until the next rebuild);
    ``dr`` depends on positions and is refreshed once per drift by
    :func:`refresh_dr`.  Spins are gathered inside ``compute`` (they change
    within a step, positions do not).
    """

    idx: jax.Array   # (N, M) int32 neighbor indices (self-padded)
    mask: jax.Array  # (N, M) bool
    tj: jax.Array    # (N, M) neighbor types
    dr: jax.Array    # (N, M, 3) min-imaged r_j - r_i


def gather_blocks(pos: jax.Array, types: jax.Array, table: NeighborTable,
                  box: jax.Array) -> Neighborhood:
    """Full gather after a table (re)build."""
    dr = pos[table.idx] - pos[:, None, :]
    dr = dr - box * jnp.round(dr / box)
    return Neighborhood(idx=table.idx, mask=table.mask,
                        tj=types[table.idx], dr=dr)


def refresh_dr(nbh: Neighborhood, pos: jax.Array,
               box: jax.Array) -> Neighborhood:
    """Refresh only the position-dependent block (one gather per drift)."""
    dr = pos[nbh.idx] - pos[:, None, :]
    dr = dr - box * jnp.round(dr / box)
    return nbh._replace(dr=dr)


def compute_from_blocks(etot, nbh: Neighborhood, spin: jax.Array):
    """The gather-once evaluation contract, in one place.

    ``etot(dr, spin) -> ()`` is the potential's total energy from the
    pre-gathered ``dr`` block; returns ``(E, F, H_eff)`` with forces
    assembled from dE/ddr via the explicit pair scatter and the effective
    field as -dE/dS.  Both shipped potentials' ``compute`` methods route
    through this so the force-assembly convention cannot diverge.
    """
    with phase("force.energy_grad"):
        e, (g_dr, g_s) = jax.value_and_grad(etot, argnums=(0, 1))(nbh.dr,
                                                                   spin)
    with phase("force.assemble"):
        f = assemble_pair_forces(g_dr, nbh)
    return e, f, -g_s


def assemble_pair_forces(g_dr: jax.Array, nbh: Neighborhood) -> jax.Array:
    """Atomic forces from dE/ddr (N, M, 3).

    With ``dr_im = pos[idx[i,m]] - pos[i]``, atom i feels the direct term
    ``+sum_m g[i,m]`` and the reaction ``-g[k,m]`` from every pair (k, m)
    that lists it as the neighbor - the scatter-add XLA would emit for the
    backward pass of the position gather, made explicit.
    """
    g = jnp.where(nbh.mask[..., None], g_dr, 0.0)
    direct = jnp.sum(g, axis=1)
    react = jnp.zeros_like(direct).at[nbh.idx.reshape(-1)].add(
        g.reshape(-1, g.shape[-1]))
    return direct - react


# ---------------------------------------------------------------------------
# Linked-cell construction (scalable path)
# ---------------------------------------------------------------------------

def _cell_coords(pos: jax.Array, box: jax.Array,
                 n_cells: tuple[int, int, int]):
    """Per-atom integer cell coordinates (ci, cj, ck) and flat cell id."""
    cx, cy, cz = n_cells
    frac = pos / box
    ci = jnp.clip((frac[:, 0] * cx).astype(jnp.int32), 0, cx - 1)
    cj = jnp.clip((frac[:, 1] * cy).astype(jnp.int32), 0, cy - 1)
    ck = jnp.clip((frac[:, 2] * cz).astype(jnp.int32), 0, cz - 1)
    return ci, cj, ck, (ci * cy + cj) * cz + ck


def grid_shape(box, cutoff: float, skin: float = 0.5) -> tuple[int, int, int]:
    """Linked-cell grid dims for a (concrete) box: cells >= cutoff+skin wide.

    Returns dims only; callers must fall back to the dense table when any
    dim is < 3 (the 27-cell stencil would wrap onto itself).
    """
    rc = cutoff + skin
    return tuple(int(x) for x in np.maximum(np.floor(np.asarray(box) / rc),
                                            1).astype(int))


def make_table_builder(box, cutoff: float, capacity: int,
                       cell_capacity: int = 24, skin: float = 0.5,
                       use_cell_list: bool = True):
    """Geometry-static builder closure for in-scan rebuilds.

    Resolves everything that must be static under jit from a *concrete*
    ``box``: returns ``(build, n_cells, use_cell)`` where
    ``build(pos, box) -> NeighborTable`` is the linked-cell construction
    with pinned grid dims when the box fits the 27-stencil (and
    ``use_cell_list``), else the dense fallback.  Shared by the fused
    ``Simulation`` driver and the replica ensemble so the fallback rule
    cannot diverge between them.
    """
    n_cells = grid_shape(np.asarray(box), cutoff, skin)
    use_cell = use_cell_list and min(n_cells) >= 3
    if use_cell:
        build = partial(cell_neighbor_table, cutoff=cutoff,
                        capacity=capacity, cell_capacity=cell_capacity,
                        skin=skin, n_cells=n_cells)
    else:
        build = partial(dense_neighbor_table, cutoff=cutoff,
                        capacity=capacity, skin=skin)
    return build, n_cells, use_cell


def cell_order(pos: jax.Array, box: jax.Array,
               n_cells: tuple[int, int, int]) -> jax.Array:
    """Permutation sorting atoms by linked-cell bin (cell-major layout).

    Applying it to the state rows makes each atom's stencil neighborhood
    near-contiguous in memory, so the (N, M) table gathers of the hot loop
    hit clustered rows - the JAX analogue of the paper's NUMA-aware layout.
    Stable sort: atoms within a cell keep their relative order.
    """
    *_, flat = _cell_coords(pos, box, n_cells)
    return jnp.argsort(flat, stable=True).astype(jnp.int32)


def bin_atoms(pos: jax.Array, box: jax.Array, n_cells: tuple[int, int, int],
              capacity: int):
    """Scatter atoms into a (cx,cy,cz,capacity) cell grid.

    Returns (cell_idx (cx,cy,cz,K) int32 atom ids, cell_mask, overflow flag).
    Atom order inside a cell is arrival order; overflowed atoms are dropped
    and flagged (callers must size capacity so overflow never fires; tests
    assert the flag).
    """
    with phase("rebuild.bin"):
        cx, cy, cz = n_cells
        *_, flat = _cell_coords(pos, box, n_cells)
        n = pos.shape[0]
        # rank of each atom within its cell via sort
        order = jnp.argsort(flat, stable=True)
        sorted_flat = flat[order]
        # position within run of equal cell ids
        idx_in_run = jnp.arange(n) - jnp.searchsorted(
            sorted_flat, sorted_flat, side="left")
        slot = jnp.zeros(n, jnp.int32).at[order].set(
            idx_in_run.astype(jnp.int32))
        overflow = jnp.any(slot >= capacity)
        slot_c = jnp.minimum(slot, capacity - 1)
        grid = jnp.full((cx * cy * cz * capacity,), -1, jnp.int32)
        grid = grid.at[flat * capacity + slot_c].set(
            jnp.where(slot < capacity, jnp.arange(n, dtype=jnp.int32), -1))
        grid = grid.reshape(cx, cy, cz, capacity)
    return grid, grid >= 0, overflow


def cell_neighbor_table(
    pos: jax.Array, box: jax.Array, cutoff: float, capacity: int,
    cell_capacity: int = 24, skin: float = 0.5,
    n_cells: tuple[int, int, int] | None = None,
) -> NeighborTable:
    """Linked-cell neighbor table: bin into cells >= cutoff+skin wide, then
    search the 27-cell stencil and keep the ``capacity`` nearest neighbors.

    ``n_cells`` pins the (static) grid dims so the build can run *inside* a
    jitted scan with a traced ``box`` (the fused driver's in-graph rebuild);
    when omitted it is derived from the concrete box as before.
    """
    if n_cells is None:
        n_cells = grid_shape(box, cutoff, skin)
        if min(n_cells) < 3:
            # stencil would wrap onto itself; fall back to dense
            return dense_neighbor_table(pos, box, cutoff, capacity, skin)
    elif min(n_cells) < 3:
        raise ValueError(f"n_cells {n_cells} too small for the 27-stencil; "
                         "use dense_neighbor_table")
    rc = cutoff + skin
    grid, _, _ = bin_atoms(pos, box, n_cells, cell_capacity)
    n = pos.shape[0]
    with phase("rebuild.search"):
        *_, flat = _cell_coords(pos, box, n_cells)

        # All atoms of a cell share its 27-cell stencil, so the candidates'
        # ids and coordinates are laid out once per cell, (C, 27K): on the
        # grid padded by one periodic image, the slice at (a, b, c) brings
        # cell (i+a-1, j+b-1, k+c-1) to (i, j, k).  Each atom then gathers
        # its cell's whole row, not 27K scalars one at a time.
        def stencil(x):
            cx, cy, cz, _ = x.shape
            xp = jnp.pad(x, ((1, 1), (1, 1), (1, 1), (0, 0)), mode="wrap")
            return jnp.stack(
                [xp[a:a + cx, b:b + cy, c:c + cz] for a in range(3)
                 for b in range(3) for c in range(3)],
                axis=3).reshape(cx * cy * cz, -1)

        grid_safe = jnp.maximum(grid, 0)
        cand = stencil(grid)[flat]                # (N, 27K)
        valid = cand >= 0
        cand_safe = jnp.where(valid, cand, 0)
        # per-component distances: an (N, 27K, 3) block would carry a minor
        # dimension of 3, which a TPU pads to 128 lanes
        d2 = None
        for c in range(3):
            d = stencil(pos[:, c][grid_safe])[flat] - pos[:, c:c + 1]
            d = d - box[c] * jnp.round(d / box[c])
            d2 = d * d if d2 is None else d2 + d * d
        good = valid & (d2 <= rc * rc) & (cand != jnp.arange(n)[:, None])
        neg = jnp.where(good, -d2, -jnp.inf)
        k = min(capacity, neg.shape[1])
        vals, sel = jax.lax.top_k(neg, k)
        mask = vals > -jnp.inf
        idx = jnp.take_along_axis(cand_safe, sel, axis=1)
        idx = jnp.where(mask, idx, jnp.arange(n)[:, None])
        if k < capacity:
            idx = jnp.pad(idx, ((0, 0), (0, capacity - k)),
                          constant_values=0)
            idx = idx.at[:, k:].set(jnp.arange(n)[:, None])
            mask = jnp.pad(mask, ((0, 0), (0, capacity - k)))
    return NeighborTable(idx=idx.astype(jnp.int32), mask=mask,
                         r0=pos, cutoff=jnp.asarray(rc))
