"""Neighbor-table construction correctness."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.md.lattice import b20_fege, simple_cubic
from repro.md.neighbor import (_cell_coords, cell_neighbor_table,
                               dense_neighbor_table, grid_shape,
                               needs_rebuild, bin_atoms)
from repro.md.state import init_state


def _pairs(table, n):
    s = set()
    idx = np.asarray(table.idx)
    mask = np.asarray(table.mask)
    for i in range(n):
        for m in range(idx.shape[1]):
            if mask[i, m]:
                s.add((i, int(idx[i, m])))
    return s


def test_dense_vs_cell_equivalence():
    lat = b20_fege()
    st = init_state(lat, (4, 4, 4), temperature=300.0,
                    key=jax.random.PRNGKey(0))
    dense = dense_neighbor_table(st.pos, st.box, 4.0, 96, skin=0.3)
    cell = cell_neighbor_table(st.pos, st.box, 4.0, 96, cell_capacity=24,
                               skin=0.3)
    assert _pairs(dense, st.n_atoms) == _pairs(cell, st.n_atoms)


def test_table_symmetric():
    """j in nbr(i) <=> i in nbr(j) (required by the pair-symmetric force
    kernel)."""
    lat = simple_cubic()
    st = init_state(lat, (4, 4, 4), key=jax.random.PRNGKey(1))
    tab = dense_neighbor_table(st.pos, st.box, 5.0, 12)
    pairs = _pairs(tab, st.n_atoms)
    assert all((j, i) in pairs for (i, j) in pairs)


def test_needs_rebuild_half_skin():
    lat = simple_cubic()
    st = init_state(lat, (3, 3, 3), key=jax.random.PRNGKey(2))
    tab = dense_neighbor_table(st.pos, st.box, 5.0, 12, skin=0.5)
    assert not bool(needs_rebuild(tab, st.pos, st.box, 0.5))
    moved = st.pos.at[0, 0].add(0.3)
    assert bool(needs_rebuild(tab, moved, st.box, 0.5))


def test_dense_capacity_exceeds_n():
    """capacity > n: the padded columns must be masked out and self-padded
    (regression for the old conditional re-pad of ``mask``, which rebuilt
    ``idx`` from a stale pre-pad mask)."""
    lat = simple_cubic()
    st = init_state(lat, (2, 2, 2), key=jax.random.PRNGKey(4))
    n = st.n_atoms
    ref = dense_neighbor_table(st.pos, st.box, 5.0, n - 1)
    big = dense_neighbor_table(st.pos, st.box, 5.0, n + 5)
    assert big.idx.shape == (n, n + 5) and big.mask.shape == (n, n + 5)
    # same neighbor set; the extra columns are all invalid
    assert _pairs(big, n) == _pairs(ref, n)
    idx, mask = np.asarray(big.idx), np.asarray(big.mask)
    assert not mask[:, n:].any()
    rows = np.broadcast_to(np.arange(n)[:, None], idx.shape)
    np.testing.assert_array_equal(idx[~mask], rows[~mask])  # self-padded


def test_bin_atoms_no_overflow_and_complete():
    lat = b20_fege()
    st = init_state(lat, (3, 3, 3), key=jax.random.PRNGKey(3))
    grid, mask, overflow = bin_atoms(st.pos, st.box, (3, 3, 3), 12)
    assert not bool(overflow)
    ids = np.asarray(grid)[np.asarray(mask)]
    assert sorted(ids.tolist()) == list(range(st.n_atoms))


def _per_atom_stencil_table(pos, box, cutoff, capacity, cell_capacity, skin,
                            n_cells):
    """The per-atom stencil construction: every atom gathers its 27
    stencil cells from the grid and each candidate coordinate on its own.
    The reference the per-cell construction must equal."""
    rc = cutoff + skin
    cx, cy, cz = n_cells
    grid, _, _ = bin_atoms(pos, box, n_cells, cell_capacity)
    n = pos.shape[0]
    ci, cj, ck, _ = _cell_coords(pos, box, n_cells)
    offs = jnp.array([(a, b, c) for a in (-1, 0, 1) for b in (-1, 0, 1)
                      for c in (-1, 0, 1)], dtype=jnp.int32)
    sci = (ci[:, None] + offs[None, :, 0]) % cx
    scj = (cj[:, None] + offs[None, :, 1]) % cy
    sck = (ck[:, None] + offs[None, :, 2]) % cz
    cand = grid[sci, scj, sck].reshape(n, -1)
    valid = cand >= 0
    cand_safe = jnp.where(valid, cand, 0)
    d2 = None
    for c in range(3):
        d = pos[:, c][cand_safe] - pos[:, c:c + 1]
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
        idx = jnp.pad(idx, ((0, 0), (0, capacity - k)), constant_values=0)
        idx = idx.at[:, k:].set(jnp.arange(n)[:, None])
        mask = jnp.pad(mask, ((0, 0), (0, capacity - k)))
    return idx.astype(jnp.int32), mask


def _thermal_b20(cells, seed):
    """B20 supercell with thermal displacements, wrapped into the box."""
    st = init_state(b20_fege(), cells, temperature=300.0,
                    key=jax.random.PRNGKey(seed))
    noise = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                    st.pos.shape, st.pos.dtype)
    return jnp.mod(st.pos + noise, st.box), st.box


def _random_atoms(n_cells, per_cell, rc, seed):
    """Uniform random atoms in a box of ``n_cells`` cells ``rc`` wide, in
    no cell order."""
    rng = np.random.default_rng(seed)
    box = np.asarray(n_cells, np.float64) * rc * 1.01
    n = int(per_cell * np.prod(n_cells))
    pos = rng.uniform(0.0, 1.0, size=(n, 3)) * box
    return jnp.asarray(pos, jnp.float32), jnp.asarray(box, jnp.float32)


# (positions and box, cutoff, capacity, cell capacity, skin, n_cells);
# cutoff + skin is 4.3 everywhere
_TABLE_CASES = {
    "b20_4x4x4_thermal": lambda: (*_thermal_b20((4, 4, 4), 0), 4.0, 96, 24,
                                  0.3, (4, 4, 4)),
    "random_3x4x6": lambda: (*_random_atoms((3, 4, 6), 6.0, 4.3, 1), 4.0, 64,
                             16, 0.3, (3, 4, 6)),
    "random_overfull": lambda: (*_random_atoms((4, 4, 4), 9.0, 4.3, 2), 4.0,
                                48, 6, 0.3, (4, 4, 4)),
    "b20_3x4x5_box_grid": lambda: (*_thermal_b20((3, 4, 5), 3), 4.0, 96, 24,
                                   0.3, None),
}


@pytest.mark.parametrize("case", sorted(_TABLE_CASES))
def test_cell_table_equals_per_atom_stencil(case):
    """The per-cell stencil tables give the per-atom construction's table
    bit for bit, ``idx`` and ``mask``, on lattices, random atoms in no cell
    order, grids one dimension 3 wide, overfull cells and uneven boxes."""
    pos, box, cutoff, cap, cell_cap, skin, n_cells = _TABLE_CASES[case]()
    tab = cell_neighbor_table(pos, box, cutoff, cap, cell_capacity=cell_cap,
                              skin=skin, n_cells=n_cells)
    if n_cells is None:
        n_cells = grid_shape(box, cutoff, skin)
        assert len(set(n_cells)) == 3
    _, _, overflow = bin_atoms(pos, box, n_cells, cell_cap)
    assert bool(overflow) == (case == "random_overfull")
    idx, mask = _per_atom_stencil_table(pos, box, cutoff, cap, cell_cap,
                                        skin, n_cells)
    np.testing.assert_array_equal(np.asarray(tab.idx), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(tab.mask), np.asarray(mask))
    assert np.asarray(tab.mask).any(axis=1).mean() > 0.9


def _gathers(jaxpr):
    """Every ``gather`` equation of a jaxpr, its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _gathers(sub)


def test_cell_table_gathers_whole_rows():
    """No gather of the linked-cell search fetches its (N, 27K) candidates
    one scalar at a time (the pattern a TPU runs per element): each gather
    that puts out N x 27K values takes whole 27K-wide rows."""
    n_cells, cell_cap = (4, 4, 5), 12
    pos, box = _random_atoms(n_cells, 4.0, 4.3, 4)
    n, row = pos.shape[0], 27 * cell_cap
    build = partial(cell_neighbor_table, cutoff=4.0, capacity=40,
                    cell_capacity=cell_cap, skin=0.3, n_cells=n_cells)
    wide = [e for e in _gathers(jax.make_jaxpr(build)(pos, box).jaxpr)
            if np.prod(e.outvars[0].aval.shape) >= n * row]
    assert wide, "the search lost its (N, 27K) candidate rows"
    for e in wide:
        shape, sizes = e.outvars[0].aval.shape, e.params["slice_sizes"]
        assert shape == (n, row) and np.prod(sizes) == row, (
            f"gather of {shape} in slices of {sizes}")
