"""Reference neighbour lists (scipy k-d tree, float64 distances) and the
linked-cell row layout."""
from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree


def wrap(pos, box) -> np.ndarray:
    box = np.asarray(box, np.float64)
    p = np.mod(np.asarray(pos, np.float64), box)
    return np.where(p >= box, 0.0, p)


def pairs(pos, box, r: float):
    """Ordered pairs (i, j), i != j, with minimum-image distance <= r, and
    their distances."""
    box = np.asarray(box, np.float64)
    p = wrap(pos, box)
    tree = cKDTree(p, boxsize=box)
    ij = tree.query_pairs(r, output_type="ndarray")
    i = np.concatenate([ij[:, 0], ij[:, 1]])
    j = np.concatenate([ij[:, 1], ij[:, 0]])
    d = p[j] - p[i]
    d -= box * np.round(d / box)
    return i, j, np.sqrt(np.sum(d * d, axis=-1))


def padded(pos, box, r: float, capacity: int | None = None):
    """(idx (N, M) int32 self-padded, mask (N, M) bool) of the neighbours
    within ``r``."""
    i, j, _ = pairs(pos, box, r)
    return _pack(i, j, np.asarray(pos).shape[0], capacity)


def _pack(i, j, n: int, capacity: int | None):
    """Ordered pairs -> (idx (N, M) self-padded, mask); ``capacity``
    defaults to the largest row rounded up to 16."""
    order = np.lexsort((j, i))
    i, j = i[order], j[order]
    counts = np.bincount(i, minlength=n)
    m = int(counts.max()) if counts.size else 0
    if capacity is None:
        capacity = max(16, -(-m // 16) * 16)
    capacity = max(capacity, m)
    start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(i.size) - start[i]
    idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, capacity))
    mask = np.zeros((n, capacity), bool)
    idx[i, slot] = j
    mask[i, slot] = True
    return idx, mask


def table_errors(r0, idx, mask, box, r: float, band: float) -> int:
    """Pairs a neighbour table gets wrong against the plain list at its
    own build positions ``r0``.

    Counts pairs closer than ``r - band`` that the table lacks, listed
    pairs farther than ``r + band``, self pairs, out-of-range indices and
    pairs listed twice.  Pairs within ``band`` of ``r`` are left out: there
    the rounding of a float32 distance decides.
    """
    idx = np.asarray(idx, np.int64)
    mask = np.asarray(mask, bool)
    n = idx.shape[0]
    bad = int(np.count_nonzero(mask & ((idx < 0) | (idx >= n))))
    rows = np.broadcast_to(np.arange(n)[:, None], idx.shape)
    bad += int(np.count_nonzero(mask & (idx == rows)))
    listed = (rows * n + np.clip(idx, 0, n - 1))[mask]
    uniq = np.unique(listed)
    bad += int(listed.size - uniq.size)
    i, j, d = pairs(r0, box, r + band)
    ref = i.astype(np.int64) * n + j
    want = ref[d < r - band]
    bad += int(np.count_nonzero(~np.isin(want, uniq, assume_unique=False)))
    allowed = ref                                   # everything within r+band
    bad += int(np.count_nonzero(~np.isin(uniq, allowed)))
    return bad


def bf16_table(r0, box, r: float, capacity: int):
    """The control's table: the plain list with each distance test made
    in bfloat16 (displacements rounded to bfloat16, squared and summed in
    bfloat16) instead of float32."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    i, j, _ = pairs(r0, box, r + 1.0)
    p = wrap(r0, box).astype(np.float32)
    b = np.asarray(box, np.float32)
    d = p[j] - p[i]
    d -= b * np.round(d / b)
    d = d.astype(bf16)
    d2 = (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]).astype(bf16)
    d2 = (d2 + d[:, 2] * d[:, 2]).astype(bf16)
    keep = d2 <= bf16(r * r)
    return _pack(i[keep], j[keep], np.asarray(r0).shape[0], capacity)


def bins(pos, box, n, dtype=np.float64):
    """Each atom's bin on a grid of ``n`` cells a dimension (flat index, x
    slowest), with the fractional position taken in ``dtype``, and its
    distance [Å] to the nearest cell face."""
    box64 = np.asarray(box, np.float64)
    n = np.asarray(n, np.int64)
    u = (np.asarray(pos, np.float64).astype(dtype) / box64.astype(dtype)
         * n.astype(dtype)).astype(np.float64)
    c = np.clip(np.floor(u).astype(np.int64), 0, n - 1)
    flat = (c[:, 0] * n[1] + c[:, 1]) * n[2] + c[:, 2]
    face = np.min(np.abs(u - np.round(u)) * (box64 / n), axis=-1)
    return flat, face


def cells(pos, box, r: float, dtype=np.float64):
    """:func:`bins` on the grid of cells at least ``r`` wide."""
    n = np.maximum(np.floor(np.asarray(box, np.float64) / r), 1)
    return bins(pos, box, n.astype(np.int64), dtype)


def pack(rank, pos, box, grid, dtype=np.float64) -> np.ndarray:
    """Atom ids in the cell slots of ``grid`` = (CX, CY, CZ, K), flat, -1
    where empty: each atom in its bin at ``pos``, a bin's atoms in the
    order of ``rank``."""
    rank = np.asarray(rank)
    n, k = rank.size, int(grid[3])
    flat, _ = bins(pos, box, grid[:3], dtype)
    order = np.lexsort((rank, flat))
    counts = np.bincount(flat, minlength=int(np.prod(grid[:3])))
    if counts.max() > k:
        raise ValueError(f"a cell holds {counts.max()} atoms, over {k}")
    start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    fo = flat[order]
    out = np.full(int(np.prod(grid)), -1, np.int64)
    out[fo * k + np.arange(n) - start[fo]] = order
    return out


def slot_errors(rows, slots, grid, pos, box, r: float, face: float) -> int:
    """Atoms held in another cell than their bin at ``pos``, where ``rows``
    holds the atom of each listed slot of the cell grid ``grid`` = (CX,
    CY, CZ, K) (-1 where empty) and ``slots`` each one's flat place in it.
    Atoms within ``face`` of a cell face are left out: there the rounding
    of a position decides their cell.  A layout that does not hold every
    atom once, or whose cells are narrower than ``r``, counts every
    atom."""
    rows = np.asarray(rows)
    n = np.asarray(pos).shape[0]
    held = rows >= 0
    ids = rows[held]
    if (ids.size != n or not np.array_equal(np.sort(ids), np.arange(n))
            or np.any(np.asarray(box, np.float64)
                      / np.asarray(grid[:3]) < r)):
        return n
    flat, dist = bins(pos, box, grid[:3])
    cell = np.asarray(slots)[held] // int(grid[3])
    return int(np.count_nonzero((flat[ids] != cell) & (dist[ids] >= face)))


def relayout(prev, pos, box, r: float, dtype=np.float64) -> np.ndarray:
    """The row layout (row -> atom) after a rebuild: the rows of ``prev``
    sorted by each atom's cell at ``pos``, keeping their order within a
    cell."""
    prev = np.asarray(prev)
    flat, _ = cells(pos, box, r, dtype)
    return prev[np.argsort(flat[prev], kind="stable")]


def layout_errors(new, prev, pos, box, r: float, face: float) -> int:
    """Rows of the layout ``new`` out of the order :func:`relayout` gives
    ``prev`` at ``pos``.  Atoms within ``face`` of a cell face are left
    out: there the rounding of a position decides their cell.  A ``new``
    that is no permutation counts every row."""
    new = np.asarray(new, np.int64)
    n = len(prev)
    if new.shape != (n,) or not np.array_equal(np.sort(new), np.arange(n)):
        return n
    flat, dist = cells(pos, box, r)
    rank = np.empty(n, np.int64)
    rank[np.asarray(prev)] = np.arange(n)
    rows = new[dist[new] >= face]
    key = flat[rows] * n + rank[rows]
    return int(np.count_nonzero(np.diff(key) <= 0))
