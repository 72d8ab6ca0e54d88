"""Work a force call requires, counted from shapes, and the peaks to hold it to.

The counts describe the algorithm, not an implementation of it, so they stay
the same whatever computes the force (autodiff, the fused NEP kernels, or a
later rewrite that drops an intermediate array):

* flops: one forward pass of the NEP-SPIN descriptor over the pairs within
  the cutoff, plus the per-atom descriptor contraction and MLP, plus a
  reverse pass at twice the forward for F = -dE/dR and H_eff = -dE/dS.
  Each pair uses the radial coefficients of its own type pair; evaluating
  every type pair and masking is an implementation's choice and does not
  count.
* bytes: the compulsory HBM traffic: read positions, spins, types, one
  neighbour index per pair and the weights, write E, F and H_eff.

Pairs are counted on the perfect crystal within the cutoff (not within the
cutoff plus skin, and not the table's capacity).
"""
from __future__ import annotations

import functools
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = os.path.join(HERE, "peaks.json")
F32 = 4  # bytes per float32 / int32 word


def b20_basis(u: float) -> np.ndarray:
    """Fractional coordinates of one B20 (P2_1 3, Wyckoff 4a) sublattice."""
    return np.array([[u, u, u],
                     [0.5 + u, 0.5 - u, 1.0 - u],
                     [1.0 - u, 0.5 + u, 0.5 - u],
                     [0.5 - u, 1.0 - u, 0.5 + u]]) % 1.0


def lattice_sites(lattice: dict, cells: int):
    """Perfect-crystal supercell of ``cells``^3 unit cells.

    Returns (positions (N, 3) float64 [Å], types (N,) int32, box (3,)).
    Unit cells are cell-major; within a cell the sublattices follow
    ``lattice["u"]`` in order, one type per sublattice.
    """
    if lattice["structure"] != "B20":
        raise ValueError(f"unknown structure {lattice['structure']!r}")
    frac = np.concatenate([b20_basis(u) for u in lattice["u"]])
    species = np.repeat(np.arange(len(lattice["u"]), dtype=np.int32), 4)
    grid = np.stack(np.meshgrid(*[np.arange(cells)] * 3, indexing="ij"),
                    axis=-1).reshape(-1, 3)
    a = float(lattice["a"])
    pos = ((grid[:, None, :] + frac[None]) * a).reshape(-1, 3)
    types = np.tile(species, grid.shape[0])
    return pos, types, np.full(3, cells * a)


@functools.lru_cache(maxsize=None)
def _pairs_per_atom(structure: str, a: float, u: tuple, cutoff: float):
    lattice = {"structure": structure, "a": a, "u": list(u)}
    cells = int(math.ceil(2.0 * cutoff / a)) + 1
    pos, _, box = lattice_sites(lattice, cells)
    d = pos[None, :, :] - pos[:, None, :]
    d -= box * np.round(d / box)
    r = np.sqrt(np.sum(d * d, axis=-1))
    np.fill_diagonal(r, np.inf)
    return float(np.count_nonzero(r < cutoff)) / pos.shape[0]


def pairs_per_atom(lattice: dict, cutoff: float) -> float:
    """Mean number of neighbours within ``cutoff`` on the perfect crystal
    (43 for B20 FeGe at 5 Å)."""
    return _pairs_per_atom(lattice["structure"], float(lattice["a"]),
                           tuple(lattice["u"]), float(cutoff))


def _monomials(p: int) -> int:
    """Number of degree-p monomials in three variables."""
    return (p + 1) * (p + 2) // 2


def nep_pair_flops(spec: dict) -> float:
    """Forward flops of one pair's descriptor accumulation."""
    k = spec["basis_size"]
    fl = 3.0 * k + 10.0                       # cutoff fn + Chebyshev basis
    n_ch = spec["n_rad"] + spec["n_ang"] + (spec["n_spin"] if spec["spin"]
                                            else 0)
    fl += 2.0 * k * n_ch                      # basis -> channel coefficients
    for p in range(spec["l_max"] + 1):
        c = _monomials(p)
        fl += 4.0 * c + 2.0 * spec["n_ang"] * c   # monomials + accumulation
    if spec["spin"]:
        fl += 30.0 + 18.0 * spec["n_spin"]    # spin couplings + accumulation
    return fl


def nep_n_desc(spec: dict) -> int:
    n = spec["n_rad"] + spec["n_ang"] * spec["l_max"]
    if spec["spin"]:
        n += spec["n_onsite"] + 6 * spec["n_spin"]
    return n


def nep_atom_flops(spec: dict) -> float:
    """Forward flops per atom outside the pair loop: the angular
    contraction to invariants and the one-hidden-layer MLP of its type."""
    fl = sum(3.0 * spec["n_ang"] * _monomials(p)
             for p in range(spec["l_max"] + 1))
    if spec["spin"]:
        fl += spec["n_onsite"] + 13.0 * spec["n_spin"]
    d, h = nep_n_desc(spec), spec["hidden"]
    fl += d + 2.0 * d * h + 2.0 * h + 2.0 * h + 1.0   # scale, W1, tanh, W2
    return fl


def nep_weight_count(spec: dict) -> int:
    t, k, d, h = spec["n_types"], spec["basis_size"], nep_n_desc(spec), \
        spec["hidden"]
    coeff = t * t * k * (spec["n_rad"] + spec["n_ang"]
                         + (spec["n_spin"] if spec["spin"] else 0))
    return coeff + t * d * h + t * h + t * h + t + d


def nep_force_work(spec: dict, lattice: dict, n_atoms: int) -> dict:
    """Required flops and compulsory bytes of one NEP-SPIN force call
    (E, F and H_eff) over ``n_atoms`` atoms of ``lattice``."""
    ppa = pairs_per_atom(lattice, spec["cutoff"])
    pairs = ppa * n_atoms
    fwd = pairs * nep_pair_flops(spec) + n_atoms * nep_atom_flops(spec)
    flops = 3.0 * fwd                          # forward + reverse at 2x
    read = (n_atoms * (3 + 3 + 1) + pairs + nep_weight_count(spec) + 3) * F32
    write = (1 + 6 * n_atoms) * F32
    return {"flops": flops, "bytes": read + write, "pairs_per_atom": ppa,
            "flops_per_atom": flops / n_atoms}


def load_peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The peak table row of ``device_kind``; a device missing from the
    table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take for ``work`` and what bounds it."""
    compute = work["flops"] / peaks["flops_per_s"]
    memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
