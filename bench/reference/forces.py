"""E, F = -dE/dR and H_eff = -dE/dS by autodiff of a plain per-atom energy,
summed over blocks of rows so that a large system fits beside nothing."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import units

BLOCK_ROWS = 32768


def dot(a, b):
    """Dot product of two vectors given as triples of arrays."""
    return a[0] * b[0] + a[1] * b[1] + a[2] * b[2]


def cross(a, b):
    """Cross product of two vectors given as triples of arrays."""
    return (a[1] * b[2] - a[2] * b[1], a[2] * b[0] - a[0] * b[2],
            a[0] * b[1] - a[1] * b[0])


class Forces:
    """``Forces(atom_energy, moments, types, box)(pos, spin, field, idx,
    mask) -> (E, F, H)`` on host arrays, at the highest matmul precision.

    ``atom_energy(dr, mask, ti, tj, si, sj) -> (B,)`` is a reference
    energy; ``moments`` the per-type moment of the Zeeman term.
    """

    def __init__(self, atom_energy, moments, types, box):
        self.types = jnp.asarray(types, jnp.int32)
        self.box = jnp.asarray(box, jnp.float32)
        mom = jnp.asarray(moments, jnp.float32)

        def block(pos, spin, field, rows, valid, idx, mask):
            def energy(pos, spin):
                # one (B, M) array per component: no short minor dimension
                d = []
                for c in range(3):
                    x = pos[:, c]
                    dc = x[idx] - x[rows][:, None]
                    d.append(dc - self.box[c] * jnp.round(dc / self.box[c]))
                si = tuple(spin[rows, c] for c in range(3))
                sj = tuple(spin[:, c][idx] for c in range(3))
                ti = self.types[rows]
                e = atom_energy(tuple(d), mask, ti, self.types[idx], si, sj)
                e = jnp.where(valid, e.astype(jnp.float32), 0.0)
                m = jnp.where(valid, mom[ti], 0.0)
                zee = units.MU_B * sum(jnp.sum(m * si[c]) * field[c]
                                       for c in range(3))
                return jnp.sum(e) - zee

            return jax.value_and_grad(energy, argnums=(0, 1))(pos, spin)

        self._block = jax.jit(block)

    def __call__(self, pos, spin, field, idx, mask):
        n, m = idx.shape
        rows_per = min(n, BLOCK_ROWS)
        pos = jnp.asarray(pos, jnp.float32)
        spin = jnp.asarray(spin, jnp.float32)
        field = jnp.asarray(field, jnp.float32)
        e = 0.0
        gp = jnp.zeros_like(pos)
        gs = jnp.zeros_like(spin)
        with jax.default_matmul_precision("highest"):
            for start in range(0, n, rows_per):
                rows = np.arange(start, start + rows_per)
                valid = rows < n
                rows = np.where(valid, rows, 0)
                eb, (dp, ds) = self._block(pos, spin, field, rows, valid,
                                           idx[rows], mask[rows] & valid[:, None])
                e = e + eb
                gp, gs = gp + dp, gs + ds
        return (np.float64(jax.device_get(e)), np.asarray(-gp, np.float64),
                np.asarray(-gs, np.float64))
