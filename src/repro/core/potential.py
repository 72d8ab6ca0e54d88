"""NEP-SPIN potential: per-element MLP over the spin-aware descriptor.

One unified energy surface E(R, S); forces F = -dE/dR and magnetic effective
fields H = -dE/dS (the 'torque' channel, T_i = S_i x H_i) are exact
derivatives of the same scalar, evaluated with JAX autodiff in the reference
path and with the fused Pallas kernel (repro.kernels.nep) in the fast path.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.descriptor import NEPSpinSpec, descriptors
from repro.md.neighbor import (NeighborTable, Neighborhood,
                               compute_from_blocks, gather_neighbors)
from repro.utils import units


class NEPSpinParams(NamedTuple):
    """All trainable parameters. Leading axis T = n_types where per-element."""

    c_rad: jax.Array    # (T, T, n_rad, K) radial expansion coefficients
    c_ang: jax.Array    # (T, T, n_ang, K)
    c_spin: jax.Array   # (T, T, n_spin, K)
    w1: jax.Array       # (T, n_desc, H)
    b1: jax.Array       # (T, H)
    w2: jax.Array       # (T, H)
    b2: jax.Array       # (T,)
    q_scale: jax.Array  # (n_desc,) fixed descriptor normalizer (not trained)

    def desc_params(self) -> dict:
        return {"c_rad": self.c_rad, "c_ang": self.c_ang, "c_spin": self.c_spin}


def init_params(spec: NEPSpinSpec, key: jax.Array,
                dtype=jnp.float32) -> NEPSpinParams:
    ks = jax.random.split(key, 6)
    T, K, H, D = spec.n_types, spec.basis_size, spec.hidden, spec.n_desc

    def norm(k, shape, scale):
        return (scale * jax.random.normal(k, shape)).astype(dtype)

    # expansion coefficients ~ U-ish init, symmetrized in (ti,tj) for the
    # structural channels (g must be symmetric under i<->j exchange carriers)
    def sym(c):
        return 0.5 * (c + jnp.swapaxes(c, 0, 1))

    c_rad = sym(norm(ks[0], (T, T, spec.n_rad, K), 0.5))
    c_ang = sym(norm(ks[1], (T, T, spec.n_ang, K), 0.5))
    c_spin = sym(norm(ks[2], (T, T, spec.n_spin, K), 0.5))
    w1 = norm(ks[3], (T, D, H), (1.0 / D) ** 0.5)
    b1 = jnp.zeros((T, H), dtype)
    w2 = norm(ks[4], (T, H), (1.0 / H) ** 0.5)
    b2 = jnp.zeros((T,), dtype)
    return NEPSpinParams(c_rad, c_ang, c_spin, w1, b1, w2, b2,
                         q_scale=jnp.ones((D,), dtype))


def mlp_energy(params: NEPSpinParams, q: jax.Array, ti: jax.Array) -> jax.Array:
    """Per-atom energy from descriptor q (N, D).

    Per-element weights via predicated dispatch: one dense (N,D)x(D,H) MXU
    matmul per element type, masked per lane (the SME/svsel analogue).
    """
    qn = q / params.q_scale
    e = None
    for a in range(params.w1.shape[0]):
        h = jnp.tanh(qn @ params.w1[a] + params.b1[a])
        ea = h @ params.w2[a] + params.b2[a]
        term = jnp.where(ti == a, ea, 0.0)
        e = term if e is None else e + term
    return e


def atom_energies(
    spec: NEPSpinSpec, params: NEPSpinParams,
    dr, dist, mask, ti, tj, si, sj,
) -> jax.Array:
    q = descriptors(spec, params.desc_params(), dr, dist, mask, ti, tj, si, sj)
    return mlp_energy(params, q, ti)


def energy(
    spec: NEPSpinSpec, params: NEPSpinParams,
    pos: jax.Array, spin: jax.Array, types: jax.Array,
    table: NeighborTable, box: jax.Array,
    field: jax.Array | None = None,
    moments: jax.Array | None = None,
) -> jax.Array:
    """Total energy E(R, S) [eV]. ``field`` (3,) Tesla adds an explicit
    Zeeman term -mu_B * m_t * sum_i S_i . B (external field is not learned)."""
    dr, dist, sj, tj, mask = gather_neighbors(pos, spin, types, table, box)
    e = atom_energies(spec, params, dr, dist, mask, types, tj, spin, sj)
    etot = jnp.sum(e)
    if field is not None:
        mom = moments[types] if moments is not None else jnp.ones_like(e)
        etot = etot - units.MU_B * jnp.sum(mom[:, None] * spin * field)
    return etot


def energy_forces_field(
    spec: NEPSpinSpec, params: NEPSpinParams,
    pos: jax.Array, spin: jax.Array, types: jax.Array,
    table: NeighborTable, box: jax.Array,
    field: jax.Array | None = None,
    moments: jax.Array | None = None,
):
    """(E, F = -dE/dR (N,3) [eV/A], H_eff = -dE/dS (N,3) [eV/spin-unit]).

    This is the reference (autodiff) evaluation; the production path fuses
    force + field into one Pallas neighbor pass (repro.kernels.nep.ops).
    """
    def efn(p, s):
        return energy(spec, params, p, s, types, table, box, field, moments)

    e, grads = jax.value_and_grad(efn, argnums=(0, 1))(pos, spin)
    return e, -grads[0], -grads[1]


def compute(
    spec: NEPSpinSpec, params: NEPSpinParams,
    nbh: Neighborhood, spin: jax.Array, types: jax.Array,
    field: jax.Array | None = None,
    moments: jax.Array | None = None,
):
    """Gather-once autodiff evaluation from pre-gathered neighbor blocks.

    Positions enter only through ``nbh.dr``; forces are dE/ddr assembled
    with the explicit pair scatter (same values as
    :func:`energy_forces_field`, which differentiates through the gather).
    """
    def etot(dr, s):
        dist = jnp.sqrt(jnp.sum(dr * dr, axis=-1) + 1e-30)
        e = atom_energies(spec, params, dr, dist, nbh.mask, types, nbh.tj,
                          s, s[nbh.idx])
        etot_ = jnp.sum(e)
        if field is not None:
            mom = moments[types] if moments is not None else jnp.ones_like(e)
            etot_ = etot_ - units.MU_B * jnp.sum(mom[:, None] * s * field)
        return etot_

    return compute_from_blocks(etot, nbh, spin)


@dataclasses.dataclass(frozen=True)
class NEPSpinPotential:
    """Bound NEP-SPIN surface: (spec, params) with the driver-facing API.

    ``energy_forces_field`` is the legacy whole-evaluation surface;
    ``compute`` is the gather-once surface consumed by the fused MD loop.
    ``use_kernel`` routes both through the fused kernels (repro.kernels.nep)
    instead of autodiff; ``mode`` selects the kernel executor ("pallas" |
    "xla_tiled" | "interpret"), with "auto" resolving per backend at trace
    time (non-interpret Pallas on TPU, compiled lax.map tiling on CPU).
    """

    spec: NEPSpinSpec
    params: NEPSpinParams
    moments: jax.Array | None = None
    use_kernel: bool = False
    mode: str = "auto"

    def energy_forces_field(self, pos, spin, types, table, box, field=None):
        if self.use_kernel:
            from repro.kernels.nep.ops import nep_energy_forces_field
            return nep_energy_forces_field(
                self.spec, self.params, pos, spin, types, table, box,
                field, self.moments, mode=self.mode)
        return energy_forces_field(self.spec, self.params, pos, spin, types,
                                   table, box, field, self.moments)

    def pair_energies(self, dr, dist, mask, ti, tj, si, sj):
        """Per-atom energies from pre-gathered pair blocks (flat (N, M)
        shapes) - the surface the domain-decomposed evaluator consumes
        (repro.parallel.domain).  Always the autodiff path: the sharded
        loop differentiates through it, so it must be jax-transparent."""
        return atom_energies(self.spec, self.params, dr, dist, mask, ti, tj,
                             si, sj)

    def site_moments(self, types):
        """Per-site magnetic moment [mu_B] entering the Zeeman term."""
        if self.moments is not None:
            return self.moments[types]
        return jnp.ones(types.shape, jnp.float32)

    def compute(self, nbh: Neighborhood, spin, types, field=None):
        if self.use_kernel:
            from repro.kernels.nep.ops import nep_compute
            return nep_compute(self.spec, self.params, nbh, spin, types,
                               field, self.moments, mode=self.mode)
        return compute(self.spec, self.params, nbh, spin, types, field,
                       self.moments)
