"""NEP-SPIN local descriptor (reference jnp implementation).

This extends the NEP (neuroevolution potential, Fan et al., PRB 104, 104309)
Chebyshev radial / Legendre angular descriptor with three groups of magnetic
channels, following the paper's Section 5-A:

  group 1 (onsite):   local spin state, including the longitudinal moment
                      magnitude |S_i| (Chebyshev features in |S|),
  group 2 (pairwise): spin-bond couplings over the neighbor list reusing the
                      same radial carrier as the structural channels:
                        sum_j g_n(r) (S_i . S_j)          Heisenberg carrier
                        sum_j g_n(r) (S_i x S_j) . r_hat  DMI carrier (parity-
                                                          odd, allowed in B20)
                        sum_j g_n(r) (S_i . r_hat)(S_j . r_hat)  pseudo-dipolar
  group 3 (angular):  spin-weighted directional accumulations contracted to
                      joint-rotation invariants:
                        V_n = sum_j g_n(r) S_j ;  W_n = sum_j g_n(r) r_hat
                        features V_n.V_n, V_n.S_i, W_n.V_n

All magnetic channels follow the structural pattern: local neighbor
traversal, channel-wise accumulation, small dense contractions - no new
global data dependencies (paper 5-A2).  Every feature is invariant under
joint SO(3) rotation of lattice + spins and even under time reversal; the
parity-odd channels encode the chirality that produces DMI physics.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class NEPSpinSpec:
    """Hyperparameters of the NEP-SPIN descriptor + network."""

    cutoff: float = 5.0         # radial cutoff [A]
    basis_size: int = 8         # Chebyshev basis functions per channel (K)
    n_rad: int = 6              # structural radial channels
    n_ang: int = 4              # structural angular channels
    l_max: int = 4              # Legendre order for angular channels
    n_spin: int = 4             # magnetic radial-carrier channels
    n_onsite: int = 3           # onsite |S| Chebyshev features
    n_types: int = 2            # chemical species (Fe, Ge)
    hidden: int = 32            # MLP hidden width
    spin: bool = True           # include magnetic channels

    @property
    def n_desc(self) -> int:
        n = self.n_rad + self.n_ang * self.l_max
        if self.spin:
            n += self.n_onsite + 3 * self.n_spin + 3 * self.n_spin
        return n


# Legendre polynomials P_l(t) coefficients in powers of t, l = 0..4
_LEGENDRE = {
    0: {0: 1.0},
    1: {1: 1.0},
    2: {0: -0.5, 2: 1.5},
    3: {1: -1.5, 3: 2.5},
    4: {0: 0.375, 2: -3.75, 4: 4.375},
}

# multinomial monomial tables: (u.v)^p = sum_c w_c mono_c(u) mono_c(v)
# each entry: list of (exponents (ex,ey,ez), weight)
_MONO = {
    0: [((0, 0, 0), 1.0)],
    1: [((1, 0, 0), 1.0), ((0, 1, 0), 1.0), ((0, 0, 1), 1.0)],
    2: [((2, 0, 0), 1.0), ((0, 2, 0), 1.0), ((0, 0, 2), 1.0),
        ((1, 1, 0), 2.0), ((1, 0, 1), 2.0), ((0, 1, 1), 2.0)],
    3: [((3, 0, 0), 1.0), ((0, 3, 0), 1.0), ((0, 0, 3), 1.0),
        ((2, 1, 0), 3.0), ((2, 0, 1), 3.0), ((1, 2, 0), 3.0),
        ((0, 2, 1), 3.0), ((1, 0, 2), 3.0), ((0, 1, 2), 3.0),
        ((1, 1, 1), 6.0)],
    4: [((4, 0, 0), 1.0), ((0, 4, 0), 1.0), ((0, 0, 4), 1.0),
        ((3, 1, 0), 4.0), ((3, 0, 1), 4.0), ((1, 3, 0), 4.0),
        ((0, 3, 1), 4.0), ((1, 0, 3), 4.0), ((0, 1, 3), 4.0),
        ((2, 2, 0), 6.0), ((2, 0, 2), 6.0), ((0, 2, 2), 6.0),
        ((2, 1, 1), 12.0), ((1, 2, 1), 12.0), ((1, 1, 2), 12.0)],
}


def _monomials(u: jax.Array, p: int) -> tuple[jax.Array, jax.Array]:
    """Degree-p monomial components of unit vectors u (..., 3).

    Returns (mono (..., C_p), weights (C_p,)).
    """
    x, y, z = u[..., 0], u[..., 1], u[..., 2]
    comps, ws = [], []
    for (ex, ey, ez), w in _MONO[p]:
        comps.append((x ** ex) * (y ** ey) * (z ** ez))
        ws.append(w)
    return jnp.stack(comps, axis=-1), jnp.asarray(ws, u.dtype)


def cutoff_fn(r: jax.Array, rc: float) -> jax.Array:
    """Smooth cosine cutoff: fc(rc)=0, fc'(rc)=0."""
    x = jnp.clip(r / rc, 0.0, 1.0)
    return 0.5 * (1.0 + jnp.cos(jnp.pi * x))


def chebyshev_basis(r: jax.Array, rc: float, k: int) -> jax.Array:
    """NEP radial basis f_k(r) = 0.5 (T_k(x)+1) fc(r), x = 2(r/rc-1)^2 - 1.

    The T_k recurrence is the kernel's 'online Chebyshev recurrence': only a
    running pair (T_{k-1}, T_k) is kept live (paper 5-B3-i).
    Returns (..., k).
    """
    x = 2.0 * jnp.square(jnp.clip(r / rc, 0.0, 1.0) - 1.0) - 1.0
    fc = cutoff_fn(r, rc)
    tkm1 = jnp.ones_like(x)
    tk = x
    out = [tkm1]
    for _ in range(1, k):
        out.append(tk)
        tkm1, tk = tk, 2.0 * x * tk - tkm1
    basis = jnp.stack(out[:k], axis=-1)
    return 0.5 * (basis + 1.0) * fc[..., None]


def _radial_g(coeffs: jax.Array, fk: jax.Array, ti: jax.Array,
              tj: jax.Array) -> jax.Array:
    """g_n(r_ij) = sum_k c[ti,tj,n,k] f_k(r_ij).

    coeffs: (T, T, n, K); fk: (..., M, K); ti: (...,), tj: (..., M).
    Per-pair type selection is the vectorized-select analogue of the paper's
    predicated multi-type dispatch (svsel, Sec. 5-B3-ii): T^2 dense MXU
    matmuls masked per lane - no type sorting, no gather/scatter.
    Returns (..., M, n).
    """
    t = coeffs.shape[0]
    g = None
    for a in range(t):
        for b in range(t):
            sel = ((ti[..., None] == a) & (tj == b))
            gab = jnp.einsum("...k,nk->...n", fk, coeffs[a, b])
            term = jnp.where(sel[..., None], gab, 0.0)
            g = term if g is None else g + term
    return g


def init_accumulators(spec: NEPSpinSpec, lead_shape: tuple[int, ...],
                      dtype) -> dict:
    """Zero per-atom channel accumulators (paper 5-A2: every magnetic channel
    is 'local neighbor traversal, channel-wise accumulation, small dense
    contraction' - the accumulators are the traversal state, so neighbor
    blocks can be streamed in any order / from any halo shift)."""
    acc = {
        "rad": jnp.zeros((*lead_shape, spec.n_rad), dtype),
        **{f"ang{p}": jnp.zeros((*lead_shape, spec.n_ang, len(_MONO[p])),
                                dtype)
           for p in range(spec.l_max + 1)},
    }
    if spec.spin:
        acc.update(
            sp_dot=jnp.zeros((*lead_shape, spec.n_spin), dtype),
            sp_dmi=jnp.zeros((*lead_shape, spec.n_spin), dtype),
            sp_pd=jnp.zeros((*lead_shape, spec.n_spin), dtype),
            sp_v=jnp.zeros((*lead_shape, spec.n_spin, 3), dtype),
            sp_w=jnp.zeros((*lead_shape, spec.n_spin, 3), dtype),
        )
    return acc


def accumulate(
    spec: NEPSpinSpec,
    desc_params: dict,
    acc: dict,
    dr: jax.Array,      # (..., M, 3) displacements r_j - r_i for this block
    dist: jax.Array,    # (..., M)
    mask: jax.Array,    # (..., M) bool
    ti: jax.Array,      # (...,) self types
    tj: jax.Array,      # (..., M) neighbor types
    si: jax.Array,      # (..., 3) self spins
    sj: jax.Array,      # (..., M, 3) neighbor spins
) -> dict:
    """Add one neighbor block's contributions to the accumulators."""
    m = mask.astype(dr.dtype)
    fk = chebyshev_basis(dist, spec.cutoff, spec.basis_size) * m[..., None]
    rhat = dr / dist[..., None]
    out = dict(acc)

    g_rad = _radial_g(desc_params["c_rad"], fk, ti, tj)
    out["rad"] = acc["rad"] + jnp.sum(g_rad, axis=-2)

    g_ang = _radial_g(desc_params["c_ang"], fk, ti, tj)
    for p in range(spec.l_max + 1):
        mono, _ = _monomials(rhat, p)                       # (...,M,C)
        out[f"ang{p}"] = acc[f"ang{p}"] + jnp.einsum(
            "...mj,...mc->...jc", g_ang, mono)

    if spec.spin:
        g_sp = _radial_g(desc_params["c_spin"], fk, ti, tj)
        si_b = si[..., None, :]
        dot_ss = jnp.sum(si_b * sj, axis=-1)
        dmi = jnp.sum(jnp.cross(jnp.broadcast_to(si_b, sj.shape), sj) * rhat,
                      axis=-1)
        pd = jnp.sum(si_b * rhat, axis=-1) * jnp.sum(sj * rhat, axis=-1)
        out["sp_dot"] = acc["sp_dot"] + jnp.einsum("...mj,...m->...j",
                                                   g_sp, dot_ss)
        out["sp_dmi"] = acc["sp_dmi"] + jnp.einsum("...mj,...m->...j",
                                                   g_sp, dmi)
        out["sp_pd"] = acc["sp_pd"] + jnp.einsum("...mj,...m->...j",
                                                 g_sp, pd)
        out["sp_v"] = acc["sp_v"] + jnp.einsum("...mj,...md->...jd", g_sp, sj)
        out["sp_w"] = acc["sp_w"] + jnp.einsum("...mj,...md->...jd", g_sp,
                                               rhat)
    return out


def finalize(spec: NEPSpinSpec, acc: dict, si: jax.Array) -> jax.Array:
    """Contract accumulators into the invariant descriptor (..., n_desc)."""
    feats = [acc["rad"]]
    mpow = {}
    for p in range(spec.l_max + 1):
        a2 = acc[f"ang{p}"] ** 2
        mpow[p] = sum(w * a2[..., c] for c, (_, w) in enumerate(_MONO[p]))
    for l in range(1, spec.l_max + 1):
        feats.append(sum(coef * mpow[p] for p, coef in _LEGENDRE[l].items()))

    if spec.spin:
        smag = jnp.sqrt(jnp.sum(si * si, axis=-1) + 1e-30)
        ons = [smag]
        for _ in range(1, spec.n_onsite):
            ons.append(ons[-1] * smag)
        feats.append(jnp.stack(ons, axis=-1))
        feats.append(acc["sp_dot"])
        feats.append(acc["sp_dmi"])
        feats.append(acc["sp_pd"])
        feats.append(jnp.sum(acc["sp_v"] ** 2, axis=-1))
        feats.append(jnp.einsum("...jd,...d->...j", acc["sp_v"], si))
        feats.append(jnp.sum(acc["sp_w"] * acc["sp_v"], axis=-1))

    q = jnp.concatenate(feats, axis=-1)
    assert q.shape[-1] == spec.n_desc, (q.shape, spec.n_desc)
    return q


def descriptors(
    spec: NEPSpinSpec,
    desc_params: dict,
    dr: jax.Array,      # (N, M, 3) displacements r_j - r_i
    dist: jax.Array,    # (N, M)
    mask: jax.Array,    # (N, M) bool
    ti: jax.Array,      # (N,) self types
    tj: jax.Array,      # (N, M) neighbor types
    si: jax.Array,      # (N, 3) self spins
    sj: jax.Array,      # (N, M, 3) neighbor spins
) -> jax.Array:
    """Per-atom NEP-SPIN descriptor vector. Returns (N, n_desc)."""
    acc = init_accumulators(spec, dr.shape[:-2], dr.dtype)
    acc = accumulate(spec, desc_params, acc, dr, dist, mask, ti, tj, si, sj)
    return finalize(spec, acc, si)
