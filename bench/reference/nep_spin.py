"""Plain NEP-SPIN energy: the descriptor of Fan et al. (PRB 104, 104309)
with the magnetic channels of the paper, and a one-hidden-layer MLP per
element, written out directly over a block of atoms and their neighbours.

Pair quantities are (B, M) arrays and vectors are triples of them, so that
no array carries a short minor dimension.  Each pair takes the radial
coefficients of its own type pair; the angular channels contract the
Legendre polynomial P_l(cos theta_jk) through the multinomial expansion of
(rhat_j . rhat_k)^p.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from bench.reference.forces import cross, dot

# P_l(t) = sum_p LEGENDRE[l][p] t^p
LEGENDRE = {1: {1: 1.0}, 2: {0: -0.5, 2: 1.5}, 3: {1: -1.5, 3: 2.5},
            4: {0: 0.375, 2: -3.75, 4: 4.375}}


def monomials(p: int):
    """(exponents, multinomial weight) of (u . v)^p = sum w u^e v^e."""
    out = []
    for ex in range(p, -1, -1):
        for ey in range(p - ex, -1, -1):
            ez = p - ex - ey
            w = math.factorial(p) // (math.factorial(ex) * math.factorial(ey)
                                      * math.factorial(ez))
            out.append(((ex, ey, ez), float(w)))
    return out


def site_moments(cfg: dict):
    """Magnetic moment [mu_B] of each type, entering the Zeeman term."""
    return list(cfg["lattice"]["moments"])


def atom_energy(cfg: dict, w: dict, dtype):
    """f(dr, mask, ti, tj, si, sj) -> per-atom energies (B,) [eV] in
    ``dtype``; dr and sj are triples of (B, M) arrays, si a triple of (B,)
    arrays, mask (B, M), ti (B,), tj (B, M)."""
    spec = cfg["spec"]
    rc, k, l_max, t = (spec["cutoff"], spec["basis_size"], spec["l_max"],
                       spec["n_types"])
    if l_max > max(LEGENDRE):
        raise ValueError(f"l_max {l_max} > {max(LEGENDRE)}")
    w = {name: jnp.asarray(v, dtype) for name, v in w.items()}

    def f(dr, mask, ti, tj, si, sj):
        dr = tuple(x.astype(dtype) for x in dr)
        si = tuple(x.astype(dtype)[:, None] for x in si)
        sj = tuple(x.astype(dtype) for x in sj)
        dist = jnp.sqrt(dot(dr, dr) + 1e-30)
        x = jnp.clip(dist / rc, 0.0, 1.0)
        fc = 0.5 * (1.0 + jnp.cos(jnp.pi * x)) * mask.astype(dtype)
        xc = 2.0 * (x - 1.0) ** 2 - 1.0
        cheb = [jnp.ones_like(xc), xc]
        while len(cheb) < k:
            cheb.append(2.0 * xc * cheb[-1] - cheb[-2])
        basis = jnp.stack([0.5 * (c + 1.0) * fc for c in cheb[:k]])  # K,B,M
        rhat = tuple(c / dist for c in dr)
        pair = [[((ti[:, None] == a) & (tj == b)).astype(dtype)
                 for b in range(t)] for a in range(t)]

        def channel(name):            # (n, B, M): sum_k c[ti, tj, n, k] f_k
            return sum(pair[a][b][None] * jnp.einsum(
                "kbm,nk->nbm", basis, w[name][a, b])
                for a in range(t) for b in range(t))

        feats = list(jnp.sum(channel("c_rad"), axis=-1))
        g_ang = channel("c_ang")
        power = []
        for p in range(l_max + 1):
            acc = 0.0
            for (ex, ey, ez), wt in monomials(p):
                mono = rhat[0] ** ex * rhat[1] ** ey * rhat[2] ** ez
                a = jnp.sum(g_ang * mono[None], axis=-1)          # (n, B)
                acc = acc + wt * a * a
            power.append(acc)
        for l in range(1, l_max + 1):
            feats += list(sum(c * power[p] for p, c in LEGENDRE[l].items()))
        if spec["spin"]:
            smag = jnp.sqrt(dot(si, si) + 1e-30)[:, 0]
            feats += [smag ** (i + 1) for i in range(spec["n_onsite"])]
            g_sp = channel("c_spin")
            couplings = (dot(si, sj), dot(cross(si, sj), rhat),
                         dot(si, rhat) * dot(sj, rhat))
            for c in couplings:
                feats += list(jnp.sum(g_sp * c[None], axis=-1))
            v = tuple(jnp.sum(g_sp * c[None], axis=-1) for c in sj)
            u = tuple(jnp.sum(g_sp * c[None], axis=-1) for c in rhat)
            feats += list(dot(v, v))
            feats += list(dot(v, tuple(c[:, 0][None] for c in si)))
            feats += list(dot(u, v))
        q = jnp.stack(feats, axis=-1) / w["q_scale"]             # (B, D)
        e = 0.0
        for a in range(t):
            hid = jnp.tanh(q @ w["w1"][a] + w["b1"][a])
            e = e + jnp.where(ti == a, hid @ w["w2"][a] + w["b2"][a], 0.0)
        return e

    return f
