"""Fused NEP-SPIN kernels (the paper's Fig. 2 pipeline, b1-b4) with a
backend-aware ``mode`` axis.

Two kernels over atom tiles, mirroring the paper's restructured three-stage
pipeline:

  K1 ``nep_atom_pass``  (stages b1+b2): one pass over the neighbor block
     computes the Chebyshev basis (online recurrence in registers), all
     structural + magnetic channel accumulators, the descriptor, the
     per-element ANN energy (predicated per-type dispatch), AND the adjoint
     accumulators Abar_i = dE_i/dA_i plus the direct spin term dE_i/dS_i -
     everything downstream of the paper's q_Fp array.

  K2 ``nep_force_pass`` (stages b3+b4): a SECOND single pass over the same
     neighbor block evaluates the fused force + torque using the
     pair-symmetric partial-force formula

        F_i = sum_j d/d(dr_ij) [ <Abar_i, a(dr_ij, S_i, S_j)>
                               + <Abar_j, a(-dr_ij, S_j, S_i)> ]

     which needs NO reverse force scatter (Newton-3 fold-back) - only a
     gather of neighbor adjoints, the exact analogue of GPUMD/NEP's
     partial-force formulation and the paper's single-traversal fusion of
     the radial / spin / torque kernels (ablation step 1).  Both adjoint
     contractions of a pair share ONE radial-basis / type-dispatch /
     spin-coupling evaluation: under ``dr -> -dr`` the distance, Chebyshev
     basis, and the scalar spin couplings (Heisenberg, DMI, pseudo-dipolar)
     are invariant and the angular monomials only flip sign as (-1)^p, so
     the i->j and j->i halves of the traversal cost one basis, not two
     (see :func:`_pair_rows`).

Layout (structure of arrays, atoms on lanes).  A tile holds ``A`` atoms on
the minor (lane) axis: per-pair quantities are ``(M, A)`` arrays (neighbor
slots on sublanes), per-atom quantities ``(1, A)`` rows, vectors are
3-tuples of such arrays, and the adjoint accumulators travel packed as
``(R, N)`` rows (:func:`acc_rows` fixes the order).  Every operation in the
tile bodies is elementwise, a sublane reduction over the neighbor axis, or
a broadcast - no dot_general and no minor dimension of 3 - so Mosaic lowers
the same bodies that XLA runs, and ``jax.vjp`` inside them only produces
more of the same.

The kernel *bodies* (:func:`atom_tile`, :func:`force_tile`) are pure traced
functions of arrays (or Pallas refs, which they only index statically) -
the Pallas grid and the XLA tiled executor lower the SAME code, selected by
``mode``:

  ``"pallas"``    non-interpret ``pallas_call`` - Mosaic lowering on TPU,
                  (M, TILE_ATOMS) lane-dense blocks resident in VMEM, the
                  scalar coefficients in SMEM;
  ``"xla_tiled"`` a compiled ``lax.map`` over lane tiles of the same bodies
                  for backends without a Pallas compiler (CPU): the tile
                  body is compiled ONCE and streamed over the atom tiles;
  ``"interpret"`` ``pallas_call(interpret=True)`` - the slow per-ref
                  debugging oracle (kept for kernel-level debugging only).

``resolve_mode("auto")`` picks ``"pallas"`` on TPU and ``"xla_tiled"``
otherwise; the choice is a trace-time static, so chunked drivers never
recompile across chunks.

K1's derivatives are obtained by ``jax.vjp`` *inside* the body over the
descriptor contraction and the network; K2 takes a ``jax.vjp`` of the
shared-basis pair contraction.  The autodiff oracle
(:mod:`repro.core.descriptor`, :mod:`repro.kernels.nep.ref`) is an
independent formulation of the same model in the array-of-structs layout.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.descriptor import NEPSpinSpec, _LEGENDRE, _MONO
from repro.core.potential import NEPSpinParams

# atoms per Pallas tile: one full lane row of a TPU vreg
TILE_ATOMS = 128
# xla_tiled fuses up to this many TILE_ATOMS tiles per lax.map step: big
# enough that XLA:CPU amortizes per-iteration dispatch, small enough that
# the per-step working set stays cache-resident
XLA_TILE_MAX = 8
# scoped VMEM the Pallas kernels may use (v5e has 128 MiB per core); K2's
# gathered-adjoint block is R x M x TILE_ATOMS floats, double-buffered
VMEM_LIMIT_BYTES = 100 * 1024 * 1024

MODES = ("pallas", "interpret", "xla_tiled")


def resolve_mode(mode: str = "auto") -> str:
    """Backend-aware dispatch: ``"auto"`` -> ``"pallas"`` where the Mosaic
    lowering exists (TPU), ``"xla_tiled"`` elsewhere (CPU)."""
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla_tiled"
    if mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; expected 'auto' or "
                         f"one of {MODES}")
    return mode


def acc_tails(spec: NEPSpinSpec) -> dict[str, tuple[int, ...]]:
    """Per-atom shape of each channel accumulator, in packing order."""
    tails = {"rad": (spec.n_rad,)}
    for p in range(spec.l_max + 1):
        tails[f"ang{p}"] = (spec.n_ang, len(_MONO[p]))
    if spec.spin:
        tails.update(sp_dot=(spec.n_spin,), sp_dmi=(spec.n_spin,),
                     sp_pd=(spec.n_spin,), sp_v=(spec.n_spin, 3),
                     sp_w=(spec.n_spin, 3))
    return tails


def acc_rows(spec: NEPSpinSpec) -> dict[str, int]:
    """First packed row of each accumulator key; a key's tail is laid out
    row-major from there (``rows["ang2"] + j * C_2 + c``)."""
    rows, off = {}, 0
    for k, tail in acc_tails(spec).items():
        rows[k] = off
        off += int(np.prod(tail))
    rows["_total"] = off
    return rows


def gather_abar(abar: jax.Array, idx: jax.Array) -> jax.Array:
    """Neighbor adjoints for K2 (the q_Fp gather): packed ``(R, N_src)``
    rows gathered by the ``(N, M)`` table into ``(R, M, N_pad)``, the atom
    axis already padded to the TILE_ATOMS multiple K2 tiles (pad lanes
    gather row 0; K2 masks them), so K2 never copies it to pad."""
    n = idx.shape[0]
    n_pad = -(-n // TILE_ATOMS) * TILE_ATOMS
    return jnp.take(abar, _pad_lanes((idx.T,), n_pad)[0], axis=1)


def _eps_for(dtype) -> float:
    return 1e-12 if jnp.dtype(dtype) == jnp.float32 else 1e-30


# ---------------------------------------------------------------------------
# parameters in kernel form
# ---------------------------------------------------------------------------

class KernelParams(NamedTuple):
    """NEP-SPIN parameters laid out for the tile bodies.

    ``coef`` holds every scalar the bodies read one at a time (radial
    expansion coefficients, output biases, descriptor scales) - SMEM on
    TPU; the network weights are per-(type, descriptor) hidden columns so
    the hidden layer is an outer-product accumulation on the VPU.
    """

    coef: jax.Array     # (L,) flat scalars, offsets from _coef_offsets
    w1: jax.Array       # (T, D, H, 1) hidden columns of each descriptor
    b1: jax.Array       # (T, H, 1)
    w2: jax.Array       # (T, H, 1)


def _coef_offsets(spec: NEPSpinSpec) -> dict[str, int]:
    t, k = spec.n_types, spec.basis_size
    off = {"c_rad": 0}
    off["c_ang"] = off["c_rad"] + t * t * spec.n_rad * k
    off["c_spin"] = off["c_ang"] + t * t * spec.n_ang * k
    off["b2"] = off["c_spin"] + t * t * spec.n_spin * k
    off["q_scale"] = off["b2"] + t
    off["_total"] = off["q_scale"] + spec.n_desc
    return off


def kernel_params(spec: NEPSpinSpec, params: NEPSpinParams) -> KernelParams:
    coef = jnp.concatenate([params.c_rad.ravel(), params.c_ang.ravel(),
                            params.c_spin.ravel(), params.b2.ravel(),
                            params.q_scale.ravel()])
    assert coef.shape[0] == _coef_offsets(spec)["_total"]
    return KernelParams(coef=coef, w1=params.w1[..., None],
                        b1=params.b1[..., None], w2=params.w2[..., None])


class _Coef:
    """Static-index reads of the flat scalar block (a 1-D array or an SMEM
    ref - both index the same way)."""

    def __init__(self, spec: NEPSpinSpec, coef):
        self.spec, self.coef = spec, coef
        self.off = _coef_offsets(spec)

    def c(self, name: str, a: int, b: int, n: int, k: int):
        s = self.spec
        n_ch = {"c_rad": s.n_rad, "c_ang": s.n_ang, "c_spin": s.n_spin}[name]
        i = ((a * s.n_types + b) * n_ch + n) * s.basis_size + k
        return self.coef[self.off[name] + i]

    def b2(self, a: int):
        return self.coef[self.off["b2"] + a]

    def q_scale(self, d: int):
        return self.coef[self.off["q_scale"] + d]


# ---------------------------------------------------------------------------
# shared pair geometry (lanes layout)
# ---------------------------------------------------------------------------

def _rsum(x):
    """Sum over the neighbor (sublane) axis, keeping a (1, A) row."""
    return jnp.sum(x, axis=0, keepdims=True)


def _dot3(u, v):
    return u[0] * v[0] + u[1] * v[1] + u[2] * v[2]


def _lane_select(ti, vals):
    """Per-lane pick of ``vals[ti]`` (scalars) -> (1, A) row (or the scalar
    itself for one type)."""
    if len(vals) == 1:
        return vals[0]
    out = jnp.where(ti == len(vals) - 1, vals[-1], 0.0)
    for a in range(len(vals) - 2, -1, -1):
        out = jnp.where(ti == a, vals[a], out)
    return out


class _Pairs(NamedTuple):
    fb: list        # fb[b][k]: basis k masked to neighbor type b, (M, A)
    rhat: tuple     # unit bond vector, 3 x (M, A)


def _pair_geometry(spec: NEPSpinSpec, dr, mask, tj) -> _Pairs:
    """Distance, Chebyshev basis (NEP f_k = 0.5 (T_k(x)+1) fc(r) with the
    online T_k recurrence), per-neighbor-type masked copies, unit bond."""
    dx, dy, dz = dr
    eps = _eps_for(dx.dtype)
    dist = jnp.sqrt(dx * dx + dy * dy + dz * dz + eps)
    u = jnp.clip(dist / spec.cutoff, 0.0, 1.0)
    x = 2.0 * jnp.square(u - 1.0) - 1.0
    fcm = 0.5 * (1.0 + jnp.cos(jnp.pi * u)) * mask
    tkm1, tk = jnp.ones_like(x), x
    fk = [0.5 * (tkm1 + 1.0) * fcm]
    for _ in range(1, spec.basis_size):
        fk.append(0.5 * (tk + 1.0) * fcm)
        tkm1, tk = tk, 2.0 * x * tk - tkm1
    if spec.n_types == 1:
        fb = [fk]
    else:
        fb = [[jnp.where(tj == b, f, 0.0) for f in fk]
              for b in range(spec.n_types)]
    return _Pairs(fb=fb, rhat=(dx / dist, dy / dist, dz / dist))


def _carriers(cf: _Coef, name: str, n_ch: int, geo: _Pairs, ti,
              swap: bool = False):
    """Radial carriers g_n(r_ij) = sum_k c[ti, tj, n, k] f_k(r_ij) as
    (M, A) arrays; ``swap`` gives the j-centered orientation c[tj, ti].
    The type dispatch is a per-lane coefficient select (the predicated
    multi-type dispatch of paper Sec. 5-B3-ii), not a gather."""
    t, kk = len(geo.fb), cf.spec.basis_size
    out = []
    for n in range(n_ch):
        g = None
        for b in range(t):
            for k in range(kk):
                vals = [cf.c(name, b, a, n, k) if swap
                        else cf.c(name, a, b, n, k)
                        for a in range(cf.spec.n_types)]
                term = _lane_select(ti, vals) * geo.fb[b][k]
                g = term if g is None else g + term
        out.append(g)
    return out


def _monomials(rhat, p: int) -> list:
    """Degree-p monomials of the unit bond vector, ordered as ``_MONO``."""
    pw = [[jnp.ones_like(c), c] for c in rhat]
    for comp in pw:
        while len(comp) <= p:
            comp.append(comp[-1] * comp[1])
    out = []
    for ex, _w in _MONO[p]:
        m = None
        for d, e in enumerate(ex):
            if e:
                m = pw[d][e] if m is None else m * pw[d][e]
        out.append(m if m is not None else pw[0][0])
    return out


def _spin_couplings(si, sj, rhat):
    """Heisenberg S_i.S_j, DMI (S_i x S_j).rhat, pseudo-dipolar
    (S_i.rhat)(S_j.rhat) - each invariant under (i, j, rhat) ->
    (j, i, -rhat)."""
    dot = _dot3(si, sj)
    cross = (si[1] * sj[2] - si[2] * sj[1], si[2] * sj[0] - si[0] * sj[2],
             si[0] * sj[1] - si[1] * sj[0])
    return dot, _dot3(cross, rhat), _dot3(si, rhat) * _dot3(sj, rhat)


# ---------------------------------------------------------------------------
# K1: descriptor + ANN + adjoint accumulators
# ---------------------------------------------------------------------------

def _accumulate_rows(spec: NEPSpinSpec, cf: _Coef, geo: _Pairs, ti, si,
                     sj) -> list:
    """Channel accumulators as packed (1, A) rows (acc_rows order)."""
    rows = [_rsum(g) for g in _carriers(cf, "c_rad", spec.n_rad, geo, ti)]
    g_ang = _carriers(cf, "c_ang", spec.n_ang, geo, ti)
    for p in range(spec.l_max + 1):
        mono = _monomials(geo.rhat, p)
        rows += [_rsum(g * m) for g in g_ang for m in mono]
    if spec.spin:
        g_sp = _carriers(cf, "c_spin", spec.n_spin, geo, ti)
        for cpl in _spin_couplings(si, sj, geo.rhat):
            rows += [_rsum(g * cpl) for g in g_sp]
        rows += [_rsum(g * s) for g in g_sp for s in sj]
        rows += [_rsum(g * r) for g in g_sp for r in geo.rhat]
    return rows


def _descriptor_rows(spec: NEPSpinSpec, acc: list, si) -> list:
    """Accumulators -> invariant descriptor rows, in the order of
    :func:`repro.core.descriptor.finalize`."""
    at = acc_rows(spec)
    q = list(acc[:spec.n_rad])
    for l in range(1, spec.l_max + 1):
        for j in range(spec.n_ang):
            feat = None
            for p, coef in _LEGENDRE[l].items():
                cp = len(_MONO[p])
                base = at[f"ang{p}"] + j * cp
                mpow = None
                for c, (_ex, w) in enumerate(_MONO[p]):
                    a = acc[base + c]
                    term = w * (a * a)
                    mpow = term if mpow is None else mpow + term
                feat = coef * mpow if feat is None else feat + coef * mpow
            q.append(feat)
    if spec.spin:
        ns = spec.n_spin
        smag = jnp.sqrt(_dot3(si, si) + 1e-30)
        ons = [smag]
        for _ in range(1, spec.n_onsite):
            ons.append(ons[-1] * smag)
        q += ons
        for key in ("sp_dot", "sp_dmi", "sp_pd"):
            q += acc[at[key]:at[key] + ns]
        v = [acc[at["sp_v"] + 3 * j:at["sp_v"] + 3 * j + 3] for j in range(ns)]
        w = [acc[at["sp_w"] + 3 * j:at["sp_w"] + 3 * j + 3] for j in range(ns)]
        q += [_dot3(vj, vj) for vj in v]
        q += [_dot3(vj, si) for vj in v]
        q += [_dot3(wj, vj) for wj, vj in zip(w, v)]
    assert len(q) == spec.n_desc, (len(q), spec.n_desc)
    return q


def _mlp_rows(spec: NEPSpinSpec, cf: _Coef, kp: KernelParams, q: list, ti):
    """Per-element energy (1, A): the hidden layer accumulates one
    (H, 1) x (1, A) outer product per descriptor, predicated per type."""
    e = None
    for a in range(spec.n_types):
        pre = kp.w1[a, 0] * (q[0] / cf.q_scale(0)) + kp.b1[a]
        for d in range(1, len(q)):
            pre = pre + kp.w1[a, d] * (q[d] / cf.q_scale(d))
        ea = _rsum(jnp.tanh(pre) * kp.w2[a]) + cf.b2(a)
        e = (jnp.where(ti == a, ea, 0.0) if e is None
             else jnp.where(ti == a, ea, e))
    return e


def atom_tile(spec: NEPSpinSpec, kp: KernelParams, dr, mask, amask, ti,
              tj, si, sj):
    """K1 body on one lane tile.

    ``dr``/``sj``: 3-tuples of (M, A); ``mask`` (M, A) float; ``tj`` (M, A)
    int; ``si``: 3-tuple of (1, A); ``ti`` (1, A) int; ``amask`` (1, A)
    float.  Returns ``(e (1, A), hdir 3 x (1, A), abar R x (1, A))`` with
    the adjoint accumulators in :func:`acc_rows` order.
    """
    cf = _Coef(spec, kp.coef)
    geo = _pair_geometry(spec, dr, mask, tj)
    acc = _accumulate_rows(spec, cf, geo, ti, si, sj)

    def f1(acc_v, si_v):
        q = _descriptor_rows(spec, acc_v, si_v)
        return _mlp_rows(spec, cf, kp, q, ti) * amask

    e, vjp = jax.vjp(f1, acc, si)
    abar, dsi = vjp(jnp.ones_like(e))
    # -dE/dS_i (direct part) is the first half of the effective field
    return e, tuple(-d for d in dsi), tuple(abar)


# ---------------------------------------------------------------------------
# K2: fused force + torque (single neighbor traversal, pair-symmetric)
# ---------------------------------------------------------------------------

def _pair_rows(spec: NEPSpinSpec, cf: _Coef, dr, mask, ti, tj, si, sj,
               abar_i, abar_j):
    """ONE masked pass over the pair block evaluating, per pair,

        t_ij = <Abar_i, a(dr_ij, S_i, S_j)> + <Abar_j, a(-dr_ij, S_j, S_i)>

    as an (M, A) array, with the radial basis, type dispatch, angular
    monomials and scalar spin couplings shared between the orientations:

    * distance / Chebyshev basis: even under ``dr -> -dr``;
    * angular monomials: ``mono_p(-rhat) = (-1)^p mono_p(rhat)``;
    * Heisenberg, DMI and pseudo-dipolar couplings: invariant under the
      joint swap (c, n, rhat_c) -> (n, c, -rhat_c);
    * the neighbor-type-masked basis feeds both orientations' carriers.

    ``abar_i[r0:r1]`` are per-atom (1, A) rows and ``abar_j[r]`` per-pair
    (M, A) blocks (a packed array or a Pallas ref - indexed statically).
    """
    at = acc_rows(spec)
    geo = _pair_geometry(spec, dr, mask, tj)

    def ai(r):
        return abar_i[r:r + 1]

    def both(g1, g2, r, c1=None, c2=None, sign=1.0):
        """g1 * c1 * Abar_i[r] + sign * g2 * c2 * Abar_j[r]."""
        t1 = g1 * ai(r)
        t2 = g2 * abar_j[r]
        if c1 is not None:
            t1 = t1 * c1
        if c2 is not None:
            t2 = t2 * c2
        return t1 + t2 if sign > 0 else t1 - t2

    tot = None

    def add(t):
        nonlocal tot
        tot = t if tot is None else tot + t

    g1 = _carriers(cf, "c_rad", spec.n_rad, geo, ti)
    g2 = _carriers(cf, "c_rad", spec.n_rad, geo, ti, swap=True)
    for n in range(spec.n_rad):
        add(both(g1[n], g2[n], at["rad"] + n))

    g1 = _carriers(cf, "c_ang", spec.n_ang, geo, ti)
    g2 = _carriers(cf, "c_ang", spec.n_ang, geo, ti, swap=True)
    for p in range(spec.l_max + 1):
        mono = _monomials(geo.rhat, p)
        cp, sign = len(mono), (-1.0 if p % 2 else 1.0)
        for j in range(spec.n_ang):
            for c, m in enumerate(mono):
                add(m * both(g1[j], g2[j], at[f"ang{p}"] + j * cp + c,
                             sign=sign))

    if spec.spin:
        g1 = _carriers(cf, "c_spin", spec.n_spin, geo, ti)
        g2 = _carriers(cf, "c_spin", spec.n_spin, geo, ti, swap=True)
        # the three scalar couplings are parity-symmetric: one evaluation
        # contracts against BOTH adjoint sets
        for cpl, key in zip(_spin_couplings(si, sj, geo.rhat),
                            ("sp_dot", "sp_dmi", "sp_pd")):
            for j in range(spec.n_spin):
                add(cpl * both(g1[j], g2[j], at[key] + j))
        # directional accumulators: V_n sums neighbor spins (j's V sees
        # S_i), W_n sums rhat (odd under the flip)
        for j in range(spec.n_spin):
            for d in range(3):
                add(both(g1[j], g2[j], at["sp_v"] + 3 * j + d,
                         c1=sj[d], c2=si[d]))
                add(geo.rhat[d] * both(g1[j], g2[j],
                                       at["sp_w"] + 3 * j + d, sign=-1.0))
    return tot


def force_tile(spec: NEPSpinSpec, kp: KernelParams, dr, mask, ti, tj, si,
               sj, abar_i, abar_j):
    """K2 body on one lane tile.

    One reverse pass over the shared-basis pair contraction gives
    ``F_i = +sum_j d(t_ij)/d(dr_ij)`` (the pair-symmetric partial force -
    no reverse scatter) and the pass-2 field ``-sum_j d(t_ij)/d(S_i)`` (S_i
    enters both as the central spin of row i and as the neighbor spin of
    the j-centered half; the ``S_j`` dependence belongs to atom j's own
    row and is held constant).  Returns ``(force 3 x (1, A), field 3 x
    (1, A))``.
    """
    cf = _Coef(spec, kp.coef)

    def pair(dx, dy, dz, sx, sy, sz):
        return _pair_rows(spec, cf, (dx, dy, dz), mask, ti, tj,
                          (sx, sy, sz), sj, abar_i, abar_j)

    t, vjp = jax.vjp(pair, *dr, *si)
    g = vjp(jnp.ones_like(t))
    return (tuple(_rsum(gd) for gd in g[:3]),
            tuple(-gs for gs in g[3:]))


# ---------------------------------------------------------------------------
# executors
# ---------------------------------------------------------------------------

def _to_lanes(dr, mask, ti, tj, si, sj):
    """Array-of-structs (N, M, 3)/(N, M)/(N, 3)/(N,) -> lanes layout."""
    dtype = dr.dtype
    return (jnp.transpose(dr, (2, 1, 0)), mask.T.astype(dtype),
            ti.astype(jnp.int32)[None, :], tj.T.astype(jnp.int32),
            si.T, jnp.transpose(sj, (2, 1, 0)))


def _pad_lanes(arrays, n_pad: int):
    """Zero-pad the trailing atom axis to ``n_pad`` (a TILE_ATOMS
    multiple).  Pad lanes carry mask = amask = 0, so every output there
    is exactly zero."""
    return tuple(
        a if a.shape[-1] == n_pad else
        jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, n_pad - a.shape[-1])])
        for a in arrays)


def _xla_tile_lanes(n: int) -> int:
    """Lanes per ``lax.map`` step on the xla_tiled path: the largest
    TILE_ATOMS multiple that divides the padded atom count, capped at
    XLA_TILE_MAX tiles."""
    g = n // TILE_ATOMS
    div = max(d for d in range(1, min(g, XLA_TILE_MAX) + 1) if g % d == 0)
    return div * TILE_ATOMS


def _map_lanes(tile_fn, n: int, arrays):
    """Compiled tiled dispatch: split the trailing atom axis into
    (G, lanes) and ``lax.map`` the tile body over the G tiles.  The body is
    lowered ONCE (lax.map is a scan), so chunked callers pay one compile
    per geometry - same contract as the Pallas grid."""
    lanes = _xla_tile_lanes(n)
    g = n // lanes
    if g == 1:
        return tile_fn(*arrays)
    tiled = tuple(jnp.moveaxis(a.reshape(a.shape[:-1] + (g, lanes)), -2, 0)
                  for a in arrays)
    outs = jax.lax.map(lambda args: tile_fn(*args), tiled)
    return jax.tree_util.tree_map(
        lambda o: jnp.moveaxis(o, 0, -2).reshape(o.shape[1:-1] + (n,)),
        outs)


def _full_spec(shape, memory_space=None):
    nd = len(shape)
    if memory_space is not None:
        return pl.BlockSpec(memory_space=memory_space)
    return pl.BlockSpec(shape, lambda i, nd=nd: (0,) * nd)


def _tile_spec(lead):
    """Block over the trailing atom axis, full on the leading dims."""
    lead = tuple(lead)
    return pl.BlockSpec(lead + (TILE_ATOMS,),
                        lambda i, nl=len(lead): (0,) * nl + (i,))


def _param_specs(kp: KernelParams, mode: str):
    smem = pltpu.SMEM if mode == "pallas" else None
    return [_full_spec(kp.coef.shape, smem), _full_spec(kp.w1.shape),
            _full_spec(kp.b1.shape), _full_spec(kp.w2.shape)]


def _compiler_params(mode: str):
    if mode != "pallas":
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _rows3(ref):
    return tuple(ref[d:d + 1, :] for d in range(3))


def _store_rows(ref, rows):
    for r, v in enumerate(rows):
        ref[r:r + 1, :] = v


def _atom_kernel(spec: NEPSpinSpec, refs):
    """Pallas wrapper over :func:`atom_tile`."""
    (dr_ref, mask_ref, ti_ref, tj_ref, si_ref, sj_ref, am_ref,
     coef_ref, w1_ref, b1_ref, w2_ref, e_ref, h_ref, a_ref) = refs
    kp = KernelParams(coef_ref, w1_ref, b1_ref, w2_ref)
    e, hdir, abar = atom_tile(
        spec, kp, tuple(dr_ref[d] for d in range(3)), mask_ref[...],
        am_ref[...], ti_ref[...], tj_ref[...], _rows3(si_ref),
        tuple(sj_ref[d] for d in range(3)))
    e_ref[...] = e
    _store_rows(h_ref, hdir)
    _store_rows(a_ref, abar)


def nep_atom_pass(spec: NEPSpinSpec, params: NEPSpinParams,
                  dr, mask, amask, ti, tj, si, sj, *, mode: str = "auto"):
    """K1 dispatch. All arrays have leading dim N: dr/sj (N, M, 3),
    mask/tj (N, M), si (N, 3), ti/amask (N,); the atom axis is padded to a
    TILE_ATOMS multiple inside.  Returns ``(e (N,), hdir (N, 3), abar (R,
    N))`` with the adjoint accumulators packed in :func:`acc_rows` order.
    ``mode`` selects the executor (see module docstring); ``"auto"``
    resolves per backend."""
    mode = resolve_mode(mode)
    n_atoms, m = mask.shape
    n = -(-n_atoms // TILE_ATOMS) * TILE_ATOMS
    dtype = dr.dtype
    kp = kernel_params(spec, params)
    drL, maskL, tiL, tjL, siL, sjL, amL = _pad_lanes(
        _to_lanes(dr, mask, ti, tj, si, sj) + (amask.astype(dtype)[None],),
        n)
    n_rows = acc_rows(spec)["_total"]

    if mode == "xla_tiled":
        def tile(drt, mt, tit, tjt, sit, sjt, amt):
            e, h, a = atom_tile(spec, kp, tuple(drt), mt, amt, tit, tjt,
                                tuple(sit[d:d + 1] for d in range(3)),
                                tuple(sjt))
            return e, jnp.concatenate(h), jnp.concatenate(a)
        e, hdir, abar = _map_lanes(tile, n,
                                   (drL, maskL, tiL, tjL, siL, sjL, amL))
        return e[0, :n_atoms], hdir[:, :n_atoms].T, abar[:, :n_atoms]

    e, hdir, abar = pl.pallas_call(
        lambda *refs: _atom_kernel(spec, refs),
        grid=(n // TILE_ATOMS,),
        in_specs=[_tile_spec((3, m)), _tile_spec((m,)), _tile_spec((1,)),
                  _tile_spec((m,)), _tile_spec((3,)), _tile_spec((3, m)),
                  _tile_spec((1,))] + _param_specs(kp, mode),
        out_specs=[_tile_spec((1,)), _tile_spec((3,)),
                   _tile_spec((n_rows,))],
        out_shape=[jax.ShapeDtypeStruct((1, n), dtype),
                   jax.ShapeDtypeStruct((3, n), dtype),
                   jax.ShapeDtypeStruct((n_rows, n), dtype)],
        compiler_params=_compiler_params(mode),
        interpret=(mode == "interpret"),
        name="nep_atom_pass",
    )(drL, maskL, tiL, tjL, siL, sjL, amL, *kp)
    return e[0, :n_atoms], hdir[:, :n_atoms].T, abar[:, :n_atoms]


def _force_kernel(spec: NEPSpinSpec, refs):
    """Pallas wrapper over :func:`force_tile`."""
    (dr_ref, mask_ref, ti_ref, tj_ref, si_ref, sj_ref, ai_ref, aj_ref,
     coef_ref, w1_ref, b1_ref, w2_ref, f_ref, h_ref) = refs
    kp = KernelParams(coef_ref, w1_ref, b1_ref, w2_ref)
    f, h = force_tile(spec, kp, tuple(dr_ref[d] for d in range(3)),
                      mask_ref[...], ti_ref[...], tj_ref[...],
                      _rows3(si_ref), tuple(sj_ref[d] for d in range(3)),
                      ai_ref, aj_ref)
    _store_rows(f_ref, f)
    _store_rows(h_ref, h)


def nep_force_pass(spec: NEPSpinSpec, params: NEPSpinParams,
                   dr, mask, ti, tj, si, sj, abar, abar_j,
                   *, mode: str = "auto"):
    """K2 dispatch. Per-atom/pair inputs as in :func:`nep_atom_pass`;
    ``abar`` is K1's packed (R, N) output and ``abar_j`` its neighbor
    gather from :func:`gather_abar`.  Returns (force (N, 3),
    field_pass2 (N, 3)).  ``mode`` as in :func:`nep_atom_pass`."""
    mode = resolve_mode(mode)
    n_atoms, m = mask.shape
    n = -(-n_atoms // TILE_ATOMS) * TILE_ATOMS
    dtype = dr.dtype
    kp = kernel_params(spec, params)
    drL, maskL, tiL, tjL, siL, sjL, abar, abar_j = _pad_lanes(
        _to_lanes(dr, mask, ti, tj, si, sj) + (abar, abar_j), n)
    n_rows = abar.shape[0]

    if mode == "xla_tiled":
        def tile(drt, mt, tit, tjt, sit, sjt, ait, ajt):
            f, h = force_tile(spec, kp, tuple(drt), mt, tit, tjt,
                              tuple(sit[d:d + 1] for d in range(3)),
                              tuple(sjt), ait, ajt)
            return jnp.concatenate(f), jnp.concatenate(h)
        f, h2 = _map_lanes(tile, n, (drL, maskL, tiL, tjL, siL, sjL, abar,
                                     abar_j))
        return f[:, :n_atoms].T, h2[:, :n_atoms].T

    f, h2 = pl.pallas_call(
        lambda *refs: _force_kernel(spec, refs),
        grid=(n // TILE_ATOMS,),
        in_specs=[_tile_spec((3, m)), _tile_spec((m,)), _tile_spec((1,)),
                  _tile_spec((m,)), _tile_spec((3,)), _tile_spec((3, m)),
                  _tile_spec((n_rows,)), _tile_spec((n_rows, m))]
        + _param_specs(kp, mode),
        out_specs=[_tile_spec((3,)), _tile_spec((3,))],
        out_shape=[jax.ShapeDtypeStruct((3, n), dtype),
                   jax.ShapeDtypeStruct((3, n), dtype)],
        compiler_params=_compiler_params(mode),
        interpret=(mode == "interpret"),
        name="nep_force_pass",
    )(drL, maskL, tiL, tjL, siL, sjL, abar, abar_j, *kp)
    return f[:, :n_atoms].T, h2[:, :n_atoms].T
