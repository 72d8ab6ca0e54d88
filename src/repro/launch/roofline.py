"""Three-term roofline model for the dry-run artifacts.

Target hardware (TPU v5e class, per chip):
  peak compute : 197 TFLOP/s bf16
  HBM bandwidth: 819 GB/s
  ICI link     : ~50 GB/s per link

``compiled.cost_analysis()`` and the parsed HLO are PER-DEVICE quantities
(the compiled module is the SPMD per-device program), so the terms are

  compute_term    = hlo_flops_device / peak_flops
  memory_term     = hlo_bytes_device / hbm_bw
  collective_term = collective_bytes_device / ici_bw

each in seconds-per-step; the dominant term is the bottleneck.  MODEL_FLOPS
uses 6*N*D for training and 2*N*D for inference (N = active params, D =
tokens), so ratio = MODEL_FLOPS / HLO_FLOPs measures how much compiled
compute is 'useful' (catches remat/redundancy waste; >1 means the compiler
sees fewer FLOPs than the analytic model, e.g. fused attention counted as
fewer ops).
"""
from __future__ import annotations

PEAK_FLOPS = 197e12     # bf16 per chip
HBM_BW = 819e9          # bytes/s
ICI_BW = 50e9           # bytes/s per link


def model_flops(arch: str, kind: str, tokens: int) -> float:
    """Analytic 'useful' FLOPs for the whole step (global)."""
    if arch == "fege-spinlattice":
        return 0.0  # per-atom descriptor cost: see nep_analytic()
    from repro import configs
    cfg = configs.get(arch)
    n = cfg.n_active_params()
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens


def terms(rec: dict) -> dict:
    n_dev = rec["devices"]
    flops_dev = rec["flops_total"]          # per-device (SPMD module)
    bytes_dev = rec["bytes_total"]
    coll_dev = sum(v["bytes"] for v in rec["collectives"].values())

    compute_t = flops_dev / PEAK_FLOPS
    memory_t = bytes_dev / HBM_BW
    coll_t = coll_dev / ICI_BW
    terms_ = {"compute": compute_t, "memory": memory_t,
              "collective": coll_t}
    bottleneck = max(terms_, key=terms_.get)

    meta = rec.get("meta", {})
    mf = model_flops(rec["arch"], meta.get("kind", "train"),
                     meta.get("tokens", 0))
    mf_dev = mf / n_dev if n_dev else 0.0
    out = {
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": coll_t,
        "collective_bytes": coll_dev,
        "bottleneck": bottleneck,
        "model_flops_global": mf,
        "useful_flops_ratio": (mf_dev / flops_dev) if flops_dev else None,
        # step time if perfectly overlapped = max term; roofline fraction =
        # dominant-term share of the max-possible utilization
        "step_time_s": max(terms_.values()),
        "roofline_fraction_compute": (
            compute_t / max(terms_.values()) if max(terms_.values()) else
            None),
    }
    return out


# ---------------------------------------------------------------------------
# NEP-SPIN fused kernel pipeline (arch "fege-spinlattice")
# ---------------------------------------------------------------------------
#
# The spin-lattice force call is not a token model, so its analytic roofline
# is a per-atom descriptor FLOP/byte model of the three pipeline stages
# (K1 descriptor+ANN+adjoints, abar_j gather, K2 pair force/torque -
# repro.kernels.nep).  The measured side walks the actual jaxprs with
# repro.utils.jaxpr_cost, so analytic-vs-measured drift catches both model
# rot and kernel-pipeline regressions (e.g. a K2 that re-runs accumulate
# per pair shows up as measured_flops >> analytic).


def nep_abar_row(spec) -> int:
    """Scalars per atom in the adjoint-accumulator set Abar (= the q_Fp
    halo payload row and the abar_j gather row)."""
    from repro.core.descriptor import _MONO
    n = spec.n_rad
    n += sum(spec.n_ang * len(_MONO[p]) for p in range(spec.l_max + 1))
    if spec.spin:
        n += 3 * spec.n_spin        # sp_dot, sp_dmi, sp_pd
        n += 2 * spec.n_spin * 3    # sp_v, sp_w vectors
    return n


def nep_pair_flops(spec) -> float:
    """Analytic FLOPs for ONE pair's descriptor accumulation (the paper's
    b1/b2 inner loop): Chebyshev recurrence + the T^2 predicated basis->
    channel einsums + angular monomial outer products + spin couplings."""
    from repro.core.descriptor import _MONO
    k = spec.basis_size
    t2 = spec.n_types ** 2
    fl = 3.0 * k + 10.0                           # recurrence + cutoff fn
    n_ch = spec.n_rad + spec.n_ang + (spec.n_spin if spec.spin else 0)
    fl += 2.0 * t2 * k * n_ch                     # dense f_k -> g_n einsums
    for p in range(spec.l_max + 1):
        c = len(_MONO[p])
        fl += 4.0 * c + 2.0 * spec.n_ang * c      # monomials + accumulation
    if spec.spin:
        fl += 30.0 + 18.0 * spec.n_spin           # couplings + contractions
    return fl


# reverse-mode multipliers: K1 runs accumulate forward + a vjp (~2x) over
# it; K2 evaluates BOTH pair orientations off one shared basis (~1.5x a
# single accumulate after the single-traversal restructuring) and then
# differentiates that closure (~3x its primal)
K1_MULT = 3.0
K2_MULT = 4.5


def nep_analytic(spec, n_atoms: int, m: int, itemsize: int = 4) -> dict:
    """Analytic FLOPs/bytes for one fused force call at (n_atoms, m_cap).

    Bytes model the two streaming HBM terms: the neighbor blocks (read by
    K1 and K2) and the abar_j gather (the dominant term - every pair pulls
    a full adjoint row, M-fold amplification of the per-atom Abar set).
    """
    pairs = float(n_atoms) * m
    c_pair = nep_pair_flops(spec)
    mlp = 6.0 * (spec.n_desc * spec.hidden + spec.hidden)    # fwd + vjp
    k1 = pairs * c_pair * K1_MULT + n_atoms * mlp
    k2 = pairs * c_pair * K2_MULT
    row = nep_abar_row(spec)
    gather_bytes = (n_atoms * m * row + n_atoms * row) * itemsize
    block_bytes = 2.0 * pairs * 8 * itemsize     # dr(3)+sj(3)+tj+mask, x2
    flops = k1 + k2
    hbm = gather_bytes + block_bytes
    return {
        "flops": flops, "k1_flops": k1, "k2_flops": k2,
        "pair_flops": c_pair, "abar_row": row,
        "gather_bytes_abar_j": gather_bytes, "hbm_bytes": hbm,
        "arithmetic_intensity": flops / hbm if hbm else None,
        "compute_s": flops / PEAK_FLOPS, "memory_s": hbm / HBM_BW,
    }


def nep_measured(spec, params, nbh, spin, types, mode: str = "auto") -> dict:
    """jaxpr-walked FLOPs/bytes of the K1 / abar_j-gather / K2 stages at
    the given geometry (repro.utils.jaxpr_cost: loop-aware, so the
    xla_tiled lax.map tiling is counted at full trip count).

    Returns {"k1": {...}, "gather": {...}, "k2": {...}, "flops",
    "gather_bytes_abar_j"} - stage dicts are jaxpr_cost triples.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels.nep.kernel import (TILE_ATOMS, gather_abar,
                                          nep_atom_pass, nep_force_pass)
    from repro.utils.jaxpr_cost import lowered_cost

    n = spin.shape[0]
    n_pad = -(-n // TILE_ATOMS) * TILE_ATOMS
    k1_args = (nbh.dr, nbh.mask, jnp.ones((n,), bool), types, nbh.tj, spin,
               spin[nbh.idx])

    def k1_fn(dr, mask, am, ti, tj, si, sjv):
        return nep_atom_pass(spec, params, dr, mask, am, ti, tj, si, sjv,
                             mode=mode)

    k1_cost = lowered_cost(jax.make_jaxpr(k1_fn)(*k1_args))
    _, _, abar = k1_fn(*k1_args)

    gather_cost = lowered_cost(jax.make_jaxpr(gather_abar)(abar, nbh.idx))
    abar_j = gather_abar(abar, nbh.idx)

    def k2_fn(dr, mask, ti, tj, si, sjv, ab, abj):
        return nep_force_pass(spec, params, dr, mask, ti, tj, si, sjv,
                              ab, abj, mode=mode)

    k2_cost = lowered_cost(jax.make_jaxpr(k2_fn)(
        nbh.dr, nbh.mask, types, nbh.tj, spin, spin[nbh.idx], abar, abar_j))

    itemsize = jnp.dtype(nbh.dr.dtype).itemsize
    row = nep_abar_row(spec)
    m = nbh.idx.shape[1]
    return {
        "k1": k1_cost, "gather": gather_cost, "k2": k2_cost,
        "flops": k1_cost["flops"] + k2_cost["flops"],
        "gather_bytes_abar_j": (n_pad * m * row + n_pad * row) * itemsize,
        "n_pad": n_pad, "m_cap": m, "mode": mode,
    }


def nep_report(spec, params, nbh, spin, types, mode: str = "auto") -> dict:
    """Measured-vs-analytic roofline record stamped into BENCH_md_loop.json:
    flops_ratio near 1 means the compiled pipeline does roughly the
    analytic work; >> 1 flags redundant traversals creeping back in."""
    n = spin.shape[0]
    m = nbh.idx.shape[1]
    meas = nep_measured(spec, params, nbh, spin, types, mode=mode)
    import jax.numpy as jnp
    ana = nep_analytic(spec, meas["n_pad"], m,
                       itemsize=jnp.dtype(nbh.dr.dtype).itemsize)
    return {
        "analytic": ana,
        "measured": meas,
        "flops_ratio": (meas["flops"] / ana["flops"]) if ana["flops"]
        else None,
        "n_atoms": n,
    }
