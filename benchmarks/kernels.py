"""Kernel microbenchmarks: fused vs reference implementations.

The NEP rows time the fused kernel pipeline stage by stage (K1
descriptor+ANN+adjoints, the abar_j adjoint gather, K2 pair force/torque)
through the mode-dispatched executor (``"auto"``: compiled Pallas on
TPU, the compiled lax.map tiling on CPU), with jaxpr-level FLOPs and
bytes per stage (repro.utils.jaxpr_cost) in the derived column - so both
wall-clock AND op-count regressions of any single stage are visible.
Attention/SSD rows time the *jnp* algorithmic variants (their Pallas
kernels remain interpret-validated only).

CSV: name, us_per_call, derived.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row, timeit


def bench_attention() -> list[str]:
    from repro.models.attention import chunked_attention
    from repro.kernels.attention.ref import attention_ref
    rows = []
    b, s, h, hkv, d = 1, 2048, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))

    naive = jax.jit(lambda q, k, v: attention_ref(
        q.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d),
        v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)))
    flash = jax.jit(lambda q, k, v: chunked_attention(q, k, v, pos, pos,
                                                      kv_chunk=512))
    t0 = timeit(naive, q, k, v)
    t1 = timeit(flash, q, k, v)
    flops = 4 * b * h * s * s * d
    rows.append(row("kernels/attention-naive", t0 * 1e6,
                    f"{flops/t0/1e9:.1f}GFLOP/s"))
    rows.append(row("kernels/attention-flash-chunked", t1 * 1e6,
                    f"{flops/t1/1e9:.1f}GFLOP/s|{t0/t1:.2f}x"))
    return rows


def bench_ssd() -> list[str]:
    from repro.models.ssm import ssd_chunked, ssd_reference
    rows = []
    bs, s, h, p, g, n, chunk = 1, 2048, 8, 32, 1, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (bs, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bs, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b = jax.random.normal(ks[3], (bs, s, g, n)) * 0.3
    c = jax.random.normal(ks[4], (bs, s, g, n)) * 0.3
    dsk = jnp.ones((h,))
    rec = jax.jit(lambda *a_: ssd_reference(*a_))
    chu = jax.jit(lambda *a_: ssd_chunked(*a_, chunk))
    t0 = timeit(rec, x, dt, a, b, c, dsk)
    t1 = timeit(chu, x, dt, a, b, c, dsk)
    rows.append(row("kernels/ssd-recurrence", t0 * 1e6, "1.00x"))
    rows.append(row("kernels/ssd-chunked", t1 * 1e6, f"{t0/t1:.2f}x"))
    return rows


def bench_nep() -> list[str]:
    """Fused NEP force pipeline, stage by stage (the paper's hot kernel)."""
    from functools import partial

    from repro.core.descriptor import NEPSpinSpec
    from repro.core.potential import energy_forces_field, init_params
    from repro.kernels.nep import resolve_mode
    from repro.kernels.nep.kernel import (gather_abar, nep_atom_pass,
                                          nep_force_pass)
    from repro.kernels.nep.ops import nep_energy_forces_field
    from repro.launch.roofline import nep_measured
    from repro.md.lattice import b20_fege
    from repro.md.neighbor import dense_neighbor_table, gather_blocks
    from repro.md.state import init_state
    lat = b20_fege()
    st = init_state(lat, (4, 4, 4), temperature=300.0,
                    key=jax.random.PRNGKey(0), dtype=jnp.float32)
    spec = NEPSpinSpec()
    params = init_params(spec, jax.random.PRNGKey(1), dtype=jnp.float32)
    tab = dense_neighbor_table(st.pos, st.box, spec.cutoff, 64)
    mode = resolve_mode("auto")
    rows = []

    # whole-evaluation reference points: autodiff vs the fused kernel path
    ad = jax.jit(lambda p, s: energy_forces_field(
        spec, params, p, s, st.types, tab, st.box))
    t_ad = timeit(ad, st.pos, st.spin)
    rows.append(row("kernels/nep-autodiff-force", t_ad * 1e6,
                    f"{st.n_atoms/t_ad:.3e} atom/s"))
    kf = jax.jit(lambda p, s: nep_energy_forces_field(
        spec, params, p, s, st.types, tab, st.box, mode=mode))
    t_k = timeit(kf, st.pos, st.spin)
    rows.append(row(f"kernels/nep-fused-force/{mode}", t_k * 1e6,
                    f"{st.n_atoms/t_k:.3e} atom/s|{t_ad/t_k:.2f}x"))

    # stage micro-rows: K1 / abar_j gather / K2 at the same geometry, each
    # with its jaxpr-walked FLOPs + anchor bytes so op-count regressions
    # (e.g. a K2 that re-runs accumulate per pair) are visible per stage
    nbh = gather_blocks(st.pos, st.types, tab, st.box)
    n = st.n_atoms
    a = {
        "dr": nbh.dr, "mask": nbh.mask, "amask": jnp.ones((n,), bool),
        "ti": st.types, "tj": nbh.tj, "si": st.spin,
        "sj": st.spin[nbh.idx], "idx": nbh.idx,
    }
    cost = nep_measured(spec, params, nbh, st.spin, st.types, mode=mode)

    k1 = jax.jit(partial(nep_atom_pass, spec, params, mode=mode))
    t1 = timeit(k1, a["dr"], a["mask"], a["amask"], a["ti"], a["tj"],
                a["si"], a["sj"])
    _, _, abar = k1(a["dr"], a["mask"], a["amask"], a["ti"], a["tj"],
                    a["si"], a["sj"])
    gather = jax.jit(gather_abar)
    tg = timeit(gather, abar, a["idx"])
    abar_j = gather(abar, a["idx"])
    k2 = jax.jit(partial(nep_force_pass, spec, params, mode=mode))
    t2 = timeit(k2, a["dr"], a["mask"], a["ti"], a["tj"], a["si"], a["sj"],
                abar, abar_j)

    for name, t, c in (("k1-atom-pass", t1, cost["k1"]),
                       ("adjoint-gather", tg, cost["gather"]),
                       ("k2-force-pass", t2, cost["k2"])):
        rows.append(row(
            f"kernels/nep-{name}/{mode}", t * 1e6,
            f"{c['flops']:.3e}flop|{c['bytes_anchor']:.3e}B|"
            f"{c['flops']/t/1e9:.1f}GFLOP/s"))
    return rows


def main() -> list[str]:
    return bench_nep() + bench_attention() + bench_ssd()


if __name__ == "__main__":
    main()
