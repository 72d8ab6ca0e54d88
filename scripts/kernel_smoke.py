"""CI smoke for the fused NEP kernel dispatch (scripts/ci.sh --smoke).

Fails fast if the kernel path regresses to interpret-mode dispatch or
loses parity:

* ``resolve_mode("auto")`` must pick a COMPILED executor on this backend
  (``"xla_tiled"`` on CPU - never ``"interpret"``);
* the compiled path must match the autodiff ref oracle on (E, F, H_eff)
  at f32 tolerance on an untruncated neighbor table (the pair-symmetric
  force formula assumes a symmetric list, so the table must not overflow);
* the compiled path must BEAT interpret-mode wall-clock on repeated
  warmed calls (median of 3) - the regression this smoke exists to catch
  turns a compiled executor back into the Python-stepped interpreter,
  which is a many-fold slowdown, so the 1.2x bar is loose but decisive;
* one warmed chunked sequence of calls must trigger ZERO further XLA
  backend compiles (the zero-recompile contract chunked drivers rely on).
"""
from __future__ import annotations

import statistics
import sys
import time

import jax
import jax.numpy as jnp


def main() -> None:
    from repro.core.descriptor import NEPSpinSpec
    from repro.core.potential import init_params
    from repro.kernels.nep import (nep_energy_forces_field,
                                   nep_energy_forces_field_ref, resolve_mode)
    from repro.md.lattice import b20_fege
    from repro.md.neighbor import dense_neighbor_table
    from repro.md.state import init_state

    mode = resolve_mode("auto")
    assert mode != "interpret", (
        f"auto dispatch resolved to interpret on {jax.default_backend()}")
    expect = "pallas" if jax.default_backend() == "tpu" else "xla_tiled"
    assert mode == expect, (mode, expect)

    spec = NEPSpinSpec(l_max=2, n_ang=2, n_rad=4, n_spin=2, basis_size=6)
    st = init_state(b20_fege(), (4, 4, 4), temperature=300.0,
                    spin_init="random", key=jax.random.PRNGKey(0))
    st = st._replace(pos=st.pos + 0.08 * jax.random.normal(
        jax.random.PRNGKey(9), st.pos.shape, st.pos.dtype))
    params = init_params(spec, jax.random.PRNGKey(1), dtype=jnp.float32)
    tab = dense_neighbor_table(st.pos, st.box, spec.cutoff, 64)
    assert not bool(tab.mask.sum(1).max() >= 64), "table overflow"
    args = (spec, params, st.pos, st.spin, st.types, tab, st.box)

    ref = nep_energy_forces_field_ref(*args)
    out = nep_energy_forces_field(*args, mode=mode)
    for got, want, name, tol in zip(out, ref, ("E", "F", "H"),
                                    (1e-4, 2e-4, 2e-4)):
        got, want = jnp.asarray(got), jnp.asarray(want)
        rel = float(jnp.max(jnp.abs(got - want))
                    / (jnp.max(jnp.abs(want)) + 1e-30))
        assert rel < tol, f"{name} parity: rel={rel:.3e} >= {tol}"
        print(f"parity {name}: rel={rel:.3e}")

    def med_time(m: str) -> float:
        r = nep_energy_forces_field(*args, mode=m)   # warmup compile
        jax.block_until_ready(r)
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(5):
                r = nep_energy_forces_field(*args, mode=m)
            jax.block_until_ready(r)
            ts.append((time.perf_counter() - t0) / 5)
        return statistics.median(ts)

    t_fast = med_time(mode)
    t_interp = med_time("interpret")
    ratio = t_interp / t_fast
    print(f"{mode}: {t_fast*1e3:.2f} ms/call, interpret: "
          f"{t_interp*1e3:.2f} ms/call ({ratio:.2f}x)")
    assert ratio > 1.2, (
        f"compiled mode {mode} only {ratio:.2f}x vs interpret - dispatch "
        f"regression?")

    # zero-recompile contract: chunked re-evaluation at fixed geometry.
    # Warm with a COMPUTED position array first - computed outputs are
    # committed to a device while init_state's arrays are not, and the
    # commitment bit is part of the jit cache key (one legitimate extra
    # entry, not a per-chunk retrace).
    r = nep_energy_forces_field(spec, params, st.pos + 0.0, st.spin,
                                st.types, tab, st.box, mode=mode)
    jax.block_until_ready(r)
    compiles = {"n": 0}

    def on_event(name, _dur, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    for i in range(4):
        r = nep_energy_forces_field(
            spec, params, st.pos + 1e-4 * i, st.spin, st.types, tab,
            st.box, mode=mode)
    jax.block_until_ready(r)
    assert compiles["n"] == 0, f"{compiles['n']} recompiles across chunks"
    print(f"kernel smoke OK: mode={mode}, {ratio:.2f}x vs interpret, "
          f"0 recompiles")


if __name__ == "__main__":
    sys.exit(main())
