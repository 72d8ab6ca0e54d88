"""Fused NEP kernel vs autodiff oracle: mode/shape/dtype/spec sweeps.

The whole-pipeline parity sweeps run through the default ``mode="auto"``
dispatch (the compiled xla_tiled executor on this CPU suite); dedicated
tests pin the other executors, the lax.map tiling, padding invariance at
``n % TILE_ATOMS != 0``, the single-compile contract across chunked calls,
and f64 oracle parity of xla_tiled vs interpret vs autodiff (subprocess -
the in-process suite stays f32).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.descriptor import NEPSpinSpec
from repro.core.potential import init_params
from repro.kernels.nep.kernel import (TILE_ATOMS, nep_atom_pass,
                                      resolve_mode)
from repro.kernels.nep.ops import nep_energy_forces_field
from repro.kernels.nep.ref import nep_energy_forces_field_ref
from repro.md.lattice import b20_fege, simple_cubic
from repro.md.neighbor import dense_neighbor_table
from repro.md.state import init_state

CASES = [
    # (lattice, cells, capacity, spec kwargs)
    ("b20", (2, 2, 2), 48, dict(l_max=2, n_ang=2, n_rad=4, n_spin=2,
                                basis_size=6)),
    ("sc", (3, 3, 3), 12, dict(l_max=3, n_ang=2, n_rad=3, n_spin=2,
                               basis_size=5, n_types=1)),
    ("b20", (2, 2, 2), 48, dict(l_max=4, n_ang=3, n_rad=4, n_spin=3,
                                basis_size=6)),
    ("sc", (3, 3, 3), 12, dict(l_max=2, n_ang=2, n_rad=4, n_spin=2,
                               basis_size=6, n_types=1, spin=False)),
]


@pytest.mark.parametrize("case", range(len(CASES)))
def test_kernel_matches_oracle(case):
    latname, cells, cap, spec_kw = CASES[case]
    lat = b20_fege() if latname == "b20" else simple_cubic()
    st = init_state(lat, cells, temperature=300.0, spin_init="random",
                    key=jax.random.PRNGKey(case))
    # thermal displacements so forces are O(1) (perfect-lattice forces are
    # roundoff-level and make relative comparisons meaningless)
    st = st._replace(pos=st.pos + 0.08 * jax.random.normal(
        jax.random.PRNGKey(100 + case), st.pos.shape, st.pos.dtype))
    spec = NEPSpinSpec(**spec_kw)
    params = init_params(spec, jax.random.PRNGKey(10 + case),
                         dtype=jnp.float32)
    tab = dense_neighbor_table(st.pos, st.box, spec.cutoff, cap)
    field = jnp.asarray([0.0, 0.1, 0.2]) if spec.spin else None
    mom = jnp.asarray([1.16, 0.0])[:spec.n_types]

    e0, f0, h0 = nep_energy_forces_field_ref(
        spec, params, st.pos, st.spin, st.types, tab, st.box, field, mom)
    e1, f1, h1 = nep_energy_forces_field(
        spec, params, st.pos, st.spin, st.types, tab, st.box, field, mom)

    assert abs(float(e1 - e0)) < 1e-4 * max(abs(float(e0)), 1.0)
    fs = float(jnp.abs(f0).max()) + 1e-9
    hs = float(jnp.abs(h0).max()) + 1e-9
    assert float(jnp.abs(f1 - f0).max()) / fs < 2e-5
    assert float(jnp.abs(h1 - h0).max()) / hs < 2e-5


def test_kernel_energy_translation_invariant():
    lat = simple_cubic()
    st = init_state(lat, (3, 3, 3), temperature=200.0, spin_init="random",
                    key=jax.random.PRNGKey(9))
    spec = NEPSpinSpec(l_max=2, n_ang=2, n_rad=3, n_spin=2, basis_size=5,
                       n_types=1)
    params = init_params(spec, jax.random.PRNGKey(0), dtype=jnp.float32)
    t1 = dense_neighbor_table(st.pos, st.box, spec.cutoff, 12)
    e1, _, _ = nep_energy_forces_field(spec, params, st.pos, st.spin,
                                       st.types, t1, st.box)
    p2 = (st.pos + 2.345) % st.box
    t2 = dense_neighbor_table(p2, st.box, spec.cutoff, 12)
    e2, _, _ = nep_energy_forces_field(spec, params, p2, st.spin, st.types,
                                       t2, st.box)
    assert abs(float(e1 - e2)) < 1e-4


def test_auto_mode_resolves_compiled():
    assert resolve_mode("auto") == (
        "pallas" if jax.default_backend() == "tpu" else "xla_tiled")
    assert resolve_mode("interpret") == "interpret"
    with pytest.raises(ValueError):
        resolve_mode("fast")


def _small_system(seed=0, cells=(3, 3, 3)):
    lat = simple_cubic()
    st = init_state(lat, cells, temperature=300.0, spin_init="random",
                    key=jax.random.PRNGKey(seed))
    st = st._replace(pos=st.pos + 0.08 * jax.random.normal(
        jax.random.PRNGKey(50 + seed), st.pos.shape, st.pos.dtype))
    spec = NEPSpinSpec(l_max=2, n_ang=2, n_rad=3, n_spin=2, basis_size=5,
                       n_types=1)
    params = init_params(spec, jax.random.PRNGKey(3), dtype=jnp.float32)
    tab = dense_neighbor_table(st.pos, st.box, spec.cutoff, 12)
    return spec, params, st, tab


def test_padding_invariance_unaligned_n():
    """n=108 pads to 128 (n % TILE_ATOMS != 0): both compiled executors
    must agree with the oracle AND with each other - pad rows are fully
    masked, so the executor split cannot leak them into real atoms."""
    spec, params, st, tab = _small_system()
    assert st.pos.shape[0] % TILE_ATOMS != 0
    args = (spec, params, st.pos, st.spin, st.types, tab, st.box)
    ref = nep_energy_forces_field_ref(*args)
    outs = {m: nep_energy_forces_field(*args, mode=m)
            for m in ("xla_tiled", "interpret")}
    for m, out in outs.items():
        for got, want in zip(out, ref):
            got, want = jnp.asarray(got), jnp.asarray(want)
            scale = float(jnp.abs(want).max()) + 1e-9
            assert float(jnp.abs(got - want).max()) / scale < 2e-5, m
    for a, b in zip(outs["xla_tiled"], outs["interpret"]):
        # same tile bodies, different executor: near-bitwise agreement
        assert float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max()) < 1e-4


def test_xla_tiled_lax_map_grouping():
    """Above XLA_TILE_MAX tiles the xla_tiled executor streams row groups
    through lax.map; K1 outputs must be identical (to f32 roundoff) to the
    interpret oracle on synthetic blocks sized to force 2 map steps."""
    spec = NEPSpinSpec(l_max=2, n_ang=2, n_rad=3, n_spin=2, basis_size=5,
                       n_types=1)
    params = init_params(spec, jax.random.PRNGKey(7), dtype=jnp.float32)
    n, m = 18 * TILE_ATOMS, 6     # 18 tiles: 6 per lax.map step, 3 steps
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    dr = jax.random.uniform(ks[0], (n, m, 3), jnp.float32, -2.5, 2.5)
    mask = jax.random.bernoulli(ks[1], 0.8, (n, m))
    amask = jnp.ones((n,), bool)
    ti = jnp.zeros((n,), jnp.int32)
    tj = jnp.zeros((n, m), jnp.int32)
    si = jax.random.normal(ks[2], (n, 3), jnp.float32)
    sj = jax.random.normal(ks[3], (n, m, 3), jnp.float32)
    e0, h0, a0 = nep_atom_pass(spec, params, dr, mask, amask, ti, tj, si,
                               sj, mode="interpret")
    e1, h1, a1 = nep_atom_pass(spec, params, dr, mask, amask, ti, tj, si,
                               sj, mode="xla_tiled")
    np.testing.assert_allclose(e1, e0, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(h1, h0, rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(a1, a0, rtol=2e-5, atol=1e-5)


def test_single_compile_across_chunked_calls():
    """The zero-recompile contract: after one warmup per executor shape,
    chunked re-evaluations at fixed geometry hit the jit cache."""
    spec, params, st, tab = _small_system(seed=1)
    compiles = {"n": 0}

    def on_event(name, _dur, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    # warm with a COMPUTED position array: computed outputs are committed
    # while init_state's are not, and commitment is part of the cache key
    r = nep_energy_forces_field(spec, params, st.pos + 0.0, st.spin,
                                st.types, tab, st.box, mode="xla_tiled")
    jax.block_until_ready(r)
    before = compiles["n"]
    for i in range(1, 5):
        r = nep_energy_forces_field(spec, params, st.pos + 1e-4 * i,
                                    st.spin, st.types, tab, st.box,
                                    mode="xla_tiled")
    jax.block_until_ready(r)
    assert compiles["n"] == before


_F64_SCRIPT = r"""
import json
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
from repro.core.descriptor import NEPSpinSpec
from repro.core.potential import init_params
from repro.kernels.nep import (nep_energy_forces_field,
                               nep_energy_forces_field_ref)
from repro.md.lattice import b20_fege
from repro.md.neighbor import dense_neighbor_table
from repro.md.state import init_state

spec = NEPSpinSpec(l_max=2, n_ang=2, n_rad=4, n_spin=2, basis_size=6)
st = init_state(b20_fege(), (2, 2, 2), temperature=300.0,
                spin_init="random", key=jax.random.PRNGKey(2),
                dtype=jnp.float64)
st = st._replace(pos=st.pos + 0.08 * jax.random.normal(
    jax.random.PRNGKey(12), st.pos.shape, st.pos.dtype))
params = init_params(spec, jax.random.PRNGKey(4), dtype=jnp.float64)
tab = dense_neighbor_table(st.pos, st.box, spec.cutoff, 64)
field = jnp.asarray([0.0, 0.1, 0.2])
mom = jnp.asarray([1.16, 0.0])
args = (spec, params, st.pos, st.spin, st.types, tab, st.box, field, mom)
ref = nep_energy_forces_field_ref(*args)
out = {}
for mode in ("xla_tiled", "interpret"):
    got = nep_energy_forces_field(*args, mode=mode)
    rels = []
    for g, w in zip(got, ref):
        g, w = jnp.asarray(g), jnp.asarray(w)
        rels.append(float(jnp.abs(g - w).max()
                          / (jnp.abs(w).max() + 1e-300)))
    out[mode] = rels
print("RESULT " + json.dumps(out))
"""


def test_f64_mode_parity_vs_oracle():
    """f64 subprocess: xla_tiled AND interpret match the autodiff oracle on
    (E, F, H_eff) to near machine precision - the executors share one
    definition of the model, so f64 disagreement means a real kernel bug,
    not accumulated f32 roundoff."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _F64_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    res = json.loads(line[len("RESULT "):])
    for mode, rels in res.items():
        for rel, name in zip(rels, ("E", "F", "H")):
            assert rel < 1e-10, (mode, name, rel)
