"""Distributed (multi-device) correctness - run in a subprocess so the
forced 8-device CPU environment never leaks into the main test process."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from repro.md.lattice import simple_cubic
from repro.md.state import init_state
from repro.md.neighbor import dense_neighbor_table
from repro.core.descriptor import NEPSpinSpec
from repro.core.potential import init_params, energy_forces_field
from repro.parallel.domain import (DomainSpec, pack_domain,
                                   distributed_energy_fn, unpack_domain)
from repro.utils.hlo import collective_bytes

out = {}
lat = simple_cubic()
st = init_state(lat, (5, 5, 5), temperature=300.0, spin_init="random",
                key=jax.random.PRNGKey(7))
spec = NEPSpinSpec(n_types=1, l_max=2, n_ang=2, n_rad=4, n_spin=2,
                   basis_size=6)
params = init_params(spec, jax.random.PRNGKey(0))
tab = dense_neighbor_table(st.pos, st.box, 5.0, 40)
e_ref, f_ref, h_ref = energy_forces_field(spec, params, st.pos, st.spin,
                                          st.types, tab, st.box)

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
dspec = DomainSpec(cells=(4, 4, 4), capacity=8, cutoff=5.0,
                   box=tuple(np.asarray(st.box)),
                   axis_map=("pod", "data", "model"))
dspec.check()
dst = pack_domain(dspec, st.pos, st.vel, st.spin, st.types)
efn, effn = distributed_energy_fn(spec, dspec, mesh)
with jax.set_mesh(mesh):
    e_d = efn(params, dst)
    e2, f_d, h_d = effn(params, dst)
out["e_diff"] = float(abs(e_ref - e_d))
pos_u, f_u, h_u, _ = unpack_domain(dst._replace(vel=f_d, spin=h_d))
pos_o = np.asarray(st.pos)
idx = [int(np.argmin(np.sum((pos_o - p) ** 2, -1))) for p in pos_u]
out["f_err"] = float(np.abs(np.asarray(f_u) - np.asarray(f_ref)[idx]).max())
out["h_err"] = float(np.abs(np.asarray(h_u) - np.asarray(h_ref)[idx]).max())

# halo-exchange collectives must appear in the compiled module
with jax.set_mesh(mesh):
    hlo = jax.jit(lambda d: efn(params, d)).lower(dst).compile().as_text()
out["coll_bytes"] = collective_bytes(hlo)

# pruned (pre-staged) evaluation path must match the stencil path
from repro.parallel.domain import distributed_energy_fn_pruned
build, effn_p = distributed_energy_fn_pruned(spec, dspec, mesh, capacity=32)
with jax.set_mesh(mesh):
    idx, nmask = build(dst.pos, dst.types, dst.mask)
    e_p, f_p, h_p = effn_p(params, dst.pos, dst.spin, dst.types, dst.mask,
                           idx, nmask)
out["pruned_e_diff"] = float(abs(e_p - e_d))
out["pruned_f_diff"] = float(jnp.abs(f_p - f_d).max())

# expert-parallel MoE (shard_map + all_to_all) must match dense dispatch
from repro.models.config import ArchConfig, MoECfg
from repro.models.moe import apply_moe_dense, apply_moe_ep, init_moe
cfgm = ArchConfig(name="t", family="moe", n_layers=1, d_model=32, vocab=64,
                  act="swiglu", dtype="float32",
                  moe=MoECfg(n_experts=8, top_k=2, n_shared=1,
                             d_ff_expert=16, router="sigmoid",
                             capacity_factor=8.0))
mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
pm = init_moe(cfgm, jax.random.PRNGKey(0))
xm = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
with jax.set_mesh(mesh2):
    y_ep, _ = jax.jit(lambda p, x: apply_moe_ep(cfgm, p, x, mesh2))(pm, xm)
    g = jax.grad(lambda p: jnp.sum(
        apply_moe_ep(cfgm, p, xm, mesh2)[0] ** 2))(pm)
y_dn, _ = apply_moe_dense(cfgm, pm, xm)
out["moe_ep_diff"] = float(jnp.abs(y_ep - y_dn).max())
out["moe_ep_grads_finite"] = bool(all(
    np.isfinite(np.asarray(v)).all()
    for v in jax.tree_util.tree_leaves(g)))

# production TPU composition: Pallas kernels over the pruned table with
# halo-exchanged adjoint accumulators (q_Fp exchange)
from repro.parallel.domain import distributed_kernel_force_fn
buildk, effn_k = distributed_kernel_force_fn(spec, dspec, mesh,
                                             capacity=32)
with jax.set_mesh(mesh):
    idxk, nmaskk = buildk(dst.pos, dst.types, dst.mask)
    e_k, f_k, h_k = effn_k(params, dst.pos, dst.spin, dst.types, dst.mask,
                           idxk, nmaskk)
out["kernel_e_diff"] = float(abs(e_k - e_d))
out["kernel_f_diff"] = float(jnp.abs(f_k - f_d).max())
out["kernel_h_diff"] = float(jnp.abs(h_k - h_d).max())

# checkpoint round-trip of the distributed state
from repro.ckpt.checkpoint import save_checkpoint, load_checkpoint
import tempfile
tmp = tempfile.mkdtemp()
save_checkpoint(tmp, 3, dst)
loaded, step = load_checkpoint(tmp, dst)
out["ckpt_ok"] = bool(step == 3 and all(
    np.array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(dst),
                    jax.tree_util.tree_leaves(loaded))))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_distributed_energy_matches_reference(result):
    assert result["e_diff"] < 1e-10


def test_distributed_forces_and_fields_match(result):
    assert result["f_err"] < 1e-12
    assert result["h_err"] < 1e-12


def test_halo_exchange_produces_collectives(result):
    assert result["coll_bytes"] > 0


def test_distributed_state_checkpoint_roundtrip(result):
    assert result["ckpt_ok"]


def test_pruned_prestaged_path_matches_stencil(result):
    """The paper's Phase-A/B pre-staging (pruned top-M table) must be exact
    vs the 27-stencil streaming evaluation (EXPERIMENTS.md SPerf cell 3)."""
    assert result["pruned_e_diff"] < 1e-8
    assert result["pruned_f_diff"] < 1e-10


def test_expert_parallel_moe_matches_dense(result):
    """shard_map+all_to_all EP dispatch == dense one-hot dispatch
    (EXPERIMENTS.md SPerf cell 1), with finite gradients."""
    assert result["moe_ep_diff"] < 1e-4
    assert result["moe_ep_grads_finite"]


def test_pallas_kernels_over_domain_match_autodiff(result):
    """The full production path (fused Pallas kernels + pruned table +
    halo-exchanged adjoints) must match the autodiff stencil evaluation."""
    assert result["kernel_e_diff"] < 1e-8
    assert result["kernel_f_diff"] < 1e-10
    assert result["kernel_h_diff"] < 1e-10
