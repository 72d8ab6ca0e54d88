"""Main-path programs compiled for a described TPU v5e chip (nothing runs).

The fused NEP kernels K1/K2 in ``pallas`` mode at the production
``config()`` widths and a real neighbor capacity, and the autodiff
evaluator, are compiled for one chip of a described ``v5e:2x2`` topology.
The TPU compiler refuses here what it would refuse on the chip: block
shapes off the (8, 128) tiling, operations Mosaic cannot lower, more VMEM
than a kernel may use.  The topology is described inside a fixture, never
at import, and the persistent compilation cache is off around these
compiles (entries written for a described chip cannot be read back
without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.fege_spinlattice import config
from repro.core.potential import compute, init_params
from repro.kernels.nep.kernel import acc_rows, nep_atom_pass, nep_force_pass
from repro.md.neighbor import Neighborhood

N, M = 4096, 96          # atoms x neighbor slots for the kernel compiles
SPEC = config().spec


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _abstract(one_chip):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: init_params(SPEC, jax.random.PRNGKey(0))))
    return sds, params


def test_k1_atom_pass_compiles_for_v5e(one_chip):
    sds, params = _abstract(one_chip)
    args = (sds((N, M, 3)), sds((N, M), jnp.bool_), sds((N,), jnp.bool_),
            sds((N,), jnp.int32), sds((N, M), jnp.int32), sds((N, 3)),
            sds((N, M, 3)))
    compiled = jax.jit(
        lambda p, *a: nep_atom_pass(SPEC, p, *a, mode="pallas")
    ).lower(params, *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_k2_force_pass_compiles_for_v5e(one_chip):
    sds, params = _abstract(one_chip)
    r = acc_rows(SPEC)["_total"]
    args = (sds((N, M, 3)), sds((N, M), jnp.bool_), sds((N,), jnp.int32),
            sds((N, M), jnp.int32), sds((N, 3)), sds((N, M, 3)),
            sds((r, N)), sds((r, M, N)))
    compiled = jax.jit(
        lambda p, *a: nep_force_pass(SPEC, p, *a, mode="pallas")
    ).lower(params, *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_autodiff_compute_compiles_for_v5e(one_chip):
    sds, params = _abstract(one_chip)
    n = 512
    nbh = Neighborhood(idx=sds((n, M), jnp.int32),
                       mask=sds((n, M), jnp.bool_),
                       tj=sds((n, M), jnp.int32), dr=sds((n, M, 3)))
    compiled = jax.jit(lambda p, *a: compute(SPEC, p, *a)).lower(
        params, nbh, sds((n, 3)), sds((n,), jnp.int32),
        sds((3,))).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert "tpu_custom_call" not in compiled.as_text()
