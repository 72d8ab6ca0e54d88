"""NEP-SPIN configurations: weights from ``weights_seed`` and the program's
:class:`repro.core.potential.NEPSpinPotential` on the fused kernels."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import work
from bench.builders.system import seed_key


def make_weights(cfg: dict) -> dict:
    """Random NEP-SPIN weights at the configuration's widths, drawn on the
    device in one jitted call from ``weights_seed`` (the distributions of
    the repository's ``init_params``)."""
    spec = cfg["spec"]
    t, k, h = spec["n_types"], spec["basis_size"], spec["hidden"]
    d = work.nep_n_desc(spec)

    def build(key):
        ks = jax.random.split(key, 6)

        def norm(kk, shape, scale):
            return (scale * jax.random.normal(kk, shape)).astype(jnp.float32)

        def sym(c):
            return 0.5 * (c + jnp.swapaxes(c, 0, 1))

        return {
            "c_rad": sym(norm(ks[0], (t, t, spec["n_rad"], k), 0.5)),
            "c_ang": sym(norm(ks[1], (t, t, spec["n_ang"], k), 0.5)),
            "c_spin": sym(norm(ks[2], (t, t, spec["n_spin"], k), 0.5)),
            "w1": norm(ks[3], (t, d, h), (1.0 / d) ** 0.5),
            "b1": jnp.zeros((t, h), jnp.float32),
            "w2": norm(ks[4], (t, h), (1.0 / h) ** 0.5),
            "b2": jnp.zeros((t,), jnp.float32),
            "q_scale": jnp.ones((d,), jnp.float32),
        }

    return jax.jit(build)(seed_key(cfg["weights_seed"]))


def make_potential(cfg: dict):
    from repro.core.descriptor import NEPSpinSpec
    from repro.core.potential import NEPSpinParams, NEPSpinPotential

    if cfg["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {cfg['dtype']!r}")
    params = NEPSpinParams(**make_weights(cfg))
    return NEPSpinPotential(
        NEPSpinSpec(**cfg["spec"]), params,
        moments=jnp.asarray(cfg["lattice"]["moments"], jnp.float32),
        use_kernel=True)
