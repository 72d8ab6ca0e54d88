"""Public jit'd wrapper around the fused NEP-SPIN kernels.

Pipeline (one MD force call):
  0. gather neighbor blocks from the table (XLA gather, stays in HBM order)
  1. K1: descriptor + ANN + adjoint accumulators (per-atom)
  2. gather neighbor adjoints Abar_j (the paper's q_Fp communication step;
     in the distributed path this is the second halo exchange)
  3. K2: fused force + torque in one neighbor traversal
  4. Zeeman term added in closed form (external field is not learned)

Step 0 is split out as the repo-wide gather -> compute contract
(repro.md.neighbor.Neighborhood): ``nep_compute`` consumes pre-gathered
blocks so the fused MD loop gathers positions once per drift and reuses the
blocks across both spin half-steps and all midpoint iterations;
``nep_energy_forces_field`` keeps the legacy whole-evaluation signature by
gathering then computing.

Both entry points take a static ``mode`` selecting the kernel executor
(``"pallas"`` | ``"xla_tiled"`` | ``"interpret"``, see
``repro.kernels.nep.kernel``); the default ``"auto"`` resolves per backend
at trace time - non-interpret Pallas (Mosaic) on TPU, the compiled
``lax.map``-over-tiles path on CPU.  ``mode`` is part of the jit cache key,
so chunked drivers that hold it fixed never recompile across chunks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.descriptor import NEPSpinSpec
from repro.core.potential import NEPSpinParams
from repro.kernels.nep.kernel import (gather_abar, nep_atom_pass,
                                      nep_force_pass)
from repro.md.neighbor import NeighborTable, Neighborhood, gather_blocks
from repro.telemetry.profiling import phase
from repro.utils import units


@partial(jax.jit, static_argnames=("spec", "mode"))
def nep_compute(
    spec: NEPSpinSpec,
    params: NEPSpinParams,
    nbh: Neighborhood,
    spin: jax.Array,
    types: jax.Array,
    field: jax.Array | None = None,
    moments: jax.Array | None = None,
    mode: str = "auto",
):
    """Fused-kernel (E, F, H_eff) from pre-gathered neighbor blocks."""
    n = spin.shape[0]
    with phase("force.spins"):
        sj = spin[nbh.idx]
    with phase("force.atom_pass"):
        e, hdir, abar = nep_atom_pass(spec, params, nbh.dr, nbh.mask,
                                      jnp.ones((n,), bool), types, nbh.tj,
                                      spin, sj, mode=mode)
    # gather neighbor adjoints (q_Fp exchange)
    with phase("force.adjoint"):
        abar_j = gather_abar(abar, nbh.idx)
    with phase("force.force_pass"):
        f, h2 = nep_force_pass(spec, params, nbh.dr, nbh.mask, types,
                               nbh.tj, spin, sj, abar, abar_j, mode=mode)

    energy = jnp.sum(e)
    force = f
    heff = hdir + h2
    if field is not None:
        mom = moments[types] if moments is not None else jnp.ones((n,),
                                                                  spin.dtype)
        energy = energy - units.MU_B * jnp.sum(
            mom[:, None] * spin * jnp.asarray(field, spin.dtype))
        heff = heff + units.MU_B * mom[:, None] * jnp.asarray(field,
                                                              spin.dtype)
    return energy, force, heff


@partial(jax.jit, static_argnames=("spec", "mode"))
def nep_energy_forces_field(
    spec: NEPSpinSpec,
    params: NEPSpinParams,
    pos: jax.Array,
    spin: jax.Array,
    types: jax.Array,
    table: NeighborTable,
    box: jax.Array,
    field: jax.Array | None = None,
    moments: jax.Array | None = None,
    mode: str = "auto",
):
    """Fused-kernel evaluation of (E, F, H_eff). Matches the ref oracle."""
    nbh = gather_blocks(pos, types, table, box)
    return nep_compute(spec, params, nbh, spin, types, field, moments,
                       mode=mode)
