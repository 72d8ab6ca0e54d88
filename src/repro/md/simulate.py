"""Simulation drivers: thin facades over the unified engine.

The chunk machinery - fused in-scan neighbor lifecycle, shard_map domain
decomposition, schedules, observables, checkpointing - lives in ONE place,
:class:`repro.md.engine.Engine`.  This module keeps the two established
driver surfaces as facades over it:

* :class:`Simulation` - the single-trajectory driver.  ``fused=True``
  (default whenever the potential exposes the gather-once ``compute``
  surface) delegates to the engine's flat plan: the whole chunk (half-skin
  test, ``lax.cond`` in-graph table rebuild, gather-once evaluation,
  per-chunk diagnostics) inside one compiled ``lax.scan``, one compile per
  geometry, optionally cell-ordered rows.  ``fused=False`` is the retained
  pre-fusion reference path (host-side skin test between chunks, recompile
  per rebuild) - the parity baseline for tests and ``benchmarks/md_loop``,
  and the only path for potentials that implement ``energy_forces_field``
  but not ``compute``.
* :class:`SimulationSharded` - the domain-decomposed driver, a facade over
  the engine's sharded plan (in-scan rebuild WITH cross-device cell
  migration, one fused halo per drift, one fused adjoint fold, psum
  diagnostics; ``replicas > 0`` composes a replica axis with the spatial
  mesh).  ``run(temperature=...)`` accepts constants *or*
  ``repro.ensemble.protocol`` Schedules - protocols now run inside the
  compiled sharded chunk.

Use the :class:`~repro.md.engine.Engine` directly for the full axis matrix
(schedules on any plan, declarative observables, streaming ``obs_every``,
checkpoint-restart).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import numpy as np

# re-exported for backward compatibility (carries now live in the engine)
from repro.md.engine import DomainCarry, Engine, FusedCarry  # noqa: F401
from repro.md.integrator import ForceField, IntegratorConfig, make_step
from repro.md.neighbor import (NeighborTable, cell_neighbor_table,
                               dense_neighbor_table, needs_rebuild)
from repro.md.state import SpinLatticeState


class ChunkTrace(NamedTuple):
    """Per-chunk diagnostics reduced inside the compiled chunk (C chunks)."""

    time: np.ndarray           # (C,) ps at chunk ends
    energy: np.ndarray         # (C,) potential energy [eV]
    kinetic: np.ndarray        # (C,) lattice kinetic energy [eV]
    magnetization: np.ndarray  # (C, 3) mean spin over magnetic sites
    charge: np.ndarray         # (C,) Berg-Luscher topological charge


class DomainChunkTrace(NamedTuple):
    """Per-chunk diagnostics of the sharded loop, psum-reduced in-graph.

    With replicas, per-replica columns (C, R); otherwise (C,).
    """

    time: np.ndarray           # (C,) ps at chunk ends
    energy: np.ndarray         # potential energy [eV]
    kinetic: np.ndarray        # lattice kinetic energy [eV]
    magnetization: np.ndarray  # (..., 3) mean spin over magnetic sites


@dataclasses.dataclass
class Simulation:
    potential: Any                     # .compute(nbh,spin,types,field) and/or
                                       # .energy_forces_field(pos,spin,types,table,box,field)
    cfg: IntegratorConfig
    state: SpinLatticeState
    masses: jax.Array                  # (n_types,)
    magnetic: jax.Array                # (n_types,) bool
    cutoff: float
    capacity: int = 64
    skin: float = 0.5
    field: jax.Array | None = None     # (3,) Tesla
    use_cell_list: bool = False
    cell_capacity: int = 24
    fused: bool | None = None          # None -> fused iff potential.compute
    cell_order: bool | None = None     # cell-ordered layout; None -> cell list
    diag_grid: tuple[int, int] = (32, 32)
    table: NeighborTable | None = None
    trace: ChunkTrace | None = None
    _step_chunk: Callable | None = None
    _ff: ForceField | None = None

    def __post_init__(self):
        self._fused = (hasattr(self.potential, "compute")
                       if self.fused is None else self.fused)
        self._legacy_rebuilds = 0
        if self._fused:
            if not hasattr(self.potential, "compute"):
                raise ValueError("fused=True requires a potential with the "
                                 "gather-once .compute() surface")
            from repro.parallel.plan import SingleDevice
            self._engine = Engine(
                potential=self.potential, cfg=self.cfg, state=self.state,
                masses=self.masses, magnetic=self.magnetic,
                cutoff=self.cutoff,
                plan=SingleDevice(cell_order=self.cell_order),
                field=self.field,
                observables=("energy", "kinetic", "magnetization",
                             "charge"),
                capacity=self.capacity, skin=self.skin,
                use_cell_list=self.use_cell_list,
                cell_capacity=self.cell_capacity,
                diag_grid=self.diag_grid, table=self.table)
            self._pull()
        else:
            self._refresh(build_table=self.table is None)

    # ------------------------------------------------------------------
    # fused path: delegation to the engine's flat plan
    # ------------------------------------------------------------------
    def _pull(self):
        """Mirror the engine's observation state onto the facade."""
        self.state = self._engine.state
        self.table = self._engine.table
        self._ff = self._engine._ff

    @property
    def _carry(self):
        return self._engine._carry

    @property
    def _chunk_fn(self):
        return self._engine._chunk_fn

    @property
    def _reorder(self) -> bool:
        return self._engine._reorder if self._fused else False

    @property
    def n_rebuilds(self) -> int:
        """In-scan neighbor-table rebuilds so far (fused path)."""
        if self._fused:
            return self._engine.n_rebuilds
        return self._legacy_rebuilds

    @property
    def halo_ledger(self):
        """Run-scoped halo ledger (empty: the flat plan moves no halos)."""
        if not self._fused:
            raise AttributeError("halo_ledger requires the fused path")
        return self._engine.halo_ledger

    # ==================================================================
    # legacy (pre-fusion) path: host-side skin test, recompile per rebuild
    # ==================================================================
    def _build_table(self, pos) -> NeighborTable:
        if self.use_cell_list:
            return cell_neighbor_table(pos, self.state.box, self.cutoff,
                                       self.capacity,
                                       cell_capacity=self.cell_capacity,
                                       skin=self.skin)
        return dense_neighbor_table(pos, self.state.box, self.cutoff,
                                    self.capacity, skin=self.skin)

    def _make_eval(self, table):
        def evaluate(pos, spin, field=None):
            f = self.field if field is None else field
            return ForceField(*self.potential.energy_forces_field(
                pos, spin, self.state.types, table, self.state.box, f))
        return evaluate

    def _refresh(self, build_table: bool = True):
        """(Re)build table + recompile closure chain after atoms drift."""
        if build_table:
            self.table = self._build_table(self.state.pos)
        evaluate = self._make_eval(self.table)
        step = make_step(evaluate, self.cfg, self.masses, self.magnetic)

        @partial(jax.jit, static_argnames=("n",))
        def chunk(state, ff, key, n):
            def body(carry, k):
                st, f = carry
                st, f = step(st, f, k)
                return (st, f), None
            keys = jax.random.split(key, n)
            (state, ff), _ = jax.lax.scan(body, (state, ff), keys)
            return state, ff

        self._step_chunk = chunk
        self._ff = ForceField(*self.potential.energy_forces_field(
            self.state.pos, self.state.spin, self.state.types, self.table,
            self.state.box, self.field))

    # ==================================================================
    def run(self, n_steps: int, key: jax.Array, chunk: int = 20,
            callback: Callable[[SpinLatticeState, ForceField], None] | None = None,
            telemetry=None):
        """Advance ``n_steps``; rebuilds the neighbor table when the skin
        test trips (in-scan on the fused path). Returns the final state.
        On the fused path, per-chunk diagnostics land in ``self.trace``
        (the legacy path leaves it None - use ``callback`` there).

        A ``callback`` receives the (observation-order) state and forces
        after every chunk; note this forces a host sync per chunk, which the
        fused path otherwise avoids entirely.

        ``telemetry`` (a :class:`repro.telemetry.Telemetry` or a runlog
        path) is forwarded to ``Engine.run`` on the fused path.
        """
        if not self._fused:
            if telemetry is not None:
                raise ValueError("telemetry requires the fused path")
            return self._run_legacy(n_steps, key, chunk, callback)

        self._engine.state = self.state   # honor a caller-swapped state
        cb = None
        if callback is not None:
            def cb(engine):
                self._pull()
                callback(self.state, self._ff)
                engine.state = self.state  # callback may perturb the state
        self._engine.run(n_steps, key, chunk=chunk, field=self.field,
                         callback=cb, telemetry=telemetry)
        self._pull()
        tr = self._engine.trace
        if tr is not None:
            self.trace = ChunkTrace(
                time=tr.time, energy=tr.values["energy"],
                kinetic=tr.values["kinetic"],
                magnetization=tr.values["magnetization"],
                charge=tr.values["charge"])
        return self.state

    def _run_legacy(self, n_steps, key, chunk, callback):
        done = 0
        while done < n_steps:
            n = min(chunk, n_steps - done)
            key, sub = jax.random.split(key)
            if bool(needs_rebuild(self.table, self.state.pos, self.state.box,
                                  self.skin)):
                self._legacy_rebuilds += 1
                self._refresh()
            self.state, self._ff = self._step_chunk(self.state, self._ff,
                                                    sub, n)
            done += n
            if callback is not None:
                callback(self.state, self._ff)
        return self.state

    @property
    def energy(self) -> float:
        return float(self._ff.energy)


# ===========================================================================
# Sharded fused loop: facade over the engine's domain-decomposed plan
# ===========================================================================

@dataclasses.dataclass
class SimulationSharded:
    """Domain-decomposed twin of :class:`Simulation` (the sharded hot loop).

    A facade over :class:`repro.md.engine.Engine` with a
    :class:`repro.parallel.plan.Sharded` plan: the whole chunk - spin-
    lattice step, half-skin drift test, ``lax.cond`` in-scan rebuild *with
    cell migration across devices*, per-chunk diagnostics via ``psum`` -
    runs inside ONE compiled ``shard_map``-wrapped ``lax.scan`` over the
    ``(CX, CY, CZ, K, ...)`` layout of :mod:`repro.parallel.domain`:

    * exactly one fused halo per drift refreshes the pruned-table
      ``dr``/``sj`` blocks (positions AND spins in one round; self-
      consistent midpoint configs instead re-exchange spins per
      evaluation);
    * reaction forces on ghosts AND neighbor-spin gradients fold back in
      one fused adjoint halo, and the global energy + next step's skin
      test share one fused scalar reduction (potentials with
      ``use_kernel=True`` instead route the Pallas NEP kernels through
      the q_Fp adjoint-accumulator exchange - no reverse scatter at all);
    * at rebuild, atoms migrate to their (possibly remote) new cells in one
      fused multi-field exchange; capacity overflow or out-of-reach jumps
      are counted in the carry and raised at the next chunk boundary.

    ``replicas > 0`` adds a leading replica axis composed with the spatial
    mesh; every replica runs at its own runtime ``(temperature, field)``.
    ``run(temperature=...)`` and ``field`` accept constants or
    ``repro.ensemble.protocol`` Schedules (evaluated in-scan).
    """

    potential: Any                     # .pair_energies / .site_moments
    cfg: IntegratorConfig
    state: SpinLatticeState            # flat (N, ...) input state
    masses: jax.Array                  # (n_types,)
    magnetic: jax.Array                # (n_types,) bool
    cutoff: float
    capacity: int = 32                 # per-atom neighbor capacity M
    skin: float = 0.5
    cells: tuple | None = None         # global cell grid (None -> auto)
    cell_capacity: int | None = None   # per-cell capacity K (None -> auto)
    mesh: Any = None                   # jax Mesh (None -> 1D over devices)
    axis_map: tuple = None             # spatial dim -> mesh axis name
    devices: tuple | None = None       # subset for the auto-built 1D mesh
    halo_mode: str = "auto"            # "ppermute" | "allgather" | "auto"
    field: jax.Array | None = None     # (3,) Tesla (or (R, 3) w/ replicas)
    replicas: int = 0                  # 0 = no replica axis
    replica_axis: str = "replica"
    trace: DomainChunkTrace | None = None

    def __post_init__(self):
        from repro.parallel.plan import Sharded
        self._engine = Engine(
            potential=self.potential, cfg=self.cfg, state=self.state,
            masses=self.masses, magnetic=self.magnetic, cutoff=self.cutoff,
            plan=Sharded(mesh=self.mesh, axis_map=self.axis_map,
                         devices=self.devices,
                         halo_mode=self.halo_mode, cells=self.cells,
                         cell_capacity=self.cell_capacity,
                         replicas=self.replicas,
                         replica_axis=self.replica_axis),
            field=self.field,
            observables=("energy", "kinetic", "magnetization"),
            capacity=self.capacity, skin=self.skin)
        rp = self._engine._rplan
        self.mesh, self.axis_map = rp.mesh, rp.axis_map
        self._pull()

    def _pull(self):
        self.state = self._engine.state
        self._ff = self._engine._ff

    # ------------------------------------------------------------------
    @property
    def _dspec(self):
        return self._engine._rplan.dspec

    @property
    def _chunk_cache(self) -> dict:
        return self._engine._chunk_cache

    @property
    def _carry(self):
        return self._engine._carry

    @_carry.setter
    def _carry(self, carry):
        self._engine._carry = carry

    def _check_dropped(self):
        self._engine._check_dropped()

    @property
    def n_replicas(self) -> int:
        return self._engine.n_replicas

    @property
    def n_rebuilds(self) -> int:
        return self._engine.n_rebuilds

    @property
    def n_migrated(self) -> int:
        """Atoms that changed link cell across all in-scan rebuilds."""
        return self._engine.n_migrated

    @property
    def energy(self):
        return self._engine.energy

    @property
    def halo_ledger(self):
        """This run's halo exchange ledger (see ``Engine.halo_ledger``)."""
        return self._engine.halo_ledger

    # ------------------------------------------------------------------
    def run(self, n_steps: int, key: jax.Array, chunk: int = 20,
            temperature=None, telemetry=None):
        """Advance ``n_steps`` through the sharded fused loop.

        ``temperature`` (scalar K, (R,) with replicas, or a Schedule) and
        ``self.field`` ((3,) Tesla, (R, 3), or a Schedule) are runtime
        arguments of the compiled chunk - schedules are evaluated per step
        INSIDE the scan.  Per-chunk diagnostics land in ``self.trace``; a
        cell-capacity overflow raises at the chunk boundary where it is
        detected.  ``telemetry`` (a :class:`repro.telemetry.Telemetry` or
        a runlog path) is forwarded to ``Engine.run``.  Returns the final
        (original-atom-order) state.
        """
        self._engine.run(n_steps, key, chunk=chunk,
                         temperature=temperature, field=self.field,
                         telemetry=telemetry)
        self._pull()
        tr = self._engine.trace
        if tr is not None:
            self.trace = DomainChunkTrace(
                time=tr.time, energy=tr.values["energy"],
                kinetic=tr.values["kinetic"],
                magnetization=tr.values["magnetization"])
        return self.state
