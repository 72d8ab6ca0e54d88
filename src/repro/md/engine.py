"""The unified spin-lattice simulation engine.

ONE schedule-driven chunk driver composes four orthogonal axes (previously
hand-wired into four near-duplicate drivers across ``md/simulate.py`` and
``ensemble/replica.py``):

* **evaluator** - any potential exposing the gather-once ``compute``
  surface (Heisenberg-DMI, autodiff NEP-SPIN) on flat plans; the
  ``pair_energies``/``site_moments`` surface or the fused Pallas NEP
  kernel (``use_kernel=True``, routed through the q_Fp adjoint halo) on
  the sharded plan;
* **parallel plan** (:mod:`repro.parallel.plan`) - ``SingleDevice`` (flat
  fused loop), ``Replicated`` (vmapped replicas sharing one neighbor
  table), ``Sharded`` (shard_map domain decomposition over the cell-major
  ``(CX, CY, CZ, K)`` layout, optionally x replicas);
* **schedule** - ``temperature`` / ``field`` each accept ``None``, a
  constant, or an :class:`repro.ensemble.protocol.Schedule`; schedules are
  pytrees of knots evaluated **inside the compiled scan** from the step
  counter, so a full field-cooling protocol runs in-scan on every plan
  with zero recompiles across chunks (knot *values* are runtime data);
* **observables** - a declarative pipeline over :mod:`repro.md.analysis`
  (``energy``, ``kinetic``, ``magnetization``, ``charge``,
  ``skyrmion_count``, ``pitch``) evaluated inside the compiled chunk -
  at chunk boundaries by default, or streamed every ``obs_every`` steps
  from inside the scan (a ``lax.cond`` per step) - and reduced with
  ``psum`` over the spatial mesh on the sharded plan via the
  accumulate/finalize splits in :mod:`repro.md.analysis`.

Every plan shares one chunk skeleton: evaluate the schedules at the
current step's time, run the half-skin test behind a ``lax.cond`` whose
taken branch rebuilds (and, sharded, migrates), step, optionally emit
observables - all inside one compiled ``lax.scan`` (wrapped in
``shard_map`` on the sharded plan).

Checkpoint-restart: :meth:`Engine.save` / :meth:`Engine.restore` snapshot
the *hot carry* plus the run RNG key at a chunk boundary through
:mod:`repro.ckpt.checkpoint`'s MD surface; resuming reproduces the
uninterrupted trajectory bitwise on every plan (the carry holds the full
loop state - neighbor blocks, permutations, rebuild counters - and the
run loop's key split sequence is position-independent).
``run(checkpoint_dir=...)`` saves periodically; ``resume=True`` picks up
the newest checkpoint.

``repro.md.simulate.Simulation`` / ``SimulationSharded`` and
``repro.ensemble.replica.ReplicaEnsemble`` are thin facades over this
class (kept for their established constructor/trace surfaces).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.md.analysis import (accumulate_spin_grid, accumulate_spin_profile,
                               charge_from_grid, helix_pitch, magnetization,
                               pitch_from_profile, skyrmion_count,
                               topological_charge)
from repro.md.integrator import (ForceField, IntegratorConfig,
                                 force_calls_per_step, make_fused_step)
from repro.md.neighbor import (NeighborTable, Neighborhood, cell_order,
                               gather_blocks, make_table_builder,
                               needs_rebuild, refresh_dr)
from repro.md.state import SpinLatticeState, kinetic_energy
from repro.parallel.halo import HaloTrace
from repro.parallel.plan import Replicated, Sharded, SingleDevice, as_plan
from repro.telemetry import (RunMetrics, TelemetrySession, annotate,
                             as_telemetry, check_chunk, phase)
from repro.telemetry.monitor import (HealthError, nonfinite_count,
                                     occupancy_fraction, spin_norm_dev)
from repro.utils import units


# ===========================================================================
# carries (device-resident loop state; one per plan family)
# ===========================================================================

class FusedCarry(NamedTuple):
    """Loop state of the flat fused driver (the scan carry)."""

    state: SpinLatticeState   # hot (possibly cell-ordered) row order
    ff: ForceField
    table: NeighborTable
    nbh: Neighborhood
    perm: jax.Array           # (N,) int32: hot row -> original atom id
    n_rebuilds: jax.Array     # () int32 in-scan rebuild count


class ReplicaCarry(NamedTuple):
    """Loop state of the vmapped-replica driver.

    ``states``/``ffs`` carry a leading replica axis; the neighbor table and
    the table-static blocks of ``nbh`` are SHARED (unbatched - one copy
    serves every replica); only the position-dependent ``dr`` block is
    replica-batched.
    """

    states: SpinLatticeState  # (R, N, ...)
    ffs: ForceField           # (R,) energies, (R, N, 3) force/field
    table: NeighborTable      # shared across replicas
    nbh: Neighborhood         # idx/mask/tj unbatched; dr (R, N, M, 3)
    n_rebuilds: jax.Array     # () int32


class DomainCarry(NamedTuple):
    """Loop state of the sharded fused driver.

    The cell-major twin of :class:`FusedCarry`: every per-atom field lives
    in the fixed-capacity ``(CX, CY, CZ, K, ...)`` link-cell layout whose
    leading spatial dims are sharded over the device mesh (with an optional
    leading replica axis).  ``types == -1`` marks empty slots; ``aid``
    carries the original atom id through migrations so observation can
    restore input order, exactly as ``FusedCarry.perm`` does on one device.
    """

    state: SpinLatticeState   # cell-blocked fields; box/step replicated
    ff: ForceField
    nbh: Any                  # DomainNbh: per-device pruned table blocks
    aid: jax.Array            # (..., CX, CY, CZ, K) int32, -1 = empty
    r0: jax.Array             # (..., CX, CY, CZ, K, 3) build positions
    trip: jax.Array           # () bool: skin test, precomputed at the END
                              # of the previous step (positions are final
                              # after the drift) so its global reduction
                              # fuses with the energy psum - one scalar
                              # collective per step instead of two
    n_rebuilds: jax.Array     # () int32, shared trip -> identical everywhere
    n_migrated: jax.Array     # () int32, psummed at rebuild
    n_dropped: jax.Array      # (n_devices,) int32 per-device overflow +
                              # skin-violation losses, replicated via psum
                              # so the HealthError can name the device


class EngineTrace(NamedTuple):
    """Streamed observables: one row per emission (chunk boundary, or every
    ``obs_every`` steps when streaming).  ``values[name]`` has leading dim
    C = number of emissions, then a replica dim on replica plans, then the
    observable's own tail (e.g. (3,) for magnetization).

    ``health`` holds the in-scan health signals at CHUNK cadence (one row
    per chunk regardless of ``obs_every``): e_drift, spin_dev, nonfinite,
    nbr_occ (+ cell_occ on the sharded plan) - see
    :mod:`repro.telemetry.monitor`."""

    time: np.ndarray              # (C,) ps at emission points
    values: dict[str, np.ndarray]
    health: dict[str, np.ndarray] | None = None   # (n_chunks,) per signal


# ===========================================================================
# observable pipeline
# ===========================================================================

OBSERVABLES = ("energy", "kinetic", "magnetization", "charge",
               "skyrmion_count", "pitch")


def _check_names(names):
    names = tuple(names)
    for n in names:
        if n not in OBSERVABLES:
            raise ValueError(f"unknown observable {n!r}; "
                             f"available: {OBSERVABLES}")
    return names


def make_flat_observe(names, masses, magnetic, diag_grid, pitch_axis,
                      pitch_bins) -> Callable:
    """Observable pipeline over flat (N, ...) arrays.

    Calls :mod:`repro.md.analysis` directly, so engine traces reproduce
    the standalone diagnostics exactly.  Replica plans ``vmap`` this.
    """
    names = _check_names(names)

    def observe(state: SpinLatticeState, ff: ForceField) -> dict:
        vals = {}
        if "energy" in names:
            vals["energy"] = ff.energy
        if "kinetic" in names:
            vals["kinetic"] = kinetic_energy(state, masses)
        if "magnetization" in names:
            mag = magnetic[jnp.maximum(state.types, 0)]
            vals["magnetization"] = magnetization(state.spin, mask=mag)
        if "charge" in names or "skyrmion_count" in names:
            q = topological_charge(state.pos, state.spin, state.box,
                                   grid=diag_grid)
            if "charge" in names:
                vals["charge"] = q
            if "skyrmion_count" in names:
                vals["skyrmion_count"] = skyrmion_count(q)
        if "pitch" in names:
            vals["pitch"] = helix_pitch(state.pos, state.spin, state.box,
                                        axis=pitch_axis, n_bins=pitch_bins)
        return {k: vals[k] for k in names}

    def scoped(state, ff):
        with phase("observe"):
            return observe(state, ff)

    return scoped


def make_domain_observe(names, masses, magnetic, diag_grid, pitch_axis,
                        pitch_bins, spatial_axes) -> Callable:
    """Observable pipeline over cell-blocked (CX, CY, CZ, K, ...) arrays.

    Per-device partial sums (masked over occupied slots) are ``psum``-
    reduced over the spatial mesh axes inside the compiled chunk, then
    finalized with the analysis accumulate/finalize splits.  ``ff.energy``
    is already globalized by the step's fused scalar reduction.
    """
    names = _check_names(names)

    def psum_axes(x):
        for name in spatial_axes:
            x = jax.lax.psum(x, name)
        return x

    def observe(state: SpinLatticeState, ff: ForceField) -> dict:
        occ = state.types >= 0
        tc = jnp.maximum(state.types, 0)
        vals = {}
        if "energy" in names:
            vals["energy"] = ff.energy
        if "kinetic" in names:
            vals["kinetic"] = psum_axes(0.5 * units.MVV2E * jnp.sum(
                jnp.where(occ[..., None],
                          masses[tc][..., None] * state.vel ** 2, 0.0)))
        if "magnetization" in names:
            mag = magnetic[tc] & occ
            msum = psum_axes(jnp.sum(
                jnp.where(mag[..., None], state.spin, 0.0),
                axis=tuple(range(state.spin.ndim - 1))))
            mcnt = psum_axes(jnp.sum(mag))
            vals["magnetization"] = msum / jnp.maximum(mcnt, 1)
        if ("charge" in names or "skyrmion_count" in names
                or "pitch" in names):
            posf = state.pos.reshape(-1, 3)
            spinf = state.spin.reshape(-1, 3)
            w = occ.reshape(-1)
        if "charge" in names or "skyrmion_count" in names:
            acc = psum_axes(accumulate_spin_grid(
                posf, spinf, state.box, grid=diag_grid, weight=w))
            q = charge_from_grid(acc, diag_grid)
            if "charge" in names:
                vals["charge"] = q
            if "skyrmion_count" in names:
                vals["skyrmion_count"] = skyrmion_count(q)
        if "pitch" in names:
            prof = psum_axes(accumulate_spin_profile(
                posf, spinf, state.box, axis=pitch_axis, n_bins=pitch_bins,
                weight=w))
            vals["pitch"] = pitch_from_profile(prof, state.box, pitch_axis)
        return {k: vals[k] for k in names}

    def scoped(state, ff):
        with phase("observe"):
            return observe(state, ff)

    return scoped


_OBS_TAIL_NDIM = {"magnetization": 1}


# ===========================================================================
# schedule arguments
# ===========================================================================

_UNSET = object()


def _is_schedule(x) -> bool:
    """Duck-typed Schedule check (works on traced pytree instances too;
    avoids importing repro.ensemble from repro.md)."""
    return (hasattr(x, "at") and hasattr(x, "times")
            and hasattr(x, "values"))


class _StepValues(NamedTuple):
    """Per-step schedule values, host-evaluated once per chunk.

    ``rows[i]`` is the (temperature / field) value of in-chunk step ``i``:
    shape (n,), (n, R), (n, 3) or (n, R, 3).  Schedules are evaluated on
    the HOST (:func:`_host_sched_rows`) rather than inside the compiled
    chunk because XLA:CPU's backend FMA-contracts the time/lerp arithmetic
    differently at different batch widths (R=1 vs R=2 vectorize
    differently), which breaks the serving layer's packed-vs-solo bitwise
    parity by 1 ulp.  Host numpy runs one ufunc at a time - nothing fuses,
    so every width computes identical bits.  The chunk only gathers
    ``rows[i]``, and the jit cache now keys on the (n, ...) row shape
    alone, not the schedule's knot count."""

    rows: jax.Array


def _host_lerp(times, values, t):
    """Numpy mirror of ``Schedule.at`` (clamped piecewise-linear)."""
    k = times.shape[0]
    hi = np.clip(np.searchsorted(times, t, side="right"), 1, k - 1)
    lo = hi - 1
    w = np.clip((t - times[lo]) / np.maximum(times[hi] - times[lo],
                                             np.float32(1e-30)),
                np.float32(0.0), np.float32(1.0))
    w = w.reshape(w.shape + (1,) * (values.ndim - 1))
    return values[lo] + w * (values[hi] - values[lo])


def _host_sched_rows(arg, t):
    """Evaluate a (Slot)Schedule at host times ``t`` in pure numpy f32.

    ``t`` is (n,) for a shared schedule (2-d ``times`` means a per-slot
    SlotSchedules stack and ``t`` is the (n, R) per-slot clock matrix).
    Separate numpy ufuncs per op: bitwise width-independent, unlike the
    same arithmetic fused inside a jitted chunk (see :class:`_StepValues`).
    """
    times = np.asarray(arg.times, np.float32)
    values = np.asarray(arg.values, np.float32)
    t = np.asarray(t, np.float32)
    if times.ndim == 2:
        cols = [_host_lerp(times[r], values[r], t[:, r])
                for r in range(times.shape[0])]
        return np.stack(cols, axis=1)
    return _host_lerp(times, values, t)


def _arg_sig(x):
    """Hashable signature of a schedule argument for the chunk cache."""
    if x is None:
        return None
    if isinstance(x, _StepValues):
        return ("rows", tuple(x.rows.shape))
    if _is_schedule(x):
        return ("sched", tuple(x.values.shape))
    return ("const", tuple(jnp.shape(x)))


def _replicate_tree(tree, n):
    return jax.tree_util.tree_map(
        lambda x: jnp.repeat(x[None], n, axis=0), tree)


def _permute_atoms(state: SpinLatticeState, order) -> SpinLatticeState:
    return state._replace(pos=state.pos[order], vel=state.vel[order],
                          spin=state.spin[order], types=state.types[order])


def _refresh(nbh: Neighborhood, pos, box) -> Neighborhood:
    """The step's one position gather after the drift."""
    with phase("integrate.refresh"):
        return refresh_dr(nbh, pos, box)


def _within(scope, name: str):
    """Decorate a function to run inside ``scope(name)``: a device phase
    (:func:`~repro.telemetry.profiling.phase`) or a host span
    (:func:`~repro.telemetry.profiling.annotate`)."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# the compiled chunk of every plan runs under ``repro.loop``, so that its
# ops outside every step phase (the scan loop itself, the half-skin test,
# the schedule lookup, the health signals) carry a scope too; the phases
# nest beneath it and stay innermost
_loop_scope = _within(phase, "loop")


@jax.jit
def _flat_observation(c: FusedCarry):
    """A cell-ordered flat carry in original atom order, as one program:
    (state, forces, table)."""
    with phase("sync"):
        inv = jnp.argsort(c.perm)
        ff = ForceField(energy=c.ff.energy, force=c.ff.force[inv],
                        field=c.ff.field[inv])
        table = NeighborTable(idx=c.perm[c.table.idx[inv]],
                              mask=c.table.mask[inv], r0=c.table.r0[inv],
                              cutoff=c.table.cutoff)
        return _permute_atoms(c.state, inv), ff, table


# vmap axis spec for a replica-shared Neighborhood: table-static blocks are
# unbatched (one copy for all replicas), dr is replica-batched
_NBH_AXES = Neighborhood(idx=None, mask=None, tj=None, dr=0)


def _scan_chunk(body, carry, key, n: int, emit, final_obs,
                slot_keys: bool = False):
    """The shared scan driver of every plan's chunk.

    ``body(carry, xs)`` consumes xs = (step key, in-chunk index[, emit
    flag]).  With ``emit`` (static in-chunk offsets) the per-step ys are
    gathered to the emitted rows; otherwise ``final_obs(carry)`` runs once
    after the scan.  Returns (carry, observable rows).

    ``slot_keys=True`` (the replica plan's ``per_slot`` mode): ``key`` is a
    stacked (R, 2) array of independent per-slot streams, split per step
    into (n, R, 2) rows - slot ``i`` consumes exactly the key sequence a
    solo run seeded with its key would, which is what makes a packed slot
    bitwise-reproducible against a solo run of the same job.
    """
    if slot_keys:
        keys = jax.vmap(lambda kk: jax.random.split(kk, n),
                        out_axes=1)(key)
    else:
        keys = jax.random.split(key, n)
    ivec = jnp.arange(n, dtype=jnp.float32)
    if emit is None:
        carry, _ = jax.lax.scan(body, carry, (keys, ivec))
        return carry, final_obs(carry)
    flags = np.zeros(n, bool)
    flags[list(emit)] = True
    carry, ys = jax.lax.scan(body, carry, (keys, ivec, jnp.asarray(flags)))
    sel = np.asarray(emit, np.int32)
    return carry, jax.tree_util.tree_map(lambda y: y[sel], ys)


# ===========================================================================
# the engine
# ===========================================================================

@dataclasses.dataclass
class Engine:
    """One schedule-driven chunk driver for every plan (see module doc).

    ``state`` is the flat (N, ...) input state - or an (R, N, ...) batch on
    the ``Replicated`` plan (a flat state is tiled automatically).
    ``temperature`` / ``field`` set the engine-level schedule axis; both
    can be overridden per :meth:`run`.
    """

    potential: Any
    cfg: IntegratorConfig
    state: SpinLatticeState
    masses: jax.Array                  # (n_types,)
    magnetic: jax.Array                # (n_types,) bool
    cutoff: float
    plan: Any = None                   # None | "single"|"replica"|"domain"
                                       # | plan object (repro.parallel.plan)
    temperature: Any = None            # None | scalar/(R,) | Schedule
    field: Any = None                  # None | (3,)/(R,3) | Schedule
    observables: tuple = ("energy", "kinetic", "magnetization", "charge")
    obs_every: int | None = None       # None -> emit at chunk boundaries;
                                       # k -> in-scan emit every k steps
    per_slot: bool = False             # Replicated plan only: treat each
                                       # replica slot as an INDEPENDENT job
                                       # (own RNG stream, own clock, own
                                       # schedule row) - the serving
                                       # layer's packing mode (repro.serve)
    capacity: int = 64                 # per-atom neighbor capacity M
    skin: float = 0.5
    use_cell_list: bool = False        # flat-plan table construction
    cell_capacity: int = 24            # flat-plan cell-list capacity
    diag_grid: tuple = (32, 32)
    pitch_axis: int = 0
    pitch_bins: int = 64
    table: NeighborTable | None = None
    trace: EngineTrace | None = None

    # ------------------------------------------------------------------
    def __post_init__(self):
        self._halo = HaloTrace()    # run-scoped halo ledger (this engine)
        self._last_ckpt = None      # newest checkpoint written by save()
        self.ckpt_pin = None        # step save() must never GC (the
                                    # supervisor's rollback target)
        self.ckpt_step_offset = 0   # added to _step_now() for checkpoint
                                    # step tags: a per_slot bucket's slot-0
                                    # clock resets on backfill, so the
                                    # serving packer rebases saves onto its
                                    # monotonic bucket-global clock (the
                                    # journal's recovery refs depend on
                                    # step tags never going backwards)
        self._fault_injector = None  # resilience hook: (engine, carry,
                                     # n) -> carry at each chunk boundary
        self.evict_slot_hook = None  # serving hook: (HealthError) -> info
                                     # dict; the supervisor calls it to
                                     # evict one poisoned per-slot job
                                     # instead of degrading the whole batch
        self.run_tags = {}           # extra run_start header fields (the
                                     # serving layer tags segments with
                                     # their bucket id for accounting)
        self._counts = RunMetrics(counters=dict.fromkeys(
            ("chunks", "steps", "restarts", "restart_builds",
             "force_calls"), 0))   # counters() adds the carry's rebuilds
        self.plan = as_plan(self.plan)
        self.observables = _check_names(self.observables)
        if self.obs_every is not None and self.obs_every < 1:
            raise ValueError("obs_every must be >= 1")
        if self.per_slot and not isinstance(self.plan, Replicated):
            raise ValueError("per_slot=True requires the Replicated plan")
        if isinstance(self.plan, SingleDevice):
            if not hasattr(self.potential, "compute"):
                raise ValueError("the flat engine plan requires a potential "
                                 "with the gather-once .compute() surface")
            self._setup_flat()
        elif isinstance(self.plan, Replicated):
            if not hasattr(self.potential, "compute"):
                raise ValueError("the replica plan requires a potential "
                                 "with the gather-once .compute() surface")
            if self.state.pos.ndim == 2:
                self.state = _replicate_tree(self.state, self.plan.replicas)
            if self.state.pos.shape[0] != self.plan.replicas:
                raise ValueError(
                    f"state batch {self.state.pos.shape[0]} != plan "
                    f"replicas {self.plan.replicas}")
            self._setup_replica()
            if self.plan.devices is not None:
                self.shard_replicas(self.plan.devices)
        elif isinstance(self.plan, Sharded):
            self._setup_domain()
        else:
            raise TypeError(f"unknown plan {self.plan!r}")

    # ------------------------------------------------------------------
    @property
    def replicas(self) -> int:
        return self.plan.replicas

    @property
    def n_replicas(self) -> int:
        return max(self.plan.replicas, 1)

    @property
    def dt(self) -> float:
        return self.cfg.dt

    @property
    def n_rebuilds(self) -> int:
        return int(self._carry.n_rebuilds)

    def counters(self) -> dict:
        """The program's own counts since construction; a window's are the
        difference of two reads.

        ``chunks`` and ``steps`` run; ``restarts``, carries (re)built from
        ``state`` (construction included); ``restart_builds``, the table
        builds those ran; ``rebuilds``, the in-scan builds as the carry
        counts them; ``force_calls``, force evaluations: each step's static
        count (:func:`~repro.md.integrator.force_calls_per_step`), one per
        in-scan build, one per restart, one per ``write_slots`` (a batched
        evaluation of the replica plan counts once).  Reads the carry's
        build count: one device-to-host transfer per call, none per chunk.
        """
        out = {k: int(v) for k, v in self._counts.counters.items()}
        out["rebuilds"] = self.n_rebuilds
        out["force_calls"] += out["rebuilds"]
        return out

    def _count_restart(self, built: bool) -> None:
        """A carry (re)built from ``state``: one force evaluation, and one
        table build unless ``built`` is False."""
        self._counts.inc("restarts")
        self._counts.inc("restart_builds", int(built))
        self._counts.inc("force_calls")

    @property
    def energy(self):
        if isinstance(self.plan, Replicated):
            return self._carry.ffs.energy
        e = self._carry.ff.energy
        return np.asarray(e) if self.replicas else float(e)

    @property
    def halo_ledger(self) -> HaloTrace:
        """This engine's run-scoped halo exchange ledger (empty on
        non-sharded plans: they move no halos)."""
        return self._halo

    # ------------------------------------------------------------------
    # schedule arguments
    # ------------------------------------------------------------------
    def _norm_arg(self, x, vec: bool):
        """None / Schedule pass through; constants become arrays (f32
        temperatures, replica-broadcast on replica plans)."""
        if x is None or _is_schedule(x):
            return x
        if vec:
            v = jnp.asarray(x)
            if self.replicas:
                v = jnp.broadcast_to(v, (self.replicas, 3))
        else:
            v = jnp.asarray(x, jnp.float32)
            if self.replicas:
                v = jnp.broadcast_to(v, (self.replicas,))
        return v

    def _value_now(self, arg, vec: bool):
        """Concrete schedule-argument value at the carry's current time
        (host-side; used for carry (re)initialization).  In ``per_slot``
        mode each slot reads its own clock (its own ``states.step`` row),
        so backfilled jobs that started at different global steps get
        their own schedule value."""
        if arg is None:
            return None
        if _is_schedule(arg):
            if self.per_slot:
                c = getattr(self, "_carry", None)
                steps = (c.states.step if c is not None else
                         jnp.asarray(self.state.step).reshape(-1))
                v = arg.at(steps.astype(jnp.float32) * self.cfg.dt)
            else:
                v = arg.at(jnp.asarray(self._step_now(), jnp.float32)
                           * self.cfg.dt)
            if self.replicas:
                v = jnp.broadcast_to(
                    v, (self.replicas, 3) if vec else (self.replicas,))
            return v
        return arg

    def _chunk_arg(self, arg, carry, n: int):
        """Lower a schedule argument to this chunk's :class:`_StepValues`.

        Called once per chunk dispatch with the live carry: builds the
        chunk's step-time vector ``t0 + arange(n)*dt`` on the host (per
        slot in ``per_slot`` mode, where every slot keeps its own clock)
        and evaluates the schedule there in pure numpy.  Keeping this
        arithmetic out of the compiled chunk is what makes schedule-driven
        runs bitwise width-independent - XLA's backend FMA-contracts the
        fused time/lerp chain differently at different replica counts (see
        :class:`_StepValues`).  None and constants pass through untouched.
        """
        if arg is None or isinstance(arg, _StepValues) \
                or not _is_schedule(arg):
            return arg
        dt = np.float32(self.cfg.dt)
        ivec = np.arange(n, dtype=np.float32) * dt
        if isinstance(self.plan, Replicated):
            steps = np.asarray(carry.states.step)
            t0 = (steps.astype(np.float32) * dt if self.per_slot
                  else np.float32(steps[0]) * dt)
        elif isinstance(self.plan, Sharded):
            t0 = np.float32(self._step_now()) * dt
        else:
            t0 = np.float32(np.asarray(carry.state.step)) * dt
        t = (t0[None, :] + ivec[:, None] if getattr(t0, "ndim", 0)
             else t0 + ivec)
        return _StepValues(rows=jnp.asarray(_host_sched_rows(arg, t)))

    def _make_eval_args(self, r_local: int):
        """Per-step schedule-argument lookup: (t0, i, targ, farg) ->
        (temperature, field) with replica broadcasting.  Schedule args
        arrive as :class:`_StepValues` (host-evaluated per chunk by
        :meth:`_chunk_arg` - see there for why evaluation cannot live
        inside the compiled chunk) and are gathered at the in-chunk step
        index; constants pass through.  The in-graph ``schedule.at``
        fallback serves direct ``chunk`` callers that skip the run loop."""
        dt = self.cfg.dt

        def eval_args(t0, i, targ, farg):

            def ev(a, vec):
                if a is None:
                    return None
                if isinstance(a, _StepValues):
                    v = a.rows[jnp.asarray(i, jnp.int32)]
                elif _is_schedule(a):
                    v = a.at(t0 + i * dt)
                else:
                    v = a
                if r_local:
                    v = jnp.broadcast_to(jnp.asarray(v),
                                         (r_local, 3) if vec else (r_local,))
                return v

            return ev(targ, False), ev(farg, True)

        return eval_args

    def _emit_for(self, n: int):
        """Static in-chunk emission offsets, or None for chunk-boundary."""
        if self.obs_every is None:
            return None
        return tuple(i for i in range(n) if (i + 1) % self.obs_every == 0)

    def _step_now(self) -> int:
        c = getattr(self, "_carry", None)
        if c is None:  # during construction: the input state's clock
            return int(np.asarray(self.state.step).reshape(-1)[0])
        if isinstance(self.plan, Replicated):
            return int(c.states.step[0])
        return int(c.state.step)

    def ckpt_step(self) -> int:
        """The step tag :meth:`save` would use right now (clock plus the
        serving packer's rebase offset) - what ``ckpt_pin`` and recovery
        refs must be expressed in."""
        return self._step_now() + int(self.ckpt_step_offset)

    # ==================================================================
    # flat single-device plan
    # ==================================================================
    def _setup_flat(self, farg=_UNSET):
        """Compile-once setup: everything geometry-static is resolved here.

        ``farg`` carries a run-level field override into the initial force
        evaluation (geometry changes mid-run re-enter here); by default
        the engine-level ``self.field`` applies (construction).
        """
        build, n_cells, use_cell = make_table_builder(
            self.state.box, self.cutoff, self.capacity, self.cell_capacity,
            self.skin, self.use_cell_list)
        self._reorder = (self.plan.cell_order
                         if self.plan.cell_order is not None else use_cell)

        potential = self.potential
        masses, magnetic, skin = self.masses, self.magnetic, self.skin
        box0, reorder = self.state.box, self._reorder
        dt = self.cfg.dt

        def compute_ff(nbh, spin, types, field):
            with phase("force"):
                return ForceField(*potential.compute(nbh, spin, types,
                                                     field))

        def rebuild(state, perm, field):
            """In-graph: (re)order atoms, rebuild table, gather, evaluate."""
            with phase("rebuild"):
                if reorder:
                    with phase("rebuild.order"):
                        order = cell_order(state.pos, state.box, n_cells)
                        state = _permute_atoms(state, order)
                        perm = perm[order]
                table = build(state.pos, state.box)
                with phase("rebuild.gather"):
                    nbh = gather_blocks(state.pos, state.types, table,
                                        state.box)
            with phase("force.after_build"):
                ff = compute_ff(nbh, state.spin, state.types, field)
            return state, ff, table, nbh, perm

        step = make_fused_step(
            gather=lambda pos, nbh: _refresh(nbh, pos, box0),
            compute=compute_ff, cfg=self.cfg, masses=masses,
            magnetic=magnetic)

        observe = make_flat_observe(self.observables, masses, magnetic,
                                    self.diag_grid, self.pitch_axis,
                                    self.pitch_bins)
        eval_args = self._make_eval_args(0)

        def health_of(c: FusedCarry, etot0):
            st, ff = c.state, c.ff
            mag = magnetic[jnp.maximum(st.types, 0)]
            return {
                "e_drift": (ff.energy + kinetic_energy(st, masses)) - etot0,
                "spin_dev": spin_norm_dev(st.spin, mag),
                "nonfinite": nonfinite_count(st.pos, ff.force, st.spin),
                "nbr_occ": occupancy_fraction(c.table.mask),
            }

        # schedule arguments are runtime pytrees (their structure - absent /
        # constant / knots - keys the jit cache; their VALUES never retrace)
        @partial(jax.jit, static_argnames=("n", "emit"))
        @_loop_scope
        def chunk(carry: FusedCarry, key, targ, farg, n: int, emit):
            t0 = carry.state.step.astype(jnp.float32) * dt
            etot0 = carry.ff.energy + kinetic_energy(carry.state, masses)
            obs_zero = (None if emit is None else jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(observe, carry.state, carry.ff)))

            def body(c, xs):
                (k, i, flag) = xs if emit is not None else (*xs, None)
                temp, field = eval_args(t0, i, targ, farg)

                def do_rebuild(c):
                    st, ff, tab, nbh, perm = rebuild(c.state, c.perm, field)
                    return FusedCarry(st, ff, tab, nbh, perm,
                                      c.n_rebuilds + 1)
                trip = needs_rebuild(c.table, c.state.pos, box0, skin)
                c = jax.lax.cond(trip, do_rebuild, lambda c: c, c)
                with phase("integrate"):
                    st, ff, nbh = step(c.state, c.ff, c.nbh, k, temp, field)
                c = FusedCarry(st, ff, c.table, nbh, c.perm, c.n_rebuilds)
                if emit is None:
                    return c, None
                ys = jax.lax.cond(flag, lambda: observe(st, ff),
                                  lambda: obs_zero)
                return c, ys

            carry, obs = _scan_chunk(body, carry, key, n, emit,
                                     lambda c: observe(c.state, c.ff))
            return carry, obs, health_of(carry, etot0)

        self._chunk_fn = chunk
        # the (re)start evaluation runs as one compiled program: dispatched
        # op by op, every primitive of the rebuild and force call would be
        # compiled on its own on an accelerator
        self._compute_ff = jax.jit(compute_ff)
        self._rebuild = jax.jit(rebuild)
        if farg is _UNSET:
            farg = self._norm_arg(self.field, vec=True)
        self._init_carry(table=self.table,
                         field_now=self._value_now(farg, vec=True))

    def _restart_if_swapped(self, farg):
        """Honor a caller-swapped ``engine.state`` (legacy-path parity).

        A swap with the same box restarts the carry; a changed box is a new
        geometry, so the compile-once statics (grid dims, builder, closures)
        are re-derived (one retrace, exactly as at construction).
        """
        if self.state is self._obs_state:
            return
        with annotate("repro.restart"):
            if np.array_equal(np.asarray(self.state.box),
                              np.asarray(self._carry.state.box)):
                self._init_carry(field_now=self._value_now(farg, vec=True))
            else:
                self.table = None
                self._setup_flat(farg)

    def _init_carry(self, table: NeighborTable | None = None,
                    field_now=None):
        """(Re)build the hot carry from ``self.state`` at the given field."""
        n = self.state.pos.shape[0]
        perm0 = jnp.arange(n, dtype=jnp.int32)
        # in-scan rebuild count is cumulative across carry restarts
        count0 = (self._carry.n_rebuilds if getattr(self, "_carry", None)
                  is not None else jnp.asarray(0, jnp.int32))
        if table is not None:
            # honor a caller-provided table (assumed to match the row order)
            nbh = gather_blocks(self.state.pos, self.state.types, table,
                                self.state.box)
            ff = self._compute_ff(nbh, self.state.spin, self.state.types,
                                  field_now)
            self._carry = FusedCarry(self.state, ff, table, nbh,
                                     perm0, count0)
        else:
            st, ff, tab, nbh, perm = self._rebuild(self.state, perm0,
                                                   field_now)
            self._carry = FusedCarry(st, ff, tab, nbh, perm, count0)
        self._count_restart(built=table is None)
        self._sync_observation()

    def _sync_flat(self):
        """Map the hot (cell-ordered) carry back to original atom order.

        Everything observable - ``state``, forces, and the ``table`` - comes
        back in the ORIGINAL atom order, so the legacy evaluation surface
        (``potential.energy_forces_field(..., table, ...)``) stays
        consistent with ``engine.state``.
        """
        if self._reorder:
            self.state, self._ff, self.table = _flat_observation(
                self._carry)
        else:   # rows are in atom order: the identity permutation
            c = self._carry
            self.state, self._ff, self.table = c.state, c.ff, c.table
        self._obs_state = self.state

    # ==================================================================
    # vmapped-replica plan
    # ==================================================================
    def _setup_replica(self):
        """Shared-table replica batch: one compiled chunk for every replica."""
        r = self.plan.replicas
        types0 = self.state.types[0]
        box0 = self.state.box[0]
        potential = self.potential
        skin, dt = self.skin, self.cfg.dt
        masses, magnetic = self.masses, self.magnetic
        per_slot = self.per_slot

        build, _, _ = make_table_builder(box0, self.cutoff, self.capacity,
                                         self.cell_capacity, skin,
                                         self.use_cell_list)

        def compute_ff(nbh, spin, types, field=None):
            with phase("force"):
                return ForceField(*potential.compute(nbh, spin, types,
                                                     field))

        def reference_pos(states):
            """Replica-mean positions (min-imaged around replica 0) - the
            crystalline reference the shared table is built from."""
            p0 = states.pos[0]
            d = states.pos - p0[None]
            d = d - box0 * jnp.round(d / box0)
            return p0 + jnp.mean(d, axis=0)

        def shared_blocks(table, pos_r):
            """Table-static blocks (one copy) + per-replica dr gather."""
            base = Neighborhood(idx=table.idx, mask=table.mask,
                                tj=types0[table.idx],
                                dr=jnp.zeros(table.idx.shape + (3,),
                                             pos_r.dtype))
            drs = jax.vmap(lambda p: refresh_dr(base, p, box0).dr)(pos_r)
            return base._replace(dr=drs)

        def build_shared(states, field_r):
            """Rebuild the shared table + per-replica dr / forces."""
            with phase("rebuild"):
                table = build(reference_pos(states), box0)
                with phase("rebuild.gather"):
                    nbh = shared_blocks(table, states.pos)
            f_ax = None if field_r is None else 0
            with phase("force.after_build"):
                ffs = jax.vmap(
                    lambda d, s, f: compute_ff(nbh._replace(dr=d), s,
                                               types0, f),
                    in_axes=(0, 0, f_ax))(nbh.dr, states.spin, field_r)
            return table, nbh, ffs

        step = make_fused_step(
            gather=lambda pos, nbh: _refresh(nbh, pos, box0),
            compute=compute_ff, cfg=self.cfg, masses=masses,
            magnetic=magnetic)

        self._vcompute = jax.jit(jax.vmap(
            lambda d, s, f, nbh: compute_ff(nbh._replace(dr=d), s, types0, f),
            in_axes=(0, 0, 0, _NBH_AXES)))

        observe = make_flat_observe(self.observables, masses, magnetic,
                                    self.diag_grid, self.pitch_axis,
                                    self.pitch_bins)
        vobserve = jax.vmap(observe)
        eval_args = self._make_eval_args(r)

        vkin = jax.vmap(lambda s: kinetic_energy(s, masses))

        def health_of(c: ReplicaCarry, etot0):
            st, ffs = c.states, c.ffs
            drift = (ffs.energy + vkin(st)) - etot0     # (R,)
            mag = magnetic[jnp.maximum(st.types, 0)]    # (R, N)
            h = {
                # the max-magnitude replica's signed drift
                "e_drift": drift[jnp.argmax(jnp.abs(drift))],
                "spin_dev": spin_norm_dev(st.spin, mag),
                "nonfinite": nonfinite_count(st.pos, ffs.force, st.spin),
                "nbr_occ": occupancy_fraction(c.table.mask),
            }
            if per_slot:
                # per-slot attribution vectors: the health check gates on
                # the scalars above; these ride along in HealthError's
                # signals so the serving layer can pin a failure on one
                # slot (supervisor.attribute_slot)
                h["slot_nonfinite"] = jax.vmap(
                    lambda p, f, s: nonfinite_count(p, f, s))(
                        st.pos, ffs.force, st.spin)
                h["slot_e_drift"] = drift
                h["slot_spin_dev"] = jax.vmap(spin_norm_dev)(st.spin, mag)
            return h

        @partial(jax.jit, static_argnames=("n", "emit"))
        @_loop_scope
        def chunk(carry: ReplicaCarry, key, targ, farg, n: int, emit):
            # per_slot: every slot keeps its own clock (R,) so backfilled
            # jobs evaluate their schedules at their own elapsed time
            t0 = (carry.states.step.astype(jnp.float32) * dt if per_slot
                  else carry.states.step[0].astype(jnp.float32) * dt)
            etot0 = carry.ffs.energy + vkin(carry.states)
            obs_zero = (None if emit is None else jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(vobserve, carry.states, carry.ffs)))

            def body(c, xs):
                (k, i, flag) = xs if emit is not None else (*xs, None)
                temp, field = eval_args(t0, i, targ, farg)
                t_ax = None if temp is None else 0
                f_ax = None if field is None else 0
                vstep = jax.vmap(step, in_axes=(0, 0, _NBH_AXES, 0, t_ax,
                                                f_ax),
                                 out_axes=(0, 0, _NBH_AXES))

                def do_rebuild(c):
                    table2, nbh2, ffs2 = build_shared(c.states, field)
                    return ReplicaCarry(c.states, ffs2, table2, nbh2,
                                        c.n_rebuilds + 1)
                trip = jnp.any(jax.vmap(
                    lambda p: needs_rebuild(c.table, p, box0, skin))(
                        c.states.pos))
                c = jax.lax.cond(trip, do_rebuild, lambda c: c, c)
                # per_slot: k is already a (R, 2) stack of independent
                # per-slot keys (see _scan_chunk slot_keys) - a job's
                # stream must not depend on which slot it landed in
                keys = k if per_slot else jax.vmap(
                    lambda i: jax.random.fold_in(k, i))(jnp.arange(r))
                with phase("integrate"):
                    states, ffs, nbh = vstep(c.states, c.ffs, c.nbh, keys,
                                             temp, field)
                c = ReplicaCarry(states, ffs, c.table, nbh, c.n_rebuilds)
                if emit is None:
                    return c, None
                ys = jax.lax.cond(flag, lambda: vobserve(states, ffs),
                                  lambda: obs_zero)
                return c, ys

            carry, obs = _scan_chunk(body, carry, key, n, emit,
                                     lambda c: vobserve(c.states, c.ffs),
                                     slot_keys=per_slot)
            return carry, obs, health_of(carry, etot0)

        self._chunk_fn = chunk
        self._build_shared = build_shared
        self._shared_blocks = shared_blocks
        self._box0, self._types0 = box0, types0

        # initial shared table + blocks + forces at the engine field's
        # current value.  Forces are seeded through the same jitted row
        # path write_slots / resync use (zeros stand in for None - same
        # numbers as skipping the Zeeman term): the eager op-by-op vmap
        # FMA-contracts differently from the fused program, and a 1-ulp
        # seed difference would break seat-vs-backfill bitwise parity.
        f0 = self._value_now(self._norm_arg(self.field, vec=True), vec=True)
        if self.table is not None:
            nbh = shared_blocks(self.table, self.state.pos)
            table = self.table
        else:
            with phase("rebuild"):
                table = build(reference_pos(self.state), box0)
                nbh = shared_blocks(table, self.state.pos)
        if f0 is None:
            f0 = jnp.zeros((self.plan.replicas, 3), self.state.pos.dtype)
        ffs = self._vcompute(nbh.dr, self.state.spin,
                             self._replica_put(f0), nbh)
        self._carry = ReplicaCarry(self.state, ffs, table, nbh,
                                   jnp.asarray(0, jnp.int32))
        self._count_restart(built=self.table is None)
        self._sync_observation()

    def _replica_restart_if_swapped(self, farg):
        """Resync only when the caller swapped/nudged ``engine.state``
        (identity check, like the flat plan's restart) - an untouched
        carry must flow through unchanged so checkpoint resume stays
        bitwise."""
        if self.state is not self._obs_state:
            with annotate("repro.restart"):
                self._replica_resync(farg)

    def _replica_resync(self, farg):
        """Explicit resync: honor caller-nudged states (sub-half-skin
        moves never trip the in-scan rebuild) and re-evaluate forces at
        the schedule's current field (a previous run / an exchange may
        have left them at another field or permutation)."""
        c = self._carry._replace(states=self.state)
        nbh = c.nbh._replace(dr=jax.vmap(
            lambda p: refresh_dr(c.nbh, p, self._box0).dr)(c.states.pos))
        f = self._value_now(farg, vec=True)
        if f is None:
            f = jnp.zeros((self.plan.replicas, 3), c.states.pos.dtype)
        ffs = self._vcompute(nbh.dr, c.states.spin, self._replica_put(f),
                             nbh)
        self._carry = c._replace(nbh=nbh, ffs=ffs)
        self._count_restart(built=False)
        self._obs_state = self.state

    def shard_replicas(self, devices=None) -> "Engine":
        """Shard the replica axis across devices (no-op on one device).

        Replica-batched leaves (states, forces, the per-replica ``dr``
        block) split over a ``("replica",)`` mesh; the SHARED leaves (the
        table and its static blocks) are replicated onto the same mesh so
        every input of the compiled chunk lives on one device set.
        """
        devices = list(devices if devices is not None else jax.devices())
        if len(devices) <= 1:
            return self
        r = self.plan.replicas
        if r % len(devices) != 0:
            raise ValueError(f"{r} replicas not divisible by "
                             f"{len(devices)} devices")
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("replica",))
        put = lambda spec: lambda tree: jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, spec)), tree)
        batched, shared = put(P("replica")), put(P())
        c = self._carry
        self._carry = ReplicaCarry(
            states=batched(c.states), ffs=batched(c.ffs),
            table=shared(c.table),
            nbh=shared(c.nbh)._replace(dr=batched(c.nbh.dr)),
            n_rebuilds=shared(c.n_rebuilds))
        self._replica_mesh = mesh
        self._sync_observation()
        return self

    def _replica_put(self, tree):
        """Replicate small chunk inputs (keys, schedule args) onto the
        replica mesh - every argument of one jitted chunk must live on one
        device set.  No-op unless :meth:`shard_replicas` is active."""
        mesh = getattr(self, "_replica_mesh", None)
        if mesh is None or tree is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(mesh, P())
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, repl), tree)

    def _sync_replica(self):
        c = self._carry
        self.state = c.states
        self._ff = c.ffs
        self.table = c.table
        self._obs_state = self.state

    def write_slots(self, slots, states, *, field=_UNSET):
        """Surgically (re)write replica slots with new job states
        (Replicated plan; the serving layer's backfill hook).

        ``slots`` is a sequence of slot indices and ``states`` the
        matching replica-stacked ``(k, N, ...)`` :class:`SpinLatticeState`
        (see :func:`repro.ensemble.replica.stack_states`).  Only the named
        slots change: their rows are scattered into the carry, their
        ``dr`` blocks refreshed against the EXISTING shared table (no
        rebuild - same-bucket jobs share one crystalline reference), and
        their forces recomputed at ``field`` evaluated on each written
        slot's own clock (``states.step``).  Untouched slots keep their
        exact bits, so batch-mates' trajectories are unaffected by a
        backfill between chunks.

        ``field`` defaults to the engine-level field; the serving packer
        passes its current per-slot stack
        (:class:`repro.ensemble.protocol.SlotSchedules`) so a freshly
        seated job sees its own protocol row.
        """
        if not isinstance(self.plan, Replicated):
            raise ValueError("write_slots requires the Replicated plan")
        idx = jnp.asarray(list(slots), jnp.int32)
        if idx.ndim != 1 or idx.shape[0] == 0:
            raise ValueError("slots must be a non-empty index sequence")
        c = self._carry
        new_states = jax.tree_util.tree_map(
            lambda cur, row: cur.at[idx].set(row.astype(cur.dtype)),
            c.states, states)
        dr_rows = jax.vmap(
            lambda p: refresh_dr(c.nbh, p, self._box0).dr)(
                new_states.pos[idx])
        nbh = c.nbh._replace(dr=c.nbh.dr.at[idx].set(dr_rows))
        farg = self._norm_arg(self.field if field is _UNSET else field,
                              vec=True)
        if farg is None:
            # None evaluates without the Zeeman term - same numbers as a
            # zero field (the batched force path needs an array)
            f_rows = jnp.zeros((idx.shape[0], 3), new_states.pos.dtype)
        elif _is_schedule(farg):
            t_rows = (new_states.step[idx].astype(jnp.float32)
                      * self.cfg.dt)
            if getattr(farg.times, "ndim", 1) == 2:   # per-slot stack
                f_rows = type(farg)(times=farg.times[idx],
                                    values=farg.values[idx]).at(t_rows)
            else:
                f_rows = farg.at(t_rows)
            f_rows = jnp.broadcast_to(jnp.asarray(f_rows),
                                      (idx.shape[0], 3))
        else:
            f_rows = jnp.asarray(farg)[idx]
        ffs_rows = self._vcompute(dr_rows, new_states.spin[idx],
                                  f_rows, c.nbh._replace(dr=dr_rows))
        ffs = jax.tree_util.tree_map(
            lambda cur, row: cur.at[idx].set(row), c.ffs, ffs_rows)
        self._carry = c._replace(states=new_states, nbh=nbh, ffs=ffs)
        self._counts.inc("force_calls")
        self._sync_observation()

    # ==================================================================
    # sharded domain plan
    # ==================================================================
    def _setup_domain(self):
        from repro.parallel.domain import pack_domain

        pot = self.potential
        self._use_kernel = bool(getattr(pot, "use_kernel", False))
        if not (hasattr(pot, "pair_energies") or self._use_kernel):
            raise ValueError("the sharded plan needs a potential exposing "
                             "the pair_energies/site_moments surface (or "
                             "the fused NEP kernel, use_kernel=True)")
        if self._use_kernel and self.cfg.midpoint:
            raise ValueError("the kernel-routed sharded evaluator computes "
                             "forces via the q_Fp adjoint exchange and does "
                             "not support self-consistent midpoint configs")

        rp = self.plan.resolve(self.state.box, self.state.pos, self.cutoff,
                               self.skin,
                               self.state.pos.dtype == jnp.float32)
        self._rplan = rp
        rp.register_halo_sizes(self._halo)
        self._n_atoms = n = self.state.pos.shape[0]
        dstate, extras = pack_domain(
            rp.dspec, self.state.pos, self.state.vel, self.state.spin,
            self.state.types, extras={"aid": np.arange(n, dtype=np.int32)})
        self._chunk_cache = {}
        self._build_domain_chunk()
        self._init_domain_carry(dstate, extras["aid"])

    def _vm(self, f, **kw):
        """vmap ``f`` over the local replica axis when replicas are on."""
        return jax.vmap(f, **kw) if self.replicas else f

    def _build_domain_chunk(self):
        from repro.parallel.domain import (DomainNbh, build_local_table,
                                           make_domain_evaluator,
                                           make_domain_kernel_evaluator,
                                           migrate_cells)
        from repro.parallel.sharding import shard_map_compat
        from jax.sharding import PartitionSpec as P

        rp = self._rplan
        dspec, local, mesh = rp.dspec, rp.local_shape, rp.mesh
        m_cap, skin = self.capacity, self.skin
        masses, magnetic, cfg = self.masses, self.magnetic, self.cfg
        axes = rp.spatial_axes
        dt = cfg.dt
        # midpoint iterations re-evaluate at updated spins, so they need a
        # fresh spin halo per evaluation; otherwise the step is the
        # classical two-message form: one fused (pos, spin) exchange per
        # drift, one fused (force, torque) adjoint fold per evaluation
        self._spin_in_gather = not cfg.midpoint
        ag = rp.allgather
        if self._use_kernel:
            refresh, compute = make_domain_kernel_evaluator(
                self.potential, dspec, local, barrier=not self.replicas,
                allgather=ag)
        else:
            refresh, compute = make_domain_evaluator(
                self.potential, dspec, local, barrier=not self.replicas,
                spin_in_gather=self._spin_in_gather, allgather=ag)
        rep = self.replicas
        vm = self._vm
        r_loc = rp.local_replicas()

        def compute_ff(nbh, spin, types, field):
            with phase("force"):
                return ForceField(*compute(nbh, spin, types, field))

        def psum_axes(x):
            for name in axes:
                x = jax.lax.psum(x, name)
            return x

        def psum_all(x):
            return jax.lax.psum(x, mesh.axis_names)

        def pmax_all(x):
            for name in mesh.axis_names:
                x = jax.lax.pmax(x, name)
            return x

        def dev_index():
            """Linear device index folding every mesh axis (incl. replica)."""
            dev = jnp.asarray(0, jnp.int32)
            for name in mesh.axis_names:
                dev = dev * jax.lax.psum(1, name) + jax.lax.axis_index(name)
            return dev

        ndev = mesh.size

        def dev_counts(x):
            """Scatter a device-local int count into a replicated
            (n_devices,) vector - the per-device breakdown the overflow
            HealthError reports."""
            onehot = (jnp.arange(ndev, dtype=jnp.int32)
                      == dev_index()).astype(jnp.int32)
            return psum_all(onehot * x.astype(jnp.int32))

        self._dev_counts = dev_counts

        def trip_local(state, r0):
            box = state.box.astype(state.pos.dtype)
            d = state.pos - r0
            d = d - box * jnp.round(d / box)
            occ = state.types >= 0
            d2 = jnp.where(occ, jnp.sum(d * d, axis=-1), 0.0)
            return jnp.max(d2) > (skin * 0.5) ** 2

        sig = self._spin_in_gather

        def rebuild_one(state, aid, field):
            with phase("rebuild"):
                pos, vel, spin, types, aid, moved, dropped = migrate_cells(
                    dspec, local, state.pos, state.vel, state.spin,
                    state.types, aid, allgather=ag)
                idx, pmask, tj = build_local_table(dspec, local, m_cap, pos,
                                                   types, allgather=ag)
                blk = jnp.zeros(idx.shape + (3,), pos.dtype)
                nbh = DomainNbh(idx=idx, mask=pmask, tj=tj, dr=blk,
                                sj=blk if sig else
                                jnp.zeros((0,), pos.dtype))
                nbh = refresh(pos, nbh, spin if sig else None,
                              tag="rebuild-pos")
                state = state._replace(pos=pos, vel=vel, spin=spin,
                                       types=types)
            with phase("force.after_build"):
                ff = compute_ff(nbh, spin, types, field)
            return state, ff, nbh, aid, pos, moved, dropped

        step = make_fused_step(
            gather=(lambda pos, nbh, spin: refresh(pos, nbh, spin,
                                                   tag="drift-pos"))
            if sig else
            (lambda pos, nbh: refresh(pos, nbh, tag="drift-pos")),
            compute=compute_ff, cfg=cfg, masses=masses, magnetic=magnetic,
            atom_mask="from_types", spin_aware_gather=sig)

        # vmap axis spec for a replica-batched state: box and step are
        # shared across replicas (same crystal, lockstep time); the sj
        # placeholder of the per-evaluation-exchange mode is unbatched
        state_ax = SpinLatticeState(pos=0, vel=0, spin=0, types=0,
                                    box=None, step=None)
        nbh_ax = DomainNbh(idx=0, mask=0, tj=0, dr=0,
                           sj=0 if sig else None)

        def dev_key(key):
            """Per-device (and per-replica) independent RNG streams.

            The linear device index already folds in the replica mesh axis,
            so (device, local-replica) pairs are globally unique.
            """
            k = jax.random.fold_in(key, dev_index())
            if rep:
                return jax.vmap(lambda r: jax.random.fold_in(k, r))(
                    jnp.arange(r_loc))
            return k

        observe = make_domain_observe(self.observables, masses, magnetic,
                                      self.diag_grid, self.pitch_axis,
                                      self.pitch_bins, axes)
        eval_args = self._make_eval_args(r_loc)
        rep_in_mesh = rp.rep_in_mesh()
        replica_axis = rp.replica_axis

        def etot_of(c: DomainCarry):
            """Global total energy, per local replica ((r_loc,) or ())."""
            st = c.state
            occ = st.types >= 0
            m = masses[jnp.maximum(st.types, 0)]
            ke = jnp.where(occ[..., None], m[..., None] * st.vel ** 2, 0.0)
            ke = 0.5 * units.MVV2E * (
                jnp.sum(ke.reshape(r_loc, -1), axis=1) if rep
                else jnp.sum(ke))
            return c.ff.energy + psum_axes(ke)

        def health_of(c: DomainCarry, etot0):
            st, ff = c.state, c.ff
            occ = st.types >= 0
            mag = magnetic[jnp.maximum(st.types, 0)] & occ
            drift = etot_of(c) - etot0
            if rep:
                drift = drift[jnp.argmax(jnp.abs(drift))]
                if rep_in_mesh:
                    # signed max-magnitude across the replica mesh axis:
                    # mask losers to -inf, pmax recovers the winner's sign
                    a = jax.lax.pmax(jnp.abs(drift), replica_axis)
                    drift = jax.lax.pmax(
                        jnp.where(jnp.abs(drift) == a, drift, -jnp.inf),
                        replica_axis)
            k_cap = st.types.shape[-1]
            return {
                "e_drift": drift,
                "spin_dev": pmax_all(spin_norm_dev(st.spin, mag)),
                "nonfinite": psum_all(
                    nonfinite_count(st.pos, ff.force, st.spin)),
                "nbr_occ": pmax_all(occupancy_fraction(c.nbh.mask)),
                "cell_occ": pmax_all(
                    jnp.max(jnp.sum(occ.astype(jnp.int32), axis=-1))
                    / float(k_cap)),
            }

        @_loop_scope
        def local_chunk(carry: DomainCarry, key, targ, farg, n: int, emit):
            t0 = carry.state.step.astype(jnp.float32) * dt
            etot0 = etot_of(carry)
            vobserve = vm(observe, in_axes=(state_ax, 0))
            obs_zero = (None if emit is None else jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(vobserve, carry.state, carry.ff)))

            def body(c, xs):
                (k, i, flag) = xs if emit is not None else (*xs, None)
                temp, field = eval_args(t0, i, targ, farg)
                t_ax = 0 if temp is not None else None
                f_ax = 0 if field is not None else None
                vstep = vm(step, in_axes=(state_ax, 0, nbh_ax, 0, t_ax,
                                          f_ax),
                           out_axes=(state_ax, 0, nbh_ax))
                vrebuild = vm(rebuild_one, in_axes=(state_ax, 0, f_ax),
                              out_axes=(state_ax, 0, nbh_ax, 0, 0, 0, 0))
                vtrip = vm(trip_local, in_axes=(state_ax, 0))

                def do_rebuild(c):
                    st, ff, nbh, aid, r0, moved, dropped = vrebuild(
                        c.state, c.aid, field)
                    moved = jax.lax.psum(jnp.sum(moved),
                                         mesh.axis_names).astype(jnp.int32)
                    dropped = dev_counts(jnp.sum(dropped))
                    return DomainCarry(st, ff, nbh, aid, r0, c.trip,
                                       c.n_rebuilds + 1,
                                       c.n_migrated + moved,
                                       c.n_dropped + dropped)

                # ``trip`` was reduced at the end of the previous step
                # (positions final after its drift): no extra collective
                c = jax.lax.cond(c.trip, do_rebuild, lambda c: c, c)
                with phase("integrate"):
                    st, ff, nbh = vstep(c.state, c.ff, c.nbh, dev_key(k),
                                        temp, field)
                # ONE fused scalar reduction per step: the global energy
                # (device-local out of compute) + the next step's skin test
                trip_loc = vtrip(st, c.r0)
                trip_loc = jnp.any(trip_loc) if rep else trip_loc
                e_loc = jnp.atleast_1d(ff.energy)
                vec = jnp.concatenate(
                    [e_loc, trip_loc[None].astype(e_loc.dtype)])
                vec = psum_axes(vec)
                if rep and rp.rep_in_mesh():
                    trip = jax.lax.psum(vec[-1], rp.replica_axis) > 0
                else:
                    trip = vec[-1] > 0
                energy = vec[:-1] if rep else vec[0]
                ff = ff._replace(energy=energy)
                c = DomainCarry(st, ff, nbh, c.aid, c.r0, trip,
                                c.n_rebuilds, c.n_migrated, c.n_dropped)
                if emit is None:
                    return c, None
                ys = jax.lax.cond(flag, lambda: vobserve(c.state, c.ff),
                                  lambda: obs_zero)
                return c, ys

            carry, obs = _scan_chunk(body, carry, key, n, emit,
                                     lambda c: vobserve(c.state, c.ff))
            return carry, obs, health_of(carry, etot0)

        carry_spec, cell_spec, rsc = rp.specs(self._spin_in_gather)
        key_spec = P()
        lead = rp.replica_axis if rp.rep_in_mesh() else None

        def arg_spec(a, vec: bool):
            """PartitionSpec tree for a schedule argument."""
            if a is None:
                return None
            if isinstance(a, _StepValues):
                per_rep = a.rows.ndim == (3 if vec else 2)
                return _StepValues(rows=P(None, lead) if per_rep
                                   and lead is not None else P())
            if _is_schedule(a):
                per_rep = a.values.ndim == (3 if vec else 2)
                vspec = (P(None, lead) if per_rep and lead is not None
                         else P())
                return type(a)(times=P(), values=vspec)
            return rsc if rep else P()

        def obs_specs(emit):
            specs = {}
            for name in self.observables:
                dims = []
                if emit is not None:
                    dims.append(None)          # emission axis
                if rep:
                    dims.append(lead)          # replica axis
                dims += [None] * _OBS_TAIL_NDIM.get(name, 0)
                specs[name] = P(*dims)
            return specs

        def make(n, emit, targ, farg):
            fn = lambda c, k, t, f: local_chunk(c, k, t, f, n, emit)
            t_spec, f_spec = arg_spec(targ, False), arg_spec(farg, True)
            if targ is not None and farg is not None:
                body = lambda c, k, t, f: fn(c, k, t, f)
                ins = (carry_spec, key_spec, t_spec, f_spec)
            elif targ is not None:
                body = lambda c, k, t: fn(c, k, t, None)
                ins = (carry_spec, key_spec, t_spec)
            elif farg is not None:
                body = lambda c, k, f: fn(c, k, None, f)
                ins = (carry_spec, key_spec, f_spec)
            else:
                body = lambda c, k: fn(c, k, None, None)
                ins = (carry_spec, key_spec)
            health_spec = {name: P() for name in
                           ("e_drift", "spin_dev", "nonfinite", "nbr_occ",
                            "cell_occ")}
            out_specs = (carry_spec, obs_specs(emit), health_spec)
            return jax.jit(shard_map_compat(body, mesh, in_specs=ins,
                                            out_specs=out_specs))

        self._make_chunk = make
        self._compute_ff = compute_ff
        self._rebuild_one = rebuild_one
        self._refresh = refresh

    def _chunk_for(self, n, emit, targ, farg):
        key = (n, emit, _arg_sig(targ), _arg_sig(farg))
        if key not in self._chunk_cache:
            self._chunk_cache[key] = self._make_chunk(n, emit, targ, farg)
        return self._chunk_cache[key]

    # ------------------------------------------------------------------
    def _init_domain_carry(self, dstate, aid):
        """Initial device-resident carry: table + forces, one shard_map."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.sharding import shard_map_compat

        rp = self._rplan
        carry_spec, cell_spec, rsc = rp.specs(self._spin_in_gather)
        rep = self.replicas
        mesh = rp.mesh
        field = self._value_now(self._norm_arg(self.field, vec=True),
                                vec=True)

        def local_init(pos, vel, spin, types, aid, field=None):
            state = SpinLatticeState(
                pos=pos, vel=vel, spin=spin, types=types,
                box=jnp.asarray(rp.dspec.box, pos.dtype),
                step=jnp.asarray(self.state.step, jnp.int32))

            state_ax = SpinLatticeState(pos=0, vel=0, spin=0, types=0,
                                        box=None, step=None)

            def one(state, aid, field):
                # migration is a no-op right after packing, but running it
                # keeps init on the exact rebuild code path
                return self._rebuild_one(state, aid, field)

            if rep:
                from repro.parallel.domain import DomainNbh
                nbh_ax = DomainNbh(
                    idx=0, mask=0, tj=0, dr=0,
                    sj=0 if self._spin_in_gather else None)
                st, ff, nbh, aid, r0, moved, dropped = jax.vmap(
                    one,
                    in_axes=(state_ax, 0,
                             0 if field is not None else None),
                    out_axes=(state_ax, 0, nbh_ax, 0, 0, 0, 0))(
                        state, aid, field)
            else:
                st, ff, nbh, aid, r0, moved, dropped = one(state, aid,
                                                           field)
            z = jnp.asarray(0, jnp.int32)
            dropped = self._dev_counts(jnp.sum(dropped))
            # compute() returns device-local energy; globalize it here
            # (in-chunk this rides the per-step fused scalar reduction)
            energy = ff.energy
            for name in rp.spatial_axes:
                energy = jax.lax.psum(energy, name)
            ff = ff._replace(energy=energy)
            return DomainCarry(st, ff, nbh, aid, r0,
                               jnp.asarray(False), z, z, dropped)

        sspec = carry_spec.state
        in_specs = [sspec.pos, sspec.vel, sspec.spin, sspec.types,
                    carry_spec.aid]
        tile = (lambda x: jnp.broadcast_to(x[None], (rep,) + x.shape)
                ) if rep else (lambda x: x)
        args = [tile(dstate.pos), tile(dstate.vel), tile(dstate.spin),
                tile(dstate.types), tile(aid)]
        if field is not None:
            in_specs.append(rsc if rep else P())
            args.append(field)
        init = jax.jit(shard_map_compat(local_init, mesh,
                                        in_specs=tuple(in_specs),
                                        out_specs=carry_spec))

        def put(x, spec):
            return jax.device_put(x, NamedSharding(mesh, spec))

        args = [put(a, s) for a, s in zip(args, in_specs)]
        with self._halo:
            self._carry = init(*args)
        self._count_restart(built=True)
        self._check_dropped()
        self._sync_observation()

    def _check_dropped(self, chunk_index: int | None = None):
        """Raise a structured :class:`HealthError` when migration dropped
        atoms, reporting per-device counts and the last-good checkpoint."""
        vec = np.atleast_1d(np.asarray(self._carry.n_dropped))
        dropped = int(vec.sum())
        if dropped:
            per_dev = {int(i): int(v) for i, v in enumerate(vec) if v}
            raise HealthError(
                f"domain cell overflow: {dropped} atom(s) dropped at "
                f"migration (cell capacity {self._rplan.dspec.capacity} "
                "exceeded or an atom jumped more than one cell between "
                "rebuilds); increase cell_capacity or shrink the "
                f"skin/timestep; per-device drop counts: {per_dev}",
                step=self._step_now(), chunk_index=chunk_index,
                signals={"dropped": dropped,
                         "dropped_per_device": per_dev},
                checkpoint_path=self._last_ckpt, kind="overflow")

    @property
    def n_migrated(self) -> int:
        """Atoms that changed link cell across all in-scan rebuilds."""
        return int(self._carry.n_migrated)

    def _sync_domain(self):
        """Host-side unpack of the hot carry into original atom order."""
        c = self._carry
        aid = np.asarray(c.aid).reshape(self.n_replicas, -1)
        flat = lambda a, tail: np.asarray(a).reshape(
            self.n_replicas, -1, *tail)
        pos, vel, spin = (flat(x, (3,)) for x in
                          (c.state.pos, c.state.vel, c.state.spin))
        force, hfield = flat(c.ff.force, (3,)), flat(c.ff.field, (3,))
        types = flat(c.state.types, ())
        n = self._n_atoms
        outs = []
        for r in range(self.n_replicas):
            sel = np.nonzero(aid[r] >= 0)[0]
            order = np.empty(n, np.int64)
            order[aid[r][sel]] = sel
            outs.append(tuple(a[r][order] for a in
                              (pos, vel, spin, types, force, hfield)))
        stack = (lambda i: np.stack([o[i] for o in outs])
                 ) if self.replicas else (lambda i: outs[0][i])
        self.state = SpinLatticeState(
            pos=jnp.asarray(stack(0)), vel=jnp.asarray(stack(1)),
            spin=jnp.asarray(stack(2)),
            types=jnp.asarray(stack(3).astype(np.int32)),
            box=jnp.asarray(np.asarray(self._rplan.dspec.box),
                            self._carry.state.pos.dtype),
            step=self._carry.state.step)
        # observed forces/effective fields, original atom order (API parity
        # with the flat driver's _ff; used by the halo-adjoint tests)
        self._ff = ForceField(energy=c.ff.energy,
                              force=jnp.asarray(stack(4)),
                              field=jnp.asarray(stack(5)))
        self._obs_state = self.state

    # ==================================================================
    # observation, run loop, checkpoint
    # ==================================================================
    @_within(annotate, "repro.sync")
    def _sync_observation(self):
        if isinstance(self.plan, SingleDevice):
            self._sync_flat()
        elif isinstance(self.plan, Replicated):
            self._sync_replica()
        else:
            self._sync_domain()

    @_within(annotate, "repro.run")
    def run(self, n_steps: int, key: jax.Array, chunk: int = 20, *,
            temperature=_UNSET, field=_UNSET,
            callback: Callable[["Engine"], None] | None = None,
            checkpoint_dir: str | None = None, checkpoint_every: int = 1,
            resume: bool = False, telemetry=None) -> SpinLatticeState:
        """Advance ``n_steps`` through the plan's compiled chunk.

        ``temperature``/``field`` override the engine-level schedule axis
        for this run (same kinds: None | constant | Schedule).  Observables
        land in ``self.trace``.  ``checkpoint_dir`` saves the hot carry +
        the loop RNG key every ``checkpoint_every`` chunks (and at the end)
        through :mod:`repro.ckpt.checkpoint`; ``resume=True`` restores the
        newest checkpoint first (carry AND key), making the interrupted +
        resumed trajectory bitwise identical to an uninterrupted one.
        ``callback`` (flat/replica plans) receives the engine after each
        chunk with observation state synced.

        ``telemetry`` (a :class:`repro.telemetry.Telemetry`, or a runlog
        path as shorthand) turns on run observability: per-chunk wall
        times / steps/s / compile deltas / halo bytes go to the JSONL
        runlog, health signals are checked against the config's
        thresholds at every chunk boundary (raising a structured
        :class:`~repro.telemetry.monitor.HealthError` that names the
        last-good checkpoint).  Health signals are computed on every run
        either way and land in ``self.trace.health``; only the checking and
        persistence are opt-in.  A ``jax.profiler`` trace the caller opens
        around the call holds the run's host spans (``repro.run``,
        ``repro.restart``, ``repro.chunk`` and its parts, ``repro.sync``,
        ``repro.checkpoint``, ``repro.callback``) beside the device ops of
        its named phases; :meth:`counters` gives its counts.

        ``key`` is a single ``(2,)`` PRNG key - except on a ``per_slot``
        Replicated plan, where it must be a per-slot ``(R, 2)`` stack:
        each slot owns an independent RNG stream (split per chunk via
        ``vmap(random.split)``), its own schedule clock (derived from its
        ``states.step`` row), and its own health signals, which is what
        lets the serving layer pack and backfill jobs whose solo
        trajectories must be reproduced bitwise.  Schedules in per-slot
        mode may be :class:`~repro.ensemble.protocol.SlotSchedules`
        stacks (one knot row per slot).
        """
        tel = as_telemetry(telemetry)
        targ = self._norm_arg(
            self.temperature if temperature is _UNSET else temperature,
            vec=False)
        farg = self._norm_arg(self.field if field is _UNSET else field,
                              vec=True)
        if self.obs_every is not None and chunk % self.obs_every:
            raise ValueError(f"chunk ({chunk}) must be a multiple of "
                             f"obs_every ({self.obs_every})")
        if resume:
            if checkpoint_dir is None:
                raise ValueError("resume=True needs checkpoint_dir")
            from repro.ckpt.checkpoint import latest_step
            if latest_step(checkpoint_dir) is not None:
                key = self.restore(checkpoint_dir)

        if isinstance(self.plan, SingleDevice):
            self._restart_if_swapped(farg)
        elif isinstance(self.plan, Replicated):
            self._replica_restart_if_swapped(farg)
            targ, farg = self._replica_put(targ), self._replica_put(farg)

        session = None
        if tel is not None:
            session = TelemetrySession(
                tel, ledger=self._halo,
                run_info=self._run_info(n_steps, chunk))
        try:
            self._run_loop(n_steps, key, chunk, targ, farg, callback,
                           checkpoint_dir, checkpoint_every, tel, session)
        except BaseException as exc:
            if session is not None:
                session.finish(status="failed", error=str(exc))
            raise
        if session is not None:
            session.finish(status="ok")
        return self.state

    def _split_key(self, key):
        """Advance the loop RNG one chunk: ``(next_key, chunk_key)``.

        In ``per_slot`` mode ``key`` is a stacked ``(R, 2)`` array of
        independent per-slot keys (one stream per packed job) and both
        returns keep that shape - each slot's chain advances exactly as a
        solo run's scalar chain would, so a job's trajectory is bitwise
        independent of its batch-mates."""
        if self.per_slot:
            key = jnp.asarray(key)
            if key.ndim != 2 or key.shape != (self.plan.replicas, 2):
                raise ValueError(
                    f"per_slot run() needs a ({self.plan.replicas}, 2) "
                    f"stacked key, got shape {key.shape}")
            pair = jax.vmap(lambda kk: jax.random.split(kk))(key)
            return pair[:, 0], pair[:, 1]
        return jax.random.split(key)

    def _run_loop(self, n_steps, key, chunk, targ, farg, callback,
                  checkpoint_dir, checkpoint_every, tel, session) -> None:
        """The chunk loop.  Each chunk runs under the host span
        ``repro.chunk`` (its index as metadata) with the parts
        ``.lower`` (key split, schedule rows), ``.enqueue`` (the call into
        the compiled chunk), ``.wait`` (where the host blocks on its
        observables and health) and ``.gate`` (health gate, runlog)."""
        carry = self._carry
        t0 = float(self._step_now()) * self.cfg.dt
        rows, times, hrows = [], [], []
        done = 0
        chunks_done = 0
        calls = force_calls_per_step(self.cfg)
        if session is not None:
            reb_prev = int(np.asarray(carry.n_rebuilds))
            mig_prev = (int(np.asarray(carry.n_migrated))
                        if isinstance(self.plan, Sharded) else 0)
        while done < n_steps:
            with annotate("repro.chunk", chunk=chunks_done):
                n = min(chunk, n_steps - done)
                emit = self._emit_for(n)
                if self._fault_injector is not None:
                    # resilience hook: host-side carry corruption at the
                    # chunk boundary (repro.resilience.faults); keeps
                    # self._carry in sync so step accounting sees the
                    # injected carry
                    carry = self._fault_injector(self, carry, n)
                    self._carry = carry
                with annotate("repro.chunk.lower"):
                    key, sub = self._split_key(key)
                    if isinstance(self.plan, Replicated):
                        sub = self._replica_put(sub)
                    # schedules lower to host-evaluated per-step rows HERE,
                    # with the live carry's clock(s) - see _chunk_arg for
                    # why this cannot happen inside the compiled chunk
                    targ_c = self._chunk_arg(targ, carry, n)
                    farg_c = self._chunk_arg(farg, carry, n)
                t_chunk = time.perf_counter()
                # the run-scoped ledger catches chunk traces
                with annotate("repro.chunk.enqueue"), self._halo:
                    if isinstance(self.plan, Sharded):
                        fn = self._chunk_for(n, emit, targ_c, farg_c)
                        args = [carry, sub]
                        if targ_c is not None:
                            args.append(targ_c)
                        if farg_c is not None:
                            args.append(farg_c)
                        carry, obs, health = fn(*args)
                    else:
                        carry, obs, health = self._chunk_fn(
                            carry, sub, targ_c, farg_c, n, emit)
                if emit is None:
                    times.append(t0 + (done + n) * self.cfg.dt)
                else:
                    times.extend(t0 + (done + i + 1) * self.cfg.dt
                                 for i in emit)
                with annotate("repro.chunk.wait"):
                    rows.append(jax.tree_util.tree_map(np.asarray, obs))
                    # per_slot health carries (R,) attribution vectors
                    # alongside the gating scalars - keep vectors as lists
                    # (JSON-able)
                    h_host = {k: (np.asarray(v).tolist()
                                  if np.asarray(v).ndim
                                  else np.asarray(v).item())
                              for k, v in health.items()}
                hrows.append(h_host)
                wall = time.perf_counter() - t_chunk  # .wait blocked
                done += n
                chunks_done += 1
                self._carry = carry
                self._counts.inc("chunks")
                self._counts.inc("steps", n)
                self._counts.inc("force_calls", n * calls)

                # health gate BEFORE checkpointing: a failing chunk must not
                # become the newest checkpoint (abort-and-resume contract)
                with annotate("repro.chunk.gate"):
                    verdict, err = "ok", None
                    try:
                        if isinstance(self.plan, Sharded):
                            self._check_dropped(chunk_index=chunks_done - 1)
                        if tel is not None and tel.health is not None:
                            verdict = check_chunk(
                                h_host, tel.health, step=self._step_now(),
                                chunk_index=chunks_done - 1,
                                checkpoint_path=self._last_ckpt)
                    except HealthError as e:
                        verdict, err = "fail", e
                    if session is not None:
                        reb = int(np.asarray(carry.n_rebuilds))
                        counters = {"rebuilds": reb - reb_prev}
                        reb_prev = reb
                        if isinstance(self.plan, Sharded):
                            mig = int(np.asarray(carry.n_migrated))
                            counters["migrations"] = mig - mig_prev
                            mig_prev = mig
                        session.chunk(
                            steps=n, step=self._step_now(),
                            time_ps=t0 + done * self.cfg.dt, wall_s=wall,
                            health=h_host, verdict=verdict,
                            chunk_cache=self._chunk_cache_size(),
                            counters=counters,
                            error=None if err is None else str(err))
                if err is not None:
                    self._fold_trace(rows, times, hrows)
                    raise err
                if checkpoint_dir is not None and (
                        chunks_done % checkpoint_every == 0
                        or done >= n_steps):
                    with annotate("repro.checkpoint"):
                        self.save(checkpoint_dir, key=key)
                if callback is not None:
                    with annotate("repro.callback"):
                        self._sync_observation()
                        callback(self)
                        # the callback may perturb the state
                        if isinstance(self.plan, SingleDevice):
                            self._restart_if_swapped(farg)
                        elif isinstance(self.plan, Replicated):
                            self._replica_restart_if_swapped(farg)
                        elif self.state is not self._obs_state:
                            # repacking the cell-major layout mid-run is
                            # not wired up; dropping the swap silently
                            # would be worse
                            raise NotImplementedError(
                                "state swaps from a callback are not "
                                "supported on the Sharded plan (callbacks "
                                "are observation-only there); build a new "
                                "Engine from the modified state instead")
                    carry = self._carry
        self._carry = carry
        self._sync_observation()
        self._fold_trace(rows, times, hrows)

    def _fold_trace(self, rows, times, hrows) -> None:
        if not rows:
            return
        cat = np.stack if self.obs_every is None else np.concatenate
        self.trace = EngineTrace(
            time=np.asarray(times),
            values={k: cat([r[k] for r in rows])
                    for k in self.observables},
            health={k: np.asarray([h[k] for h in hrows])
                    for k in hrows[0]})

    def _chunk_cache_size(self) -> int:
        """Compiled chunk-variant count (the compile watchdog's partner:
        a steady-state run holds this at 1 per (n, emit) signature)."""
        if isinstance(self.plan, Sharded):
            return len(self._chunk_cache)
        try:
            return self._chunk_fn._cache_size()
        except Exception:
            return -1

    def _run_info(self, n_steps: int, chunk: int) -> dict:
        """Static run descriptor for the runlog header."""
        if isinstance(self.plan, Sharded):
            n_atoms = self._n_atoms
        elif isinstance(self.plan, Replicated):
            n_atoms = self.state.pos.shape[1]
        else:
            n_atoms = self.state.pos.shape[0]
        info = {"plan": type(self.plan).__name__, "n_steps": n_steps,
                "chunk": chunk, "n_atoms": int(n_atoms),
                "dt_ps": float(self.cfg.dt), "replicas": self.replicas,
                "observables": list(self.observables),
                "potential": type(self.potential).__name__}
        if self.per_slot:
            info["per_slot"] = True
        info.update(getattr(self, "run_tags", {}) or {})
        if isinstance(self.plan, Sharded):
            rp = self._rplan
            info["mesh"] = {a: int(rp.mesh.shape[a])
                            for a in rp.mesh.axis_names}
            info["cells"] = list(rp.dspec.cells)
            info["cell_capacity"] = int(rp.dspec.capacity)
        return info

    # ------------------------------------------------------------------
    def save(self, directory: str, key: jax.Array, keep: int = 3) -> str:
        """Checkpoint the hot carry + run RNG key at a chunk boundary.

        ``key`` is the loop key the NEXT chunk would split (between
        :meth:`run` calls that is the key you would pass to the next run)
        - :meth:`restore` hands it back, and resuming with it reproduces
        the uninterrupted trajectory bitwise.  It is deliberately
        required: a checkpoint without the true key could not honor that
        contract, and failing loudly beats silently replaying an
        unrelated RNG stream.
        """
        from repro.ckpt.checkpoint import save_md
        path = save_md(directory,
                       self._step_now() + int(self.ckpt_step_offset),
                       self._carry, key, keep=keep, pin=self.ckpt_pin)
        self._last_ckpt = path
        return path

    def restore(self, directory: str, step: int | None = None, *,
                plan=None) -> jax.Array:
        """Restore the hot carry from a checkpoint; returns the saved run
        RNG key (continue with ``engine.run(remaining, key)`` for a
        bitwise-identical trajectory).

        ``plan`` switches on **elastic restart**: the checkpointed sharded
        carry is gathered to the canonical unsharded form, re-binned onto
        the new plan's cell grid/mesh, and the neighbor table and forces
        are rebuilt - the engine continues the trajectory on a different
        device count.  The rebuild happens at a chunk boundary, so it is
        exactly the migration-rebuild contract the in-scan loop already
        honors (same-mesh vs cross-mesh restores agree to the force
        evaluation's reduction order).
        """
        if plan is not None:
            return self._restore_elastic(directory, step, plan)
        from repro.ckpt.checkpoint import load_md
        key_shape = ((self.plan.replicas, 2) if self.per_slot else (2,))
        carry, key, _ = load_md(directory, self._carry, step=step,
                                shardings=self._carry_shardings(),
                                key_shape=key_shape)
        self._carry = carry
        self._sync_observation()
        # hand the key back the way run() receives it: an uncommitted
        # default-device array, not the mesh-replicated placement the
        # loader used - a committed key would recompile random.split on
        # the first retried chunk
        return jnp.asarray(np.asarray(key))

    def _restore_elastic(self, directory: str, step: int | None,
                         plan) -> jax.Array:
        from repro.ckpt.elastic import gather_md_state
        if not isinstance(self.plan, Sharded) or self.replicas:
            raise NotImplementedError(
                "elastic restore re-bins sharded single-trajectory "
                "carries; current plan is "
                f"{type(self.plan).__name__}(replicas={self.replicas})")
        plan = as_plan(plan)
        if not isinstance(plan, Sharded) or plan.replicas:
            raise NotImplementedError(
                "elastic restore targets a Sharded plan without replicas")
        state, key, _ = gather_md_state(directory, self._carry, step=step)
        self.plan = plan
        self.state = state
        self.table = None
        # drop the old-mesh carry BEFORE setup: _step_now must fall back
        # to the restored state's step while schedules are re-evaluated
        self.__dict__.pop("_carry", None)
        self._setup_domain()    # re-resolve, re-bin, rebuild, re-evaluate
        return key

    # ------------------------------------------------------------------
    def rebind(self, *, cfg: IntegratorConfig | None = None,
               skin: float | None = None, plan=None) -> None:
        """Rebuild the compiled chunk around a new config / skin / plan.

        The supervisor's graceful-degradation lever: the current carry is
        synced to the canonical ``self.state`` (original atom order), the
        requested knobs are swapped, and the plan setup re-runs from that
        state - one retrace, exactly as at construction.  Trajectory
        continuity is the chunk-boundary contract: positions / velocities
        / spins / step carry over bitwise; the neighbor table and forces
        are rebuilt.

        On the ``Sharded`` plan a new plan object may change the cell
        grid, capacity, or mesh (elastic in-place rescale).  Replica
        batches cannot be re-packed through the flat state and are
        rejected.
        """
        if isinstance(self.plan, Sharded) and self.replicas:
            raise NotImplementedError(
                "rebind on the replicated-sharded plan is not supported "
                "(the flat re-pack path is single-trajectory)")
        self._sync_observation()
        if cfg is not None:
            self.cfg = cfg
        if skin is not None:
            self.skin = skin
        if plan is not None:
            self.plan = as_plan(plan, replicas=self.replicas)
        self.table = None
        self.__dict__.pop("_carry", None)   # _step_now -> state.step
        if isinstance(self.plan, SingleDevice):
            self._setup_flat()
        elif isinstance(self.plan, Replicated):
            self._setup_replica()
            if self.plan.devices is not None:
                self.shard_replicas(self.plan.devices)
        elif isinstance(self.plan, Sharded):
            self._setup_domain()
        else:
            raise TypeError(f"unknown plan {self.plan!r}")

    def _carry_shardings(self):
        """Sharding tree for direct placement at restore: each leaf goes
        back exactly where the live carry holds it (mesh-sharded on the
        domain plan, replica-axis-sharded after :meth:`shard_replicas`).
        Returns None on unsharded plans: there a committed ``device_put``
        would change the jit cache key of the already-compiled chunk (the
        warm chunk was traced against uncommitted arrays), so restore
        places leaves with plain ``jnp.asarray`` and retries recompile
        nothing."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if isinstance(self.plan, Sharded):
            key_shd = NamedSharding(self._rplan.mesh, P())
        elif getattr(self, "_replica_mesh", None) is not None:
            key_shd = NamedSharding(self._replica_mesh, P())
        else:
            return None
        carry_shd = jax.tree_util.tree_map(lambda x: x.sharding,
                                           self._carry)
        return {"carry": carry_shd, "key": key_shd}
