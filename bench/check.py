"""The comparison that decides ``correct``.

What the window produced is compared with the plain references at the
timed sizes, layer by layer:

* neighbour refresh: the table of the last episode restart and the last
  table the window built, each against the plain list at that table's own
  build positions (``table_pairs``); the row layout of the restart and of
  the compared chunk's in-scan rebuild against the reference's own
  linked-cell sort (``layout_rows``);
* force evaluation: E, F and H_eff of the episode restart, the evaluation
  at the seeded state (``restart_E`` / ``_F`` / ``_H``);
* integrator, with the in-scan rebuild and force: the last episode replayed
  by the plain integrator from its seeded state with the same random
  numbers, chunk by chunk, through the chunk of its first in-scan rebuild
  (``chunk_pos`` / ``_vel`` / ``_spin`` of that chunk's end state and its
  E, F and H_eff, ``chunk_E`` / ``_F`` / ``_H``).  The program has to
  rebuild in the chunks where the reference does.  Where the program
  rebuilt more than once in that chunk, whose layouts in between no chunk
  shows, the chunk before it is compared.

The control puts the reference, evaluated in bfloat16 (distance tests, cell
binning and energies), in the program's place; :func:`outputs` builds
either side.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench.builders.system import potential_cutoff, schedule_values
from bench.reference import neighbors
from bench.reference.forces import Forces
from bench.reference.integrator import Step

BAND = 1e-4     # Å: pairs this close to the table radius are left out
FACE = 1e-3     # Å: atoms this close to a cell face are left out of the layout
MARGIN = 1.0    # Å: the replay's neighbour list reaches this past the cutoff
NAMES = ("table_pairs", "layout_rows", "restart_E", "restart_F", "restart_H",
         "chunk_pos", "chunk_vel", "chunk_spin", "chunk_E", "chunk_F",
         "chunk_H")


def chunk_outputs(carry) -> dict:
    """What the comparison may need of a chunk's result: device arrays
    only, so keeping them costs no host sync and little memory."""
    return {"pos": carry.state.pos, "vel": carry.state.vel,
            "spin": carry.state.spin, "ff": tuple(carry.ff),
            "perm": carry.perm, "n_rebuilds": carry.n_rebuilds}


@dataclasses.dataclass
class Captured:
    """Host copies of what the window produced (program row order is
    "hot": row i holds atom ``perm[i]``)."""

    state0: dict          # seeded state, atom order: pos vel spin types box
    perm0: np.ndarray     # layout after the episode restart
    ff0: tuple            # (E, F, H) of the restart, hot order
    builds: list          # in-scan rebuilds of each chunk of the episode
    chunks: list          # each chunk's end: pos vel spin ff perm, hot order
    tables: list          # restart's and last table: r0 idx mask (hot)
    chunk_keys: np.ndarray  # keys handed to run(), one per chunk
    steps: int            # steps of a chunk


def capture(state0, c0, outs, c_end, chunk_keys, steps) -> Captured:
    """Copy what the comparison needs of an episode to the host: its
    restart carry ``c0``, its chunks' outputs ``outs``
    (:func:`chunk_outputs`) and the window's last carry ``c_end``."""
    get = jax.device_get
    s0 = {k: np.asarray(v) for k, v in get(state0._asdict()).items()}
    c0h, ceh, outs = get((c0, c_end, outs))
    counts = [int(c0h.n_rebuilds)] + [int(o["n_rebuilds"]) for o in outs]
    return Captured(
        state0=s0, perm0=np.asarray(c0h.perm),
        ff0=tuple(np.asarray(x, np.float64) for x in c0h.ff),
        builds=[b - a for a, b in zip(counts, counts[1:])],
        chunks=[{"pos": np.asarray(o["pos"], np.float64),
                 "vel": np.asarray(o["vel"], np.float64),
                 "spin": np.asarray(o["spin"], np.float64),
                 "ff": tuple(np.asarray(x, np.float64) for x in o["ff"]),
                 "perm": np.asarray(o["perm"])} for o in outs],
        tables=[{"r0": np.asarray(c.table.r0), "idx": np.asarray(c.table.idx),
                 "mask": np.asarray(c.table.mask)} for c in (c0h, ceh)],
        chunk_keys=np.stack([np.asarray(get(k)) for k in chunk_keys]),
        steps=steps)


def to_atoms(hot, perm):
    """Hot-order rows -> atom order."""
    out = np.empty_like(hot)
    out[perm] = hot
    return out


def program_outputs(cap: Captured, k: int | None) -> dict:
    """The program's side of the comparison, at the end of chunk ``k``."""
    o = cap.chunks[0 if k is None else k]
    perm = o["perm"]
    return {
        "tables": [(t["idx"], t["mask"]) for t in cap.tables],
        "builds": cap.builds,
        "layouts": (cap.perm0, perm),
        "restart": (cap.ff0[0], to_atoms(cap.ff0[1], cap.perm0),
                    to_atoms(cap.ff0[2], cap.perm0)),
        "chunk": {"pos": to_atoms(o["pos"], perm),
                  "vel": to_atoms(o["vel"], perm),
                  "spin": to_atoms(o["spin"], perm),
                  "E": o["ff"][0], "F": to_atoms(o["ff"][1], perm),
                  "H": to_atoms(o["ff"][2], perm)},
    }


def load_reference(root: str, kind: str):
    from bench.harness import load_module
    return load_module(os.path.join(root, "bench", "reference", f"{kind}.py"),
                       f"bench_reference_{kind}")


def step_keys(key, steps: int):
    """The engine's key use: run() splits (next, sub) and hands ``sub`` to
    the chunk, which splits one key per step."""
    return jax.random.split(jax.random.split(jnp.asarray(key))[1], steps)


def outputs(cell, cap: Captured, dtype, weights, like: dict | None = None
            ) -> dict:
    """The plain reference's outputs in ``dtype``: float32 is the
    reference, which decides the compared chunk; bfloat16 with ``like``,
    the reference's outputs, is the control, which rebuilds where the
    reference did and stops at the same chunk.

    The reference draws its random numbers in the program's row layouts,
    which :func:`numbers` holds to the reference's own sort; the control
    sorts its rows itself, binning in bfloat16."""
    cfg, traffic = cell.config, cell.traffic
    ref = load_reference(cell.root, cfg["kind"])
    s0 = cap.state0
    types, box = s0["types"], s0["box"]
    lat, nb = cfg["lattice"], cfg["neighbor"]
    rc = potential_cutoff(cfg)
    control = like is not None
    forces = Forces(ref.atom_energy(cfg, weights, dtype),
                    ref.site_moments(cfg), types, box)
    step = Step(lat["masses"], np.asarray(lat["moments"]) > 0, types, box,
                cfg["dt_ps"], cfg["spin_moment"],
                traffic["integrator"]["lattice_gamma"],
                traffic["integrator"]["spin_alpha"])
    dt, n = cfg["dt_ps"], cap.steps
    temps, fields = schedule_values(
        traffic, np.arange(len(cap.chunk_keys) * n) * dt)

    pos = jnp.asarray(s0["pos"], jnp.float32)
    vel = jnp.asarray(s0["vel"], jnp.float32)
    spin = jnp.asarray(s0["spin"], jnp.float32)
    lists = {"at": np.asarray(pos)}
    lists["idx"], lists["mask"] = neighbors.padded(pos, box, rc + MARGIN)

    def evaluate(pos, spin, field):
        if bool(step.moved(pos, jnp.asarray(lists["at"]), 0.5 * MARGIN)):
            lists["at"] = np.asarray(pos)
            lists["idx"], lists["mask"] = neighbors.padded(
                pos, box, rc + MARGIN)
        return forces(pos, spin, field, lists["idx"], lists["mask"])

    r_cell = rc + nb["skin"]
    layout = (neighbors.relayout(np.arange(len(types)), s0["pos"], box,
                                 r_cell, ml_dtypes.bfloat16)
              if control else cap.perm0)
    first = layout
    e, f, h = restart = evaluate(pos, spin, fields[0])
    r0, trip_pos, partial = pos, None, False
    trips, trip_steps, ends = [], [], []
    last = like["compared"] if control else len(cap.chunk_keys) - 1
    for c in range(0 if last is None else last + 1):
        keys = step_keys(cap.chunk_keys[c], n)
        trips.append(0)
        for s in range(n):
            i = c * n + s
            if (i in like["trip_steps"] if control else
                    bool(step.moved(pos, r0, 0.5 * nb["skin"]))):
                # the engine rebuilds here: new layout, new (E, F, H)
                trips[c] += 1
                trip_steps.append(i)
                r0 = pos
                if trip_pos is None:
                    trip_pos = np.asarray(pos, np.float64)
                    if control:
                        layout = neighbors.relayout(
                            first, trip_pos, box, r_cell, ml_dtypes.bfloat16)
                    elif cap.builds[c] == 1:
                        layout = cap.chunks[c]["perm"]
                    else:   # no chunk end shows the layout after it
                        partial = True
                        break
                e, f, h = evaluate(pos, spin, fields[i])
            perm = jnp.asarray(layout)
            pos, vel, spin = step.before(
                pos, vel, spin, jnp.asarray(f, jnp.float32),
                jnp.asarray(h, jnp.float32), keys[s], perm, temps[i])
            e, f, h = evaluate(pos, spin, fields[i])
            vel, spin = step.after(vel, spin, jnp.asarray(f, jnp.float32),
                                   jnp.asarray(h, jnp.float32), keys[s],
                                   perm, temps[i])
        if partial:
            break
        ends.append({"pos": np.asarray(pos, np.float64),
                     "vel": np.asarray(vel, np.float64),
                     "spin": np.asarray(spin, np.float64),
                     "E": e, "F": f, "H": h})
        if trips[c] and not control:
            break
    out = {"restart": restart, "trips": trips, "partial": partial,
           "trip_steps": like["trip_steps"] if control else trip_steps,
           # the compared chunk: the last one replayed to its end
           "compared": len(ends) - 1 if ends else None,
           "chunk": ends[-1] if ends else None,
           "trip_pos": None if partial else trip_pos}
    if control:
        out["builds"] = None    # it rebuilds where the reference did
        out["layouts"] = (first, layout)
        out["tables"] = [neighbors.bf16_table(t["r0"], box, r_cell,
                                              nb["capacity"])
                         for t in cap.tables]
    return out


def rebuilds_agree(builds, ref: dict) -> bool:
    """Did the program rebuild in the chunks where the reference did?  In
    a chunk the reference left at its first rebuild, the program's second
    is what sent it away."""
    for c, t in enumerate(ref["trips"]):
        if ref["partial"] and c == len(ref["trips"]) - 1:
            if builds[c] < 2:
                return False
        elif builds[c] != t:
            return False
    return True


def _rel(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max()
                 / max(float(np.abs(np.asarray(b)).max()), 1e-30))


def numbers(cell, cap: Captured, ref: dict, cand: dict) -> dict:
    """Each compared number of ``cand`` against the reference ``ref``."""
    cfg = cell.config
    rc = potential_cutoff(cfg)
    r_cell = rc + cfg["neighbor"]["skin"]
    box = np.asarray(cap.state0["box"], np.float64)
    out = {"table_pairs": float(sum(
        neighbors.table_errors(t["r0"], idx, mask, box, r_cell, BAND)
        for t, (idx, mask) in zip(cap.tables, cand["tables"])))}
    # the restart sorts the seeded rows; a rebuild in the compared chunks
    # re-sorts the restart's layout, and without one the layout stays
    first, last = cand["layouts"]
    rows = neighbors.layout_errors(first, np.arange(len(first)),
                                   cap.state0["pos"], box, r_cell, FACE)
    if ref["trip_pos"] is not None:
        rows += neighbors.layout_errors(last, first, ref["trip_pos"], box,
                                        r_cell, FACE)
    else:
        rows += int(np.count_nonzero(np.asarray(last) != np.asarray(first)))
    out["layout_rows"] = float(rows)
    e0, f0, h0 = ref["restart"]
    e1, f1, h1 = cand["restart"]
    out["restart_E"] = float(abs(e1 - e0) / max(abs(e0), 1.0))
    out["restart_F"] = _rel(f1, f0)
    out["restart_H"] = _rel(h1, h0)
    r, c = ref["chunk"], cand["chunk"]
    if r is None or (cand["builds"] is not None
                     and not rebuilds_agree(cand["builds"], ref)):
        for k in NAMES[5:]:
            out[k] = float("inf")
        return out
    d = c["pos"] - r["pos"]
    d -= box * np.round(d / box)
    out["chunk_pos"] = float(np.sqrt(np.sum(d * d, axis=-1)).max())
    out["chunk_vel"] = _rel(c["vel"], r["vel"])
    out["chunk_spin"] = _rel(c["spin"], r["spin"])
    out["chunk_E"] = float(abs(c["E"] - r["E"]) / max(abs(r["E"]), 1.0))
    out["chunk_F"] = _rel(c["F"], r["F"])
    out["chunk_H"] = _rel(c["H"], r["H"])
    return out


def limits(root: str, config: str) -> dict:
    """The configuration's limits, kept beside this module."""
    with open(os.path.join(root, "bench", "limits", f"{config}.json")) as f:
        return json.load(f)["limits"]


def verdict(nums: dict, lim: dict) -> bool:
    return all(k in nums and np.isfinite(nums[k]) and nums[k] <= lim[k]
               for k in lim)
