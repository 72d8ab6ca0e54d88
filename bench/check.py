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

A configuration that names a domain-decomposed plan keeps its atoms in
cell slots, ``(CX, CY, CZ, K)``, with each slot's atom id in ``aid`` (-1
where empty) and a pruned table per device that indexes that device's
slots and its halo.  :func:`capture` reads either carry into atom order and
a :class:`Layout`, so that the comparison is one for every plan: the
tables as atom pairs, the layout as each atom's cell on the program's grid,
the replay's random numbers drawn device by device over its slots.
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
    out = {"pos": carry.state.pos, "vel": carry.state.vel,
           "spin": carry.state.spin, "ff": tuple(carry.ff),
           "n_rebuilds": carry.n_rebuilds}
    if hasattr(carry, "aid"):           # cell slots of a decomposed plan
        out["aid"] = carry.aid
    else:
        out["perm"] = carry.perm
    return out


@dataclasses.dataclass
class Layout:
    """Where the program keeps each atom, in the order in which it draws
    its random numbers: ``rows`` holds the atom of every row, or on a
    decomposed plan of every cell slot, device after device (-1 where
    empty).  There ``blocks`` is (devices, one device's slot block shape),
    ``slots`` each listed slot's place in the global ``(CX, CY, CZ, K)``
    grid, flat, and ``grid`` that shape."""

    rows: np.ndarray
    blocks: tuple | None = None
    slots: np.ndarray | None = None
    grid: tuple | None = None


def device_blocks(grid, plan: dict) -> list[tuple]:
    """Each device's block of the global cell grid, in the order of the
    linear device index (the mesh's axes in order, the first slowest):
    (first cell, cells) per dimension."""
    names = list(plan["mesh"])
    sizes = [int(plan["mesh"][a]) for a in names]
    axis_map = plan["axis_map"]
    local = [c // (int(plan["mesh"][a]) if a else 1)
             for c, a in zip(grid, axis_map)]
    out = []
    for d in range(int(np.prod(sizes))):
        at = dict(zip(names, np.unravel_index(d, sizes)))
        out.append(tuple((int(at[a]) * n if a else 0, n)
                         for a, n in zip(axis_map, local)))
    return out


def domain_layout(aid, plan: dict) -> Layout:
    """The :class:`Layout` of a decomposed carry's ``aid``."""
    aid = np.asarray(aid)
    grid, k = aid.shape[:3], aid.shape[3]
    slots = np.arange(aid.size).reshape(aid.shape)
    blocks = device_blocks(grid, plan)
    order = np.concatenate([
        slots[tuple(slice(o, o + n) for o, n in b)].reshape(-1)
        for b in blocks])
    shape = tuple(n for _, n in blocks[0]) + (k,)
    return Layout(rows=aid.reshape(-1)[order], blocks=(len(blocks), shape),
                  slots=order, grid=tuple(aid.shape))


def to_atoms(values, ids, n: int) -> np.ndarray:
    """Rows or cell slots -> atom order: ``ids`` holds each one's atom, -1
    where empty, and ``values`` has its shape and a tail; an atom that no
    row holds reads NaN."""
    ids = np.asarray(ids)
    values = np.asarray(values)
    values = values.reshape((ids.size,) + values.shape[ids.ndim:])
    ids = ids.reshape(-1)
    out = np.full((n,) + values.shape[1:], np.nan, values.dtype)
    keep = ids >= 0
    out[ids[keep]] = values[keep]
    return out


def domain_table(aid, r0, idx, mask, plan: dict, n: int) -> dict:
    """Every device's pruned table as one table in atom order: row ``a``
    holds the atom ids that atom ``a``'s slot lists.  A device's table
    indexes its slots with one cell of halo on each side, ``(cx + 2, cy +
    2, cz + 2, K)``, flat; halo cell ``e`` of a device whose block starts
    at cell ``o`` is global cell ``(o + e - 1) mod C``.  ``extra`` counts
    what no atom row can show: entries listed by empty slots, and atoms
    held by no slot or by two."""
    aid, idx, mask = np.asarray(aid), np.asarray(idx), np.asarray(mask)
    r0 = np.asarray(r0)
    grid, m = aid.shape[:3], idx.shape[-1]
    out_idx = np.tile(np.arange(n, dtype=np.int64)[:, None], (1, m))
    out_mask = np.zeros((n, m), bool)
    out_r0 = np.zeros((n, 3), r0.dtype)
    seen = np.zeros(n, np.int64)
    extra = 0
    for b in device_blocks(grid, plan):
        own = tuple(slice(o, o + c) for o, c in b)
        halo = np.ix_(*[(o - 1 + np.arange(c + 2)) % g
                        for (o, c), g in zip(b, grid)])
        ext = aid[halo].reshape(-1)
        ids = aid[own].reshape(-1)
        loc = idx[own].reshape(-1, m)
        msk = mask[own].reshape(-1, m)
        j = np.where((loc >= 0) & (loc < ext.size),
                     ext[np.clip(loc, 0, ext.size - 1)], -1)
        occ = ids >= 0
        extra += int(np.count_nonzero(msk[~occ]))
        seen += np.bincount(ids[occ], minlength=n)
        out_idx[ids[occ]] = np.where(msk[occ], j[occ], ids[occ][:, None])
        out_mask[ids[occ]] = msk[occ]
        out_r0[ids[occ]] = r0[own].reshape(-1, 3)[occ]
    extra += int(np.count_nonzero(seen != 1))
    return {"r0": out_r0, "idx": out_idx, "mask": out_mask, "extra": extra}


@dataclasses.dataclass
class Captured:
    """Host copies of what the window produced, in atom order."""

    state0: dict          # seeded state, atom order: pos vel spin types box
    layout0: Layout       # layout after the episode restart
    ff0: tuple            # (E, F, H) of the restart
    builds: list          # in-scan rebuilds of each chunk of the episode
    chunks: list          # each chunk's end: pos vel spin ff layout
    tables: list          # restart's and last table: r0 idx mask extra
    chunk_keys: np.ndarray  # keys handed to run(), one per chunk
    steps: int            # steps of a chunk


def capture(state0, c0, outs, c_end, chunk_keys, steps, plan=None
            ) -> Captured:
    """Copy what the comparison needs of an episode to the host: its
    restart carry ``c0``, its chunks' outputs ``outs``
    (:func:`chunk_outputs`) and the window's last carry ``c_end``.
    ``plan`` is the configuration's decomposed plan, None for one
    device."""
    get = jax.device_get
    s0 = {k: np.asarray(v) for k, v in get(state0._asdict()).items()}
    n = s0["pos"].shape[0]
    outs = get(outs)
    if plan is None:
        tabs = get([(c.table.r0, c.table.idx, c.table.mask)
                    for c in (c0, c_end)])
        tables = [{"r0": np.asarray(r0), "idx": np.asarray(idx),
                   "mask": np.asarray(mask), "extra": 0}
                  for r0, idx, mask in tabs]
    else:
        tabs = get([(c.aid, c.r0, c.nbh.idx, c.nbh.mask)
                    for c in (c0, c_end)])
        tables = [domain_table(*t, plan, n) for t in tabs]

    def atoms(o):
        ids = o["perm"] if plan is None else o["aid"]
        f64 = lambda a: np.asarray(a, np.float64)
        return {"pos": to_atoms(f64(o["pos"]), ids, n),
                "vel": to_atoms(f64(o["vel"]), ids, n),
                "spin": to_atoms(f64(o["spin"]), ids, n),
                "ff": (np.float64(o["ff"][0]),
                       to_atoms(f64(o["ff"][1]), ids, n),
                       to_atoms(f64(o["ff"][2]), ids, n)),
                "layout": (Layout(rows=np.asarray(ids)) if plan is None
                           else domain_layout(ids, plan))}

    first = get(chunk_outputs(c0))
    counts = [int(first["n_rebuilds"])] + [int(o["n_rebuilds"])
                                           for o in outs]
    first = atoms(first)
    return Captured(
        state0=s0, layout0=first["layout"], ff0=first["ff"],
        builds=[b - a for a, b in zip(counts, counts[1:])],
        chunks=[atoms(o) for o in outs], tables=tables,
        chunk_keys=np.stack([np.asarray(get(k)) for k in chunk_keys]),
        steps=steps)


def program_outputs(cap: Captured, k: int | None) -> dict:
    """The program's side of the comparison, at the end of chunk ``k``."""
    o = cap.chunks[0 if k is None else k]
    return {
        "tables": [(t["idx"], t["mask"], t["extra"]) for t in cap.tables],
        "builds": cap.builds,
        "layouts": (cap.layout0, o["layout"]),
        "restart": cap.ff0,
        "chunk": {"pos": o["pos"], "vel": o["vel"], "spin": o["spin"],
                  "E": o["ff"][0], "F": o["ff"][1], "H": o["ff"][2]},
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
                traffic["integrator"]["spin_alpha"], cap.layout0.blocks)
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
    layout = (control_layout(cap.layout0, None, s0["pos"], box, r_cell)
              if control else cap.layout0)
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
                        layout = control_layout(cap.layout0, first,
                                                trip_pos, box, r_cell)
                    elif cap.builds[c] == 1:
                        layout = cap.chunks[c]["layout"]
                    else:   # no chunk end shows the layout after it
                        partial = True
                        break
                e, f, h = evaluate(pos, spin, fields[i])
            perm = jnp.asarray(layout.rows)
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
                                              nb["capacity"]) + (0,)
                         for t in cap.tables]
    return out


def control_layout(like: Layout, prev: Layout | None, pos, box,
                   r_cell: float) -> Layout:
    """The control's own layout of the atoms at ``pos``, binned in
    bfloat16: rows sorted by cell (:func:`neighbors.relayout`), or on a
    decomposed plan atoms packed into the cells of the program's grid
    (:func:`neighbors.pack`), laid out as ``like``; each cell keeps the
    order of ``prev``, or without it of the atom ids."""
    bf16 = ml_dtypes.bfloat16
    n = np.asarray(pos).shape[0]
    if like.blocks is None:
        rows = np.arange(n) if prev is None else prev.rows
        return Layout(rows=neighbors.relayout(rows, pos, box, r_cell, bf16))
    rank = np.arange(n)
    if prev is not None:
        held = prev.rows >= 0
        rank[prev.rows[held]] = prev.slots[held]
    aid = neighbors.pack(rank, pos, box, like.grid, bf16)
    return dataclasses.replace(like, rows=aid[like.slots])


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
        neighbors.table_errors(t["r0"], idx, mask, box, r_cell, BAND) + extra
        for t, (idx, mask, extra) in zip(cap.tables, cand["tables"])))}
    # the restart sorts the seeded rows; a rebuild in the compared chunks
    # re-sorts the restart's layout, and without one the layout stays.  On
    # a decomposed plan each atom's cell is held to its bin on the
    # program's grid.
    first, last = cand["layouts"]
    if first.blocks is None:
        rows = neighbors.layout_errors(first.rows, np.arange(len(first.rows)),
                                       cap.state0["pos"], box, r_cell, FACE)
        if ref["trip_pos"] is not None:
            rows += neighbors.layout_errors(last.rows, first.rows,
                                            ref["trip_pos"], box, r_cell,
                                            FACE)
    else:
        rows = neighbors.slot_errors(first.rows, first.slots, first.grid,
                                     cap.state0["pos"], box, r_cell, FACE)
        if ref["trip_pos"] is not None:
            rows += neighbors.slot_errors(last.rows, last.slots, last.grid,
                                          ref["trip_pos"], box, r_cell, FACE)
    if ref["trip_pos"] is None:
        rows += int(np.count_nonzero(last.rows != first.rows))
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
