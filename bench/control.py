"""Readings that set the limits of ``correct``: the program's numbers and
the control's over many seeds, in one process.

    python3 bench/control.py --workload nep-fc-64k --seeds 1-12

One set-up serves every seed: each seed's state is handed to the engine
through its restart path and runs one whole episode, which holds a
restart, the chunk a window's check compares and tables built in it.  For
each seed the program's numbers, and on the first four seeds the
control's (the reference in bfloat16 put in the program's place), are
printed as one JSON line; the last line holds, per number, the largest
program reading and the smallest control reading.  Not part of a
benchmark run.
"""
import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def seeds_of(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi) + 1)) if hi else [int(lo)]
    return out


CONTROL_SEEDS = 4  # each costs a second replay; the limits need three or more


def readings(root: str, workload: str, seeds: list[int], *,
             require_accelerator: bool = True):
    """Yield (seed, program numbers, control numbers or None); the control
    is read on the first :data:`CONTROL_SEEDS` seeds."""
    import jax
    import jax.numpy as jnp

    from bench import check, harness
    from bench.builders import system

    cell = harness.load_cell(root, workload)
    if require_accelerator and jax.devices()[0].platform == "cpu":
        raise SystemExit("control: no accelerator")
    if jax.devices()[0].platform != "cpu":
        harness.use_cache(root)
    build = harness.builder(cell)
    weights = jax.device_get(build.make_weights(cell.config))
    potential = build.make_potential(cell.config)
    mesh = system.make_mesh(cell.config)
    pools = [harness.make_pool(cell.config, cell.traffic, [seed], mesh)
             for seed in seeds]
    plan = system.make_plan(cell.config, mesh, [p[0][0] for p in pools])
    eng = None
    for n, (seed, (states, keys)) in enumerate(zip(seeds, pools)):
        t0 = time.perf_counter()
        # Engine.run restarts a swapped state on one device only, so a
        # Sharded plan gets an engine built from each seed's state
        if eng is None or mesh is not None:
            eng = system.make_engine(cell.config, cell.traffic, potential,
                                     states[0], plan)
        episodes = harness.Episodes(eng, states, keys,
                                    cell.traffic["chunk_steps"])
        c0, outs, _ = episodes.episode(0)
        cap = check.capture(episodes.states[0], c0, outs, eng._carry,
                            episodes.keys[0], episodes.chunk_steps,
                            cell.config.get("plan"))
        ref = check.outputs(cell, cap, jnp.float32, weights)
        prog = check.numbers(cell, cap, ref,
                             check.program_outputs(cap, ref["compared"]))
        ctrl = None
        if n < CONTROL_SEEDS:
            ctrl = check.numbers(cell, cap, ref, check.outputs(
                cell, cap, jnp.bfloat16, weights, like=ref))
        print(f"control: seed {seed} in {time.perf_counter() - t0:.1f} s, "
              f"in-scan rebuilds per chunk {cap.builds}, the reference's "
              f"{ref['trips']}, compared chunk {ref['compared']}",
              file=sys.stderr, flush=True)
        yield seed, prog, ctrl


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,9")
    args = ap.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise; keep them in TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    lower, upper = {}, {}
    for seed, prog, ctrl in readings(ROOT, args.workload,
                                     seeds_of(args.seeds)):
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl}),
              flush=True)
        for k in prog:
            lower[k] = max(lower.get(k, 0.0), prog[k])
            if ctrl is not None:
                upper[k] = min(upper.get(k, float("inf")), ctrl[k])
    print(json.dumps({"lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
