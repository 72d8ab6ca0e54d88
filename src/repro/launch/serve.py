"""CLI front-end for the simulation job server (:mod:`repro.serve`).

Builds a synthetic multi-tenant fleet of heterogeneous (T, B)-protocol
jobs - mixed step budgets, two geometries (two shape buckets), constant
holds, linear anneals, and field protocols - submits them through
admission control, drains the server, and prints per-job statuses plus
the per-tenant accounting replayed from the runlog:

    PYTHONPATH=src python -m repro.launch.serve --smoke
    PYTHONPATH=src python -m repro.launch.serve --jobs 12 --slots 4 \\
        --runlog runs/serve.jsonl --report

``--threaded`` exercises the background worker (submit-then-wait)
instead of the synchronous ``drain()``.  ``--report`` renders the runlog
through ``launch/report.py`` afterwards.  See ``docs/serving.md`` for
the job API and operator runbook.
"""
from __future__ import annotations

import argparse
import os
import tempfile

import jax
import numpy as np

from repro.core.hamiltonian import HeisenbergDMIModel
from repro.ensemble import protocol
from repro.md.integrator import IntegratorConfig
from repro.md.lattice import simple_cubic
from repro.md.state import init_state
from repro.serve import ServeConfig, SimJob, SimServer


def build_fleet(n_jobs: int, chunk: int, obs_every: int,
                dt: float = 2e-3) -> list[SimJob]:
    """A deterministic synthetic job mix: two geometries, two tenants,
    four protocol shapes, step budgets cycling over 2/3/4 chunks."""
    lat = simple_cubic()
    # frozen_lattice: the server admits spin-dynamics jobs only (packed
    # slots share one neighbor table - see serve.validate_job)
    cfg = IntegratorConfig(dt=dt, spin_alpha=0.05, frozen_lattice=True,
                           temperature=100.0)
    geoms = [(4, 4, 4), (6, 4, 4)]
    tenants = ["alice", "bob"]
    jobs = []
    for i in range(n_jobs):
        n_cells = geoms[i % len(geoms)]
        steps = chunk * (2 + i % 3)
        if i % 4 == 0:
            temp, field = 100.0, None                      # plain hold
        elif i % 4 == 1:
            temp = protocol.linear(0.0, steps * dt, 300.0, 50.0)
            field = None                                   # anneal
        elif i % 4 == 2:
            temp, field = 100.0, np.asarray([0.0, 0.0, 5.0])
        else:
            temp, field = protocol.field_cooling(
                300.0, 50.0, 10.0, t_hold=chunk * dt,
                t_ramp=chunk * dt)                         # Fig. 9 shape
        state = init_state(lat, n_cells, key=jax.random.PRNGKey(100 + i),
                           temperature=100.0, spin_init="helix_x")
        jobs.append(SimJob(
            state=state, potential=HeisenbergDMIModel(d0=0.01), cfg=cfg,
            masses=np.asarray(lat.masses),
            magnetic=np.asarray(lat.moments) > 0,
            steps=steps, temperature=temp, field=field,
            obs_every=obs_every, seed=100 + i,
            tenant=tenants[i % len(tenants)],
            name=f"fleet-{i:02d}"))
    return jobs


def main(argv=None) -> int:
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--jobs", type=int, default=8,
                    help="fleet size (default 8)")
    ap.add_argument("--slots", type=int, default=2,
                    help="replica slots per packed batch")
    ap.add_argument("--chunk", type=int, default=10,
                    help="segment length in steps")
    ap.add_argument("--obs-every", type=int, default=5,
                    help="observable cadence in steps")
    ap.add_argument("--runlog", default=None,
                    help="runlog path (default: workdir/serve.jsonl)")
    ap.add_argument("--workdir", default=None,
                    help="checkpoint/working dir (default: temp dir)")
    ap.add_argument("--journal", default=None, metavar="DIR",
                    help="enable the durable job journal (WAL) in DIR")
    ap.add_argument("--recover", action="store_true",
                    help="replay the journal instead of starting fresh "
                         "(requires --journal; resubmits the same fleet, "
                         "completed jobs deduplicate, interrupted jobs "
                         "resume from their committed watermark)")
    ap.add_argument("--threaded", action="store_true",
                    help="background worker + wait() instead of drain()")
    ap.add_argument("--report", action="store_true",
                    help="render the runlog report afterwards")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast fleet (6 jobs, tiny geometries)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.jobs = min(args.jobs, 6)
    workdir = args.workdir or tempfile.mkdtemp(prefix="simserve-")
    runlog = args.runlog or os.path.join(workdir, "serve.jsonl")
    cfg = ServeConfig(runlog=runlog, workdir=workdir, slots=args.slots,
                      chunk=args.chunk, journal_dir=args.journal)
    if args.recover:
        if not args.journal:
            ap.error("--recover requires --journal DIR")
        server = SimServer.recover(cfg)
    else:
        server = SimServer(cfg)
    fleet = build_fleet(args.jobs, args.chunk, args.obs_every)
    print(f"submitting {len(fleet)} jobs "
          f"({args.slots} slots, chunk {args.chunk}) -> {runlog}")
    handles = [server.submit(job) for job in fleet]
    n_buckets = len({h.bucket for h in handles if h.bucket is not None})
    print(f"{n_buckets} shape bucket(s)")

    if args.threaded:
        server.start()
        for h in handles:
            h.wait(timeout=600)
        server.stop()
    else:
        server.drain()

    for h in handles:
        tail = (f"{h.rows_streamed} rows"
                if h.status == "done" else (h.error or "")[:48])
        if h.recovered and h.rows_streamed == 0:
            tail = "deduplicated"     # journal match: no bucket, no rows
        bucket = h.bucket.id if h.bucket is not None else "-"
        print(f"  {h.id} [{h.job.name}] tenant={h.tenant} "
              f"bucket={bucket} steps={h.job.steps}: "
              f"{h.status} ({tail})")

    acct = server.accounting
    print("accounting consistent:", acct.consistent())
    for tenant, t in sorted(acct.tenants.items()):
        print(f"  {tenant}: {t['jobs_done']}/{t['jobs_submitted']} done, "
              f"{t['charged_steps']} slot-steps charged "
              f"({t['wall_s']:.2f}s wall share)")
    for bid, b in sorted(acct.buckets.items()):
        print(f"  bucket {bid}: {b['chunks']} chunks, "
              f"{b['warmup_compiles']} warmup / "
              f"{b['steady_compiles']} steady compiles")

    if args.report:
        from repro.launch.report import runlog_report
        print()
        print(runlog_report(runlog))
    bad = [h for h in handles if h.status != "done"]
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
