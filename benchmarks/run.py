"""Benchmark harness: one registered module per paper table/figure.

  ablation    - Fig. 5  single-node optimization ablation
  throughput  - Fig. 6 / Table I  atom-step/s vs system size, TtS
  scaling     - Fig. 7/8 / Table V  weak scaling of the SHARDED fused loop
                (writes BENCH_scaling.json, incl. the nep_kernel entry)
  accuracy    - Table IV  NEP-SPIN vs baseline accuracy
  kernels     - kernel-level microbenchmarks (fused vs reference)
  ensemble    - Fig. 9 scenario engine: vmapped replicas vs sequential
  serve       - serving tier: packed drain jobs/s, WAL journal overhead,
                recovery-replay latency (writes BENCH_serve.json)
  md_loop     - fused in-scan hot loop vs pre-fusion driver
                (writes BENCH_md_loop.json)

One command refreshes every emitted ``BENCH_*.json`` (each stamped with
jax-version/backend/device-count provenance via ``benchmarks.common``):

  PYTHONPATH=src python -m benchmarks.run                 # all modules
  PYTHONPATH=src python -m benchmarks.run --only md_loop,scaling

Prints ``name,us_per_call,derived`` CSV rows.  ``--smoke`` (or
BENCH_SMOKE=1) runs every benchmark for 1 iteration on downscaled problems
so perf code can't silently rot (wired into scripts/ci.sh --smoke).
``--strict`` (or BENCH_STRICT=1) promotes perf-regression warnings to hard
failures - currently the md_loop kernel gates: dispatch must resolve to a
compiled executor, and on full runs ``nep_kernel.vs_autodiff >= 1.0``.
"""
from __future__ import annotations

import os
import sys
import traceback

# registration order = execution order (cheap first)
REGISTRY = ("kernels", "ablation", "throughput", "scaling", "accuracy",
            "ensemble", "serve", "md_loop")


def main() -> None:
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    argv = sys.argv[1:]
    if "--smoke" in argv:
        os.environ["BENCH_SMOKE"] = "1"
    if "--strict" in argv:
        os.environ["BENCH_STRICT"] = "1"
    selected = list(REGISTRY)
    if "--only" in argv:
        if argv.index("--only") + 1 >= len(argv):
            sys.exit(f"--only needs a comma-separated subset of: "
                     f"{', '.join(REGISTRY)}")
        names = argv[argv.index("--only") + 1].split(",")
        unknown = [n for n in names if n not in REGISTRY]
        if unknown:
            sys.exit(f"unknown benchmark(s) {unknown}; registry: "
                     f"{', '.join(REGISTRY)}")
        selected = names
    import importlib
    modules = [importlib.import_module(f"benchmarks.{n}") for n in selected]
    print("name,us_per_call,derived")
    failures = []
    for mod in modules:
        try:
            mod.main()
        except Exception as e:
            failures.append((mod.__name__, e))
            traceback.print_exc()
    if failures:
        print(f"FAILED: {[f[0] for f in failures]}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
