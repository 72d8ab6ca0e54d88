#!/usr/bin/env bash
# Tier-1 verification (see ROADMAP.md).
# Usage: scripts/ci.sh [pytest args]   - run the tier-1 test suite
#        scripts/ci.sh --smoke         - 1-iteration benchmark smoke run
#                                        (every benchmarks/ module executes
#                                        on downscaled problems, so perf
#                                        code can't silently rot; CI FAILS
#                                        if any module crashes).  This
#                                        includes benchmarks/scaling.py,
#                                        which spawns a 2-simulated-device
#                                        subprocess so the shard_map domain
#                                        loop compiles in CI, and the
#                                        2-device ENGINE smoke: one
#                                        schedule-driven sharded chunk plus
#                                        a checkpoint/resume cycle asserted
#                                        bitwise (scripts/engine_smoke.py).
#                                        The engine smoke also asserts the
#                                        telemetry contract: the runlog
#                                        JSONL has >=1 chunk record whose
#                                        halo bytes match the run-scoped
#                                        ledger, compile count is 0 after
#                                        warmup, energy drift + health
#                                        verdict are present, and
#                                        `python -m repro.launch.report`
#                                        renders it without error.  Also
#                                        the resilience smoke
#                                        (scripts/resilience_smoke.py):
#                                        a supervised seeded-NaN
#                                        rollback-retry asserted bitwise
#                                        with zero retry recompiles, and
#                                        a SIGKILL kill-and-resume cycle
#                                        (<= 1 chunk lost, bitwise).
#                                        Plus the serving smoke
#                                        (scripts/serve_smoke.py): a
#                                        mixed fleet through the batched
#                                        job server at f64 with bitwise
#                                        packed-vs-solo parity, the
#                                        serve chaos smoke
#                                        (scripts/serve_chaos_smoke.py):
#                                        a seeded NaN/bit-flip/SIGKILL
#                                        campaign through the serving
#                                        tier with WAL recovery asserted
#                                        bitwise at f64, the NEP kernel
#                                        smoke (scripts/kernel_smoke.py):
#                                        auto dispatch must resolve to a
#                                        compiled executor (xla_tiled on
#                                        CPU), match the autodiff oracle,
#                                        beat interpret wall-clock, and
#                                        recompile zero times across
#                                        chunked calls, and the docs
#                                        link check
#                                        (scripts/check_docs.py).
#                                        The benchmark pass runs --strict:
#                                        perf-regression warnings become
#                                        failures (md_loop hard-fails if
#                                        kernel dispatch is interpret).
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${1:-}" == "--smoke" ]]; then
  env PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
      XLA_FLAGS="--xla_force_host_platform_device_count=2" \
      python scripts/engine_smoke.py
  # CPU-only: the resilience and serve chaos smokes touch JAX in the
  # parent and then start children that need a device, which a chip held
  # by the parent would refuse (one process per chip)
  env PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" JAX_PLATFORMS=cpu \
      python scripts/resilience_smoke.py
  # serving smoke: >=6 mixed-size jobs over >=2 shape buckets at f64 -
  # zero steady-state recompiles, packed-vs-solo bitwise parity, and a
  # consistent per-tenant accounting ledger (scripts/serve_smoke.py)
  env PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
      python scripts/serve_smoke.py
  # serve chaos smoke: a child server dies by SIGKILL mid-fleet under a
  # seeded fault plan; the parent recovers from the durable job journal
  # and proves the remaining streams bitwise with zero steady recompiles
  env PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" JAX_PLATFORMS=cpu \
      python scripts/serve_chaos_smoke.py
  # NEP kernel smoke: compiled dispatch (never interpret), oracle parity,
  # faster-than-interpret, and zero recompiles across chunked calls
  env PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
      python scripts/kernel_smoke.py
  # docs must not reference files that no longer exist
  python scripts/check_docs.py
  exec env PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" BENCH_SMOKE=1 \
      python -m benchmarks.run --smoke --strict
fi

# install prerequisites only when missing (the CI image bakes them in)
python - <<'EOF' || pip install -r requirements.txt
import importlib.util as u, sys
sys.exit(0 if all(u.find_spec(m) for m in
                  ("jax", "numpy", "pytest", "hypothesis")) else 1)
EOF

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
