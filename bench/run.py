"""Run one cell of ``BENCHMARK.json`` on the accelerator this process holds.

    python3 bench/run.py --workload nep-fc-64k --seed 7 --seconds 30 --trace 0

Prints the set-up breakdown and every compared number beside its limit on
standard error, and as the last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics, or the per-layer ones with ``--trace 1``), ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``.  Exits non-zero,
printing no result, where JAX finds no accelerator or fewer chips than the
cell asks for.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu logs to /tmp/tpu_logs unless told otherwise; keep them in TMPDIR
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                  "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import process_age, run_cell

    started = STARTED - process_age()
    rc, result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), started=started)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
