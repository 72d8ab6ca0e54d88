"""Chip benchmark of the coupled spin-lattice engine.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
Everything that judges the program (traffic generation, the plain
references, the work counts, the peak table, the trace reduction) lives in
this package; from the program it takes only the system under test
(``src/repro``) and its named scopes and counters.
"""
