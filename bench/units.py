"""Physical constants of the benchmark's inputs and references.

Metal units (Å, ps, eV, g/mol, Tesla), the same system the engine uses;
the values are CODATA 2018.
"""
KB = 8.617333262e-5        # Boltzmann constant [eV/K]
MVV2E = 1.0364269e-4       # (g/mol)(Å/ps)^2 per eV
FORCE2ACC = 1.0 / MVV2E    # F [eV/Å] / m [g/mol] * FORCE2ACC = a [Å/ps^2]
GYRO = 0.17608596          # electron gyromagnetic ratio [rad/(ps T)]
MU_B = 5.7883818060e-5     # Bohr magneton [eV/T]
