"""Heisenberg-DMI configurations: the program's classical spin-lattice
Hamiltonian (:class:`repro.core.hamiltonian.HeisenbergDMIModel`)."""
from __future__ import annotations


def make_weights(cfg: dict) -> dict:
    """Fixed couplings: the configuration's parameters are its weights."""
    return dict(cfg["params"])


def make_potential(cfg: dict):
    from repro.core.hamiltonian import HeisenbergDMIModel

    if cfg["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {cfg['dtype']!r}")
    p = dict(cfg["params"])
    p["ka_axis"] = tuple(p["ka_axis"])
    return HeisenbergDMIModel(**p)
