"""The yardstick: work counts from shapes and the peak table."""
import json
import os

import pytest

from bench import work
from bench.tests.conftest import REPO


def _cfg(name):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_counts_repeat_exactly():
    cfg = _cfg("fege-nep-spin")
    a = work.nep_force_work(cfg["spec"], cfg["lattice"], 64000)
    b = work.nep_force_work(dict(cfg["spec"]), dict(cfg["lattice"]), 64000)
    assert a == b
    assert a["flops"] > 0 and a["bytes"] > 0


def test_b20_pairs_within_cutoff():
    lat = _cfg("fege-nep-spin")["lattice"]
    assert work.pairs_per_atom(lat, 5.0) == 43.0
    assert work.pairs_per_atom(lat, 5.5) == 55.0


def test_missing_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.load_peaks("TPU v99 imaginary")
    peaks = work.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["source"]


def test_roofline_names_its_bound():
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.roofline_seconds({"flops": 2e12, "bytes": 1e9}, peaks) == \
        (2.0, "compute")
    assert work.roofline_seconds({"flops": 1e12, "bytes": 3e9}, peaks) == \
        (3.0, "memory")


def test_nep_count_does_not_depend_on_the_implementation():
    """The count reads only the widths the potential was built with, so
    the fused kernels and the autodiff evaluator get the same one."""
    import dataclasses

    import jax

    from repro.core.descriptor import NEPSpinSpec
    from repro.core.potential import NEPSpinPotential, init_params

    cfg = _cfg("fege-nep-spin")
    spec = NEPSpinSpec(**cfg["spec"])
    params = init_params(spec, jax.random.PRNGKey(0))
    counts = []
    for use_kernel in (True, False):
        pot = NEPSpinPotential(spec, params, use_kernel=use_kernel)
        counts.append(work.nep_force_work(dataclasses.asdict(pot.spec),
                                          cfg["lattice"], 64000))
    assert counts[0] == counts[1]
    assert work.nep_n_desc(cfg["spec"]) == spec.n_desc
